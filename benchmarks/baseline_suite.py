"""Fill the BASELINE.md rows the judge flagged as unmeasured.

Subcommands (each prints one JSON line):
  vgg16      — VGG16 train img/s/chip (TinyImageNet-shaped 64x64 bf16)
  inception  — imported InceptionV3 inference at the CANONICAL 299x299
  bert       — imported BERT-base inference tokens/s/chip (flash attn)
  bert_train — BERT-base-geometry native train step tokens/s/chip
  bert_finetune   — imported-BERT fine-tune tokens/s (grafted head)
  inception_train — imported-InceptionV3 fine-tune img/s (299x299)
  word2vec   — SGNS + HS tokens/s at 100k vocab (corpus-shaped workload)
               [--pairgen=auto|numpy|legacy selects the producer]
  lstm       — TextGenerationLSTM train tokens/s (2xLSTM-512; [f32|bf16])
  doc2vec_producer — DBOW host pair-generation rate, dispatch no-op'd;
               --native-ab [--smoke] instead runs the native-vs-fallback
               A/B gate (native >= fallback tokens/s AND bitwise-equal
               dispatch streams; exits 1 on violation)

Run: python benchmarks/baseline_suite.py <subcommand>
"""

import json
import sys
import time

import numpy as np


def _sync(x):
    return float(np.asarray(x).ravel()[0])


def vgg16():
    import jax.numpy as jnp
    import jax.random as jrandom
    from deeplearning4j_tpu.optimize.solver import make_scan_train_step
    from deeplearning4j_tpu.zoo.models import VGG16

    batch, k, n = 512, 48, 2
    model = VGG16(num_classes=200, height=64, width=64, channels=3,
                  compute_dtype="bfloat16").init()

    def loss_fn(params, mstate, feats, labels, fmask, lmask, rng, it):
        # VGG16 is a MultiLayerNetwork: _loss takes raw arrays
        return model._loss(params, mstate, feats, labels, fmask,
                           lmask, rng, it)

    steps_fn = make_scan_train_step(loss_fn, model._tx)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 64, 64, 3)).astype(np.float32))
    y = np.zeros((batch, 200), np.float32)
    y[np.arange(batch), rng.integers(0, 200, batch)] = 1.0
    xs = jnp.broadcast_to(x, (k,) + x.shape)
    ys = jnp.broadcast_to(jnp.asarray(y), (k, batch, 200))
    key = jrandom.PRNGKey(0)
    ts = model.train_state
    ts, losses = steps_fn(ts, xs, ys, None, None, key)
    _sync(losses[-1])
    t0 = time.perf_counter()
    for i in range(n):
        ts, losses = steps_fn(ts, xs, ys, None, None,
                              jrandom.fold_in(key, i))
    _sync(losses[-1])
    dt = time.perf_counter() - t0
    print(json.dumps({"metric": "vgg16_64x64_bf16_train_images_per_sec",
                      "value": round(n * k * batch / dt, 1),
                      "unit": "images/sec/chip"}))


def inception():
    import jax
    import jax.numpy as jnp
    import keras
    from deeplearning4j_tpu.modelimport.keras import (
        import_keras_model_and_weights)
    import tempfile, os

    km = keras.applications.InceptionV3(weights=None,
                                        input_shape=(299, 299, 3),
                                        classes=1000)
    fd, p = tempfile.mkstemp(suffix=".h5")
    os.close(fd)
    try:
        km.save(p)
        model = import_keras_model_and_weights(p)
    finally:
        os.unlink(p)

    batch, k, n = 128, 8, 3
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 299, 299, 3)).astype(np.float32))
    xs = jnp.broadcast_to(x, (k,) + x.shape)
    params = model.train_state.params
    mstate = model.train_state.model_state

    def fwd_many(params, mstate, xs):
        def one(_, xk):
            inputs = {model.conf.network_inputs[0]: xk}
            acts, _ = model._walk(params, mstate, inputs,
                                  {"__default__": None}, False, None,
                                  stop_before_loss=False)
            out = acts[model.conf.network_outputs[0]]
            return None, jnp.sum(out)
        _, sums = jax.lax.scan(one, None, xs)
        return sums[-1]

    jf = jax.jit(fwd_many)
    _sync(jf(params, mstate, xs))
    t0 = time.perf_counter()
    for _ in range(n):
        s = jf(params, mstate, xs)
    _sync(s)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "metric": "inception_v3_299x299_f32_infer_images_per_sec",
        "value": round(n * k * batch / dt, 1),
        "unit": "images/sec/chip"}))


def bert():
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.modelimport.bert import (
        BERT_BASE, example_inputs, import_bert_base)

    seq, batch, k, n = 128, 64, 4, 3
    model, _km = import_bert_base(seq_len=seq)
    ids, pos = example_inputs(batch, seq, BERT_BASE["vocab"])
    ids = jnp.asarray(ids)
    pos = jnp.asarray(pos)
    idss = jnp.broadcast_to(ids, (k,) + ids.shape)
    poss = jnp.broadcast_to(pos, (k,) + pos.shape)
    params = model.train_state.params
    mstate = model.train_state.model_state

    def fwd_many(params, mstate, idss, poss):
        def one(_, xk):
            i, p = xk
            inputs = dict(zip(model.conf.network_inputs, (i, p)))
            acts, _ = model._walk(params, mstate, inputs,
                                  {"__default__": None}, False, None,
                                  stop_before_loss=False)
            return None, jnp.sum(acts[model.conf.network_outputs[0]])
        _, sums = jax.lax.scan(one, None, (idss, poss))
        return sums[-1]

    jf = jax.jit(fwd_many)
    _sync(jf(params, mstate, idss, poss))
    t0 = time.perf_counter()
    for _ in range(n):
        s = jf(params, mstate, idss, poss)
    _sync(s)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "metric": "bert_base_seq128_infer_tokens_per_sec",
        "value": round(n * k * batch * seq / dt, 1),
        "unit": "tokens/sec/chip"}))


def bert_train():
    """Native BERT-base-geometry training throughput: 12 blocks, width
    768, MLM-style dense head, bf16 compute, flash attention."""
    import jax.numpy as jnp
    import jax.random as jrandom
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.attention import (
        LearnedPositionalEmbedding, TransformerEncoderBlock)
    from deeplearning4j_tpu.nn.layers.feedforward import (
        EmbeddingSequenceLayer)
    from deeplearning4j_tpu.nn.layers.output import RnnOutputLayer
    from deeplearning4j_tpu.optimize.solver import make_scan_train_step
    from deeplearning4j_tpu.optimize.updaters import Adam

    vocab, width, seq, batch, k, n = 30522, 768, 128, 32, 4, 3
    b = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-4))
         .compute_dtype("bfloat16").list()
         .layer(EmbeddingSequenceLayer(n_in=vocab, n_out=width))
         .layer(LearnedPositionalEmbedding(max_len=seq)))
    for _ in range(12):
        b = b.layer(TransformerEncoderBlock(n_out=width, n_heads=12,
                                            ffn_mult=4))
    conf = (b.layer(RnnOutputLayer(n_out=vocab))
            .set_input_type(InputType.recurrent(1, seq)).build())
    model = MultiLayerNetwork(conf).init()

    def loss_fn(params, mstate, feats, labels, fmask, lmask, rng, it):
        return model._loss(params, mstate, feats, labels, fmask, lmask,
                           rng, it)

    steps_fn = make_scan_train_step(loss_fn, model._tx)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, vocab, (batch, seq)).astype(np.float32)
    lab = np.zeros((batch, seq, vocab), np.float32)
    lab[np.arange(batch)[:, None], np.arange(seq)[None, :],
        rng.integers(0, vocab, (batch, seq))] = 1.0
    xs = jnp.broadcast_to(jnp.asarray(toks), (k, batch, seq))
    ys = jnp.broadcast_to(jnp.asarray(lab), (k, batch, seq, vocab))
    key = jrandom.PRNGKey(0)
    ts = model.train_state
    ts, losses = steps_fn(ts, xs, ys, None, None, key)
    _sync(losses[-1])
    t0 = time.perf_counter()
    for i in range(n):
        ts, losses = steps_fn(ts, xs, ys, None, None,
                              jrandom.fold_in(key, i))
    _sync(losses[-1])
    dt = time.perf_counter() - t0
    print(json.dumps({
        "metric": "bert_base_seq128_bf16_train_tokens_per_sec",
        "value": round(n * k * batch * seq / dt, 1),
        "unit": "tokens/sec/chip"}))


def build_inception_finetune(batch: int = 64, k: int = 8):
    """The canonical imported-InceptionV3 fine-tune setup (BASELINE
    config 3's training half): import the Keras graph, swap the
    1000-way head for 200 classes via TransferLearning.GraphBuilder,
    train the WHOLE network (fwd+bwd+Adam) with K scanned steps per
    dispatch. Shared by ``inception_train`` and ``profile_hw.py
    inception`` so the profiler measures the EXACT graph the benchmark
    ships. Returns ``(model, steps_fn, xs, ys)``."""
    import jax.numpy as jnp
    import keras
    import os
    import tempfile

    from deeplearning4j_tpu.modelimport.keras import (
        import_keras_model_and_weights)
    from deeplearning4j_tpu.nn.transferlearning import (
        FineTuneConfiguration, TransferLearning)
    from deeplearning4j_tpu.optimize.solver import make_scan_train_step
    from deeplearning4j_tpu.optimize.updaters import Adam

    km = keras.applications.InceptionV3(weights=None,
                                        input_shape=(299, 299, 3),
                                        classes=1000)
    fd, p = tempfile.mkstemp(suffix=".h5")
    os.close(fd)
    try:
        km.save(p)
        model = import_keras_model_and_weights(p)
    finally:
        os.unlink(p)

    head = model.conf.network_outputs[0]
    # bf16 fine-tune dtype (round 5): params stay f32, convs run at MXU
    # rate — 725.5 (f32) -> 1,175.6 img/s measured, same harness
    model = (TransferLearning.GraphBuilder(model)
             .fine_tune_configuration(
                 FineTuneConfiguration.Builder().updater(Adam(1e-4))
                 .compute_dtype("bfloat16").build())
             .n_out_replace(head, 200)
             .build())

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 299, 299, 3))
                    .astype(np.float32))
    y = np.zeros((batch, 200), np.float32)
    y[np.arange(batch), rng.integers(0, 200, batch)] = 1.0
    xs = jnp.broadcast_to(x, (k,) + x.shape)
    ys = jnp.broadcast_to(jnp.asarray(y), (k, batch, 200))

    def loss_fn(params, mstate, feats, labels, fmask, lmask, rng_, it):
        return model._loss(params, mstate, (feats,), (labels,), fmask,
                           lmask, rng_, it)

    steps_fn = make_scan_train_step(loss_fn, model._tx)
    return model, steps_fn, xs, ys


def inception_train():
    """Imported-InceptionV3 FINE-TUNE throughput — see
    build_inception_finetune."""
    import jax.random as jrandom

    batch, k, n = 64, 8, 3
    model, steps_fn, xs, ys = build_inception_finetune(batch, k)
    key = jrandom.PRNGKey(0)
    ts = model.train_state
    ts, losses = steps_fn(ts, xs, ys, None, None, key)
    _sync(losses[-1])
    t0 = time.perf_counter()
    for i in range(n):
        ts, losses = steps_fn(ts, xs, ys, None, None,
                              jrandom.fold_in(key, i))
    _sync(losses[-1])
    dt = time.perf_counter() - t0
    print(json.dumps({
        "metric": "imported_inception_v3_299x299_finetune_images_per_sec",
        "value": round(n * k * batch / dt, 1),
        "unit": "images/sec/chip"}))


def build_bert_finetune(seq: int = 128, batch: int = 128, k: int = 16,
                        dtype: str = "bf16"):
    """The canonical imported-BERT fine-tune setup (BASELINE config 3
    training half): graft a mean-pool + 2-class head on the imported
    encoder via TransferLearning.GraphBuilder — the reference's flagship
    Keras-import workflow (KerasModelImport.java:41 → TransferLearning).

    Shared by ``bert_finetune`` and ``profile_hw.py bert`` so the
    profiler measures the EXACT graph the benchmark ships. Returns
    ``(ft, steps_fn, (idss, poss), ys)``.

    bf16 compute via FineTuneConfiguration (round 5): imported params
    stay f32, activations/matmuls run at MXU rate. Batch 128 (vs 32)
    keeps every matmul MXU-shaped; attention dispatches to the plain
    XLA path at seq 128 (measured crossover, benchmarks/attn_crossover).
    """
    import jax.numpy as jnp
    from deeplearning4j_tpu.modelimport.bert import (
        BERT_BASE, example_inputs, import_bert_base)
    from deeplearning4j_tpu.nn.layers.output import (
        GlobalPoolingLayer, OutputLayer, PoolingType)
    from deeplearning4j_tpu.nn.transferlearning import (
        FineTuneConfiguration, TransferLearning)
    from deeplearning4j_tpu.optimize.solver import make_scan_train_step
    from deeplearning4j_tpu.optimize.updaters import Adam

    model, _km = import_bert_base(seq_len=seq)
    enc_out = model.conf.network_outputs[0]
    ftc = FineTuneConfiguration.Builder().updater(Adam(2e-5))
    if dtype == "bf16":
        ftc = ftc.compute_dtype("bfloat16")
    ft = (TransferLearning.GraphBuilder(model)
          .fine_tune_configuration(ftc.build())
          .add_layer("pool",
                     GlobalPoolingLayer(pooling_type=PoolingType.AVG),
                     enc_out)
          .add_layer("cls", OutputLayer(n_out=2), "pool")
          .set_outputs("cls")
          .build())

    rng = np.random.default_rng(0)
    ids, pos = example_inputs(batch, seq, BERT_BASE["vocab"])
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, batch)]
    idss = jnp.broadcast_to(jnp.asarray(ids), (k,) + ids.shape)
    poss = jnp.broadcast_to(jnp.asarray(pos), (k,) + pos.shape)
    ys = jnp.broadcast_to(jnp.asarray(y), (k, batch, 2))

    def loss_fn(params, mstate, feats, labels, fmask, lmask, rng_, it):
        return ft._loss(params, mstate, feats, labels, fmask, lmask,
                        rng_, it)

    # bf16 shadow params carried through the scan (round 6): kills the
    # per-step f32→bf16 recast at the top of the loss (~6.8 ms/step
    # measured in PERF_ANALYSIS r5) — the cast rides the optimizer
    # update's epilogue instead. Bit-identical numerics.
    shadow = None
    if dtype == "bf16":
        from deeplearning4j_tpu.models.base import cast_params
        shadow = lambda p: cast_params(p, "bfloat16")
    steps_fn = make_scan_train_step(loss_fn, ft._tx, shadow_cast=shadow)
    return ft, steps_fn, (idss, poss), ys


def bert_finetune():
    """Imported-BERT-base FINE-TUNE tokens/s — see build_bert_finetune."""
    import jax.random as jrandom

    seq, batch, k, n = 128, 128, 16, 3
    ft, steps_fn, feats, ys = build_bert_finetune(seq, batch, k)
    key = jrandom.PRNGKey(0)
    ts = ft.train_state
    ts, losses = steps_fn(ts, feats, (ys,), None, None, key)
    _sync(losses[-1])
    t0 = time.perf_counter()
    for i in range(n):
        ts, losses = steps_fn(ts, feats, (ys,), None, None,
                              jrandom.fold_in(key, i))
    _sync(losses[-1])
    dt = time.perf_counter() - t0
    print(json.dumps({
        "metric": "imported_bert_base_seq128_finetune_tokens_per_sec",
        "value": round(n * k * batch * seq / dt, 1),
        "unit": "tokens/sec/chip"}))


def build_textgen_lstm(units: int = 512, seq: int = 128,
                       batch: int = 256, k: int = 16,
                       dtype: str = "f32", vocab: int = 77):
    """The BASELINE TextGenerationLSTM throughput config (scaled
    geometry: 2×LSTM-``units``, one-hot vocab inputs, RnnOutputLayer) —
    shared by the ``lstm`` bench and ``profile_hw.py lstm`` so the
    profiler measures the exact benchmarked graph."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.recurrent import LSTM
    from deeplearning4j_tpu.nn.layers.output import RnnOutputLayer
    from deeplearning4j_tpu.optimize.solver import make_scan_train_step
    from deeplearning4j_tpu.optimize.updaters import Adam

    # matches zoo TextGenerationLSTM.conf() incl. the gradient clip the
    # named model ships with — scaled geometry only
    b = (NeuralNetConfiguration.Builder().seed(123).updater(Adam(2e-3))
         .gradient_normalization("clip_value", 5.0))
    if dtype == "bf16":
        b = b.compute_dtype("bfloat16")
    conf = (b.list()
            .layer(LSTM(n_out=units))
            .layer(LSTM(n_out=units))
            .layer(RnnOutputLayer(n_out=vocab))
            .set_input_type(InputType.recurrent(vocab, seq))
            .build())
    model = MultiLayerNetwork(conf).init()

    def loss_fn(params, mstate, feats, labels, fmask, lmask, rng, it):
        return model._loss(params, mstate, feats, labels, fmask, lmask,
                           rng, it)

    steps_fn = make_scan_train_step(loss_fn, model._tx)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (batch, seq))
    x = np.eye(vocab, dtype=np.float32)[ids]          # (N, T, vocab)
    nxt = np.roll(ids, -1, axis=1)
    y = np.eye(vocab, dtype=np.float32)[nxt]
    xs = jnp.broadcast_to(jnp.asarray(x), (k,) + x.shape)
    ys = jnp.broadcast_to(jnp.asarray(y), (k, ) + y.shape)
    # prime model_state (the LSTM layers add last_h/last_c on first
    # apply; the K-step scan needs carry-in == carry-out structure) —
    # forward-only jit, much cheaper to compile than a full train step
    import jax
    import jax.random as jrandom
    _, ms = jax.jit(loss_fn)(
        model.train_state.params, model.train_state.model_state,
        jnp.asarray(x), jnp.asarray(y), None, None,
        jrandom.PRNGKey(99), model.train_state.iteration)
    model.train_state = model.train_state._replace(model_state=ms)
    return model, steps_fn, xs, ys


def lstm():
    """TextGenerationLSTM train throughput (BASELINE config: 2×LSTM-512,
    T=128, batch 256). Optional argv: dtype f32|bf16."""
    import jax.random as jrandom

    dtype = sys.argv[2] if len(sys.argv) > 2 else "f32"
    if dtype not in ("f32", "bf16"):
        sys.exit(f"unknown dtype {dtype!r}: expected f32|bf16")
    seq, batch, k, n = 128, 256, 16, 3
    model, steps_fn, xs, ys = build_textgen_lstm(
        seq=seq, batch=batch, k=k, dtype=dtype)
    key = jrandom.PRNGKey(0)
    ts = model.train_state
    ts, losses = steps_fn(ts, xs, ys, None, None, key)
    _sync(losses[-1])
    t0 = time.perf_counter()
    for i in range(n):
        ts, losses = steps_fn(ts, xs, ys, None, None,
                              jrandom.fold_in(key, i))
    _sync(losses[-1])
    dt = time.perf_counter() - t0
    print(json.dumps({
        "metric": f"textgen_lstm512_seq128_{dtype}_train_tokens_per_sec",
        "value": round(n * k * batch * seq / dt, 1),
        "unit": "tokens/sec/chip"}))


def word2vec():
    """SGNS and HS at 100k vocab on a zipf-shaped corpus (the scale the
    reference's native AggregateSkipGram targets — SkipGram.java:176)."""
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    v, n_tokens = 100_000, 3_000_000
    pairgen = "auto"
    for a in sys.argv[2:]:
        if a.startswith("--pairgen="):
            pairgen = a.split("=", 1)[1]
    rng = np.random.default_rng(0)
    # zipf-ish draw over a 100k vocab, chunked into 40-token sentences
    freq = 1.0 / np.arange(1, v + 1) ** 1.05
    freq /= freq.sum()
    tokens = rng.choice(v, size=n_tokens, p=freq)
    words = np.char.add("w", tokens.astype("U7"))
    seqs = [words[i:i + 40].tolist() for i in range(0, n_tokens, 40)]

    for label, kw in (("sgns", {}),
                      ("hs", {"use_hierarchic_softmax": True}),
                      ("cbow", {"use_cbow": True})):
        # 64k-pair scanned superchunks (8 chunks/dispatch) amortize the
        # per-dispatch overhead; warm = steady-state throughput, cold =
        # warm + the one-off XLA compile (cached for the process)
        times = []          # drained e2e
        pipe_times = []     # fit-return (the host/producer pipeline rate)
        for _trial in range(2):
            model = Word2Vec(layer_size=128, window_size=5, negative=5,
                             min_word_frequency=1, epochs=1,
                             batch_size=65536, seed=3, pairgen=pairgen,
                             **kw)
            model.build_vocab(seqs)
            t0 = time.perf_counter()
            model.fit(seqs)
            pipe_times.append(time.perf_counter() - t0)
            # drain the async device queue INSIDE the timer (round-5
            # methodology fix): fit() returns with dispatches queued,
            # so excluding the tail overstates e2e. The pipeline
            # (fit-return) rate is reported too: where transfers are
            # fast, host pair generation is the bound. Drain via a
            # 4-byte element read — np.asarray(syn0) would pull the
            # whole ~50 MB table back INSIDE the timer.
            _sync(model.syn0[0, 0])
            times.append(time.perf_counter() - t0)
        print(json.dumps({
            "metric": f"word2vec_{label}_100kvocab_tokens_per_sec",
            "value": round(n_tokens / times[1], 1),
            "cold_value": round(n_tokens / times[0], 1),
            "pipeline_value": round(n_tokens / pipe_times[1], 1),
            "unit": "tokens/sec (warm, device-drained; pipeline_value ="
                    " fit-return rate)",
            "pairgen": pairgen,
            "vocab": int(model.vocab.num_words())}))


def doc2vec_producer():
    """DBOW host pair-generation rate (the r5 measured bound: 249k
    tokens/s fit-return, "per-doc host pairgen bound") at the r5
    geometry — 20k docs × 100 tokens, 50k vocab. Device dispatch is
    no-op'd so both numbers isolate the HOST producer: the round-6
    corpus-level walk (_window_slabs + per-slot label gathers,
    ``pairgen="legacy"`` pinned for metric continuity) vs the r5
    per-doc loop it replaced (inlined here as the baseline).

    ``--native-ab`` runs the round-11 CI gate instead: interleaved
    native-vs-numpy A/B of the FUSED producer (nlp/pairgen.py), failing
    (exit 1) unless native >= fallback tokens/s AND both arms hand the
    device a bitwise-identical dispatch stream (sha256 over every prep
    array). ``--smoke`` shrinks the geometry for the runtests.sh tier."""
    from deeplearning4j_tpu.nlp import skipgram as sk
    from deeplearning4j_tpu.nlp.paragraph_vectors import ParagraphVectors
    from deeplearning4j_tpu.nlp.sentence_iterators import LabelledDocument
    from deeplearning4j_tpu.nlp.sequence_vectors import _PairStream

    native_ab = "--native-ab" in sys.argv[2:]
    if "--smoke" in sys.argv[2:]:
        v, n_docs, doc_len = 5_000, 2_000, 60
    else:
        v, n_docs, doc_len = 50_000, 20_000, 100
    rng = np.random.default_rng(0)
    freq = 1.0 / np.arange(1, v + 1) ** 1.05
    freq /= freq.sum()
    tokens = rng.choice(v, size=n_docs * doc_len, p=freq)
    words = np.char.add("w", tokens.astype("U7"))
    docs = [LabelledDocument(" ".join(words[i * doc_len:(i + 1) * doc_len]),
                             [f"DOC_{i}"]) for i in range(n_docs)]
    n_tokens = n_docs * doc_len

    def per_doc_produce(pv, tokenized, total, chunk):
        # the r5 producer this round replaced — per-doc numpy
        stream = _PairStream(pv, chunk, total, sink=lambda prep: None)
        W = pv.window_size
        for _ep in range(pv.epochs):
            for toks, labels in tokenized:
                idxs = np.asarray(pv._indices(toks), np.int32)
                lidxs = np.asarray(
                    [i for i in (pv.vocab.index_of(lb) for lb in labels)
                     if i >= 0], np.int32)
                n = len(idxs)
                if n and len(lidxs):
                    stream.push(np.repeat(lidxs, n),
                                np.tile(idxs, len(lidxs)))
                    stream.seen += len(lidxs) * n
                if n >= 2:
                    grid, valid = sk.window_grid(n, W, pv._rng)
                    stream.push(np.repeat(idxs, valid.sum(axis=1)),
                                idxs[grid[valid]])
                stream.seen += n
        stream.finish()

    def make_pv(pairgen):
        pv = ParagraphVectors(dm=False, layer_size=128, window_size=5,
                              negative=5, min_word_frequency=1, epochs=1,
                              batch_size=65536, seed=3,
                              overlap_pairgen=False, pairgen=pairgen)
        tokenized = [(d.content.split(), d.labels) for d in docs]
        pv._label_set = {lb for _t, lbs in tokenized for lb in lbs}
        pv.build_vocab([t for t, _ in tokenized],
                       special_tokens=sorted(pv._label_set))
        pv._init_tables()
        pv._dispatch_chunks = lambda prep: None   # host producer only
        return pv, tokenized

    if native_ab:
        _doc2vec_native_ab(make_pv, n_tokens)
        return

    out = {}
    for label in ("corpus_level", "per_doc_r5"):
        pv, tokenized = make_pv("legacy")
        total = max(1, n_tokens * 2)
        best = np.inf
        for _trial in range(2):
            t0 = time.perf_counter()
            if label == "corpus_level":
                pv._fit_fast_dbow(tokenized, total)
            else:
                chunk = pv._pair_chunk_size(
                    (total // 2) * (pv.window_size + 2))
                per_doc_produce(pv, tokenized, total, chunk)
            best = min(best, time.perf_counter() - t0)
        out[label] = n_tokens / best
    print(json.dumps({
        "metric": "doc2vec_dbow_host_producer_tokens_per_sec",
        "value": round(out["corpus_level"], 1),
        "per_doc_r5_value": round(out["per_doc_r5"], 1),
        "speedup": round(out["corpus_level"] / out["per_doc_r5"], 2),
        "unit": "tokens/sec (host pair generation only, dispatch "
                "no-op'd; 20k docs x 100 tokens, 50k vocab)"}))


def _doc2vec_native_ab(make_pv, n_tokens):
    """The --native-ab gate body: bitwise stream equality (one hashed
    pass per arm) then interleaved best-of-2 timing with the dispatch
    no-op'd. Skips cleanly (exit 0) when the native library is absent —
    runtests.sh runs this tier only after a successful build, but a
    toolchain-less checkout must still pass the suite."""
    import hashlib
    from deeplearning4j_tpu.utils import native as native_lib

    if not native_lib.pairgen_available():
        print(json.dumps({"metric": "doc2vec_producer_native_ab",
                          "skipped": "native pairgen unavailable"}))
        return
    total = max(1, n_tokens * 2)
    arms = {}
    for pairgen in ("auto", "numpy"):
        pv, tokenized = make_pv(pairgen)
        h = hashlib.sha256()

        def hash_sink(prep, _h=h):
            for a in prep[1:]:
                _h.update(np.ascontiguousarray(a).tobytes())
        pv._dispatch_chunks = hash_sink
        pv._fit_fast_dbow(tokenized, total)
        pv._dispatch_chunks = lambda prep: None
        arms[pairgen] = (pv, tokenized, h.hexdigest())
    best = {p: np.inf for p in arms}
    for _trial in range(2):              # interleaved A/B
        for p, (pv, tokenized, _hx) in arms.items():
            t0 = time.perf_counter()
            pv._fit_fast_dbow(tokenized, total)
            best[p] = min(best[p], time.perf_counter() - t0)
    rate = {p: n_tokens / best[p] for p in best}
    bitwise_equal = arms["auto"][2] == arms["numpy"][2]
    ok = bitwise_equal and rate["auto"] >= rate["numpy"]
    print(json.dumps({
        "metric": "doc2vec_producer_native_ab",
        "native_tokens_per_sec": round(rate["auto"], 1),
        "fallback_tokens_per_sec": round(rate["numpy"], 1),
        "speedup": round(rate["auto"] / rate["numpy"], 2),
        "bitwise_equal": bitwise_equal,
        "ok": ok,
        "unit": "tokens/sec (fused producer, dispatch no-op'd)"}))
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    globals()[sys.argv[1]]()
