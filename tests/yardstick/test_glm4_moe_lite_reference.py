"""glm47-flash-ep8 at a preset small enough for the CPU (hidden 32, a
dense layer and two expert layers, the multi-token-prediction module, 4
heads with latents of 12 and 8, heads of 6 + 4 and values of 8, 4
experts held of a router's 16, top-3, T = 24, vocabulary 64, float32):
the system against the plain reference, and each of the mechanisms the
configuration forced against the form of it that can be checked by
hand."""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers.base import LayerContext
from yardstick import cells
from yardstick.weights import init_on_device

NAME = "glm47-flash-ep8"
CELL = NAME + ".fit-seq8k"
TINY = {"hidden_size": 32, "num_hidden_layers": 3,
        "num_attention_heads": 4, "q_lora_rank": 12, "kv_lora_rank": 8,
        "qk_nope_head_dim": 6, "qk_rope_head_dim": 4, "v_head_dim": 8,
        "intermediate_size": 48, "moe_intermediate_size": 16,
        "n_routed_experts": 4, "router_width": 16, "expert_parallel_rank": 1,
        "num_experts_per_tok": 3, "vocab_size": 64, "seq_len": 24,
        "batch": 2, "examples": 8, "repeated_span": 6,
        "compute_dtype": "float32", "router_aux_loss_coef": 0.05,
        "rope_theta": 1e4, "updater": {"type": "Adam", "learning_rate": 1e-2}}
LAYERS = ("layer0", "layer1", "layer2", "mtp")


def reference_module():
    return cells.load_file_module(
        cells.ROOT / "yardstick" / "reference" / "glm4_moe_lite.py")


@pytest.fixture(scope="module")
def tiny():
    cell = cells.resolve_cell(CELL)
    cell = dataclasses.replace(cell, config={**cell.config, **TINY})
    build = cells.load_build(cell)
    model = init_on_device(build.build(cell.config, 5), 5)
    return cell.config, build, model, cells.load_reference(cell)


def _system_heads(model, ids):
    """Both heads' logits as the system computes them."""
    params, state = model.train_state.params, model.train_state.model_state
    acts, _ = model._walk(params, state, {"ids": jnp.asarray(ids)},
                          {"__default__": None}, False, None,
                          stop_before_loss=False)
    head = model._nodes["lm_head"].layer
    return acts["lm_head"], head._logits(params["lm_head"], acts["mtp"])


def test_both_heads_and_both_terms_agree_with_the_reference(tiny):
    cfg, build, model, reference = tiny
    batch = build.rows_with_labels(cfg, 5, 4)
    assert batch.features.shape == batch.labels.shape == (4, 24)
    ts = model.train_state
    feats, labels = (batch.features,), (batch.labels,)
    want = float(reference.loss(cfg, ts.params, ts.model_state, feats,
                                labels))
    got = float(model.score(batch))
    # float32 on both sides: the order of summation alone
    assert np.isfinite(want) and abs(got - want) / want < 1e-5
    main, mtp = _system_heads(model, batch.features)
    ref_main, ref_mtp = reference.heads(cfg, ts.params, ts.model_state,
                                        feats)
    for a, b in ((main, ref_main), (mtp, ref_mtp)):
        assert a.shape == b.shape == (4, 24, 64)
        assert np.abs(np.asarray(a) - b).max() < 2e-5 * np.abs(b).max()
    # ``output`` is the next-token head: the same program, jitted apart
    assert np.allclose(model.output(batch.features), main, rtol=1e-6,
                       atol=1e-6)
    # the two cross-entropies the head leaves in its state
    _, new_state = model._loss(ts.params, ts.model_state, feats, labels,
                               None, None, None, ts.iteration)
    terms = np.asarray(new_state["lm_head"]["lm_loss_terms"])
    ref_terms = reference.loss_terms(cfg, ts.params, ts.model_state, feats,
                                     labels)
    assert terms == pytest.approx(np.asarray(ref_terms[:2]), rel=1e-5)
    assert want == pytest.approx(
        float(ref_terms[0]) + 0.3 * float(ref_terms[1])
        + 0.05 * float(ref_terms[2]), rel=1e-6)


def test_the_harness_compares_both_losses_and_the_balance_term(tiny):
    """``check_batch`` is ``rows_with_labels``, labels and all: the score
    the harness compares is both cross-entropies, the module's weighted,
    plus the balance term of the two expert layers and the module's, on
    both sides, and each part moves it."""
    cfg, build, model, reference = tiny
    rows, check = build.rows_with_labels(cfg, 7, 2), build.check_batch(
        cfg, 7, 2)
    assert np.array_equal(check.features, rows.features)
    assert np.array_equal(check.labels, rows.labels)
    assert (np.asarray(check.labels)[:, :-1] >= 0).all()
    ts = model.train_state
    feats, labels = (check.features,), (check.labels,)
    want = float(reference.loss(cfg, ts.params, ts.model_state, feats,
                                labels))
    main, mtp, balance = (float(v) for v in reference.loss_terms(
        cfg, ts.params, ts.model_state, feats, labels))
    assert want == pytest.approx(main + 0.3 * mtp + 0.05 * balance,
                                 rel=1e-6)
    # three expert layers (the module's among them), each k = 3 when even
    assert balance >= 3 * 3 * 0.99
    assert min(main, 0.3 * mtp) > 0.05 * balance
    assert float(model.score(check)) == pytest.approx(want, rel=1e-5)
    # a wrong target for the module moves the score the harness compares
    off = {**cfg, "mtp_loss_weight": 0.0}
    assert float(reference.loss(off, ts.params, ts.model_state, feats,
                                labels)) < want * (1 - 1e-2)


def test_parameter_gradients_agree_with_the_reference(tiny):
    cfg, build, model, reference = tiny
    batch = build.rows_with_labels(cfg, 6, 2)
    ts = model.train_state
    ids, labels = jnp.asarray(batch.features), jnp.asarray(batch.labels)

    def system(params):
        return model._loss(params, ts.model_state, (ids,), (labels,), None,
                           None, None, ts.iteration)[0]

    got = jax.jit(jax.grad(system))(ts.params)
    want = jax.jit(jax.grad(reference.loss_fn(cfg)))(ts.params, ids, labels)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    # embed; a layer's 2 norms and 7 latent-attention leaves, then 2 of a
    # dense MLP or 7 of the experts (router, 3 routed, 3 shared); the
    # final norm; the module's 3 norms and W_eh on an expert layer's 16;
    # the head's one matrix
    assert len(flat_got) == len(flat_want) == (
        1 + 11 + 2 * 16 + 1 + (4 + 16) + 1)
    for path, g in flat_got:
        w = flat_want[path]
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, path                 # every parameter is reached
        assert float(jnp.linalg.norm(g - w)) < 2e-4 * scale, path


def test_the_reference_reads_the_bias_the_state_holds(tiny):
    """After some steps the routers' bias has moved, the module's too;
    the system's score and the reference handed the same state agree, and
    differ from the reference at a zero bias."""
    cfg, build, _, reference = tiny
    fast = {**cfg, "bias_update_rate": 0.05}
    model = init_on_device(build.build(fast, 4), 4)
    model.fit(build.train_set(fast, 4, 2), epochs=3)
    ts = model.train_state
    biased = sorted(k for k, v in ts.model_state.items()
                    if "moe_router_bias" in v)
    assert biased == ["layer1", "layer2", "mtp"]
    for name in biased:
        bias = np.asarray(ts.model_state[name]["moe_router_bias"])
        assert bias.shape == (16,) and np.abs(bias).max() > 0.1
        assert set(np.unique(np.round(np.abs(bias) / 0.05, 3)) % 1) == {0.0}
    batch = build.rows_with_labels(fast, 9, 4)
    got = float(model.score(batch))
    want = float(reference.loss(fast, ts.params, ts.model_state,
                                (batch.features,), (batch.labels,)))
    unbiased = float(reference.loss(fast, ts.params, {}, (batch.features,),
                                    (batch.labels,)))
    assert abs(got - want) / want < 1e-5
    assert abs(unbiased - want) / want > 1e-4


def test_fit_trains_the_zoo_model_and_it_round_trips(tiny, tmp_path):
    """``zoo_model`` reads the release's keys: a dense first layer, expert
    layers after it under a sigmoid router at scale 1.8 with one ungated
    shared expert, latent attention everywhere, and the module between
    the final norm, the embedding and the one head. ``fit()`` lowers the
    loss and leaves both terms in the head's state and in a gauge; a save
    and a restore keep outputs, weights and the routers' bias."""
    from deeplearning4j_tpu.models.computation_graph import (
        ComputationGraph)
    from deeplearning4j_tpu.models.serialization import (
        restore_computation_graph, save_model)
    from deeplearning4j_tpu.nn.graph.config import (
        ComputationGraphConfiguration)
    from deeplearning4j_tpu.observe.registry import default_registry
    cfg, build, _, _ = tiny
    conf = build.zoo_model(cfg, 3).conf()
    text = conf.to_json()
    again = ComputationGraphConfiguration.from_json(text)
    assert again.to_json() == text
    nodes = {n.name: n for n in again.nodes}
    kinds = {name: type(n.layer).__name__ for name, n in nodes.items()
             if n.layer is not None}
    assert kinds == {"embed": "TokenEmbedding",
                     "layer0": "LatentDecoderBlock",
                     "layer1": "LatentDecoderBlock",
                     "layer2": "LatentDecoderBlock", "norm": "RMSNorm",
                     "mtp": "MultiTokenPredictionBlock",
                     "lm_head": "MultiTokenLMOutputLayer"}
    assert tuple(nodes["mtp"].inputs) == ("norm", "embed")
    assert tuple(nodes["lm_head"].inputs) == ("norm", "mtp")
    assert [nodes[n].layer.ffn for n in LAYERS] == [
        "dense", "experts", "experts", "experts"]
    moe = nodes["layer1"].layer._expert_layer()
    assert (moe.expert_form, moe.router_scoring, moe.shared_gate,
            moe.shared_hidden, moe.routed_scale, moe.top_k,
            moe.bias_update_rate) == ("gated", "sigmoid", False, 16, 1.8, 3,
                                      1e-3)
    assert moe.held == (4, 5, 6, 7) and moe.num_experts == 16
    attn = nodes["mtp"].layer._parts()[0]
    assert (attn.q_lora_rank, attn.kv_lora_rank, attn.qk_nope_head_dim,
            attn.qk_rope_head_dim, attn.v_head_dim) == (12, 8, 6, 4, 8)
    assert nodes["lm_head"].layer.mtp_weight == 0.3
    assert not hasattr(nodes["lm_head"].layer, "norm")
    model = ComputationGraph(again).init(3)
    assert model.num_params() == build.parameter_count(cfg)["on_the_chip"]
    rows = build._dataset(cfg, 3, cfg["examples"])
    first = float(model.score(rows))
    model.fit(build.train_set(cfg, 3, 2), epochs=12)
    assert np.isfinite(model.score())
    assert float(model.score(rows)) < first - 0.3
    row = np.asarray(model.train_state.model_state["mtp"]["moe_routing"])
    assert row[0] > 0 and row[1] >= row[2] > 0 and row[3] == 0
    terms = np.asarray(
        model.train_state.model_state["lm_head"]["lm_loss_terms"])
    assert (terms > 0).all() and np.isfinite(terms).all()
    gauge = default_registry().get_metric("dl4j_loss_term").series()
    assert gauge[(("layer", "lm_head"), ("term", "next_token"))] == \
        pytest.approx(float(terms[0]))
    assert gauge[(("layer", "lm_head"), ("term", "mtp"))] == pytest.approx(
        float(terms[1]))
    path = str(tmp_path / "glm.zip")
    save_model(model, path)
    back = restore_computation_graph(path)
    assert back.conf.to_json() == text
    assert np.array_equal(np.asarray(back.output(rows.features)),
                          np.asarray(model.output(rows.features)))
    for a, b in zip(jax.tree_util.tree_leaves(back.train_state.model_state),
                    jax.tree_util.tree_leaves(
                        model.train_state.model_state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("modules", [0, 2])
def test_the_zoo_builds_the_releases_one_module_alone(tiny, modules):
    """``num_nextn_predict_layers`` is the release's 1: no model without
    the module, no chain of them."""
    cfg, build, _, _ = tiny
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        dataclasses.replace(build.zoo_model(cfg, 3),
                            num_nextn_predict_layers=modules).conf()


def test_a_recomputing_model_computes_what_the_plain_one_does(tiny):
    cfg, build, _, _ = tiny
    batch = build.rows_with_labels(cfg, 2, 2)
    ids, labels = jnp.asarray(batch.features), jnp.asarray(batch.labels)
    out = []
    for recompute in (True, False):
        model = init_on_device(
            build.build({**cfg, "recompute": recompute}, 7), 7)
        ts = model.train_state

        def loss(params):
            return model._loss(params, ts.model_state, (ids,), (labels,),
                               None, None, None, ts.iteration)[0]
        out.append(jax.jit(jax.value_and_grad(loss))(ts.params))
    (l0, g0), (l1, g1) = out
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-6 * float(
            jnp.linalg.norm(b))


# ---- the multi-token-prediction module and the one head -------------------

def _mtp_head(weight=0.3):
    from deeplearning4j_tpu.nn.layers.decoder import MultiTokenLMOutputLayer
    head = MultiTokenLMOutputLayer(n_out=11, mtp_weight=weight)
    params = head.initialize(jax.random.PRNGKey(1), RecurrentType(6, None))
    rng = np.random.default_rng(4)
    h, g = (jnp.asarray(rng.normal(size=(2, 7, 6)), jnp.float32)
            for _ in range(2))
    ids = rng.integers(0, 11, (2, 7))
    return head, params, h, g, ids


def test_the_module_is_scored_on_the_token_after_the_next():
    """By hand on a row of 7: position t of the module's logits is
    scored against ``ids[t + 2]`` and the last two positions count for
    nothing; the next-token term against ``ids[t + 1]``."""
    from deeplearning4j_tpu.nn.layers.decoder import next_token_labels
    head, params, h, g, ids = _mtp_head()
    labels = jnp.asarray(next_token_labels(ids))
    loss, state = head.compute_loss(params, head.init_state(None), (h, g),
                                    labels, LayerContext(train=True))

    def ce(x, targets):
        logp = np.asarray(jax.nn.log_softmax(x @ params["W"], -1))
        return -np.mean([logp[n, t, targets[n, t]]
                         for n in range(2) for t in range(len(targets[n]))])

    main = ce(h[:, :6], ids[:, 1:])
    mtp = ce(g[:, :5], ids[:, 2:])
    assert np.asarray(state["lm_loss_terms"]) == pytest.approx(
        [main, mtp], rel=1e-5)
    assert float(loss) == pytest.approx(main + 0.3 * mtp, rel=1e-5)
    # what the module reads at the last two positions changes nothing
    moved = g.at[:, 5:].add(3.0)
    again, _ = head.compute_loss(params, {}, (h, moved), labels,
                                 LayerContext(train=True))
    assert float(again) == pytest.approx(float(loss), rel=1e-6)
    assert np.array_equal(np.asarray(head.apply(params, {}, (h, g), None)[0]),
                          np.asarray(h @ params["W"]))
    with pytest.raises(ValueError, match="next-token"):
        head.compute_loss(params, {}, (h, g), jnp.zeros((2, 7, 2)),
                          LayerContext(train=True))


def test_both_losses_reach_the_one_head_and_their_gradients_add():
    """One matrix ``W``: the gradient of the weighted sum is the
    next-token term's plus the weight times the module's, and neither is
    zero."""
    from deeplearning4j_tpu.nn.layers.decoder import next_token_labels
    head, params, h, g, ids = _mtp_head()
    assert set(params) == {"W"}
    labels = jnp.asarray(next_token_labels(ids))
    ctx = LayerContext(train=True)

    def grad(weight):
        layer = dataclasses.replace(head, mtp_weight=weight)
        return jax.grad(lambda p: layer.compute_loss(
            p, {}, (h, g), labels, ctx)[0])(params)["W"]

    main, both, unit = grad(0.0), grad(0.3), grad(1.0)
    mtp = unit - main
    assert float(jnp.linalg.norm(main)) > 0 and float(
        jnp.linalg.norm(mtp)) > 0
    assert np.allclose(both, main + 0.3 * mtp, atol=1e-6)


def test_the_module_reads_the_next_tokens_embedding(tiny):
    """``MultiTokenPredictionBlock`` at position i reads the embedding of
    position i + 1 and, at the last, zeros: the first position's embedding
    reaches nothing, the last one's reaches the positions from T - 2 on
    (the module's attention is causal), and the output is the block
    written out from its parts."""
    from deeplearning4j_tpu.nn.layers.normalization import rms_norm
    cfg, build, model, _ = tiny
    block = model._nodes["mtp"].layer
    params = model.train_state.params["mtp"]
    state = model.train_state.model_state["mtp"]
    rng = np.random.default_rng(5)
    h, e = (jnp.asarray(rng.normal(size=(2, 24, 32)), jnp.float32)
            for _ in range(2))
    ctx = LayerContext(train=False)

    def run(emb):
        return np.asarray(block.apply(params, state, (h, emb), ctx)[0])

    base = run(e)
    assert np.array_equal(run(e.at[:, 0].add(5.0)), base)
    moved = run(e.at[:, -1].add(5.0))
    assert np.array_equal(moved[:, :22], base[:, :22])
    assert not np.allclose(moved[:, 22], base[:, 22], atol=1e-4)
    with jax.default_matmul_precision("highest"):
        later = jnp.concatenate([e[:, 1:], jnp.zeros_like(e[:, :1])], 1)
        u = jnp.concatenate([rms_norm(later, params["enorm"]["w"], 1e-5),
                             rms_norm(h, params["hnorm"]["w"], 1e-5)], -1)
        g, _ = block._block(params, state, u @ params["W_eh"], ctx)
        want = rms_norm(g, params["head_norm"]["w"], 1e-5)
    assert np.abs(base - np.asarray(want)).max() < 1e-5 * np.abs(want).max()


# ---- the configuration file and the arithmetic ----------------------------

def test_the_files_parameter_table_is_parameter_counts():
    """``parameter_count`` at the published widths, by shapes alone,
    against the model's own count (no weight is made)."""
    cell = cells.resolve_cell(CELL)
    build = cells.load_build(cell)
    count = build.parameter_count(cell.config)
    assert count["latent_attention_with_norms"] == 21_759_232
    assert count["dense_layer"] == 84_677_888
    assert count["expert_layer"] == 106_829_056
    assert count["mtp_module"] == 115_223_808
    assert count["embedding_head_and_final_norm"] == 79_300_608
    assert count["on_the_chip"] == 706_518_528
    assert count["bytes_at_16_per_parameter"] == 11_304_296_448
    for key, value in count.items():
        assert cell.config["parameters"][key] == value, key
    model = build.build(cell.config, 0)
    shapes = jax.eval_shape(lambda: model.init(0).train_state)
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes.params)) == count["on_the_chip"]
    biases = [v["moe_router_bias"].shape
              for v in shapes.model_state.values()
              if "moe_router_bias" in v]
    assert biases == [(64,)] * 5
    t = 8192
    pairs = t * (t + 1) // 2
    flops = build.train_flops_per_example(cell.config)
    assert 29.6e12 < flops < 29.8e12
    work, nbytes = build.latent_attention_work(cell.config)
    assert work == 6 * (6 * t * 21_757_952 + 3 * 2 * pairs * 20 * 512)
    assert nbytes == 3 * 6 * (21_757_952 + t * (1344 + 20 * 1024)) * 2
    assert work / 197e12 > nbytes / 819e9          # bound by operations
    assert 0.6 < work / flops < 0.66


def test_the_configuration_file_states_its_source_cuts_and_limit():
    """What ``test_cells.py`` holds of every configuration's file, held
    here for this one too (its own case trips over a pattern that reads
    the ``hidden`` of ``num_hidden_layers`` as a width, PERF.md section 7),
    and every number of the catalog's config under the same key."""
    manifest = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    entry, = [c for c in manifest["configs"] if c["name"] == NAME]
    path = cells.ROOT / entry["file"]
    assert any(str(path.relative_to(cells.ROOT)).startswith(p + "/")
               for p in manifest["paths"])
    body = json.loads(path.read_text())
    assert body["source"] == entry["source"]
    assert body["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert body["published"] == {"num_hidden_layers": 47,
                                 "n_routed_experts": 64,
                                 "vocab_size": 154880}
    assert (body["num_hidden_layers"], body["n_routed_experts"],
            body["router_width"], body["vocab_size"]) == (
        5, 8, 64, 154880 // 8)
    published = {
        "attention_bias": False, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "kv_lora_rank": 512,
        "max_position_embeddings": 202752, "model_type": "glm4_moe_lite",
        "moe_intermediate_size": 1536, "n_group": 1, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 20,
        "num_experts_per_tok": 4, "num_key_value_heads": 20,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "q_lora_rank": 768, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 1.8, "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 256}
    for key, value in published.items():
        assert body[key] == value, key
    for key in ("mtp_loss_weight", "mtp_hidden_state", "rotary_pairing",
                "initializer_range", "bias_update_rate",
                "router_aux_loss_coef", "seq_len", "batch"):
        assert key in body["assumed"], key
    assert body["mtp_loss_weight"] == 0.3
    assert body["departures"] and "8 chips" in body["deployment"]
    assert body["batch"] == 1 and body["seq_len"] == 8192
    assert body["loss_tolerance"] > 0 and body["loss_tolerance_why"]
    assert len(body["why"]) <= 200


@pytest.mark.parametrize("control,least,most", [
    ({}, 0.0, 0.0),
    ({"control_operand_dtype": "bfloat16"}, 1e-4, 3e-2),
    ({"control_operand_dtype": "float8_e4m3fn"}, 3e-2, 1.0),
], ids=["none", "operands_bfloat16", "operands_float8"])
def test_the_references_controls_round_what_they_say(tiny, control, least,
                                                     most):
    """The reference in a lower precision, which the chip check's limits
    are set against: both heads' logits leave the float32 ones by a share
    of their spread that grows as the type shrinks; with no control, by
    nothing."""
    cfg, build, model, reference = tiny
    ts = model.train_state
    ids = (jnp.asarray(build.rows_with_labels(cfg, 9, 2).features),)
    want = reference.heads(cfg, ts.params, ts.model_state, ids)
    got = reference.heads({**cfg, **control}, ts.params, ts.model_state,
                          ids)
    for a, b in zip(got, want):
        apart = float(jnp.sqrt(jnp.mean((a - b) ** 2)) / jnp.std(b))
        assert least <= apart <= most


@pytest.mark.parametrize("compute,bands", [
    ("float32", {"logits": 1e-4, "mtp_logits": 1e-4, "loss": 1e-5,
                 "dense": 1e-3, "latent": 1e-3, "routed": 1e-3}),
    ("bfloat16", {"logits": 0.02, "mtp_logits": 0.02, "loss": 2e-2,
                  "dense": 0.4, "latent": 0.4, "routed": 0.5}),
])
def test_the_system_meets_stated_bands_that_the_float8_control_fails(
        compute, bands):
    """Both heads' logits (rms over the reference's spread), the loss and
    gradients by kind, system against float32 reference on seeded
    weights: tight at float32 compute, inside stated bands at bfloat16;
    the reference with float8 operands is outside the bfloat16 bands on
    both heads' logits and on every kind of gradient."""
    cell = cells.resolve_cell(CELL)
    cfg = {**cell.config, **TINY, "compute_dtype": compute}
    build, reference = cells.load_build(cell), cells.load_reference(cell)
    model = init_on_device(build.build(cfg, 11), 11)
    ts = model.train_state
    batch = build.rows_with_labels(cfg, 11, 4)
    ids, labels = jnp.asarray(batch.features), jnp.asarray(batch.labels)

    def readings(heads, loss, grads):
        want_heads = reference.heads(cfg, ts.params, ts.model_state, (ids,))
        want_loss = float(reference.loss(cfg, ts.params, ts.model_state,
                                         (ids,), (labels,)))
        want = dict(jax.tree_util.tree_leaves_with_path(jax.jit(jax.grad(
            reference.loss_fn(cfg)))(ts.params, ids, labels)))
        worst = {"dense": 0.0, "latent": 0.0, "routed": 0.0}
        for path, g in jax.tree_util.tree_leaves_with_path(grads):
            name = jax.tree_util.keystr(path)
            kind = ("routed" if name.endswith((
                "['router']", "['w_gate']", "['w_up']", "['w_down']"))
                else "latent" if "['mixer']" in name else "dense")
            worst[kind] = max(worst[kind], float(
                jnp.linalg.norm(g - want[path])
                / jnp.linalg.norm(want[path])))
        out = {"loss": abs(float(loss) - want_loss) / want_loss, **worst}
        for name, a, b in zip(("logits", "mtp_logits"), heads, want_heads):
            out[name] = float(jnp.sqrt(jnp.mean((a - b) ** 2)) / jnp.std(b))
        return out

    def system(params):
        return model._loss(params, ts.model_state, (ids,), (labels,), None,
                           None, None, ts.iteration)[0]

    got = readings(_system_heads(model, batch.features), model.score(batch),
                   jax.jit(jax.grad(system))(ts.params))
    for name, limit in bands.items():
        assert got[name] < limit, (name, got)
    if compute == "bfloat16":
        low = {**cfg, "control_operand_dtype": "float8_e4m3fn"}
        control = readings(
            reference.heads(low, ts.params, ts.model_state, (ids,)),
            reference.loss(low, ts.params, ts.model_state, (ids,),
                           (labels,)),
            jax.jit(jax.grad(reference.loss_fn(low)))(ts.params, ids, labels))
        for name in ("logits", "mtp_logits", "dense", "latent", "routed"):
            assert control[name] > bands[name], (name, control)


# ---- the expert layer: shares, scale and bias -----------------------------

@pytest.mark.parametrize("layer", ["layer1", "mtp"])
def test_the_eight_shares_add_up_to_the_whole_layer(layer):
    """An expert layer of the model (``layer1``) and the module's
    (``mtp``), each cut into 8 shares of a router of 32 (width 24): each
    share routes over all 32 outputs under the sigmoid router, its bias
    and ``routed_scaling_factor`` 1.8, and computes its own 4 experts; the
    shared expert, which every chip computes alike, is counted once; the
    blocks of all the shares add up to the uncut block (the residual and
    the attention, which every chip computes alike, counted once too) as
    the reference computes it."""
    from deeplearning4j_tpu.nn.layers.decoder import (
        EXPERTS, LatentDecoderBlock, MultiTokenPredictionBlock)
    reference = reference_module()
    rng = np.random.default_rng(2)
    e, d, shares = 32, 32, 8
    per = e // shares
    kind = MultiTokenPredictionBlock if layer == "mtp" else LatentDecoderBlock
    whole = kind(n_out=d, ffn=EXPERTS, n_heads=4, q_lora_rank=12,
                 kv_lora_rank=8, qk_nope_head_dim=6, qk_rope_head_dim=4,
                 v_head_dim=8, rope_theta=1e4, num_experts=e,
                 expert_hidden=24, shared_hidden=24, top_k=4,
                 routed_scale=1.8)
    params = whole.initialize(jax.random.PRNGKey(1), RecurrentType(d, None))
    params["moe"]["router"] = params["moe"]["router"] * 10.0
    bias = jnp.asarray(rng.normal(size=e) * 0.05, jnp.float32)
    state = {**whole.init_state(None), "moe_router_bias": bias}
    x = jnp.asarray(rng.normal(size=(2, 20, d)), jnp.float32)
    inputs = (x, jnp.asarray(rng.normal(size=(2, 20, d)), jnp.float32)) \
        if layer == "mtp" else x
    ctx = LayerContext(train=False)
    routed = ("w_gate", "w_up", "w_down")
    cfg = {"hidden_size": d, "num_attention_heads": 4, "q_lora_rank": 12,
           "kv_lora_rank": 8, "qk_nope_head_dim": 6, "qk_rope_head_dim": 4,
           "v_head_dim": 8, "rope_theta": 1e4, "rms_norm_eps": 1e-5,
           "n_routed_experts": e, "router_width": e,
           "num_experts_per_tok": 4, "routed_scaling_factor": 1.8}
    with jax.default_matmul_precision("highest"):
        if layer == "mtp":
            want, _ = reference._mtp(cfg, params, bias, *inputs)
        else:
            want, _ = reference._block(cfg, False, params, bias, x)
        if layer == "mtp":
            # the module's block reads u; its last norm is not linear, so
            # the blocks' outputs are added up before it
            emb = inputs[1]
            u = jnp.concatenate([
                whole._norm(params, "enorm", jnp.concatenate(
                    [emb[:, 1:], jnp.zeros_like(emb[:, :1])], 1)),
                whole._norm(params, "hnorm", x)], -1) @ params["W_eh"]
        else:
            u = x
        # what every share computes alike: the input and the attention
        alike = u + whole._parts()[0].apply(
            params["mixer"], {}, whole._norm(params, "norm1", u), ctx)[0]
        total, landed = jnp.zeros_like(u), 0.0
        for share in range(shares):
            layer_share = dataclasses.replace(
                whole, held_experts=tuple(range(share * per,
                                                (share + 1) * per)),
                shared_hidden=24 if share == 0 else 0)
            moe = {k: (v[share * per:(share + 1) * per] if k in routed
                       else v) for k, v in params["moe"].items()
                   if share == 0 or not k.startswith("shared")}
            y, new = layer_share._block({**params, "moe": moe}, state, u,
                                        ctx)
            total = total + (y if share == 0 else y - alike)
            landed += float(new["moe_routing"][0])
        if layer == "mtp":
            total = whole._norm(params, "head_norm", total)
    assert landed == 2 * 20 * 4                 # every assignment, once
    assert np.abs(total - want).max() < 2e-5 * np.abs(want).max()


def test_the_routed_scale_multiplies_the_routed_part_and_the_bias_picks():
    """The routed part (the layer's output less the shared expert's) is
    proportional to ``routed_scaling_factor``; a bias that lifts one
    expert far above the rest puts it among every token's top-k without
    changing its weight's score."""
    from deeplearning4j_tpu.nn.layers.decoder import (
        EXPERTS, LatentDecoderBlock)
    block = LatentDecoderBlock(n_out=16, ffn=EXPERTS, n_heads=2,
                               q_lora_rank=8, kv_lora_rank=8,
                               qk_nope_head_dim=4, qk_rope_head_dim=4,
                               v_head_dim=4, num_experts=8, expert_hidden=8,
                               shared_hidden=8, top_k=2)
    rt = RecurrentType(16, None)
    params = block.initialize(jax.random.PRNGKey(3), rt)["moe"]
    x = jnp.asarray(np.random.default_rng(6).normal(size=(1, 10, 16)),
                    jnp.float32)
    ctx = LayerContext(train=False)

    def routed(scale, bias):
        moe = dataclasses.replace(block, routed_scale=scale)._expert_layer()
        state = {**moe.init_state(rt), "moe_router_bias": bias}
        y, new = moe.apply(params, state, x, ctx)
        bare, _ = dataclasses.replace(moe, shared_hidden=0).apply(
            params, state, x, ctx)
        return y, bare, new

    zero = jnp.zeros((8,), jnp.float32)
    y1, r1, _ = routed(1.0, zero)
    y18, r18, _ = routed(1.8, zero)
    assert np.allclose(r18, 1.8 * r1, rtol=1e-5, atol=1e-7)
    assert np.allclose(y18 - r18, y1 - r1, atol=1e-6)      # shared: same
    lifted = zero.at[5].set(100.0)
    _, _, new = routed(1.8, lifted)
    assert float(new["moe_routing"][1]) == 10.0   # expert 5: every token


# ---- LatentAttention --------------------------------------------------------

D, H, DN, DR, DV, QR, KR, T = 32, 4, 6, 4, 8, 12, 8, 24


def _latent_attention():
    from deeplearning4j_tpu.nn.layers.attention import LatentAttention
    layer = LatentAttention(n_in=D, n_out=D, n_heads=H, q_lora_rank=QR,
                            kv_lora_rank=KR, qk_nope_head_dim=DN,
                            qk_rope_head_dim=DR, v_head_dim=DV,
                            rope_theta=1e4, eps=1e-5)
    params = layer.initialize(jax.random.PRNGKey(4), RecurrentType(D, None))
    params = {k: (v * 10.0 if k.startswith("W_") else v + 0.3)
              for k, v in params.items()}
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, T, D)),
                    jnp.float32)
    return layer, params, x


def _by_hand(params, x):
    """The layer written out in numpy: both latents and their norms, the
    one rotary key, turned at each position and handed to every head, a
    dense softmax over the causal mask, the output projection."""
    ref = reference_module()
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    xs = np.asarray(x, np.float64)

    def norm(a, w):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-5) * (1 + w)

    def turn(a):
        return np.asarray(ref._rotate(jnp.asarray(a, jnp.float32), 1e4),
                          np.float64)

    q = (norm(xs @ p["W_qa"], p["q_norm"]) @ p["W_qb"]).reshape(
        2, T, H, DN + DR)
    kv = xs @ p["W_kva"]
    k_r = turn(kv[..., KR:].reshape(2, T, 1, DR))[:, :, 0]
    kvb = (norm(kv[..., :KR], p["kv_norm"]) @ p["W_kvb"]).reshape(
        2, T, H, DN + DV)
    q_rope = turn(q[..., DN:])
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    out = np.zeros((2, T, H, DV))
    for head in range(H):
        qh = np.concatenate([q[:, :, head, :DN], q_rope[:, :, head]], -1)
        kh = np.concatenate([kvb[:, :, head, :DN], k_r], -1)
        s = np.einsum("nid,njd->nij", qh, kh) / np.sqrt(DN + DR)
        s = np.where(j <= i, s, -np.inf)
        pr = np.exp(s - s.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        out[:, :, head] = np.einsum("nij,njd->nid", pr, kvb[:, :, head, DN:])
    return out.reshape(2, T, H * DV) @ p["W_o"]


def test_latent_attention_is_the_written_out_layer():
    """On the XLA path (the CPU's), against the layer written out by hand
    and against the reference's blocked attention."""
    layer, params, x = _latent_attention()
    assert layer.scope if hasattr(layer, "scope") else True
    assert layer.named_scopes == ("attn.latent",)
    cfg = {"num_attention_heads": H, "qk_nope_head_dim": DN,
           "qk_rope_head_dim": DR, "v_head_dim": DV, "kv_lora_rank": KR,
           "rms_norm_eps": 1e-5, "rope_theta": 1e4}
    with jax.default_matmul_precision("highest"):
        got, _ = layer.apply(params, {}, x, LayerContext(train=False))
        ref = reference_module()._latent_attention(cfg, x, params)
    want = _by_hand(params, x)
    assert np.abs(np.asarray(got) - want).max() < 2e-5 * np.abs(want).max()
    assert np.abs(np.asarray(ref) - want).max() < 2e-5 * np.abs(want).max()


def test_the_latent_norms_and_the_shared_rotary_key_bite():
    """Each latent norm's weight moves the output; the rotary key is one
    per token: its columns of ``W_kva`` reach every head's scores, and a
    key's rotary part turns with its position, so the layer is not blind
    to order; the later positions do not reach the earlier ones."""
    layer, params, x = _latent_attention()
    ctx = LayerContext(train=False)
    base, _ = layer.apply(params, {}, x, ctx)
    for name in ("q_norm", "kv_norm"):
        moved, _ = layer.apply({**params, name: params[name] + 0.5}, {}, x,
                               ctx)
        assert not np.allclose(moved, base, atol=1e-4), name
    assert params["W_kva"].shape == (D, KR + DR)
    assert params["W_qb"].shape == (QR, H * (DN + DR))
    assert params["W_kvb"].shape == (KR, H * (DN + DV))
    # every head's output moves when the one rotary key's columns do
    w = params["W_kva"].at[:, KR:].multiply(3.0)
    moved, _ = layer.apply({**params, "W_kva": w}, {}, x, ctx)
    per_head = np.abs(np.asarray(moved - base)).reshape(2, T, -1)
    assert per_head.max() > 1e-3
    from deeplearning4j_tpu.nn.layers import attention as attn
    heads = []

    def spy(q, k, v, **kw):
        heads.append(np.asarray(k[..., DN:]))
        return attn.scaled_dot_product_attention(q, k, v, mask=kw["mask"],
                                                 visibility=kw["visibility"])

    import deeplearning4j_tpu.ops.pallas_kernels as pk
    before = pk.attention
    pk.attention = spy
    try:
        layer.apply(params, {}, x, ctx)
    finally:
        pk.attention = before
    k_rope, = heads
    assert all(np.array_equal(k_rope[:, :, 0], k_rope[:, :, h])
               for h in range(1, H))
    far = x.at[:, 20].add(1.0)
    moved, _ = layer.apply(params, {}, far, ctx)
    assert np.allclose(moved[:, :20], base[:, :20], atol=1e-6)
    order = np.r_[np.random.default_rng(0).permutation(T - 1), T - 1]
    shuffled, _ = layer.apply(params, {}, x[:, order], ctx)
    assert not np.allclose(shuffled[:, -1], base[:, -1], atol=1e-4)


@pytest.mark.parametrize("block", [8, 16])
def test_latent_attention_on_the_flash_kernels(monkeypatch, block):
    """The same layer through the Pallas flash kernels in interpret mode
    (queries and keys of 10, values of 8): values and the gradients of
    every parameter, the two latent norms among them, agree with the XLA
    path's."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    layer, params, x = _latent_attention()
    ctx = LayerContext(train=False)

    def loss(p, a):
        return jnp.sum(layer.apply(p, {}, a, ctx)[0] ** 2)

    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(loss)(params, x)
        monkeypatch.setattr(pk, "attention", functools.partial(
            pk.flash_attention, block_q=block, block_k=block,
            interpret=True))
        got = jax.value_and_grad(loss)(params, x)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name in params:
        g, w = got[1][name], want[1][name]
        assert float(jnp.linalg.norm(g - w)) < 1e-4 * float(
            jnp.linalg.norm(w)), name


def test_the_reference_imports_nothing_of_the_systems_layers_or_kernels():
    text = (cells.ROOT / "yardstick" / "reference"
            / "glm4_moe_lite.py").read_text()
    assert "deeplearning4j_tpu" not in text and "pallas" not in text.lower()
