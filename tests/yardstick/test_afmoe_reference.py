"""trinity-mini-ep8 at a preset small enough for the CPU (hidden 32, layer
kinds ``S S S F S`` with the first two dense, 4 experts held of a router's
16, top-3, a window of 7 over T = 24, vocabulary 64, float32): the system
against the plain reference, and each of the mechanisms the configuration
forced against the form of it that can be checked by eye."""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.nn.layers.feedforward import HeldExpertsMoE
from yardstick import cells
from yardstick.weights import init_on_device

NAME = "trinity-mini-ep8"
CELL = NAME + ".fit-seq8k"
S, F = "sliding_attention", "full_attention"
TINY = {"hidden_size": 32, "layer_types": [S, S, S, F, S],
        "num_hidden_layers": 5, "head_dim": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 48,
        "moe_intermediate_size": 16, "num_experts": 4, "router_width": 16,
        "expert_parallel_rank": 1, "num_experts_per_tok": 3,
        "sliding_window": 7, "vocab_size": 64, "seq_len": 24, "batch": 2,
        "examples": 8, "repeated_span": 6, "compute_dtype": "float32",
        "router_aux_loss_coef": 0.05,
        "updater": {"type": "Adam", "learning_rate": 1e-2}}


def reference_module():
    return cells.load_file_module(
        cells.ROOT / "yardstick" / "reference" / "afmoe.py")


@pytest.fixture(scope="module")
def tiny():
    cell = cells.resolve_cell(CELL)
    cell = dataclasses.replace(cell, config={**cell.config, **TINY})
    build = cells.load_build(cell)
    model = init_on_device(build.build(cell.config, 5), 5)
    return cell.config, build, model, cells.load_reference(cell)


def test_loss_and_logits_agree_with_the_reference(tiny):
    cfg, build, model, reference = tiny
    batch = build.rows_with_labels(cfg, 5, 4)
    assert batch.features.shape == batch.labels.shape == (4, 24)
    ts = model.train_state
    want = float(reference.loss(cfg, ts.params, ts.model_state,
                                (batch.features,), (batch.labels,)))
    got = float(model.score(batch))
    # float32 on both sides: the order of summation alone
    assert np.isfinite(want) and abs(got - want) / want < 1e-5
    logits = np.asarray(model.output(batch.features))
    ref = np.asarray(reference.logits(cfg, ts.params, ts.model_state,
                                      (batch.features,)))
    assert logits.shape == ref.shape == (4, 24, 64)
    assert np.abs(logits - ref).max() < 2e-5 * np.abs(ref).max()


def test_the_harness_compares_the_balance_term_alone(tiny):
    """``check_batch`` is ``rows_with_labels`` without a label: the score
    of system and reference on it is the routers' balance term, the same
    on both sides, and the next-token term is what the labels add."""
    cfg, build, model, reference = tiny
    rows, check = build.rows_with_labels(cfg, 7, 2), build.check_batch(
        cfg, 7, 2)
    assert np.array_equal(check.features, rows.features)
    assert (np.asarray(check.labels) < 0).all()
    ts = model.train_state
    balance = float(reference.loss(cfg, ts.params, ts.model_state,
                                   (check.features,), (check.labels,)))
    whole = float(reference.loss(cfg, ts.params, ts.model_state,
                                 (rows.features,), (rows.labels,)))
    by_hand = cfg["router_aux_loss_coef"] * float(reference_module()._forward(
        cfg, ts.params, ts.model_state, jnp.asarray(rows.features))[1])
    assert balance == pytest.approx(by_hand, rel=1e-6)
    # three expert layers, each k = 3 when the load is even
    assert 0.05 * 3 * 3 * 0.99 <= balance < whole
    assert float(model.score(check)) == pytest.approx(balance, rel=1e-5)
    assert float(model.score(rows)) == pytest.approx(whole, rel=1e-5)


def test_parameter_gradients_agree_with_the_reference(tiny):
    cfg, build, model, reference = tiny
    batch = build.rows_with_labels(cfg, 6, 2)
    ts = model.train_state
    ids, labels = jnp.asarray(batch.features), jnp.asarray(batch.labels)

    def system(params):
        return model._loss(params, ts.model_state, ids, labels, None, None,
                           None, ts.iteration)[0]

    got = jax.jit(jax.grad(system))(ts.params)
    want = jax.jit(jax.grad(reference.loss_fn(cfg)))(ts.params, ids, labels)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    # embed; a block's 4 norms and 6 attention leaves, then 2 of a dense
    # MLP or 7 of the experts (router, 3 routed, 3 shared); head 2
    assert len(flat_got) == len(flat_want) == 1 + 2 * 12 + 3 * 17 + 2
    for path, g in flat_got:
        w = flat_want[path]
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, path                 # every parameter is reached
        assert float(jnp.linalg.norm(g - w)) < 2e-4 * scale, path


def test_the_reference_reads_the_bias_the_state_holds(tiny):
    """After some steps the routers' bias has moved; the system's score
    and the reference handed the same state agree, and differ from the
    reference at a zero bias."""
    cfg, build, _, reference = tiny
    fast = {**cfg, "load_balance_coeff": 0.05}
    model = init_on_device(build.build(fast, 4), 4)
    model.fit(build.train_set(fast, 4, 2), epochs=3)
    ts = model.train_state
    assert sorted(k for k, v in ts.model_state.items() if v) == [
        "block2", "block3", "block4"]
    bias = np.asarray(ts.model_state["block2"]["moe_router_bias"])
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


def test_fit_trains_the_zoo_model_and_it_round_trips(tiny):
    """The builder reads ``layer_types`` and ``num_dense_layers``: window
    and rotary on sliding layers, neither on the full one, a dense MLP on
    the first two and the experts after; the embedding is scaled."""
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
    cfg, build, _, _ = tiny
    conf = build.zoo_model(cfg, 3).conf()
    text = conf.to_json()
    again = MultiLayerConfiguration.from_json(text)
    assert again.to_json() == text
    kinds = [type(l).__name__ for l in again.layers]
    assert kinds == ["ScaledTokenEmbedding"] + [
        "SandwichDecoderBlock"] * 5 + ["CausalLMOutputLayer"]
    blocks = again.layers[1:6]
    assert [b.ffn for b in blocks] == ["dense"] * 2 + ["experts"] * 3
    assert [b.window for b in blocks] == [7, 7, 7, None, 7]
    attns = [b._parts()[0] for b in blocks]
    assert [a.partial_rotary_factor for a in attns] == [1, 1, 1, 0, 1]
    assert [a.scope for a in attns] == [
        "attn.window"] * 3 + ["attn.gated", "attn.window"]
    experts = blocks[2]
    assert experts.held_experts == (4, 5, 6, 7)
    assert (experts.routed_scale, experts.bias_update_rate) == (2.826, 1e-3)
    moe = experts._expert_layer()
    assert (moe.expert_form, moe.router_scoring, moe.shared_gate,
            moe.shared_hidden) == ("gated", "sigmoid", False, 16)
    attn = experts._parts()[0]
    assert (attn.qk_norm, attn.output_gate) == (True, True)
    assert blocks[0]._expert_layer() is None
    model = MultiLayerNetwork(again).init(3)
    assert model.num_params() == build.parameter_count(cfg)["on_the_chip"]
    data = build.train_set(cfg, 3, 2)
    rows = build._dataset(cfg, 3, cfg["examples"])
    first = float(model.score(rows))
    model.fit(data, epochs=12)
    assert np.isfinite(model.score())
    assert float(model.score(rows)) < first - 0.3
    row = np.asarray(model.train_state.model_state["block2"]["moe_routing"])
    assert row[0] > 0 and row[1] >= row[2] > 0 and row[3] == 0
    assert 0 < row[5] <= 48 * 1e-3 * 1.001
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(build.zoo_model(cfg, 3),
                            layer_types=(S, "chunked_attention")).conf()
    plain = dataclasses.replace(build.zoo_model(cfg, 3), mup_enabled=False)
    assert type(plain.conf().layers[0]).__name__ == "TokenEmbedding"


def test_a_recomputing_block_computes_what_the_plain_block_does(tiny):
    cfg, build, _, _ = tiny
    batch = build.rows_with_labels(cfg, 2, 2)
    ids, labels = jnp.asarray(batch.features), jnp.asarray(batch.labels)
    out = []
    for recompute in (True, False):
        model = init_on_device(
            build.build({**cfg, "recompute": recompute}, 7), 7)
        ts = model.train_state

        def loss(params):
            return model._loss(params, ts.model_state, ids, labels, None,
                               None, None, ts.iteration)[0]
        out.append(jax.jit(jax.value_and_grad(loss))(ts.params))
    (l0, g0), (l1, g1) = out
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-6 * float(
            jnp.linalg.norm(b))


def test_the_embedding_is_scaled_and_the_sandwich_norms_wrap_each_branch(
        tiny):
    """``x_0 = sqrt(h) E[ids]``; with the four norms' weights moved, the
    first block is ``h = x + N2(Attn(N1 x))``, ``y = h + N4(MLP(N3 h))``
    written out from its parts."""
    from deeplearning4j_tpu.nn.layers.decoder import gated_mlp
    from deeplearning4j_tpu.nn.layers.normalization import rms_norm
    cfg, build, model, _ = tiny
    ids = jnp.asarray(build.rows_with_labels(cfg, 3, 2).features)
    embed, block = model.conf.layers[0], model.conf.layers[1]
    params = model.train_state.params
    x, _ = embed.apply(params["embed"], {}, ids, LayerContext(train=False))
    assert np.allclose(x, np.sqrt(32) * params["embed"]["W"][ids],
                       rtol=1e-6)
    rng = np.random.default_rng(0)
    p = {**params["block0"], **{
        f"norm{i}": {"w": jnp.asarray(rng.normal(size=32) * 0.3,
                                      jnp.float32)} for i in (1, 2, 3, 4)}}
    ctx = LayerContext(train=False)
    with jax.default_matmul_precision("highest"):
        got, _ = block.apply(p, {}, x, ctx)
        attn = block._parts()[0]

        def n(i, a):
            return rms_norm(a, p[f"norm{i}"]["w"], 1e-5)
        h = x + n(2, attn.apply(p["mixer"], {}, n(1, x), ctx)[0])
        want = h + n(4, gated_mlp(p["mlp"], n(3, h)))
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()


def test_the_files_parameter_table_is_the_builders_count():
    """``parameter_count`` at the published widths, by shapes alone,
    against the model's own count (no weight is made)."""
    cell = cells.resolve_cell(CELL)
    build = cells.load_build(cell)
    count = build.parameter_count(cell.config)
    assert count["attention_with_norms"] == 27_271_424
    assert count["dense_layer"] == 65_020_160
    assert count["expert_layer"] == 134_488_320
    assert count["embedding_head_and_final_norm"] == 102_500_352
    assert count["on_the_chip"] == 770_493_952
    assert count["bytes_at_16_per_parameter"] == 12_327_903_232
    for key, value in count.items():
        assert cell.config["parameters"][key] == value, key
    model = build.build(cell.config, 0)
    shapes = jax.eval_shape(lambda: model.init(0).train_state)
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes.params)) == count["on_the_chip"]
    biases = [v["moe_router_bias"].shape
              for v in shapes.model_state.values() if v]
    assert biases == [(128,)] * 4
    flops = build.train_flops_per_example(cell.config)
    assert 22.0e12 < flops < 22.1e12
    local, nbytes = build.local_attention_work(cell.config)
    rows = cell.config["batch"]
    pairs = 2048 * 2049 // 2 + (8192 - 2048) * 2048
    assert local == 3 * 5 * rows * 4 * pairs * 32 * 128
    assert nbytes == 3 * 5 * 8192 * rows * (2 * 4096 + 2 * 512) * 2
    assert local / 197e12 > nbytes / 819e9            # bound by operations
    moe, _ = build.moe_grouped_work(cell.config)
    assert 0.05 < moe / (rows * flops) < 0.07


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
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    period = [S, S, S, F]
    assert body["published"] == {
        "num_hidden_layers": 32, "layer_types": period * 8,
        "num_experts": 128, "vocab_size": 200192}
    # the cut: published layers 0-5, a literal prefix, both dense layers
    # and four expert layers, three sliding to one full in the first four
    assert body["layer_types"] == body["published"]["layer_types"][:6]
    assert len(body["layer_types"]) == body["num_hidden_layers"] == 6
    assert (body["num_experts"], body["router_width"],
            body["vocab_size"]) == (16, 128, 200192 // 8)
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True}
    for key, value in published.items():
        assert body[key] == value, key
    for key in ("qk_norm", "output_gate", "rotary_on_sliding_layers_only",
                "sandwich_norms", "mup_embedding_scale", "initializer_range",
                "router_aux_loss_coef", "seq_len", "batch"):
        assert key in body["assumed"], key
    assert body["departures"] and "8 chips" in body["deployment"]
    assert body["batch"] == 1 and body["seq_len"] == 8192
    assert body["loss_tolerance"] > 0 and body["loss_tolerance_why"]


@pytest.mark.parametrize("control,least,most", [
    ({}, 0.0, 0.0),
    ({"control_operand_dtype": "bfloat16"}, 1e-4, 3e-2),
    ({"control_operand_dtype": "float8_e4m3fn"}, 3e-2, 1.0),
], ids=["none", "operands_bfloat16", "operands_float8"])
def test_the_references_controls_round_what_they_say(tiny, control, least,
                                                     most):
    """The reference in a lower precision, which the chip check's limits
    are set against: logits leave the float32 ones by a share of their
    spread that grows as the type shrinks; with no control, by nothing."""
    cfg, build, model, reference = tiny
    ts = model.train_state
    ids = (jnp.asarray(build.rows_with_labels(cfg, 9, 2).features),)
    want = reference.logits(cfg, ts.params, ts.model_state, ids)
    got = reference.logits({**cfg, **control}, ts.params, ts.model_state,
                           ids)
    apart = float(jnp.sqrt(jnp.mean((got - want) ** 2)) / jnp.std(want))
    assert least <= apart <= most


@pytest.mark.parametrize("compute,bands", [
    ("float32", {"logits": 1e-4, "loss": 1e-5, "dense": 1e-3,
                 "routed": 1e-3}),
    ("bfloat16", {"logits": 0.06, "loss": 2e-2, "dense": 0.4,
                  "routed": 0.5}),
])
def test_the_system_meets_stated_bands_that_the_float8_control_fails(
        compute, bands):
    """Logits (rms over the reference's spread), loss and gradients by
    kind, system against float32 reference on seeded weights: tight at
    float32 compute, inside stated bands at bfloat16; the reference with
    float8 operands is outside the bfloat16 bands on the logits and on
    both kinds of gradient."""
    cell = cells.resolve_cell(CELL)
    cfg = {**cell.config, **TINY, "compute_dtype": compute}
    build, reference = cells.load_build(cell), cells.load_reference(cell)
    model = init_on_device(build.build(cfg, 11), 11)
    ts = model.train_state
    batch = build.rows_with_labels(cfg, 11, 4)
    ids, labels = jnp.asarray(batch.features), jnp.asarray(batch.labels)

    def readings(logits, loss, grads):
        want_logits = reference.logits(cfg, ts.params, ts.model_state, (ids,))
        want_loss = float(reference.loss(cfg, ts.params, ts.model_state,
                                         (ids,), (labels,)))
        want = dict(jax.tree_util.tree_leaves_with_path(jax.jit(jax.grad(
            reference.loss_fn(cfg)))(ts.params, ids, labels)))
        worst = {"dense": 0.0, "routed": 0.0}
        for path, g in jax.tree_util.tree_leaves_with_path(grads):
            name = jax.tree_util.keystr(path)
            kind = ("routed" if name.endswith((
                "['router']", "['w_gate']", "['w_up']", "['w_down']"))
                else "dense")
            worst[kind] = max(worst[kind], float(
                jnp.linalg.norm(g - want[path])
                / jnp.linalg.norm(want[path])))
        return {"logits": float(jnp.sqrt(jnp.mean(
                    (logits - want_logits) ** 2)) / jnp.std(want_logits)),
                "loss": abs(float(loss) - want_loss) / want_loss, **worst}

    def system(params):
        return model._loss(params, ts.model_state, ids, labels, None, None,
                           None, ts.iteration)[0]

    got = readings(model.output(batch.features), model.score(batch),
                   jax.jit(jax.grad(system))(ts.params))
    for name, limit in bands.items():
        assert got[name] < limit, (name, got)
    if compute == "bfloat16":
        low = {**cfg, "control_operand_dtype": "float8_e4m3fn"}
        control = readings(
            reference.logits(low, ts.params, ts.model_state, (ids,)),
            reference.loss(low, ts.params, ts.model_state, (ids,),
                           (labels,)),
            jax.jit(jax.grad(reference.loss_fn(low)))(ts.params, ids, labels))
        for name in ("logits", "dense", "routed"):
            assert control[name] > bands[name], (name, control)


def test_the_eight_shares_add_up_to_the_whole_layer():
    """Each of the 8 shares routes over all 128 experts' outputs (here 32
    of width 24) and computes its own 1/8 with gated experts under the
    sigmoid router and ``route_scale``; the shared expert, which every
    chip computes alike and adds ungated, is counted once; the parts of
    all the shares are the uncut layer as the reference computes it."""
    reference = reference_module()
    rng = np.random.default_rng(2)
    e, d, shares = 32, 32, 8
    per = e // shares
    whole = HeldExpertsMoE(n_in=d, n_out=d, num_experts=e, hidden=24,
                           shared_hidden=24, top_k=8, expert_form="gated",
                           router_scoring="sigmoid", routed_scale=2.826,
                           shared_gate=False)
    rt = RecurrentType(d, None)
    params = whole.initialize(jax.random.PRNGKey(1), rt)
    params["router"] = params["router"] * 10.0
    bias = jnp.asarray(rng.normal(size=e) * 0.05, jnp.float32)
    state = {**whole.init_state(rt), "moe_router_bias": bias}
    x = jnp.asarray(rng.normal(size=(2, 40, d)), jnp.float32)
    ctx = LayerContext(train=False)
    routed = ("w_gate", "w_up", "w_down")
    total, landed = jnp.zeros_like(x), 0.0
    with jax.default_matmul_precision("highest"):
        for share in range(shares):
            held = tuple(range(share * per, (share + 1) * per))
            layer = dataclasses.replace(
                whole, held_experts=held,
                shared_hidden=24 if share == 0 else 0)
            mine = {k: (v[share * per:(share + 1) * per] if k in routed
                        else v) for k, v in params.items()}
            y, new = layer.apply(mine, state, x, ctx)
            total = total + y
            landed += float(new["moe_routing"][0])
        assert landed == 2 * 40 * 8             # every assignment, once
        cfg = {"num_experts": e, "num_experts_per_tok": 8,
               "route_norm": True, "route_scale": 2.826}
        want, _ = reference._experts(cfg, x, params, bias)
        assert np.abs(total - want).max() < 2e-5 * np.abs(want).max()
        # and one share is what the reference gives for that share
        cfg = {**cfg, "num_experts": per, "expert_parallel_rank": 3}
        held = reference.held_experts(cfg)
        assert held == (12, 13, 14, 15)
        layer = dataclasses.replace(whole, held_experts=held)
        mine = {k: (v[12:16] if k in routed else v)
                for k, v in params.items()}
        y, _ = layer.apply(mine, state, x, ctx)
        want, _ = reference._experts(cfg, x, mine, bias)
    assert np.abs(y - want).max() < 2e-5 * max(1.0, np.abs(want).max())


# ---- GatedAttention(window=W) ----------------------------------------------

D, H, HK, DH, T = 32, 4, 2, 8, 24


def _gated_attention(window, rotary=1.0):
    from deeplearning4j_tpu.nn.layers.attention import GatedAttention
    layer = GatedAttention(n_in=D, n_out=D, n_heads=H, n_kv_heads=HK,
                           head_dim=DH, partial_rotary_factor=rotary,
                           rope_theta=1e4, eps=1e-5, window=window)
    params = layer.initialize(jax.random.PRNGKey(4), RecurrentType(D, None))
    params = {k: (v * 10.0 if k.startswith("W_") else v + 0.1)
              for k, v in params.items()}
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, T, D)),
                    jnp.float32)
    return layer, params, x


def _by_the_mask(params, x, window):
    """The layer written out in numpy: projections, q/k norms, rotary on
    the whole head, a dense softmax over the (T, T) mask, the gate."""
    ref = reference_module()
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    xs = np.asarray(x, np.float64)
    qg = (xs @ p["W_q"]).reshape(2, T, H, 2 * DH)
    q, gate = qg[..., :DH], qg[..., DH:]
    k = (xs @ p["W_k"]).reshape(2, T, HK, DH)
    v = (xs @ p["W_v"]).reshape(2, T, HK, DH)

    def norm(a, w):
        return a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-5) * (1 + w)

    q, k = norm(q, p["q_norm"]), norm(k, p["k_norm"])
    q = np.asarray(ref._rotate(jnp.asarray(q, jnp.float32), 1e4), np.float64)
    k = np.asarray(ref._rotate(jnp.asarray(k, jnp.float32), 1e4), np.float64)
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    mask = (j <= i) & (j > i - window)
    out = np.zeros((2, T, H, DH))
    for head in range(H):
        kv = head // (H // HK)
        s = np.einsum("nid,njd->nij", q[:, :, head], k[:, :, kv]) \
            / np.sqrt(DH)
        s = np.where(mask, s, -np.inf)
        pr = np.exp(s - s.max(-1, keepdims=True))
        pr /= pr.sum(-1, keepdims=True)
        out[:, :, head] = np.einsum("nij,njd->nid", pr, v[:, :, kv])
    out = out / (1 + np.exp(-gate))
    return out.reshape(2, T, H * DH) @ p["W_o"]


@pytest.mark.parametrize("window", [1, 5, 7, 23])
def test_windowed_gated_attention_is_the_dense_masked_softmax(window):
    """On the XLA path (the CPU's), against the layer written out in
    numpy over the literal mask ``i - W < j <= i``, and against the
    reference's blocked attention."""
    layer, params, x = _gated_attention(window)
    assert layer.scope == "attn.window"
    cfg = {"num_attention_heads": H, "num_key_value_heads": HK,
           "head_dim": DH, "rms_norm_eps": 1e-5, "rope_theta": 1e4,
           "sliding_window": window}
    with jax.default_matmul_precision("highest"):
        got, _ = layer.apply(params, {}, x, LayerContext(train=False))
        ref = reference_module()._attention(cfg, x, params, True)
    want = _by_the_mask(params, x, window)
    assert np.abs(np.asarray(got) - want).max() < 2e-5 * np.abs(want).max()
    assert np.abs(np.asarray(ref) - want).max() < 2e-5 * np.abs(want).max()


@pytest.mark.parametrize("window,block", [(5, 8), (16, 8), (7, 16)])
def test_windowed_gated_attention_on_the_flash_kernels(monkeypatch, window,
                                                       block):
    """The same layer through the Pallas flash kernels in interpret mode
    (tiles narrower and wider than the window): values and the gradients
    of every parameter agree with the XLA path's."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    layer, params, x = _gated_attention(window)
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


def test_both_mask_kinds_bite():
    """A windowed layer's row does not see a key more than ``W - 1``
    behind it; a full layer without positions sees every earlier key and
    not their order."""
    from deeplearning4j_tpu.nn.layers.attention import GatedAttention
    ctx = LayerContext(train=False)
    layer, params, x = _gated_attention(5)
    base, _ = layer.apply(params, {}, x, ctx)
    far = x.at[:, 3].add(1.0)            # 12 - 3 >= 5: outside row 12's window
    moved, _ = layer.apply(params, {}, far, ctx)
    assert np.allclose(moved[:, 12:], base[:, 12:], atol=1e-5)
    assert not np.allclose(moved[:, 3:8], base[:, 3:8], atol=1e-3)
    full = dataclasses.replace(layer, window=None, partial_rotary_factor=0.0)
    assert full.scope == "attn.gated" and isinstance(full, GatedAttention)
    got, _ = full.apply(params, {}, x, ctx)
    moved, _ = full.apply(params, {}, far, ctx)
    assert not np.allclose(moved[:, -1], got[:, -1], atol=1e-3)
    order = np.r_[np.random.default_rng(0).permutation(T - 1), T - 1]
    shuffled, _ = full.apply(params, {}, x[:, order], ctx)
    assert np.abs(shuffled[:, -1] - got[:, -1]).max() < 1e-5 * np.abs(
        got).max()


def test_a_window_is_refused_where_it_means_nothing():
    from deeplearning4j_tpu.nn.layers.attention import GatedAttention
    with pytest.raises(ValueError, match="window"):
        GatedAttention(n_heads=4, n_kv_heads=2, window=0)
    with pytest.raises(ValueError, match="window"):
        GatedAttention(n_heads=4, n_kv_heads=2, window=8, block_length=4)
    assert GatedAttention(n_heads=4, n_kv_heads=2).window is None


# ---- the one dense gated MLP ------------------------------------------------

def test_the_phi_block_computes_bit_for_bit_what_its_inline_mlp_did():
    """``StateSpaceHybridBlock`` calls ``gated_mlp`` where it computed the
    MLP inline: the parent's lines, written out here, give the same
    output and gradients to the last bit, at bfloat16 and float32, so the
    Phi-4-mini-flash cell compiles the same program."""
    from deeplearning4j_tpu.nn.layers.decoder import (
        ATTENTION, StateSpaceHybridBlock)
    from deeplearning4j_tpu.nn.layers.normalization import LayerNormalization
    width, hidden = 16, 24
    block = StateSpaceHybridBlock(n_out=width, mixer=ATTENTION, n_heads=4,
                                  n_kv_heads=2, head_dim=4, window=5,
                                  mlp_hidden=hidden)
    params = block.initialize(jax.random.PRNGKey(2),
                              RecurrentType(width, None))
    assert params["mlp"]["W1"].shape == (width, 2 * hidden)
    ctx = LayerContext(train=False)

    def inline(p, x):                   # the parent's _apply, as it was
        norm = LayerNormalization(eps=block.eps)
        h, _ = norm.apply(p["norm1"], {}, x, ctx)
        m, _ = block._mixer().mix(p["mixer"], h, mask=None)
        x = x + m
        h, _ = norm.apply(p["norm2"], {}, x, ctx)
        with jax.named_scope("mlp.glu"):
            f32 = jnp.promote_types(jnp.float32, h.dtype)
            gu = jnp.einsum("ntf,fe->nte", h, p["mlp"]["W1"])
            g, u = gu[..., :hidden], gu[..., hidden:]
            act = (u.astype(f32) * jax.nn.silu(g.astype(f32))).astype(
                h.dtype)
            f = jnp.einsum("nte,ef->ntf", act, p["mlp"]["W2"])
        return x + f

    for dtype in (jnp.bfloat16, jnp.float32):
        p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
        x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 12, width)),
                        dtype)
        now = jax.jit(lambda p, x: block.apply(p, {}, x, ctx)[0])(p, x)
        before = jax.jit(inline)(p, x)
        assert np.array_equal(np.asarray(now), np.asarray(before))
        g_now = jax.jit(jax.grad(lambda p: jnp.sum(
            block.apply(p, {}, x, ctx)[0].astype(jnp.float32) ** 2)))(p)
        g_before = jax.jit(jax.grad(lambda p: jnp.sum(
            inline(p, x).astype(jnp.float32) ** 2)))(p)
        for a, b in zip(jax.tree_util.tree_leaves(g_now),
                        jax.tree_util.tree_leaves(g_before)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_reference_imports_nothing_of_the_systems_layers_or_kernels():
    text = (cells.ROOT / "yardstick" / "reference" / "afmoe.py").read_text()
    assert "deeplearning4j_tpu" not in text and "pallas" not in text.lower()
