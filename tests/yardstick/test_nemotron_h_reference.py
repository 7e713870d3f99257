"""nemotron3-nano-30b-a3b-ep16 at a preset small enough for the CPU (hidden
32, pattern ``MEM*E``, 4 experts held of a router's 16, top-3, T = 24 in
chunks of 8, vocabulary 64, float32): the system against the plain
reference, and each of the mechanisms the configuration forced against
the form of it that can be checked by eye."""

import dataclasses
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

NAME = "nemotron3-nano-30b-a3b-ep16"
CELL = NAME + ".fit-seq8k"
TINY = {"hidden_size": 32, "hybrid_override_pattern": "MEM*E",
        "num_hidden_layers": 5, "head_dim": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "mamba_num_heads": 8, "mamba_head_dim": 4,
        "n_groups": 2, "ssm_state_size": 6, "chunk_size": 8,
        "moe_intermediate_size": 16,
        "moe_shared_expert_intermediate_size": 24, "n_routed_experts": 4,
        "router_width": 16, "expert_parallel_rank": 1,
        "num_experts_per_tok": 3, "vocab_size": 64, "seq_len": 24,
        "batch": 2, "examples": 8, "repeated_span": 6,
        "compute_dtype": "float32", "router_aux_loss_coef": 0.05,
        "updater": {"type": "Adam", "learning_rate": 1e-2}}


def reference_module():
    return cells.load_file_module(
        cells.ROOT / "yardstick" / "reference" / "nemotron_h.py")


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
    assert np.isfinite(want) and abs(got - want) / want < 1e-5
    logits = np.asarray(model.output(batch.features))
    ref = np.asarray(reference.logits(cfg, ts.params, ts.model_state,
                                      (batch.features,)))
    assert logits.shape == ref.shape == (4, 24, 64)
    assert np.abs(logits - ref).max() < 2e-5 * np.abs(ref).max()


def test_the_loss_is_the_next_token_mean_and_the_balance_terms_by_hand(tiny):
    cfg, build, model, reference = tiny
    batch = build.rows_with_labels(cfg, 8, 3)
    ts = model.train_state
    logits = np.asarray(reference.logits(cfg, ts.params, ts.model_state,
                                         (batch.features,)), np.float64)
    labels = np.asarray(batch.labels)
    assert (labels[:, -1] == -1).all() and (labels[:, :-1] >= 0).all()
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    total, counted = 0.0, 0
    for n in range(3):
        for i in range(23):
            total -= logp[n, i, labels[n, i]]
            counted += 1
    # the expert layers' balance terms, each by hand from its router's
    # scores: all 16 outputs count, the 4 held and the 12 absent
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    ts.params)
    balance = 0.0
    bias = jnp.zeros(16)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["W"][np.asarray(batch.features)]
        for l, kind in enumerate(cfg["hybrid_override_pattern"]):
            p = params[f"block{l}"]
            if kind == "E":
                u = reference._rms_norm(x, p["norm"]["w"], 1e-5)
                s = np.asarray(jax.nn.sigmoid(
                    u.reshape(-1, 32) @ p["mixer"]["router"]), np.float64)
                top = np.argsort(-s, -1)[:, :3]
                received = np.bincount(top.reshape(-1), minlength=16)
                assert received.sum() == 3 * 24 * 3
                balance += 16 * float((received / len(s)) @ (
                    s / s.sum(-1, keepdims=True)).mean(0))
            x = reference._block(cfg, kind, p, bias, x)[0]
    assert 2 * 3 * 0.99 <= balance < 2 * 16     # k a layer when even
    want = total / counted + cfg["router_aux_loss_coef"] * balance
    assert float(model.score(batch)) == pytest.approx(want, rel=1e-5)


def test_the_harness_compares_the_balance_term_alone(tiny):
    """``check_batch`` is ``rows_with_labels`` without a label: the score
    of system and reference on it is the routers' balance term, the same
    on both sides, and the next-token term is what the labels add."""
    cfg, build, model, reference = tiny
    rows, check = build.rows_with_labels(cfg, 7, 2), build.check_batch(
        cfg, 7, 2)
    assert np.array_equal(check.features, rows.features)
    assert check.labels.shape == rows.labels.shape
    assert (np.asarray(check.labels) < 0).all()
    ts = model.train_state
    balance = float(reference.loss(cfg, ts.params, ts.model_state,
                                   (check.features,), (check.labels,)))
    whole = float(reference.loss(cfg, ts.params, ts.model_state,
                                 (rows.features,), (rows.labels,)))
    by_hand = cfg["router_aux_loss_coef"] * float(reference_module()._forward(
        cfg, ts.params, ts.model_state, jnp.asarray(rows.features))[1])
    assert balance == pytest.approx(by_hand, rel=1e-6)
    assert 0.0 < balance < whole
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
    # embed; 2 x (norm + 8), attention norm + 4, 2 x (norm + 5); head 2
    assert len(flat_got) == len(flat_want) == 1 + 2 * 9 + 5 + 2 * 6 + 2
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
    fast = {**cfg, "bias_update_rate": 0.05}
    model = init_on_device(build.build(fast, 4), 4)
    model.fit(build.train_set(fast, 4, 2), epochs=3)
    ts = model.train_state
    bias = np.asarray(ts.model_state["block1"]["moe_router_bias"])
    assert bias.shape == (16,) and np.abs(bias).max() > 0.1
    assert set(np.unique(np.round(np.abs(bias) / 0.05, 3)) % 1) == {0.0}
    row = np.asarray(ts.model_state["block1"]["moe_routing"])
    assert row.shape == (6,) and row[5] == pytest.approx(np.abs(bias).max())
    batch = build.rows_with_labels(fast, 9, 4)
    got = float(model.score(batch))
    want = float(reference.loss(fast, ts.params, ts.model_state,
                                (batch.features,), (batch.labels,)))
    unbiased = float(reference.loss(fast, ts.params, {}, (batch.features,),
                                    (batch.labels,)))
    assert abs(got - want) / want < 1e-5
    assert abs(unbiased - want) / want > 1e-4


def test_fit_trains_the_zoo_model_and_it_round_trips(tiny):
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
    from deeplearning4j_tpu.observe.registry import default_registry
    cfg, build, _, _ = tiny
    conf = build.zoo_model(cfg, 3).conf()
    text = conf.to_json()
    again = MultiLayerConfiguration.from_json(text)
    assert again.to_json() == text
    kinds = [type(l).__name__ for l in again.layers]
    assert kinds == ["TokenEmbedding"] + ["SingleMixerBlock"] * 5 + [
        "CausalLMOutputLayer"]
    assert [l.mixer for l in again.layers[1:6]] == [
        "mamba2", "experts", "mamba2", "causal_attention", "experts"]
    experts = again.layers[2]
    assert experts.held_experts == (4, 5, 6, 7)
    assert (experts.routed_scale, experts.bias_update_rate) == (2.5, 1e-3)
    moe = experts._mixer()
    assert (moe.expert_form, moe.router_scoring, moe.shared_gate) == (
        "relu2", "sigmoid", False)
    attn = again.layers[4]._mixer()
    assert (attn.qk_norm, attn.output_gate, attn.partial_rotary_factor,
            attn.scope) == (False, False, 0.0, "attn.causal")
    model = MultiLayerNetwork(again).init(3)
    assert model.num_params() == build.parameter_count(cfg)["on_the_chip"]
    data = build.train_set(cfg, 3, 2)
    rows = build._dataset(cfg, 3, cfg["examples"])
    first = float(model.score(rows))
    model.fit(data, epochs=12)
    assert np.isfinite(model.score())
    assert float(model.score(rows)) < first - 0.3
    state = model.train_state.model_state
    assert sorted(k for k, v in state.items() if v) == ["block1", "block4"]
    row = np.asarray(state["block1"]["moe_routing"])
    assert row[0] > 0 and row[1] >= row[2] > 0 and row[3] == 0
    assert 0 < row[5] <= 48 * 1e-3 * 1.001
    model._publish_routing_gauges()
    bias = default_registry().get_metric("dl4j_moe_router_bias_absmax")
    assert bias.series()[(("layer", "block1"),)] == pytest.approx(row[5])
    chunks = default_registry().get_metric("dl4j_ssd_chunks").series()
    assert chunks[(("layer", "block0"),)] == 3
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        dataclasses.replace(build.zoo_model(cfg, 3),
                            hybrid_override_pattern="ME-").conf()


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


def test_the_files_parameter_table_is_the_builders_count():
    """``parameter_count`` at the published widths, by shapes alone,
    against the model's own count (no weight is made)."""
    cell = cells.resolve_cell(CELL)
    build = cells.load_build(cell)
    count = build.parameter_count(cell.config)
    assert count["mamba2_layer"] == 38_744_896
    assert count["attention_layer"] == 23_399_040
    assert count["expert_layer"] == 100_125_312
    assert count["embedding_head_and_final_norm"] == 88_083_072
    assert count["on_the_chip"] == 666_962_944
    assert count["bytes_at_16_per_parameter"] == 10_671_407_104
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
    assert 17.4e12 < flops < 17.8e12
    scan, nbytes = build.ssd_scan_work(cell.config)
    rows = cell.config["batch"]
    assert nbytes == 3 * 4 * 8192 * rows * (6144 + 64 + 4096 + 4096) * 2
    assert scan == 3 * 4 * 8192 * rows * (
        5 * 64 * 64 * 128 + 6144 * 10 + 8 * 4096)
    assert nbytes / 819e9 > scan / 197e12           # bound by the bytes
    moe, _ = build.moe_grouped_work(cell.config)
    assert 0.04 < moe / (rows * flops) < 0.06


def test_the_configuration_file_states_its_source_cuts_and_limit():
    """What ``test_cells.py`` holds of every configuration's file, held
    here for this one too: its own case trips over a pattern that reads
    the ``hidden`` of ``num_hidden_layers`` as a width (PERF.md §7), and
    stops before these."""
    manifest = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    entry, = [c for c in manifest["configs"] if c["name"] == NAME]
    path = cells.ROOT / entry["file"]
    assert any(str(path.relative_to(cells.ROOT)).startswith(p + "/")
               for p in manifest["paths"])
    body = json.loads(path.read_text())
    assert body["source"] == entry["source"]
    assert body["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert all(k in body for k in entry["reduced"])
    assert "assumed" in body and "batch" in body
    assert body["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    # the cut: a literal prefix of the published pattern, every kind in it
    assert body["hybrid_override_pattern"] == "MEMEM*EME"
    assert body["published"]["hybrid_override_pattern"].startswith(
        body["hybrid_override_pattern"])
    assert len(body["hybrid_override_pattern"]) == body[
        "num_hidden_layers"] == 9
    assert (body["n_routed_experts"], body["router_width"],
            body["vocab_size"]) == (8, 128, 16384)
    for key in ("no_positional_encoding", "expand", "time_step_limit",
                "bias_update_rate", "router_aux_loss_coef",
                "initializer_range", "rescale_prenorm_residual",
                "residual_in_fp32", "seq_len", "batch"):
        assert key in body["assumed"], key
    assert body["departures"] and "16 chips" in body["deployment"]
    assert body["batch"] in (1, 2) and body["seq_len"] == 8192
    # every number of the catalog's config, under the same key
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True}
    for key, value in published.items():
        assert body[key] == value, key
    # the limit lies between the two readings on the chip of what the
    # harness compares, the balance term alone (PERF.md section 6): the
    # system's largest and the float8 control's smallest, room on both sides
    assert 3 * 9.96e-5 < body["loss_tolerance"] < 3.34e-3 / 3
    assert body["router_aux_loss_coef"] == 0.1 and body["batch"] == 1
    for said in ("float8", "balance term alone", "9.96e-5", "3.34e-3"):
        assert said in body["loss_tolerance_why"], said
    assert "17.34 GB" in body["assumed"]["batch"]
    assert "3,018" in body["deployment"]


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
    ("bfloat16", {"logits": 0.04, "loss": 2e-2, "dense": 0.12,
                  "routed": 0.30}),
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
            kind = ("routed" if name.endswith(("['router']", "['w_up']",
                                               "['w_down']")) else "dense")
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


def test_the_sixteen_shares_add_up_to_the_whole_layer():
    """Each of the 16 shares routes over all 128 experts' outputs (here 32
    of width 24) and computes its own 1/16; the shared expert, which every
    chip computes alike, is counted once; the parts of all the shares are
    the uncut layer as the reference computes it."""
    reference = reference_module()
    rng = np.random.default_rng(2)
    e, d, shares = 32, 32, 16
    per = e // shares
    whole = HeldExpertsMoE(n_in=d, n_out=d, num_experts=e, hidden=24,
                           shared_hidden=40, top_k=6, expert_form="relu2",
                           router_scoring="sigmoid", routed_scale=2.5,
                           shared_gate=False)
    rt = RecurrentType(d, None)
    params = whole.initialize(jax.random.PRNGKey(1), rt)
    params["router"] = params["router"] * 10.0
    bias = jnp.asarray(rng.normal(size=e) * 0.05, jnp.float32)
    state = {**whole.init_state(rt), "moe_router_bias": bias}
    x = jnp.asarray(rng.normal(size=(2, 40, d)), jnp.float32)
    ctx = LayerContext(train=False)
    total, landed = jnp.zeros_like(x), 0.0
    with jax.default_matmul_precision("highest"):
        for share in range(shares):
            held = tuple(range(share * per, (share + 1) * per))
            layer = dataclasses.replace(
                whole, held_experts=held,
                shared_hidden=40 if share == 0 else 0)
            mine = {k: (v[share * per:(share + 1) * per]
                        if k in ("w_up", "w_down") else v)
                    for k, v in params.items()}
            y, new = layer.apply(mine, state, x, ctx)
            total = total + y
            landed += float(new["moe_routing"][0])
        assert landed == 2 * 40 * 6             # every assignment, once
        cfg = {"n_routed_experts": e, "num_experts_per_tok": 6,
               "norm_topk_prob": True, "routed_scaling_factor": 2.5}
        want, _ = reference._experts(cfg, x, params, bias)
        assert np.abs(total - want).max() < 2e-5 * np.abs(want).max()
        # and one share is what the reference gives for that share
        cfg = {**cfg, "n_routed_experts": per, "expert_parallel_rank": 3}
        held = reference.held_experts(cfg)
        assert held == (6, 7)
        layer = dataclasses.replace(whole, held_experts=held)
        mine = {k: (v[6:8] if k in ("w_up", "w_down") else v)
                for k, v in params.items()}
        y, _ = layer.apply(mine, state, x, ctx)
        want, _ = reference._experts(cfg, x, mine, bias)
    assert np.abs(y - want).max() < 2e-5 * max(1.0, np.abs(want).max())


def test_attention_without_positions_against_a_loop_over_heads():
    """``GatedAttention`` with neither gate, q/k norm nor rotary, sixteen
    query heads a key/value head: against the reference's attention and
    against a loop over heads written out; and, having no positional
    encoding, a position's result does not change when the positions
    before it change places."""
    from deeplearning4j_tpu.nn.layers.attention import GatedAttention
    reference = reference_module()
    d, h, hk, dh, t = 32, 32, 2, 4, 13
    layer = GatedAttention(n_in=d, n_out=d, n_heads=h, n_kv_heads=hk,
                           head_dim=dh, partial_rotary_factor=0.0,
                           output_gate=False, qk_norm=False)
    params = layer.initialize(jax.random.PRNGKey(4), RecurrentType(d, None))
    params = {k: v * 10.0 for k, v in params.items()}
    assert set(params) == {"W_q", "W_k", "W_v", "W_o"}
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, t, d)),
                    jnp.float32)
    cfg = {"num_attention_heads": h, "num_key_value_heads": hk,
           "head_dim": dh}
    with jax.default_matmul_precision("highest"):
        got, _ = layer.apply(params, {}, x, LayerContext(train=False))
        want = reference._attention(cfg, x, params)
        assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
        q = np.asarray(x @ params["W_q"]).reshape(2, t, h, dh)
        k = np.asarray(x @ params["W_k"]).reshape(2, t, hk, dh)
        v = np.asarray(x @ params["W_v"]).reshape(2, t, hk, dh)
        out = np.zeros((2, t, h, dh))
        for j in range(h):
            for i in range(t):
                sc = np.einsum("nd,nkd->nk", q[:, i, j],
                               k[:, :i + 1, j // 16]) / np.sqrt(dh)
                pr = np.exp(sc - sc.max(-1, keepdims=True))
                pr /= pr.sum(-1, keepdims=True)
                out[:, i, j] = np.einsum("nk,nkd->nd", pr,
                                         v[:, :i + 1, j // 16])
        by_hand = out.reshape(2, t, h * dh) @ np.asarray(params["W_o"])
        assert np.abs(got - by_hand).max() < 2e-5 * np.abs(by_hand).max()
        order = np.r_[np.random.default_rng(0).permutation(t - 1), t - 1]
        moved, _ = layer.apply(params, {}, x[:, order],
                               LayerContext(train=False))
    assert np.abs(moved[:, -1] - got[:, -1]).max() < 1e-5 * np.abs(got).max()
