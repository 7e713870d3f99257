"""sdar-30b-a3b-ep8 at a preset small enough for the CPU (hidden 32, two
blocks, 4 experts held of a router's 8, top-2, T = 32 in blocks of 4,
vocabulary 64, float32): the system against the plain reference, and each
of the mechanisms the configuration forced against the form of it that can
be checked by eye."""

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

CELL = "sdar-30b-a3b-ep8.fit-seq8k"
TINY = {"hidden_size": 32, "head_dim": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "moe_intermediate_size": 16,
        "num_hidden_layers": 2, "num_experts": 4, "router_width": 8,
        "expert_parallel_rank": 1, "num_experts_per_tok": 2,
        "vocab_size": 64, "mask_token_id": 63, "seq_len": 32,
        "block_length": 4, "examples": 8, "repeated_span": 8,
        "compute_dtype": "float32", "router_aux_loss_coef": 0.05,
        "updater": {"type": "Adam", "learning_rate": 1e-2}}


@pytest.fixture(scope="module")
def tiny():
    cell = cells.resolve_cell(CELL)
    cell = dataclasses.replace(cell, config={**cell.config, **TINY})
    build = cells.load_build(cell)
    model = init_on_device(build.build(cell.config, 5), 5)
    return cell.config, build, model, cells.load_reference(cell)


def test_loss_and_logits_agree_with_the_reference(tiny):
    cfg, build, model, reference = tiny
    batch = build.check_batch(cfg, 5, 4)
    t = cfg["seq_len"]
    assert batch.features.shape == (4, 2 * t)
    assert batch.labels.shape == (4, t, 2)
    ts = model.train_state
    want = float(reference.loss(cfg, ts.params, ts.model_state,
                                (batch.features,), (batch.labels,)))
    got = float(model.score(batch))
    assert np.isfinite(want) and abs(got - want) / want < 1e-5
    # the head's logits are over all 2 T positions; the loss and the
    # reference read the noisy half
    logits = np.asarray(model.output(batch.features))
    ref = np.asarray(reference.logits(cfg, ts.params, ts.model_state,
                                      (batch.features,)))
    assert logits.shape == (4, 2 * t, cfg["vocab_size"])
    assert ref.shape == (4, t, cfg["vocab_size"])
    assert np.abs(logits[:, :t] - ref).max() < 2e-5 * np.abs(ref).max()


def test_the_loss_is_the_weighted_sum_over_the_masked_by_hand(tiny):
    cfg, build, model, reference = tiny
    batch = build.check_batch(cfg, 8, 3)
    ts = model.train_state
    t = cfg["seq_len"]
    logits = np.asarray(reference.logits(cfg, ts.params, ts.model_state,
                                         (batch.features,)), np.float64)
    x0 = np.asarray(batch.features)[:, t:]
    masked = np.asarray(batch.features)[:, :t] == cfg["mask_token_id"]
    weight = np.asarray(batch.labels)[..., 1]
    assert (np.asarray(batch.labels)[..., 0][masked] == x0[masked]).all()
    assert (np.asarray(batch.labels)[..., 0][~masked] == -1).all()
    assert (weight[~masked] == 0).all() and masked.any()
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    total = 0.0
    for n in range(3):
        for i in np.flatnonzero(masked[n]):
            total -= weight[n, i] * logp[n, i, x0[n, i]]
    # and the layers' load-balancing losses, each by hand from its router's
    # probabilities: all 8 outputs count, the 4 held and the 4 absent
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    ts.params)
    balance = 0.0
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["W"][np.asarray(batch.features)]
        for l in range(cfg["num_hidden_layers"]):
            p = params[f"block{l}"]
            eps = cfg["rms_norm_eps"]
            x = x + reference._attention(
                reference._rms_norm(x, p["norm1"]["w"], eps), p["mixer"], cfg)
            a = reference._rms_norm(x, p["norm2"]["w"], eps)
            probs = np.asarray(jax.nn.softmax(
                a.reshape(-1, a.shape[-1]) @ p["moe"]["router"], -1),
                np.float64)
            top = np.argsort(-probs, -1)[:, :cfg["num_experts_per_tok"]]
            received = np.bincount(top.reshape(-1),
                                   minlength=cfg["router_width"])
            assert received.sum() == 3 * 2 * t * 2
            balance += cfg["router_width"] * float(
                (received / len(probs)) @ probs.mean(0))
            x = x + reference._experts(a, p["moe"], cfg)[0]
    assert 2 * 2 <= balance < 2 * 8        # k a layer when even; below E
    want = total / (3 * t) + cfg["router_aux_loss_coef"] * balance
    assert float(model.score(batch)) == pytest.approx(want, rel=1e-5)


def test_parameter_gradients_agree_with_the_reference(tiny):
    cfg, build, model, reference = tiny
    batch = build.check_batch(cfg, 6, 2)
    ts = model.train_state
    ids, labels = jnp.asarray(batch.features), jnp.asarray(batch.labels)

    def system(params):
        return model._loss(params, ts.model_state, ids, labels, None, None,
                           None, ts.iteration)[0]

    got = jax.jit(jax.grad(system))(ts.params)
    want = jax.jit(jax.grad(reference.loss_fn(cfg)))(ts.params, ids, labels)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert len(flat_got) == len(flat_want) == 2 * 12 + 3
    for path, g in flat_got:
        w = flat_want[path]
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, path                 # every parameter is reached
        assert float(jnp.linalg.norm(g - w)) < 2e-4 * scale, path


def test_the_training_layout_computes_what_generation_block_by_block_will(
        tiny):
    """For every block b, the noisy half's logits of block b equal a plain
    forward over ``[x0 blocks < b | xt block b]`` under the block-causal
    mask, at those positions: the clean prefix attends block-causally
    among itself and the block being denoised sees all of it and itself,
    as a sampler that commits block after block computes it."""
    cfg, build, model, reference = tiny
    batch = build.check_batch(cfg, 7, 1)
    ts = model.train_state
    t, b = cfg["seq_len"], cfg["block_length"]
    feats = np.asarray(batch.features)
    xt, x0 = feats[:, :t], feats[:, t:]
    whole = np.asarray(reference.logits(cfg, ts.params, ts.model_state,
                                        (batch.features,)))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    ts.params)

    def plain_forward(ids):
        """The decoder on ``ids`` (1, L) at positions 0 .. L - 1 under the
        block-causal mask ``block(r) <= block(s)``, literally."""
        length = ids.shape[1]
        pos = np.arange(length)
        seen = (pos[None, :] // b) <= (pos[:, None] // b)
        x = params["embed"]["W"][ids]
        for l in range(cfg["num_hidden_layers"]):
            p = params[f"block{l}"]
            a = reference._rms_norm(x, p["norm1"]["w"], cfg["rms_norm_eps"])
            h, hk, dh = (cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["head_dim"])
            m = p["mixer"]
            q = (a @ m["W_q"]).reshape(1, length, h, dh)
            k = (a @ m["W_k"]).reshape(1, length, hk, dh)
            v = (a @ m["W_v"]).reshape(1, length, hk, dh)
            q = reference._rotate(reference._rms_norm(
                q, m["q_norm"], cfg["rms_norm_eps"]), jnp.asarray(pos),
                cfg["rope_theta"])
            k = reference._rotate(reference._rms_norm(
                k, m["k_norm"], cfg["rms_norm_eps"]), jnp.asarray(pos),
                cfg["rope_theta"])
            k, v = (jnp.repeat(z, h // hk, axis=2) for z in (k, v))
            s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(dh)
            s = jnp.where(jnp.asarray(seen)[None, None], s, -jnp.inf)
            o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1), v)
            x = x + o.reshape(1, length, h * dh) @ m["W_o"]
            x = x + reference._experts(reference._rms_norm(
                x, p["norm2"]["w"], cfg["rms_norm_eps"]), p["moe"], cfg)[0]
        head = params["lm_head"]
        return reference._rms_norm(x, head["norm"]["w"],
                                   cfg["rms_norm_eps"]) @ head["W"]

    assert t // b == 8
    with jax.default_matmul_precision("highest"):
        for blk in range(t // b):
            lo, hi = blk * b, (blk + 1) * b
            ids = np.concatenate([x0[:, :lo], xt[:, lo:hi]], axis=1)
            got = np.asarray(plain_forward(jnp.asarray(ids)))[:, lo:hi]
            assert np.abs(got - whole[:, lo:hi]).max() < 2e-5 * np.abs(
                whole).max(), blk


def test_fit_trains_the_zoo_model_and_it_round_trips(tiny):
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
    cfg, build, _, _ = tiny
    zoo = build.zoo_model(cfg, 3)
    # the model's own noiser is the configuration's: same [MASK], same eps
    assert (zoo.noiser().mask_id, zoo.noiser().eps) == (
        cfg["mask_token_id"], cfg["noise_eps"])
    assert dataclasses.replace(zoo, mask_token_id=None).mask_id == 63
    conf = zoo.conf()
    text = conf.to_json()
    again = MultiLayerConfiguration.from_json(text)
    assert again.to_json() == text
    block = again.layers[1]
    assert block.held_experts == tuple(range(4, 8))
    assert (block.mixer, block.block_length, block.shared_hidden) == (
        "block_diffusion_attention", 4, 0)
    model = MultiLayerNetwork(again).init(3)
    assert model.num_params() == build.parameter_count(cfg)["on_the_chip"]
    data = build.train_set(cfg, 3, 2)
    # single steps' losses swing with their noise level (the 1/t weights),
    # so the trend is read on the set's own rows under one fixed noise
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rows = build.noiser(cfg, 99).pre_process(
        DataSet(build._token_ids(cfg, 3, cfg["examples"]), None))
    first = float(model.score(rows))
    model.fit(data, epochs=12)
    assert np.isfinite(model.score())
    assert float(model.score(rows)) < first - 0.3
    from deeplearning4j_tpu.observe.registry import default_registry
    row = np.asarray(model.train_state.model_state["block0"]["moe_routing"])
    assert row[0] > 0 and row[1] >= row[2] > 0 and row[3] == 0
    share = default_registry().get_metric("dl4j_diffusion_masked_share")
    assert 0.0 < list(share.series().values())[-1] < 1.0


def test_a_saved_qwen3_next_configuration_still_loads():
    """A configuration written before ``GatedAttention`` had its
    ``output_gate`` and ``block_length`` fields, and ``HybridDecoderBlock``
    its ``block_length``, lacks those keys: it loads as the gated causal
    attention it was."""
    from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
    from deeplearning4j_tpu.zoo.models import Qwen3Next
    conf = Qwen3Next(vocab_size=64, hidden_size=32, num_hidden_layers=4,
                     num_attention_heads=4, num_key_value_heads=2,
                     head_dim=16, linear_num_key_heads=2,
                     linear_num_value_heads=4, linear_key_head_dim=8,
                     linear_value_head_dim=8, num_experts=8,
                     num_experts_per_tok=2, moe_intermediate_size=16,
                     shared_expert_intermediate_size=16, seq_len=16,
                     chunk_size=8, compute_dtype="float32").conf()
    saved = json.loads(conf.to_json())

    def strip(node):
        if isinstance(node, dict):
            node.pop("block_length", None)
            node.pop("output_gate", None)
            for v in node.values():
                strip(v)
        elif isinstance(node, list):
            for v in node:
                strip(v)
    strip(saved)
    assert "block_length" not in json.dumps(saved)
    again = MultiLayerConfiguration.from_json(json.dumps(saved))
    assert again.to_json() == conf.to_json()
    mixer = again.layers[4]._parts()[0]
    assert mixer.output_gate and mixer.block_length == 0
    assert mixer.scope == "attn.gated"


def test_the_files_parameter_table_is_the_builders_count():
    """``parameter_count`` at the published widths, by shapes alone,
    against the model's own count (no weight is made)."""
    cell = cells.resolve_cell(CELL)
    build = cells.load_build(cell)
    count = build.parameter_count(cell.config)
    assert count["on_the_chip"] == 456_346_624
    assert count["bytes_at_16_per_parameter"] == 7_301_545_984
    for key, value in count.items():
        assert cell.config["parameters"][key] == value, key
    model = build.build(cell.config, 0)
    shapes = jax.eval_shape(lambda: model.init(0).train_state.params)
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes)) == count["on_the_chip"]
    flops = build.train_flops_per_example(cell.config)
    assert 24.4e12 < flops < 24.6e12
    attention, nbytes = build.block_diffusion_attention_work(cell.config)
    assert 0.53 < attention / flops < 0.55
    assert nbytes == 3 * 4 * 16384 * 72 * 128 * 2
    moe, _ = build.moe_grouped_work(cell.config)
    assert 0.07 < moe / flops < 0.09


def test_the_configuration_file_states_its_source_cuts_and_limit():
    """What ``test_cells.py`` holds of every configuration's file, held
    here for this one too: its own case trips over a pattern that reads
    the ``hidden`` of ``num_hidden_layers`` as a width (PERF.md §7), and
    stops before these."""
    manifest = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    entry, = [c for c in manifest["configs"]
              if c["name"] == "sdar-30b-a3b-ep8"]
    body = json.loads((cells.ROOT / entry["file"]).read_text())
    assert body["source"] == entry["source"]
    assert body["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert body["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                 "vocab_size": 151936}
    assert all(k in body and body[k] < body["published"][k]
               for k in body["reduced"])
    for key in ("block_length", "mask_token_id", "noise_schedule",
                "no_shift", "seq_len", "batch"):
        assert key in body["assumed"], key
    assert body["block_length"] == 4
    assert body["mask_token_id"] == body["vocab_size"] - 1
    assert body["departures"] and "8 chips" in body["deployment"]
    # every number of the catalog's config, under the same key
    published = {"attention_bias": False, "decoder_sparse_step": 1,
                 "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
                 "intermediate_size": 6144, "max_position_embeddings": 32768,
                 "max_window_layers": 48, "mlp_only_layers": [],
                 "model_type": "sdar_moe", "moe_intermediate_size": 768,
                 "norm_topk_prob": True, "num_attention_heads": 32,
                 "num_experts_per_tok": 8, "num_key_value_heads": 4,
                 "rms_norm_eps": 1e-06, "rope_scaling": None,
                 "rope_theta": 1000000, "sliding_window": None,
                 "tie_word_embeddings": False, "use_sliding_window": False}
    for key, value in published.items():
        assert body[key] == value, key
    # the limit sits over the system's largest reading on the chip and
    # under the float8 control's smallest, both in its reason
    assert 1.075e-3 < body["loss_tolerance"] < 0.166
    assert body["router_aux_loss_coef"] == 0.1
    assert "router_aux_loss_coef" in body["assumed"]
    assert "float8" in body["loss_tolerance_why"]
    assert "masked positions" in body["loss_tolerance_why"]


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
    ids = (jnp.asarray(build.check_batch(cfg, 9, 2).features),)
    want = reference.logits(cfg, ts.params, ts.model_state, ids)
    got = reference.logits({**cfg, **control}, ts.params, ts.model_state,
                           ids)
    apart = float(jnp.sqrt(jnp.mean((got - want) ** 2)) / jnp.std(want))
    assert least <= apart <= most


@pytest.mark.parametrize("compute,bands", [
    ("float32", {"logits": 1e-4, "loss": 1e-5, "dense": 1e-3,
                 "routed": 1e-3}),
    ("bfloat16", {"logits": 0.04, "loss": 2e-2, "dense": 0.10,
                  "routed": 0.30}),
])
def test_the_system_meets_stated_bands_that_the_float8_control_fails(
        compute, bands):
    """Logits (rms over the reference's spread, noisy half), loss and
    gradients by kind, system against float32 reference on seeded weights:
    tight at float32 compute, inside stated bands at bfloat16; the
    reference with float8 operands is outside the bfloat16 bands on the
    logits and on both kinds of gradient."""
    cell = cells.resolve_cell(CELL)
    cfg = {**cell.config, **TINY, "compute_dtype": compute}
    build, reference = cells.load_build(cell), cells.load_reference(cell)
    model = init_on_device(build.build(cfg, 11), 11)
    ts = model.train_state
    batch = build.check_batch(cfg, 11, 4)
    ids, labels = jnp.asarray(batch.features), jnp.asarray(batch.labels)
    t = cfg["seq_len"]

    def readings(logits, loss, grads):
        want_logits = reference.logits(cfg, ts.params, ts.model_state, (ids,))
        want_loss = float(reference.loss(cfg, ts.params, ts.model_state,
                                         (ids,), (labels,)))
        want = jax.jit(jax.grad(reference.loss_fn(cfg)))(ts.params, ids,
                                                         labels)
        worst = {"dense": 0.0, "routed": 0.0}
        for path, g in jax.tree_util.tree_leaves_with_path(grads):
            w = dict(jax.tree_util.tree_leaves_with_path(want))[path]
            kind = "routed" if "moe" in jax.tree_util.keystr(path) \
                else "dense"
            worst[kind] = max(worst[kind], float(
                jnp.linalg.norm(g - w) / jnp.linalg.norm(w)))
        return {"logits": float(jnp.sqrt(jnp.mean(
                    (logits - want_logits) ** 2)) / jnp.std(want_logits)),
                "loss": abs(float(loss) - want_loss) / want_loss, **worst}

    def system(params):
        return model._loss(params, ts.model_state, ids, labels, None, None,
                           None, ts.iteration)[0]

    got = readings(model.output(batch.features)[:, :t], model.score(batch),
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
    """Each of the 8 shares routes over all 128 experts' outputs (here 16
    of width 24) and computes its own 1/8; there is no shared expert; the
    parts of all the shares are the uncut layer as the reference computes
    it."""
    reference = cells.load_file_module(
        cells.ROOT / "yardstick" / "reference" / "sdar_moe.py")
    rng = np.random.default_rng(2)
    e, d, shares = 16, 32, 8
    per = e // shares
    whole = HeldExpertsMoE(n_in=d, n_out=d, num_experts=e, hidden=24,
                           shared_hidden=0, top_k=4)
    params = whole.initialize(jax.random.PRNGKey(1), RecurrentType(d, None))
    assert not [k for k in params if k.startswith("shared")]
    x = jnp.asarray(rng.normal(size=(2, 40, d)), jnp.float32)
    ctx = LayerContext(train=False)
    total, landed = jnp.zeros_like(x), 0.0
    for share in range(shares):
        held = tuple(range(share * per, (share + 1) * per))
        layer = dataclasses.replace(whole, held_experts=held)
        mine = {k: (v[share * per:(share + 1) * per]
                    if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in params.items()}
        y, state = layer.apply(mine, {}, x, ctx)
        total, landed = total + y, landed + float(state["moe_routing"][0])
    assert landed == 2 * 40 * 4                 # every assignment, once
    cfg = {"num_experts": e, "num_experts_per_tok": 4,
           "norm_topk_prob": True}
    want, _ = reference._experts(x, params, cfg)
    assert np.abs(total - want).max() < 2e-5 * np.abs(want).max()
    # and one share is what the reference gives for that share
    cfg = {"num_experts": per, "expert_parallel_rank": 3,
           "num_experts_per_tok": 4, "norm_topk_prob": True}
    held = reference.held_experts(cfg)
    assert held == (6, 7)
    layer = dataclasses.replace(whole, held_experts=held)
    mine = {k: (v[6:8] if k in ("w_gate", "w_up", "w_down") else v)
            for k, v in params.items()}
    y, _ = layer.apply(mine, {}, x, ctx)
    want, _ = reference._experts(x, mine, cfg)
    assert np.abs(y - want).max() < 2e-5 * max(1.0, np.abs(want).max())


def test_full_rotary_at_positions_mod_t_against_a_loop_over_heads():
    """``GatedAttention`` without its gate, rotary on the whole head,
    positions ``s mod T``, grouped heads: against the reference's
    attention, which builds the mask from its definition."""
    from deeplearning4j_tpu.nn.layers.attention import GatedAttention
    reference = cells.load_file_module(
        cells.ROOT / "yardstick" / "reference" / "sdar_moe.py")
    d, h, hk, dh, t, b = 32, 4, 2, 8, 12, 4
    layer = GatedAttention(n_in=d, n_out=d, n_heads=h, n_kv_heads=hk,
                           head_dim=dh, partial_rotary_factor=1.0,
                           rope_theta=1e6, output_gate=False, block_length=b)
    params = layer.initialize(jax.random.PRNGKey(4), RecurrentType(d, None))
    assert params["W_q"].shape == (d, h * dh)
    rng = np.random.default_rng(3)
    params = {**params,
              "q_norm": jnp.asarray(rng.normal(size=dh) * 0.3, jnp.float32),
              "k_norm": jnp.asarray(rng.normal(size=dh) * 0.3, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(2, 2 * t, d)), jnp.float32)
    got, _ = layer.apply(params, {}, x, LayerContext(train=False))
    cfg = {"num_attention_heads": h, "num_key_value_heads": hk,
           "head_dim": dh, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
           "block_length": b}
    want = reference._attention(x, params, cfg)
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
    with pytest.raises(ValueError, match="even number"):
        layer.apply(params, {}, x[:, :-1], LayerContext(train=False))
