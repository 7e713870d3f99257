"""qwen3-next-80b-a3b-ep16 at a preset small enough for the CPU (hidden
32, one period of four blocks, 8 experts held of a router's 16, top-2,
T = 48, vocabulary 64, float32): the system against the plain reference,
and each of the mechanisms the configuration forced against the form of
it that can be checked by eye."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers.attention import (GatedAttention,
                                                    rotary_embedding)
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.nn.layers.feedforward import HeldExpertsMoE
from deeplearning4j_tpu.nn.layers.linear_attention import (
    chunk_gated_delta_rule, l2_normalize, recurrent_gated_delta_rule)
from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers.normalization import rms_norm
from deeplearning4j_tpu.parallel.moe import (held_experts_ffn,
                                             route_top_k_probs)
from yardstick import cells
from yardstick.weights import init_on_device

CELL = "qwen3-next-80b-a3b-ep16.fit-seq8k"
TINY = {"hidden_size": 32, "head_dim": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "linear_key_head_dim": 8,
        "linear_value_head_dim": 8, "moe_intermediate_size": 16,
        "shared_expert_intermediate_size": 16, "num_experts": 8,
        "router_width": 16, "expert_parallel_rank": 1,
        "num_experts_per_tok": 2, "vocab_size": 64, "seq_len": 48,
        "chunk_size": 16, "examples": 8, "repeated_span": 8,
        "compute_dtype": "float32",
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
    ts = model.train_state
    want = float(reference.loss(cfg, ts.params, ts.model_state,
                                (batch.features,), (batch.labels,)))
    got = float(model.score(batch))
    assert np.isfinite(want) and abs(got - want) / want < 1e-5
    # the loss is over T - 1 positions a row: the last has no next token
    assert (np.asarray(batch.labels)[:, -1] == -1).all()
    logits = np.asarray(model.output(batch.features))
    ref = np.asarray(reference.logits(cfg, ts.params, ts.model_state,
                                      (batch.features,)))
    assert logits.shape == (4, cfg["seq_len"], cfg["vocab_size"])
    assert np.abs(logits - ref).max() < 2e-5 * np.abs(ref).max()


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
    assert len(flat_got) == len(flat_want) > 40
    for path, g in flat_got:
        w = flat_want[path]
        scale = float(jnp.linalg.norm(w))
        assert scale > 0, path                 # every parameter is reached
        assert float(jnp.linalg.norm(g - w)) < 2e-4 * scale, path


def test_fit_trains_the_zoo_model_and_it_round_trips(tiny):
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
    cfg, build, _, _ = tiny
    conf = build.zoo_model(cfg, 3).conf()
    text = conf.to_json()
    again = MultiLayerConfiguration.from_json(text)
    assert again.to_json() == text
    assert again.layers[1].held_experts == tuple(range(8, 16))
    model = MultiLayerNetwork(again).init(3)
    assert model.num_params() == build.parameter_count(cfg)["on_the_chip"]
    data = build.train_set(cfg, 3, 2)
    model.fit(data, epochs=1)
    first = model.score()
    model.fit(data, epochs=8)
    assert model.score() < first - 0.3
    # the step's routing counters ride in the layer state and come out as
    # gauges when fit() ends: held assignments, largest and mean load, 0
    # dropped
    from deeplearning4j_tpu.observe.registry import default_registry
    row = np.asarray(model.train_state.model_state["block0"]["moe_routing"])
    assert row[0] > 0 and row[1] >= row[2] > 0 and row[3] == 0
    reg = default_registry()
    assert reg.get_metric("dl4j_moe_assignments_held").get(
        layer="block0") == row[0]
    assert reg.get_metric("dl4j_moe_dropped_assignments").get(
        layer="block3") == 0


def test_the_files_parameter_table_is_the_builders_count():
    cell = cells.resolve_cell(CELL)
    count = cells.load_build(cell).parameter_count(cell.config)
    assert cell.config["parameters"]["on_the_chip"] == count["on_the_chip"]
    assert count["on_the_chip"] == 625_667_136
    for key, value in count.items():
        assert cell.config["parameters"][key] == value, key
    flops = cells.load_build(cell).train_flops_per_example(cell.config)
    assert 1.38e9 < flops / cell.config["seq_len"] < 1.40e9


def test_the_configuration_file_states_its_source_cuts_and_limit():
    """What ``test_cells.py`` holds of every configuration's file, held
    here for this one too: its own case trips over a pattern that reads
    the ``hidden`` of ``num_hidden_layers`` as a width (PERF.md §7), and
    stops before these."""
    manifest = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    entry, = [c for c in manifest["configs"]
              if c["name"] == "qwen3-next-80b-a3b-ep16"]
    body = json.loads((cells.ROOT / entry["file"]).read_text())
    assert body["source"] == entry["source"]
    assert body["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert body["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                 "vocab_size": 151936}
    assert all(k in body and body[k] < body["published"][k]
               for k in body["reduced"])
    assert "assumed" in body and "batch" in body and body["departures"]
    # the limit sits over the system's largest reading on the chip and
    # under the float8 control's smallest, both in its reason
    assert 7.3e-5 < body["loss_tolerance"] < 2.2e-4
    assert "float8" in body["loss_tolerance_why"]


@pytest.mark.parametrize("control,least,most", [
    ({}, 0.0, 0.0),
    ({"control_operand_dtype": "bfloat16"}, 1e-4, 3e-2),
    ({"control_operand_dtype": "float8_e4m3fn"}, 3e-2, 1.0),
    ({"control_state_dtype": "bfloat16"}, 1e-6, 3e-2),
], ids=["none", "operands_bfloat16", "operands_float8", "state_bfloat16"])
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


def _delta_inputs(rng, n=2, t=48, h=3, dk=8, dv=16):
    q = l2_normalize(jnp.asarray(rng.normal(size=(n, t, h, dk)),
                                 jnp.float32)) / np.sqrt(dk)
    k = l2_normalize(jnp.asarray(rng.normal(size=(n, t, h, dk)),
                                 jnp.float32))
    v = jnp.asarray(rng.normal(size=(n, t, h, dv)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.01, 2.0, (n, t, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.95, (n, t, h)), jnp.float32)
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk", [16, 24, 20, 64])
def test_chunked_delta_rule_is_the_recurrence(rng, chunk):
    """Chunks that divide T = 48 (16, 24) and that do not (20, 64)."""
    args = _delta_inputs(rng)
    o_ref, s_ref = recurrent_gated_delta_rule(*args)
    o, s = chunk_gated_delta_rule(*args, chunk_size=chunk)
    assert np.abs(o - o_ref).max() < 2e-6 and np.abs(s - s_ref).max() < 5e-6

    def total(fn, **kw):
        return lambda *a: jnp.sum(jnp.sin(fn(*a, **kw)[0]))

    want = jax.grad(total(recurrent_gated_delta_rule), argnums=range(5))(
        *args)
    got = jax.grad(total(chunk_gated_delta_rule, chunk_size=chunk),
                   argnums=range(5))(*args)
    for a, b in zip(got, want):
        assert np.abs(a - b).max() < 2e-5 * max(1.0, np.abs(b).max())


def test_a_bfloat16_state_is_caught_at_float32_and_not_at_bfloat16(rng):
    """The carried state stays float32 between chunks. At float32 compute
    the chunked form is the recurrence to 1e-5 of its size, and rounding
    the state to bfloat16 after every token is a thousand times that: this
    file's tolerances catch it. Under the bf16 policy the matrix products
    round their operands (as the published kernels do), which alone costs
    about 0.4%: as much as a bfloat16 state would, so a comparison of
    bfloat16 logits on the chip cannot tell the two apart, and the float32
    state is held here."""
    bf = jnp.bfloat16
    q, k, v, g, beta = _delta_inputs(rng, n=1, t=512, h=2, dk=16, dv=16)
    q, k, v = (a.astype(bf).astype(jnp.float32) for a in (q, k, v))
    g = g * 0.02                                # long memory: errors add up
    exact, _ = recurrent_gated_delta_rule(q, k, v, g, beta)

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[..., None, None]
        delta = (vt - jnp.einsum("nhk,nhkv->nhv", kt, s)) * bt[..., None]
        s = (s + kt[..., :, None] * delta[..., None, :]).astype(bf).astype(
            jnp.float32)
        return s, jnp.einsum("nhk,nhkv->nhv", qt, s)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, rounded = jax.lax.scan(step, jnp.zeros((1, 2, 16, 16)), xs)
    scale = float(jnp.sqrt(jnp.mean(exact ** 2)))

    def err(a):
        return float(jnp.sqrt(jnp.mean((a - exact) ** 2))) / scale

    at_f32, _ = chunk_gated_delta_rule(q, k, v, g, beta, chunk_size=64)
    at_bf16, _ = chunk_gated_delta_rule(q.astype(bf), k.astype(bf),
                                        v.astype(bf), g, beta, chunk_size=64)
    assert err(at_f32) < 1e-5
    assert err(jnp.moveaxis(rounded, 0, 1)) > 3e-3
    assert 1e-3 < err(at_bf16) < 8e-3


def _expert_weights(rng, g, d=32, f=24):
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
    return mk(g, d, f), mk(g, d, f), mk(g, f, d)


def _masked_dense(x, router, w_gate, w_up, w_down, held, k):
    ids, w = route_top_k_probs(x, router, k)
    y = jnp.zeros_like(x)
    for j, eid in enumerate(held):
        p = jnp.sum(jnp.where(ids == eid, w, 0.0), -1)
        y = y + p[:, None] * (
            (jax.nn.silu(x @ w_gate[j]) * (x @ w_up[j])) @ w_down[j])
    return y


@pytest.mark.parametrize("held,favoured", [
    ((3, 4, 5, 9, 10, 11, 12, 15), 4),
    ((3, 4, 5, 9, 10, 11, 12, 15), 0),
    (tuple(range(16)), 4),
], ids=["a_held_expert", "an_absent_expert", "every_expert_held"])
def test_routing_drops_no_token_under_a_router_that_favours_one_expert(
        rng, held, favoured):
    """All 96 tokens pick the favoured expert first: held, it takes a load
    of 96 where an even router gives 12; absent, only the tokens' second
    choices land here; with all 16 held the buffer is every assignment."""
    t, d, e, k = 96, 32, 16, 2
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e)) * 0.1, jnp.float32)
    # the favoured expert's logit is far ahead for every token whatever x is
    x = x.at[:, 0].set(1.0)
    router = router.at[0, favoured].add(8.0)
    w = _expert_weights(rng, len(held))
    y, counters = jax.jit(lambda *a: held_experts_ffn(
        *a, held, top_k=k))(x, router, *w)
    ids = np.asarray(route_top_k_probs(x, router, k)[0])
    landed = int(np.isin(ids, held).sum())
    assert int((ids == favoured).sum()) == t        # all 96 on one expert
    load_max = max(int((ids == eid).sum()) for eid in held)
    if favoured in held:
        assert load_max == t
    assert counters.tolist() == [landed, load_max, landed / len(held), 0.0]
    want = _masked_dense(x, router, *w, held, k)
    assert np.abs(y - want).max() < 1e-5 * np.abs(want).max()
    grads = jax.grad(lambda *a: jnp.sum(jnp.sin(held_experts_ffn(
        *a, held, top_k=k)[0])), argnums=range(5))(x, router, *w)
    wants = jax.grad(lambda *a: jnp.sum(jnp.sin(_masked_dense(
        *a, held, k))), argnums=range(5))(x, router, *w)
    for a, b in zip(grads, wants):
        assert np.abs(a - b).max() < 1e-4 * max(1.0, np.abs(b).max())


@pytest.mark.parametrize("shares", [1, 2, 4])
def test_the_shares_add_up_to_the_whole_layer(rng, shares):
    """Each share routes over all 16 experts and computes its own; the
    routed parts of all the shares, with the shared expert counted once,
    are the uncut layer as the reference computes it."""
    reference = cells.load_file_module(
        cells.ROOT / "yardstick" / "reference" / "qwen3_next.py")
    e, d, per = 16, 32, 16 // shares
    whole = HeldExpertsMoE(n_in=d, n_out=d, num_experts=e, hidden=24,
                           shared_hidden=24, top_k=3)
    params = whole.initialize(jax.random.PRNGKey(1), RecurrentType(d, None))
    x = jnp.asarray(rng.normal(size=(2, 40, d)), jnp.float32)
    ctx = LayerContext(train=False)
    total, landed = jnp.zeros_like(x), 0.0
    for share in range(shares):
        held = tuple(range(share * per, (share + 1) * per))
        layer = dataclasses.replace(
            whole, held_experts=held,
            shared_hidden=24 if share == 0 else 0)
        mine = {k: (v[share * per:(share + 1) * per]
                    if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in params.items()
                if share == 0 or not k.startswith("shared")}
        y, state = layer.apply(mine, {}, x, ctx)
        total, landed = total + y, landed + float(state["moe_routing"][0])
    assert landed == 2 * 40 * 3                 # every assignment, once
    cfg = {"num_experts": e, "num_experts_per_tok": 3,
           "norm_topk_prob": True}
    want = reference._experts(x, params, cfg)
    assert np.abs(total - want).max() < 2e-5 * np.abs(want).max()


def test_partial_rotary_and_grouped_heads_against_a_loop_over_heads(rng):
    d, h, hk, dh, t = 32, 4, 2, 16, 12
    layer = GatedAttention(n_in=d, n_out=d, n_heads=h, n_kv_heads=hk,
                           head_dim=dh, partial_rotary_factor=0.25,
                           rope_theta=100.0)
    p = layer.initialize(jax.random.PRNGKey(2), RecurrentType(d, t))
    p = {k: v + 0.1 * jnp.asarray(rng.normal(size=v.shape), jnp.float32)
         for k, v in p.items()}
    x = jnp.asarray(rng.normal(size=(2, t, d)), jnp.float32)
    got, _ = layer.apply(p, {}, x, LayerContext(train=False))

    rot = 4                                     # a quarter of the head
    inv = 100.0 ** (-np.arange(rot // 2) * 2.0 / rot)

    def turn(vec, pos):                         # one head's vector at pos
        out = np.array(vec, np.float64)
        for i in range(rot // 2):
            a, b = vec[i], vec[i + rot // 2]
            c, s = np.cos(pos * inv[i]), np.sin(pos * inv[i])
            out[i], out[i + rot // 2] = a * c - b * s, b * c + a * s
        return out

    xn = np.asarray(x, np.float64)
    want = np.zeros((2, t, d))
    for n in range(2):
        heads = []
        for head in range(h):
            kv = head // (h // hk)              # two query heads a kv head
            wq = np.asarray(p["W_q"])[:, head * 2 * dh:(head + 1) * 2 * dh]
            q, gate = xn[n] @ wq[:, :dh], xn[n] @ wq[:, dh:]
            k = xn[n] @ np.asarray(p["W_k"])[:, kv * dh:(kv + 1) * dh]
            v = xn[n] @ np.asarray(p["W_v"])[:, kv * dh:(kv + 1) * dh]
            q = np.asarray(rms_norm(jnp.asarray(q), p["q_norm"]))
            k = np.asarray(rms_norm(jnp.asarray(k), p["k_norm"]))
            q = np.stack([turn(q[i], i) for i in range(t)])
            k = np.stack([turn(k[i], i) for i in range(t)])
            s = q @ k.T / np.sqrt(dh)
            s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
            a = np.exp(s - s.max(-1, keepdims=True))
            a = a / a.sum(-1, keepdims=True)
            heads.append((a @ v) / (1.0 + np.exp(-gate)))
        want[n] = np.concatenate(heads, -1) @ np.asarray(p["W_o"])
    assert np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()
    # the rest of the head passes untouched, position 0 is the identity
    v = jnp.asarray(rng.normal(size=(1, 3, 1, dh)), jnp.float32)
    turned = rotary_embedding(v, jnp.arange(3), rot, 100.0)
    assert np.array_equal(turned[..., rot:], v[..., rot:])
    assert np.allclose(turned[:, 0], v[:, 0])
