"""The forms ``held_experts_ffn`` / ``HeldExpertsMoE`` took on with the
Nemotron-H family: non-gated relu^2 experts (two grouped products), a
sigmoid router whose choice adds a bias that the load moves and no
gradient reaches, weights times a scaling factor, a shared expert added
ungated; and that configurations saved before the fields were there load
and train as they did."""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.nn.layers.feedforward import HeldExpertsMoE
from deeplearning4j_tpu.parallel import moe

D, F, E = 16, 12, 16
NEW_KEYS = ("expert_form", "router_scoring", "routed_scale", "shared_gate",
            "bias_update_rate", "qk_norm")


def weights(held, seed=0, gated=False):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    g = len(held)
    return {"x": jax.random.normal(k[0], (40, D)),
            "router": jax.random.normal(k[1], (D, E)),
            "w_gate": (jax.random.normal(k[4], (g, D, F)) * 0.3
                       if gated else None),
            "w_up": jax.random.normal(k[2], (g, D, F)) * 0.3,
            "w_down": jax.random.normal(k[3], (g, F, D)) * 0.3}


def dense(w, held, top_k, bias=None, scale=1.0):
    """Every token through every held expert, times its weight or 0."""
    s = jax.nn.sigmoid(w["x"] @ w["router"])
    _, ids = jax.lax.top_k(s if bias is None else s + bias, top_k)
    top = jnp.take_along_axis(s, ids, -1)
    top = scale * top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    y = jnp.zeros_like(w["x"])
    for j, e in enumerate(held):
        weight = jnp.sum(jnp.where(ids == e, top, 0.0), -1)
        h = jnp.square(jax.nn.relu(w["x"] @ w["w_up"][j]))
        y = y + weight[:, None] * (h @ w["w_down"][j])
    return y


@pytest.mark.parametrize("held,top_k", [
    (tuple(range(E)), 3),            # everything held
    ((4, 5, 6, 7), 4),               # a share
    ((1, 9), 6),                     # fewer held than chosen
])
def test_plain_relu2_experts_are_the_dense_loop(held, top_k):
    w = weights(held)
    bias = jnp.linspace(-0.2, 0.2, E)
    with jax.default_matmul_precision("highest"):
        got, counters = moe.held_experts_ffn(
            w["x"], w["router"], None, w["w_up"], w["w_down"], held,
            top_k=top_k, scoring="sigmoid", router_bias=bias,
            routed_scale=2.5)
        want = dense(w, held, top_k, bias, 2.5)
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    assert counters[3] == 0                               # nothing dropped


def test_plain_relu2_gradients_are_the_dense_loops():
    held = (2, 3, 4, 5, 6)
    w = weights(held)
    names = ("x", "router", "w_up", "w_down")

    def ours(*a):
        p = dict(zip(names, a))
        return jnp.sum(jnp.sin(moe.held_experts_ffn(
            p["x"], p["router"], None, p["w_up"], p["w_down"], held,
            top_k=4, scoring="sigmoid", router_bias=jnp.zeros(E),
            routed_scale=2.5)[0]))

    def plain(*a):
        return jnp.sum(jnp.sin(dense(dict(zip(names, a)), held, 4,
                                     jnp.zeros(E), 2.5)))

    args = [w[n] for n in names]
    with jax.default_matmul_precision("highest"):
        got = jax.grad(ours, (0, 1, 2, 3))(*args)
        want = jax.grad(plain, (0, 1, 2, 3))(*args)
    for name, g, r in zip(names, got, want):
        assert float(jnp.linalg.norm(r)) > 0, name
        assert float(jnp.linalg.norm(g - r)) < 1e-5 * float(
            jnp.linalg.norm(r)), name


def _count(jaxpr, primitive):
    """Equations of ``primitive`` in ``jaxpr`` and the jaxprs inside it."""
    total = 0
    for eqn in jaxpr.eqns:
        total += eqn.primitive.name.startswith(primitive)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _count(sub, primitive)
    return total


def test_the_plain_form_runs_two_grouped_products_a_block():
    held = (0, 1, 2, 3)
    counts = {}
    for gated in (False, True):
        w = weights(held, gated=gated)
        jaxpr = jax.make_jaxpr(lambda x: moe.held_experts_ffn(
            x, w["router"], w["w_gate"], w["w_up"], w["w_down"], held,
            top_k=2)[0])(w["x"])
        counts[gated] = _count(jaxpr.jaxpr, "ragged_dot")
    assert counts == {False: 2, True: 3}


def test_the_bias_steers_the_choice_and_reaches_no_weight():
    """The chosen experts are the largest of ``s + b``; their weights are
    the unbiased ``s`` over their sum, times the scale; no gradient
    reaches ``b``."""
    s = jnp.asarray([[0.9, 0.8, 0.1, 0.2], [0.3, 0.2, 0.6, 0.5]])
    ids, w = moe.top_k_weights(s, 2, bias=jnp.asarray([0., -1., 1., 0.]),
                               scale=2.5)
    assert sorted(ids[0].tolist()) == [0, 2]
    assert sorted(ids[1].tolist()) == [2, 3]
    by_id = dict(zip(ids[0].tolist(), w[0].tolist()))
    assert by_id[0] == pytest.approx(2.5 * 0.9 / 1.0)
    assert by_id[2] == pytest.approx(2.5 * 0.1 / 1.0)
    assert float(jnp.sum(w[1])) == pytest.approx(2.5)
    # without a bias, and without renormalising, the scores as they are
    ids, w = moe.top_k_weights(s, 2, norm_topk=False)
    assert ids[0].tolist() == [0, 1] and w[0].tolist() == [
        pytest.approx(0.9), pytest.approx(0.8)]
    held = (0, 1, 2, 3)
    p = weights(held)

    def of_bias(b):
        return jnp.sum(moe.held_experts_ffn(
            p["x"], p["router"], None, p["w_up"], p["w_down"], held, top_k=2,
            scoring="sigmoid", router_bias=b)[0] ** 2)

    assert not np.asarray(jax.grad(of_bias)(jnp.zeros(E) + 0.01)).any()
    with pytest.raises(ValueError, match="scoring"):
        moe.router_probs(p["x"], p["router"], "tanh")


def layer(**over):
    return HeldExpertsMoE(**{**dict(
        n_in=D, n_out=D, num_experts=E, held_experts=(0, 1, 2, 3),
        hidden=F, shared_hidden=20, top_k=3, expert_form="relu2",
        router_scoring="sigmoid", routed_scale=2.5, shared_gate=False,
        bias_update_rate=1e-3), **over})


def test_the_plain_form_allocates_no_gate():
    rt = RecurrentType(D, None)
    params = layer().initialize(jax.random.PRNGKey(0), rt)
    assert set(params) == {"router", "w_up", "w_down", "shared_up",
                           "shared_down"}
    assert sum(int(np.prod(v.shape)) for v in params.values()) == (
        D * E + 4 * 2 * D * F + 2 * D * 20)
    gated = HeldExpertsMoE(n_in=D, n_out=D, num_experts=E, hidden=F,
                           shared_hidden=20).initialize(
        jax.random.PRNGKey(0), rt)
    assert set(gated) == {"router", "w_gate", "w_up", "w_down",
                          "shared_gate", "shared_up", "shared_down",
                          "shared_w"}
    # the same keys give the matrices both forms share the same values
    for k in ("router", "shared_up", "shared_down"):
        assert (np.asarray(params[k]) == np.asarray(gated[k])).all()
    for bad in (dict(expert_form="geglu"), dict(router_scoring="tanh"),
                dict(router_scoring="softmax")):
        with pytest.raises(ValueError):
            layer(**bad)


def test_the_shared_expert_is_added_ungated():
    lay = layer()
    rt = RecurrentType(D, None)
    params = lay.initialize(jax.random.PRNGKey(1), rt)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 20, D))
    state = lay.init_state(rt)
    assert set(state) == {"moe_routing", "moe_router_bias"}
    assert state["moe_routing"].shape == (6,)
    ctx = LayerContext(train=False)
    with jax.default_matmul_precision("highest"):
        y, _ = lay.apply(params, state, x, ctx)
        bare, _ = dataclasses.replace(lay, shared_hidden=0).apply(
            params, state, x, ctx)
        xt = x.reshape(-1, D)
        shared = jnp.square(jax.nn.relu(xt @ params["shared_up"])) \
            @ params["shared_down"]
    assert np.abs((y - bare).reshape(-1, D) - shared).max() < 1e-5


def test_the_load_moves_the_bias_towards_the_even_load():
    """One step: ``b_e += u sign(mean(c) - c_e)`` by the step's own counts
    over all E outputs; over many steps on one batch the load evens out.
    Evaluation moves nothing."""
    lay = layer(held_experts=(), shared_hidden=0, bias_update_rate=0.01)
    rt = RecurrentType(D, None)
    params = lay.initialize(jax.random.PRNGKey(1), rt)
    # scores spread over (0, 1), so that a step of the bias is a fine one
    params["router"] = params["router"] * 20.0
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 200, D))
    state = lay.init_state(rt)
    train, evaluate = LayerContext(train=True), LayerContext(train=False)
    _, same = lay.apply(params, state, x, evaluate)
    assert not np.asarray(same["moe_router_bias"]).any()
    scores = jax.nn.sigmoid(x.reshape(-1, D) @ params["router"])
    counts = np.bincount(np.asarray(jax.lax.top_k(scores, 3)[1]).ravel(),
                         minlength=E)
    _, after = lay.apply(params, state, x, train)
    want = 0.01 * np.sign(counts.mean() - counts)
    assert np.allclose(after["moe_router_bias"], want)
    assert float(after["moe_routing"][5]) == pytest.approx(0.01)
    # from a bias that favours the first experts, back to an even load
    state = {**state, "moe_router_bias": jnp.linspace(0.5, -0.5, E)}
    step = jax.jit(lambda s: lay.apply(params, s, x, train)[1])
    spread = []
    for i in range(200):
        state = step(state)
        if i in (0, 199):
            s = scores + state["moe_router_bias"]
            got = np.bincount(np.asarray(jax.lax.top_k(s, 3)[1]).ravel(),
                              minlength=E)
            spread.append(got.max() / got.mean())
    assert spread[0] > 2.0 and spread[1] < 1.5

    def of_params(p):
        y, s = lay.apply(p, lay.init_state(rt), x, train)
        return jnp.sum(y ** 2) + jnp.sum(s["moe_router_bias"])
    grads = jax.grad(of_params)(params)
    assert np.isfinite(grads["router"]).all()


def test_the_balance_loss_reads_the_normalised_sigmoid_scores():
    lay = layer(aux_loss_coef=0.1, shared_hidden=0)
    rt = RecurrentType(D, None)
    params = lay.initialize(jax.random.PRNGKey(3), rt)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, D))
    state = lay.init_state(rt)
    assert set(state) == {"moe_routing", "moe_router_bias", "moe_aux_loss"}
    _, new = lay.apply(params, state, x, LayerContext(train=True))
    s = np.asarray(jax.nn.sigmoid(x.reshape(-1, D) @ params["router"]),
                   np.float64)
    top = np.argsort(-s, -1)[:, :3]
    f = np.bincount(top.ravel(), minlength=E) / 64
    want = 0.1 * E * float(f @ (s / s.sum(-1, keepdims=True)).mean(0))
    assert float(new["moe_aux_loss"]) == pytest.approx(want, rel=1e-5)
    assert 3 * 0.1 * 0.99 < want < E * 0.1


def test_the_bias_gauge_is_published_with_the_routing_gauges():
    from deeplearning4j_tpu.observe.registry import MetricsRegistry
    from deeplearning4j_tpu.observe.telemetry import (ROUTING_GAUGES,
                                                      publish_routing)
    assert ROUTING_GAUGES[5][0] == "dl4j_moe_router_bias_absmax"
    reg = MetricsRegistry()
    publish_routing({"sig": np.array([5., 3., 2.5, 0., 1., 0.004]),
                     "soft": np.array([7., 4., 3.5, 0., 2.])}, reg)
    bias = reg.get_metric("dl4j_moe_router_bias_absmax").series()
    assert list(bias) == [(("layer", "sig"),)]
    assert bias[(("layer", "sig"),)] == pytest.approx(0.004)
    blocks = reg.get_metric("dl4j_moe_dispatch_blocks").series()
    assert blocks == {(("layer", "sig"),): 1.0, (("layer", "soft"),): 2.0}
    # a softmax layer's row stays five long, saved or new
    soft = HeldExpertsMoE(n_in=D, n_out=D, num_experts=E, hidden=F)
    assert soft.upgrade_state({"moe_routing": jnp.ones(4)})[
        "moe_routing"].shape == (5,)
    assert layer().upgrade_state({"moe_routing": jnp.ones(5)})[
        "moe_routing"].shape == (6,)


# a saved configuration's text (keys sorted) and its scores on seeded weights and rows, as the commit before the fields wrote
# and computed them (7ce79ed; CPU, float32)
SAVED = {
    "qwen3next": ("37ddd28687146bbcb27fe1ddd376391895570e9897bfb080ca528f86"
                  "f5cb2bf8", "0x1.0ab5c40000000p+2", "0x1.09c12e0000000p+2"),
    "sdar": ("914179308b90d1d30960bf29e0a90df334a31c646e47910afa6b6cd0709aa"
             "d38", "0x1.2873ec0000000p+2", "0x1.27e29c0000000p+2"),
}


@pytest.mark.parametrize("family", sorted(SAVED))
def test_a_saved_configuration_loads_scores_and_trains_as_before(family):
    """A Qwen3-Next or SDAR configuration saved before the expert layer
    had its form, scoring, scale, shared-gate and bias fields and the
    attention its ``qk_norm``: the blocks serialise flat, so the text is
    the one the parent commit wrote (by its hash), and it builds what it
    built: the score on seeded weights, before and after three steps, is
    the parent's to the last bit."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
    from deeplearning4j_tpu.nn.layers.decoder import next_token_labels
    from deeplearning4j_tpu.zoo.models import Qwen3Next, SDARMoE
    ids = np.random.default_rng(0).integers(0, 63, (3, 16)).astype(np.int32)
    if family == "qwen3next":
        conf = Qwen3Next(
            vocab_size=64, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8, num_experts=8,
            held_experts=(2, 3, 4, 5), num_experts_per_tok=2,
            moe_intermediate_size=16, shared_expert_intermediate_size=16,
            seq_len=16, chunk_size=8, compute_dtype="float32",
            seed=7).conf()
        data = DataSet(ids, next_token_labels(ids))
    else:
        zoo = SDARMoE(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            num_experts=8, held_experts=(4, 5, 6, 7), num_experts_per_tok=2,
            moe_intermediate_size=16, router_aux_loss_coef=0.05,
            block_length=4, seq_len=16, compute_dtype="float32", seed=7)
        conf = zoo.conf()
        data = zoo.noiser(seed=5).pre_process(DataSet(ids, None))
    digest, before, after = SAVED[family]
    saved = json.dumps(json.loads(conf.to_json()), sort_keys=True)
    assert hashlib.sha256(saved.encode()).hexdigest() == digest
    loaded = MultiLayerConfiguration.from_json(saved)
    assert loaded.to_json() == conf.to_json()
    model = MultiLayerNetwork(loaded).init(11)
    assert float(model.score(data)).hex() == before
    model.fit(data, epochs=3)
    assert float(model.score(data)).hex() == after


def test_layers_saved_before_the_fields_load_with_the_defaults():
    """The layers themselves, saved on their own (a user's network that
    lists them) without the new keys."""
    from deeplearning4j_tpu.nn.layers.attention import GatedAttention
    from deeplearning4j_tpu.utils import serde
    for lay in (HeldExpertsMoE(n_in=D, n_out=D, num_experts=E, hidden=F,
                               shared_hidden=8, aux_loss_coef=0.1),
                GatedAttention(n_in=D, n_out=D, n_heads=4, n_kv_heads=2,
                               head_dim=8)):
        saved = serde.to_dict(lay)
        assert [saved.pop(k) for k in NEW_KEYS if k in saved]
        assert serde.from_dict(saved) == lay
    attn = GatedAttention(n_in=D, n_out=D, n_heads=4, n_kv_heads=2,
                          head_dim=8, output_gate=False)
    assert attn.qk_norm and attn.scope == "attn.gated"
    bare = dataclasses.replace(attn, qk_norm=False, partial_rotary_factor=0.0)
    assert bare.scope == "attn.causal"
    assert set(bare.initialize(jax.random.PRNGKey(0),
                               RecurrentType(D, None))) == {
        "W_q", "W_k", "W_v", "W_o"}
