"""``held_experts_ffn``'s loop over blocks of rows: values and all five
gradients against a dense masked reference, at routings that fill 0, 1,
2, several and all of the blocks the shapes allow."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.nn.layers.feedforward import HeldExpertsMoE
from deeplearning4j_tpu.observe.scopes import scopes_in_hlo
from deeplearning4j_tpu.parallel.moe import dispatch_block, held_experts_ffn

D, F, E, K = 32, 24, 16, 2
HELD = (3, 5, 9, 12)              # local 0..3; 0, 1 and 2 are absent


@dataclasses.dataclass(frozen=True)
class Routing:
    """``tokens`` rows whose first feature is 1 and whose second is +1 for
    the first quarter and -1 for the rest; ``push`` adds to the router's
    rows of those two features, so a large entry sends every token (first
    feature) or one of the two groups (second) to an expert whatever the
    other features say."""
    held: tuple
    blocks: int                   # passes the loop must make
    tokens: int = 512
    push: tuple = ()              # (feature, expert, logit)
    landed: int = -1              # held assignments, where the push fixes them


ROUTINGS = {
    # both choices of every token on absent experts: the loop does not run
    "no_token_on_a_held_expert": Routing(
        HELD, 0, push=((0, 0, 12.0), (0, 1, 10.0)), landed=0),
    # three held: 512 * 2 * 3 / 16 = 192 expected in a block of 256
    "an_even_router": Routing(HELD[:3], 1),
    # 512 first choices on expert 5, the second choices where they fall
    "every_first_choice_on_one_held_expert": Routing(
        HELD, 3, push=((0, 5, 12.0),)),
    # 512 on expert 5, the second choices absent: two whole blocks, and
    # the boundary at row 256 cuts expert 5's group in two
    "an_exact_multiple_of_the_block": Routing(
        HELD, 2, push=((0, 5, 12.0), (0, 1, 10.0)), landed=512),
    # a quarter's second choice on expert 3 (128 rows, sorted first), the
    # others' on an absent one: expert 5's 512 rows lie in [128, 640),
    # cut at 256 and at 512
    "a_group_over_three_blocks": Routing(
        HELD, 3, push=((0, 5, 12.0), (1, 3, 6.0), (1, 1, -6.0)), landed=640),
    # both choices of every token held: all T * k rows, four blocks
    "every_assignment_lands_here": Routing(
        HELD, 4, push=((0, 5, 12.0), (0, 9, 10.0)), landed=1024),
    # G == E: the block is all the rows, one pass
    "every_expert_held": Routing(tuple(range(E)), 1, landed=1024),
    # 400 rows are no multiple of the block of 256: the second pass runs
    # into the padding
    "rows_that_end_inside_a_block": Routing(
        HELD, 2, tokens=200, push=((0, 5, 12.0), (0, 9, 10.0)), landed=400),
}


def _inputs(routing, dtype=jnp.float32):
    rng = np.random.default_rng(7)
    t, g = routing.tokens, len(routing.held)
    x = rng.normal(size=(t, D))
    x[:, 0] = 1.0
    x[:, 1] = np.where(np.arange(t) < t // 4, 1.0, -1.0)
    router = rng.normal(size=(D, E)) * 0.1
    for feature, expert, logit in routing.push:
        router[feature, expert] += logit
    weights = [rng.normal(size=s) * 0.2
               for s in ((g, D, F), (g, D, F), (g, F, D))]
    return [jnp.asarray(a, dtype) for a in (x, router, *weights)]


def _routing(x, router):
    """Every expert's weight for every token, ``(T, E)``: softmax over
    all experts, the K largest kept and renormalised. Written out here,
    so that a fault in the program's router does not pass on both sides."""
    probs = jax.nn.softmax(x @ router, -1)
    kept = jnp.where(probs >= jnp.sort(probs, -1)[:, -K, None], probs, 0.0)
    return kept / jnp.sum(kept, -1, keepdims=True)


def _masked_dense(x, router, w_gate, w_up, w_down, held):
    p = _routing(x, router)
    y = jnp.zeros_like(x)
    for j, eid in enumerate(held):
        y = y + p[:, eid, None] * (
            (jax.nn.silu(x @ w_gate[j]) * (x @ w_up[j])) @ w_down[j])
    return y


def _loss_and_grads(f, args):
    return jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(f(*a).astype(jnp.float32))),
        argnums=range(5))(*args)


@pytest.mark.parametrize("under", ["jit", "checkpoint", "bfloat16"])
@pytest.mark.parametrize("name", list(ROUTINGS))
def test_blocks_give_the_dense_layers_values_and_gradients(name, under):
    routing = ROUTINGS[name]
    held = routing.held
    # bfloat16: the same rounded operands for the float32 reference, or a
    # free router's near ties would fall otherwise on the two sides
    run = _inputs(routing, jnp.bfloat16 if under == "bfloat16"
                  else jnp.float32)
    args = [a.astype(jnp.float32) for a in run]
    block = dispatch_block(routing.tokens, K, len(held), E)
    assert block == (256 if len(held) < E else routing.tokens * K)

    loads = (np.asarray(_routing(*args[:2]))[:, list(held)] > 0).sum(0)
    landed = int(loads.sum())
    assert routing.landed in (-1, landed)
    assert math.ceil(landed / block) == routing.blocks

    def ours(*a):
        return held_experts_ffn(*a, held, top_k=K)[0]

    if under == "checkpoint":
        ours = jax.checkpoint(ours)
    y, counters = jax.jit(
        lambda *a: held_experts_ffn(*a, held, top_k=K))(*run)
    _, grads = jax.jit(lambda *a: _loss_and_grads(ours, a))(*run)
    want = _masked_dense(*args, held)
    _, wants = _loss_and_grads(lambda *a: _masked_dense(*a, held), args)

    if under == "bfloat16":
        value_tol, grad_tol = 3e-2, 6e-2
        assert y.dtype == jnp.bfloat16
        assert all(a.dtype == jnp.bfloat16 for a in grads)
    else:
        value_tol, grad_tol = 1e-5, 1e-4
    assert counters.tolist() == [landed, loads.max(), pytest.approx(
        landed / len(held), rel=1e-6), 0.0]
    assert np.isfinite(np.asarray(y, np.float32)).all()
    scale = max(float(np.abs(want).max()), 1e-6)
    assert np.abs(np.asarray(y, np.float32) - want).max() <= value_tol * scale
    for got, ref in zip(grads, wants):
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all()
        assert np.abs(got - ref).max() <= grad_tol * max(
            1.0, float(np.abs(ref).max()))

    # the layer's state row: the four counters, then the loop's passes
    layer = HeldExpertsMoE(n_in=D, n_out=D, num_experts=E, hidden=F,
                           held_experts=held, top_k=K)
    assert layer.init_state(RecurrentType(D, None))["moe_routing"].shape == (5,)
    params = dict(zip(("router", "w_gate", "w_up", "w_down"), run[1:]))
    out, state = layer.apply(params, {}, run[0][None], LayerContext(train=False))
    assert np.array_equal(np.asarray(out[0], np.float32),
                          np.asarray(y, np.float32))
    row = np.asarray(state["moe_routing"])
    assert row.shape == (5,)
    assert row[:4].tolist() == pytest.approx(counters.tolist(), rel=1e-6)
    assert row[4] == routing.blocks


def test_block_length_follows_the_shapes():
    # the cell: 5,120 expected of 81,920 possible rows
    assert dispatch_block(8192, 10, 32, 512) == 8192
    # every expert held: all the rows, one pass
    assert dispatch_block(8192, 10, 512, 512) == 81920
    # toy shapes: the floor, or all the rows where they are fewer
    assert dispatch_block(512, 2, 4, 16) == 256
    assert dispatch_block(96, 2, 8, 16) == 192
    assert dispatch_block(4096, 8, 8, 64) == 4096
    assert dispatch_block(4096, 8, 9, 64) == 8192


def test_every_operation_of_both_loops_is_in_a_moe_scope():
    """``moe_experts_ms_per_step`` joins device time to these names: an
    operation of the forward or the backward loop outside them would make
    that metric fall without the step."""
    routing = ROUTINGS["a_group_over_three_blocks"]
    args = _inputs(routing)
    step = jax.jit(lambda *a: _loss_and_grads(jax.checkpoint(
        lambda *b: held_experts_ffn(*b, routing.held, top_k=K)[0]), a))
    table = scopes_in_hlo(step.lower(*args).compile().as_text())
    in_loops = {name: op for name, op in table.items() if "/while" in op}
    assert any("transpose(" in op for op in in_loops.values())
    assert any("ragged_dot" in op or "dot_general" in op
               for op in in_loops.values())
    outside = {name: op for name, op in in_loops.items()
               if not any(s in op for s in (
                   "moe.dispatch", "moe.experts", "moe.combine"))}
    assert not outside


def test_a_model_saved_with_four_counters_still_loads(tmp_path):
    import io
    import zipfile

    from deeplearning4j_tpu.models.serialization import (
        restore_multi_layer_network, save_model)
    from deeplearning4j_tpu.zoo.models import Qwen3Next

    model = Qwen3Next(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        full_attention_interval=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=8, num_experts=16,
        held_experts=tuple(range(8)), num_experts_per_tok=2,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        seq_len=48, chunk_size=16, compute_dtype="float32").init()
    new, old = tmp_path / "new.zip", tmp_path / "old.zip"
    save_model(model, str(new))
    rows = 0
    with zipfile.ZipFile(new) as src, zipfile.ZipFile(old, "w") as dst:
        for item in src.infolist():
            data = src.read(item)
            if item.filename.endswith("moe_routing.npy"):
                assert np.load(io.BytesIO(data)).shape == (5,)
                buf = io.BytesIO()
                np.save(buf, np.array([7.0, 3.0, 1.75, 0.0], np.float32))
                data, rows = buf.getvalue(), rows + 1
            dst.writestr(item, data)
    assert rows == 2
    again = restore_multi_layer_network(str(old))
    found = [s["moe_routing"].tolist()
             for s in again.train_state.model_state.values()
             if isinstance(s, dict) and "moe_routing" in s]
    assert found == [[7.0, 3.0, 1.75, 0.0, 0.0]] * 2
    ids = np.random.default_rng(0).integers(0, 64, (2, 48))
    assert np.isfinite(np.asarray(again.output(ids))).all()


# ---- the router's load-balancing loss ---------------------------------------

def test_the_load_balancing_loss_by_hand_and_at_its_two_ends():
    from deeplearning4j_tpu.parallel.moe import (load_balancing_loss,
                                                 top_k_weights)
    rng = np.random.default_rng(4)
    t, e, k = 96, 8, 3
    probs = jax.nn.softmax(jnp.asarray(rng.normal(size=(t, e)), jnp.float32))
    ids, _ = top_k_weights(probs, k)
    received = np.zeros(e)
    for row in np.asarray(ids):
        for i in row:
            received[i] += 1
    assert received.sum() == t * k
    want = e * float((received / t) @ np.asarray(probs, np.float64).mean(0))
    assert float(load_balancing_loss(probs, ids)) == pytest.approx(
        want, rel=1e-6)
    # an even router reads k; every token on the same k with all of its
    # probability reads E
    even = jnp.full((t, e), 1.0 / e)
    spread = jnp.asarray((np.arange(t)[:, None] + np.arange(k)) % e)
    assert float(load_balancing_loss(even, spread)) == pytest.approx(k)
    same = jnp.zeros((t, e)).at[:, :k].set(1.0 / k)
    assert float(load_balancing_loss(same, top_k_weights(same, k)[0])) == (
        pytest.approx(e))
    # the gradient reaches the router through the mean probability alone
    grad = jax.grad(lambda p: load_balancing_loss(p, ids))(probs)
    np.testing.assert_allclose(
        grad, np.broadcast_to(e * received / t / t, (t, e)), rtol=1e-6)


@pytest.mark.parametrize("coef", [0.0, 0.25])
def test_the_layer_adds_the_loss_to_the_training_loss_only_when_asked(coef):
    from deeplearning4j_tpu.parallel.moe import (load_balancing_loss,
                                                 router_probs, top_k_weights)
    layer = HeldExpertsMoE(n_in=D, n_out=D, num_experts=E, hidden=F,
                           held_experts=HELD, top_k=K, aux_loss_coef=coef)
    rt = RecurrentType(D, None)
    params = layer.initialize(jax.random.PRNGKey(0), rt)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 64, D)),
                    jnp.float32)
    y, state = layer.apply(params, layer.init_state(rt), x,
                           LayerContext(train=True))
    plain, _ = dataclasses.replace(layer, aux_loss_coef=0.0).apply(
        params, {}, x, LayerContext(train=True))
    assert np.array_equal(np.asarray(y), np.asarray(plain))
    assert set(state) == set(layer.init_state(rt))
    if not coef:
        assert set(state) == {"moe_routing"}
        return
    probs = router_probs(x.reshape(-1, D), params["router"])
    want = coef * load_balancing_loss(probs, top_k_weights(probs, K)[0])
    assert float(state["moe_aux_loss"]) == pytest.approx(float(want),
                                                         rel=1e-6)
    # absent experts count: the loss is over all E router outputs
    assert float(want) / coef >= K

    # and through a model: the score is the head's loss plus the term
    from deeplearning4j_tpu.zoo.models import SDARMoE
    tiny = dict(vocab_size=32, hidden_size=16, num_hidden_layers=2,
                num_attention_heads=2, num_key_value_heads=1, head_dim=8,
                num_experts=8, held_experts=(0, 1), num_experts_per_tok=2,
                moe_intermediate_size=8, seq_len=8, block_length=4,
                compute_dtype="float32", recompute=False)
    zoo = SDARMoE(router_aux_loss_coef=coef, **tiny)
    model, bare = zoo.init(), SDARMoE(**tiny).init()
    from deeplearning4j_tpu.datasets.dataset import DataSet
    clean = DataSet(np.random.default_rng(2).integers(0, 31, (3, 8)), None)
    batch = zoo.noiser(seed=3).pre_process(clean)
    term = sum(float(s["moe_aux_loss"]) for s in jax.device_get(
        model._loss(model.train_state.params, model.train_state.model_state,
                    jnp.asarray(batch.features), jnp.asarray(batch.labels),
                    None, None, None, jnp.zeros((), jnp.int32))[1]).values()
               if "moe_aux_loss" in s)
    assert term >= coef * 2 * 2                 # k a layer at the least
    assert model.score(batch) == pytest.approx(bare.score(batch) + term,
                                               rel=1e-5)
    # the coefficient survives the configuration's JSON
    from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
    again = MultiLayerConfiguration.from_json(zoo.conf().to_json())
    assert [l.router_aux_loss_coef for l in again.layers[1:-1]] == [coef] * 2
