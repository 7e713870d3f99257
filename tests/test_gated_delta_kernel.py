"""The gated delta rule's Pallas kernels (``ops/pallas_delta_rule.py``),
interpreted on the CPU, against the per-token recurrence and the plain
chunked form at head sizes of 128: result, final state and the gradients
of all five inputs. What the chip's compiler makes of them is
``tests/test_tpu_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.nn.layers.linear_attention import (
    GatedDeltaNet, chunk_gated_delta_rule, l2_normalize,
    recurrent_gated_delta_rule)
from deeplearning4j_tpu.observe.registry import default_registry
from deeplearning4j_tpu.ops import pallas_delta_rule
from deeplearning4j_tpu.ops.pallas_delta_rule import (
    gated_delta_rule, gated_delta_rule_kernels, kernel_chunks)

D = 128


@pytest.fixture
def rng():
    return np.random.default_rng(11)


def _inputs(rng, t, hk=1, hv=1, n=1, dtype=jnp.float32, dv=D):
    q = l2_normalize(jnp.asarray(rng.normal(size=(n, t, hk, D)),
                                 jnp.float32)) / np.sqrt(D)
    k = l2_normalize(jnp.asarray(rng.normal(size=(n, t, hk, D)),
                                 jnp.float32))
    v = jnp.asarray(rng.normal(size=(n, t, hv, dv)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.01, 2.0, (n, t, hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 0.95, (n, t, hv)), jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _repeated(fn, rep, **kw):
    """``fn`` of the plain forms' arguments: ``q`` and ``k`` repeated to
    the value heads, float32 inputs."""
    def call(q, k, v, g, beta, s0=None):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        return fn(jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2), v, g, beta,
                  initial_state=s0, **kw)
    return call


def _kernels(chunk, wrap=lambda f: f):
    def rule(q, k, v, g, beta, s0=None):
        return gated_delta_rule_kernels(q, k, v, g, beta, chunk_size=chunk,
                                        initial_state=s0)
    return wrap(rule)


def _total(fn):
    def loss(*a):
        o, s = fn(*a)
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(s))
    return loss


CASES = {
    # t, chunk, key heads, value heads, value head size, initial state,
    # wrapper
    "whole_chunks": (256, 64, 1, 1, D, False, jax.jit),
    "tail": (150, 64, 1, 1, D, False, jax.jit),
    "one_chunk": (64, 64, 1, 1, D, False, jax.jit),
    "two_value_heads_a_key_head": (128, 64, 1, 2, D, False, jax.jit),
    "two_key_heads": (128, 64, 2, 4, D, False, jax.jit),
    "initial_state": (128, 64, 1, 2, D, True, jax.jit),
    "checkpoint": (128, 64, 1, 1, D, False, jax.checkpoint),
    "chunk_32_tail_and_state": (80, 32, 1, 2, D, True, jax.jit),
    "chunk_128": (256, 128, 1, 1, D, False, jax.jit),
    # a chunk that does not divide 128 is its own super-chunk
    "chunk_48": (144, 48, 1, 1, D, False, jax.jit),
    "value_heads_of_256": (128, 64, 1, 1, 256, True, jax.jit),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_are_the_recurrence_at_float32(rng, case):
    """Within ``test_chunked_delta_rule_is_the_recurrence``'s tolerances:
    result, final state, and the gradients of ``q``, ``k``, ``v``, ``g``
    and ``beta`` (and of the initial state where one is handed in). A tail
    is padded with tokens that leave the state alone."""
    t, chunk, hk, hv, dv, with_state, wrap = CASES[case]
    args = _inputs(rng, t, hk, hv, dv=dv)
    if with_state:
        args += (jnp.asarray(rng.normal(size=(1, hv, D, dv)) * 0.1,
                             jnp.float32),)
    kern = _kernels(chunk, wrap)
    o, s = kern(*args)
    got = jax.grad(_total(kern), argnums=range(len(args)))(*args)
    for oracle in (_repeated(recurrent_gated_delta_rule, hv // hk),
                   _repeated(chunk_gated_delta_rule, hv // hk,
                             chunk_size=chunk)):
        o_ref, s_ref = oracle(*args)
        assert o.shape == o_ref.shape and o.dtype == jnp.float32
        assert np.abs(o - o_ref).max() < 2e-6
        assert np.abs(s - s_ref).max() < 5e-6
        want = jax.grad(_total(oracle), argnums=range(len(args)))(*args)
        for a, b in zip(got, want):
            assert a.shape == b.shape and a.dtype == b.dtype
            assert np.abs(a - b).max() < 2e-5 * max(1.0, np.abs(b).max())


def _rel_rms(a, exact):
    a, exact = (np.asarray(x, np.float64) for x in (a, exact))
    return np.sqrt(np.mean((a - exact) ** 2) / np.mean(exact ** 2))


def test_a_bfloat16_state_is_caught_at_float32_and_bfloat16_is_in_band(rng):
    """``test_a_bfloat16_state_is_caught_at_float32_and_not_at_bfloat16``
    for the kernels, on a long memory at head sizes of 128: at float32
    they are the recurrence to 1e-5 of its size, where a state rounded to
    bfloat16 between tokens is a thousand times that; with bfloat16
    operands they land in the band the plain form is held to, and their
    gradients as near the exact ones as the plain form's."""
    bf = jnp.bfloat16
    q, k, v, g, beta = _inputs(rng, 512, 1, 2, dtype=bf)
    g = g * 0.02
    exact_fn = _repeated(recurrent_gated_delta_rule, 2)
    exact, _ = exact_fn(q, k, v, g, beta)

    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[..., None, None]
        delta = (vt - jnp.einsum("nhk,nhkv->nhv", kt, s)) * bt[..., None]
        s = (s + kt[..., :, None] * delta[..., None, :]).astype(bf).astype(
            jnp.float32)
        return s, jnp.einsum("nhk,nhkv->nhv", qt, s)

    f32 = lambda a: a.astype(jnp.float32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (
        jnp.repeat(f32(q), 2, 2), jnp.repeat(f32(k), 2, 2), f32(v), g, beta))
    _, rounded = jax.lax.scan(step, jnp.zeros((1, 2, D, D)), xs)

    at_f32, _ = gated_delta_rule_kernels(f32(q), f32(k), f32(v), g, beta)
    at_bf16, s_bf16 = gated_delta_rule_kernels(q, k, v, g, beta)
    assert at_bf16.dtype == jnp.float32 and s_bf16.dtype == jnp.float32
    assert _rel_rms(at_f32, exact) < 1e-5
    assert _rel_rms(jnp.moveaxis(rounded, 0, 1), exact) > 3e-3
    assert 1e-3 < _rel_rms(at_bf16, exact) < 8e-3

    def plain(q, k, v, g, beta):
        return chunk_gated_delta_rule(jnp.repeat(q, 2, 2),
                                      jnp.repeat(k, 2, 2), v, g, beta)

    want = jax.grad(_total(exact_fn), argnums=range(5))(q, k, v, g, beta)
    got = jax.grad(_total(gated_delta_rule_kernels), argnums=range(5))(
        q, k, v, g, beta)
    plains = jax.grad(_total(plain), argnums=range(5))(q, k, v, g, beta)
    for a, p, b in zip(got, plains, want):
        assert a.dtype == p.dtype
        assert _rel_rms(a, b) < max(2.0 * _rel_rms(p, b), 2e-2)


def test_padded_tokens_leave_the_state_alone(rng):
    """What stands behind the sequence in the kernels' last grid step is
    padding the wrapper writes (zeros: ``beta`` 0 and ``g`` 0), never
    memory nobody wrote: the final state after 70 tokens is the state
    after the same 70 tokens of a longer sequence cut there."""
    q, k, v, g, beta = _inputs(rng, 200, 1, 2)
    cut = tuple(a[:, :70] for a in (q, k, v, g, beta))
    _, s_cut = gated_delta_rule_kernels(*cut)
    _, s_ref = _repeated(recurrent_gated_delta_rule, 2)(*cut)
    assert np.isfinite(np.asarray(s_cut)).all()
    assert np.abs(s_cut - s_ref).max() < 5e-6
    assert kernel_chunks(70, 64) == 4 and kernel_chunks(8192, 64) == 128


def test_the_backward_takes_the_forwards_matmul_precision(rng):
    """``jax.default_matmul_precision`` round the forward alone (as
    ``chip_check.py`` sets it for its float32 row) reaches every product
    of the backward kernel too, which is traced after the block is left."""
    args = _inputs(rng, 128)

    def loss(*a):
        with jax.default_matmul_precision("highest"):
            o, _ = gated_delta_rule_kernels(*a)
        return jnp.sum(o)

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=range(5)))(*args))
    assert "gdn_delta_rule_bwd" in text
    assert text.count("Precision.HIGHEST") > 100
    assert "precision=None" not in text


def _layer(name, heads, dim, chunk):
    return GatedDeltaNet(name=name, n_in=32, n_out=32, n_key_heads=heads,
                         n_value_heads=2 * heads, key_head_dim=dim,
                         value_head_dim=dim, chunk_size=chunk)


def _gauge(layer):
    return default_registry().gauge(
        *pallas_delta_rule.GDN_KERNEL_GAUGE).get(layer=layer)


def test_the_gauge_says_which_path_a_layer_traced(monkeypatch):
    """``dl4j_gdn_kernel_chunks``: 0 where the plain form was traced (the
    CPU, or a TPU at the tiny preset's head size of 8), the chunks a head's
    pass walks through the kernels where they run: 128 for 8,192 tokens at
    heads of 128 and chunks of 64 on a TPU. The test stands in for the
    backend; nothing is lowered."""
    def trace(layer, t):
        params = jax.eval_shape(
            lambda key: layer.initialize(key, RecurrentType(32, t)),
            jax.random.PRNGKey(0))
        x = jax.ShapeDtypeStruct((1, t, 32), jnp.float32)
        jax.eval_shape(lambda p, a: layer.apply(p, {}, a, LayerContext()),
                       params, x)

    wide, tiny = _layer("wide", 1, 128, 64), _layer("tiny", 2, 8, 16)
    trace(wide, 8192)
    assert _gauge("wide") == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    trace(wide, 8192)
    trace(tiny, 48)
    assert _gauge("wide") == 128
    assert _gauge("tiny") == 0


def test_the_layer_runs_the_plain_form_off_the_tpu(rng):
    """On the CPU ``gated_delta_rule`` is ``chunk_gated_delta_rule`` with
    ``q`` and ``k`` repeated, to the bit."""
    q, k, v, g, beta = _inputs(rng, 96, 1, 2)
    o, s = gated_delta_rule(q, k, v, g, beta, chunk_size=32)
    o_ref, s_ref = chunk_gated_delta_rule(
        jnp.repeat(q, 2, 2), jnp.repeat(k, 2, 2), v, g, beta, chunk_size=32)
    assert (np.asarray(o) == np.asarray(o_ref)).all()
    assert (np.asarray(s) == np.asarray(s_ref)).all()
