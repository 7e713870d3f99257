"""The selective scan: the chunked form a training step runs against the
per-token recurrence, the decode step, and the mixers built on it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.nn.layers.state_space import (
    GatedMemoryUnit, MambaMixer, selective_scan_chunked,
    selective_scan_recurrent, selective_scan_step)


def inputs(n=2, t=37, d=12, s=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, t, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (n, t, d)) - 1.0)
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (d, s)))
    b = jax.random.normal(ks[3], (n, t, s))
    c = jax.random.normal(ks[4], (n, t, s))
    return x, dt, a, b, c


def plain_scan(x, dt, a, b, c):
    """The recurrence as the module's docstring writes it, in numpy
    float64, channel-major state (D, S)."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    n, t, d = x.shape
    y = np.zeros((n, t, d))
    for i in range(n):
        s = np.zeros(a.shape)
        for j in range(t):
            s = np.exp(dt[i, j][:, None] * a) * s \
                + (dt[i, j] * x[i, j])[:, None] * b[i, j][None, :]
            y[i, j] = s @ c[i, j]
    return y


def test_the_recurrence_is_the_written_one():
    args = inputs()
    y, state = selective_scan_recurrent(*args)
    np.testing.assert_allclose(y, plain_scan(*args), rtol=2e-5, atol=2e-5)
    assert state.shape == (2, 4, 12)            # (N, S, D)


# a chunk that divides T, one that does not, one longer than T, one token
@pytest.mark.parametrize("chunk", [1, 8, 37, 10, 64])
def test_chunked_values_equal_the_recurrence(chunk):
    args = inputs()
    want, _ = selective_scan_recurrent(*args)
    got = selective_scan_chunked(*args, chunk_size=chunk)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# a chunk that divides T = 29 with a tail, one that does not, a short one
@pytest.mark.parametrize("chunk", [8, 10, 4])
def test_chunked_gradients_equal_the_recurrences(chunk):
    args = inputs(t=29)
    w = jax.random.normal(jax.random.PRNGKey(9), (2, 29, 12))

    def through(scan):
        return lambda *a: jnp.sum(w * jnp.tanh(scan(*a)))

    want = jax.grad(through(lambda *a: selective_scan_recurrent(*a)[0]),
                    argnums=range(5))(*args)
    got = jax.grad(through(lambda *a: selective_scan_chunked(
        *a, chunk_size=chunk)), argnums=range(5))(*args)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5)


def test_the_backward_pass_keeps_border_states_only():
    """No (T, D, S) tensor in the differentiated program: the largest
    state-shaped array has a chunk's tokens, or the borders'."""
    n, t, d, s, chunk = 1, 64, 16, 4, 8
    args = inputs(n, t, d, s)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        selective_scan_chunked(*a, chunk_size=chunk))))(*args)
    worst = 0

    def walk(j):
        nonlocal worst
        for eqn in j.eqns:
            for v in eqn.outvars:
                shape = getattr(v.aval, "shape", ())
                if len(shape) >= 3 and shape[-2:] == (s, d):
                    worst = max(worst, int(np.prod(shape[:-2])))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    assert 0 < worst <= max(chunk, t // chunk) * n


def test_a_decode_step_continues_a_prefix():
    x, dt, a, b, c = inputs(t=20)
    want, _ = selective_scan_recurrent(x, dt, a, b, c)
    _, state = selective_scan_recurrent(x[:, :19], dt[:, :19], a,
                                        b[:, :19], c[:, :19])
    state, y = selective_scan_step(state, x[:, 19], dt[:, 19], a.T,
                                   b[:, 19], c[:, 19])
    np.testing.assert_allclose(y, want[:, 19], rtol=1e-5, atol=1e-5)
    again, final = selective_scan_recurrent(
        x[:, 19:], dt[:, 19:], a, b[:, 19:], c[:, 19:],
        initial_state=selective_scan_recurrent(
            x[:, :19], dt[:, :19], a, b[:, :19], c[:, :19])[1])
    np.testing.assert_allclose(again[:, 0], want[:, 19], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(final, state, rtol=1e-6)


def test_the_scan_computes_in_float32_whatever_comes_in():
    x, dt, a, b, c = inputs()
    low = selective_scan_chunked(x.astype(jnp.bfloat16), dt, a, b, c, 8)
    assert low.dtype == jnp.float32
    want = selective_scan_chunked(
        x.astype(jnp.bfloat16).astype(jnp.float32), dt, a, b, c, 8)
    np.testing.assert_array_equal(low, want)


MIXER = dict(n_in=16, n_out=16, d_inner=32, d_state=4, d_conv=4, dt_rank=3)


def test_mamba_mixer_starts_as_the_family_does_and_counts_its_parameters():
    layer = MambaMixer(**MIXER)
    p = layer.initialize(jax.random.PRNGKey(0), RecurrentType(16, None))
    count = sum(int(np.prod(v.shape)) for v in p.values())
    assert count == (16 * 64 + 32 * 4 + 32 + 32 * (3 + 8) + 3 * 32 + 32
                     + 32 * 4 + 32 + 32 * 16)
    np.testing.assert_allclose(jnp.exp(p["A_log"]),
                               np.tile(np.arange(1.0, 5.0), (32, 1)),
                               rtol=1e-6)
    np.testing.assert_array_equal(p["D"], np.ones(32))
    step = jax.nn.softplus(p["b_dt"])
    assert 1e-3 * 0.999 <= float(step.min()) and float(step.max()) <= 0.1001
    assert float(jnp.abs(p["conv_w"]).max()) <= 0.5


def test_mamba_mixer_is_causal_and_emits_its_memory_before_the_gate():
    layer = MambaMixer(**MIXER)
    p = layer.initialize(jax.random.PRNGKey(1), RecurrentType(16, None))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 21, 16))
    out, (memory,) = layer.mix(p, x)
    assert out.shape == (2, 21, 16) and memory.shape == (2, 21, 32)
    y, _ = layer.apply(p, {}, x, LayerContext(train=True))
    np.testing.assert_array_equal(y, out)
    # a later token moves nothing before it
    x2 = x.at[:, 13].add(1.0)
    out2, (memory2,) = layer.mix(p, x2)
    np.testing.assert_array_equal(out2[:, :13], out[:, :13])
    np.testing.assert_array_equal(memory2[:, :13], memory[:, :13])
    assert float(jnp.abs(out2[:, 13:] - out[:, 13:]).max()) > 0
    # the gate comes after the memory: out = (memory * silu(z)) W_out
    z = (x @ p["W_in"])[..., 32:]
    np.testing.assert_allclose(out, (memory * jax.nn.silu(z)) @ p["W_out"],
                               rtol=1e-4, atol=1e-6)


def test_gated_memory_unit_is_two_products_round_a_gate():
    layer = GatedMemoryUnit(n_in=16, n_out=16, d_memory=32)
    p = layer.initialize(jax.random.PRNGKey(3), RecurrentType(16, None))
    assert {k: v.shape for k, v in p.items()} == {"W1": (16, 32),
                                                  "W2": (32, 16)}
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 16))
    m = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 32))
    y, _ = layer.apply(p, {}, (h, m), LayerContext())
    np.testing.assert_allclose(y, (m * jax.nn.silu(h @ p["W1"])) @ p["W2"],
                               rtol=1e-5, atol=1e-6)
