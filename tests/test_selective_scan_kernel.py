"""The selective scan's Pallas kernels (``ops/pallas_selective_scan.py``),
interpreted on the CPU, against the per-token recurrence: the result and
the gradients of all five inputs at ``tests/test_state_space.py``'s
tolerances. What the chip's compiler makes of them is
``tests/test_tpu_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.nn.layers.state_space import (
    MambaMixer, selective_scan_chunked, selective_scan_recurrent)
from deeplearning4j_tpu.observe.registry import default_registry
from deeplearning4j_tpu.ops import pallas_selective_scan
from deeplearning4j_tpu.ops.pallas_selective_scan import (
    kernel_chunks, kernels_take, selective_scan, selective_scan_kernels)


def inputs(n, t, d, s=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (n, t, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (n, t, d)) - 1.0)
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (d, s)))
    b = jax.random.normal(ks[3], (n, t, s))
    c = jax.random.normal(ks[4], (n, t, s))
    return x, dt, a, b, c


def recurrence(*a):
    return selective_scan_recurrent(*a)[0]


CASES = {
    # n, t, d, s, chunk: the time block is 256 tokens, a channel block 512
    "whole_blocks": (1, 512, 128, 8, 64),
    "tail": (1, 300, 128, 8, 64),
    "two_rows": (2, 256, 128, 8, 64),
    "two_channel_blocks": (1, 256, 1024, 8, 64),
    "two_rows_two_channel_blocks_tail": (2, 70, 1024, 16, 64),
    "chunk_32": (1, 256, 256, 16, 32),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_values_equal_the_recurrences(case):
    n, t, d, s, chunk = CASES[case]
    args = inputs(n, t, d, s)
    got = jax.jit(lambda *a: selective_scan_kernels(
        *a, chunk_size=chunk))(*args)
    want = recurrence(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_gradients_equal_the_recurrences(case):
    """``x``, ``dt``, ``a``, ``b`` and ``c``, within
    ``test_chunked_gradients_equal_the_recurrences``' tolerances; a tail is
    padded with tokens of step 0."""
    n, t, d, s, chunk = CASES[case]
    args = inputs(n, t, d, s)
    w = jax.random.normal(jax.random.PRNGKey(9), (n, t, d))

    def through(scan):
        return lambda *a: jnp.sum(w * jnp.tanh(scan(*a)))

    want = jax.grad(through(recurrence), argnums=range(5))(*args)
    got = jax.grad(through(lambda *a: selective_scan_kernels(
        *a, chunk_size=chunk)), argnums=range(5))(*args)
    for g, r in zip(got, want):
        assert g.shape == r.shape and g.dtype == r.dtype
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5)


def test_kernels_differentiate_under_checkpoint_as_the_block_runs_them():
    args = inputs(1, 256, 128)
    total = lambda scan: (lambda *a: jnp.sum(jnp.sin(scan(*a))))
    want = jax.grad(total(recurrence), argnums=range(5))(*args)
    got = jax.grad(total(jax.checkpoint(selective_scan_kernels)),
                   argnums=range(5))(*args)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5)


def test_padded_tokens_leave_the_state_alone():
    """What stands behind the sequence in the kernels' last time block is
    padding the wrapper writes (step 0: decay 1, nothing written), never
    memory nobody wrote: 70 tokens give what the first 70 of 300 give, and
    a token behind them moves nothing before it."""
    x, dt, a, b, c = inputs(2, 300, 128)
    whole = selective_scan_kernels(x, dt, a, b, c)
    cut = selective_scan_kernels(x[:, :70], dt[:, :70], a, b[:, :70],
                                 c[:, :70])
    assert np.isfinite(np.asarray(cut)).all()
    np.testing.assert_allclose(cut, whole[:, :70], rtol=1e-6, atol=1e-6)
    assert kernel_chunks(70, 64) == 4 and kernel_chunks(8192, 64) == 128


def test_the_kernels_compute_in_float32_whatever_comes_in():
    x, dt, a, b, c = inputs(1, 256, 128)
    low = selective_scan_kernels(x.astype(jnp.bfloat16), dt, a, b, c)
    assert low.dtype == jnp.float32
    want = selective_scan_kernels(
        x.astype(jnp.bfloat16).astype(jnp.float32), dt, a, b, c)
    np.testing.assert_array_equal(low, want)
    dx = jax.grad(lambda v: jnp.sum(selective_scan_kernels(
        v, dt, a, b, c)))(x.astype(jnp.bfloat16))
    assert dx.dtype == jnp.bfloat16


def test_the_backward_pass_keeps_border_states_only():
    """``test_state_space.py``'s property for the kernels' path: the
    largest state-shaped array of the differentiated program is the
    borders', ``T / chunk`` states; no (T, D, S) array in either pass."""
    n, t, d, s = 1, 512, 128, 8
    args = inputs(n, t, d, s)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
        selective_scan_kernels(*a))))(*args)
    sizes = [int(np.prod(v.aval.shape)) for eqn in jaxpr.jaxpr.eqns
             for v in eqn.outvars]
    assert max(sizes) <= max(n * t * d, n * (t // 64) * s * d)
    assert "ssm_selective_scan_bwd" in str(jaxpr)


MIXER = dict(n_in=16, n_out=16, d_inner=128, d_state=8, d_conv=4, dt_rank=3)


def _gauge(layer):
    return default_registry().gauge(
        *pallas_selective_scan.SSM_KERNEL_GAUGE).get(layer=layer)


def test_the_gauge_says_which_path_a_layer_traced(monkeypatch):
    """``dl4j_ssm_kernel_chunks``: 0 where the plain form was traced (the
    CPU, or a TPU at channels that fill no lane tile), the chunks a pass
    walks through the kernels where they run: 128 for 8,192 tokens on a
    TPU. The test stands in for the backend; nothing is lowered."""
    def trace(layer, t):
        params = jax.eval_shape(
            lambda key: layer.initialize(key, RecurrentType(16, t)),
            jax.random.PRNGKey(0))
        x = jax.ShapeDtypeStruct((1, t, 16), jnp.float32)
        jax.eval_shape(lambda p, a: layer.apply(p, {}, a, LayerContext()),
                       params, x)

    wide = MambaMixer(name="wide", **MIXER)
    odd = MambaMixer(name="odd", **{**MIXER, "d_inner": 96})
    trace(wide, 8192)
    assert _gauge("wide") == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    trace(wide, 8192)
    trace(odd, 48)
    assert _gauge("wide") == 128
    assert _gauge("odd") == 0


def test_the_choice_rests_on_the_inputs(monkeypatch):
    x, _, a, _, _ = inputs(1, 8, 128)
    assert not kernels_take(x, a, 64)                   # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels_take(x, a, 64) and kernels_take(x, a, 32)
    assert not kernels_take(x[..., :96], a[:96], 64)    # no lane tile
    assert not kernels_take(x, a[:, :4], 64)            # no sublane tile
    assert not kernels_take(x, a, 48)                   # 128 % 48
    assert not kernels_take(x, a, 4)


def test_the_layer_runs_the_plain_form_off_the_tpu():
    """On the CPU ``selective_scan`` is ``selective_scan_chunked`` to the
    bit, and a ``MambaMixer``'s memory is that form's over the layer's own
    projections."""
    args = inputs(2, 37, 128)
    np.testing.assert_array_equal(selective_scan(*args, chunk_size=8),
                                  selective_scan_chunked(*args, 8))
    layer = MambaMixer(**MIXER)
    p = layer.initialize(jax.random.PRNGKey(1), RecurrentType(16, None))
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 21, 16))
    out, (memory,) = layer.mix(p, h)

    d, s, r = 128, 8, 3
    u = (h @ p["W_in"])[..., :d]
    from deeplearning4j_tpu.nn.layers.linear_attention import (
        causal_depthwise_conv)
    u = jax.nn.silu(causal_depthwise_conv(u, p["conv_w"]) + p["conv_b"])
    dbc = u @ p["W_x"]
    step = jax.nn.softplus(dbc[..., :r] @ p["W_dt"] + p["b_dt"])
    y = selective_scan_chunked(u, step, -jnp.exp(p["A_log"]),
                               dbc[..., r:r + s], dbc[..., r + s:])
    np.testing.assert_allclose(memory, y + p["D"] * u, rtol=1e-5, atol=1e-6)
    assert _gauge("ssm") == 0
