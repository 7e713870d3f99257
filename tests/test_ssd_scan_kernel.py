"""The Mamba-2 scan's Pallas kernels (``ops/pallas_ssd_scan.py``: the scan
with the mixer's skip, gate and grouped norm), interpreted on the CPU,
against the recurrence token by token under the plain
``gated_group_norm``: the result and the gradients of all eight inputs at
``tests/test_mamba2.py``'s tolerances. What the chip's compiler makes of
them is ``tests/test_tpu_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.nn.layers.state_space import (
    Mamba2Mixer, gated_group_norm, ssd_chunked, ssd_recurrent)
from deeplearning4j_tpu.observe.registry import default_registry
from deeplearning4j_tpu.ops import pallas_ssd_scan
from deeplearning4j_tpu.ops.pallas_ssd_scan import (
    kernel_chunks, kernels_take, ssd_scan, ssd_scan_kernels)

ARGS = ("x", "dt", "a", "b", "c", "z", "skip", "norm_w")
EPS = 1e-5


def inputs(n=1, t=256, h=4, p=64, g=2, s=128, seed=0, dtype=jnp.float32):
    """Steps of a few hundredths, as the layer's ``dt_bias`` starts them:
    the float32 running sums of a 128-token chunk then leave the chunked
    forms as near the recurrence as ``test_mamba2.py``'s chunks of 8."""
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    return (jax.random.normal(k[0], (n, t, h, p), dtype),
            jax.nn.softplus(jax.random.normal(k[1], (n, t, h)) - 4.0),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (n, t, g, s), dtype),
            jax.random.normal(k[4], (n, t, g, s), dtype),
            jax.random.normal(k[5], (n, t, h * p), dtype),
            1.0 + 0.3 * jax.random.normal(k[6], (h,)),
            1.0 + 0.3 * jax.random.normal(k[7], (h * p,)))


def plain(scan):
    """The mixer's rows from a plain form of the scan."""
    def rows(x, dt, a, b, c, z, skip, w):
        return gated_group_norm(scan(x, dt, a, b, c)[0], x, z, skip, w,
                                b.shape[-2], EPS)
    return rows


recurrence = plain(ssd_recurrent)


def kernels(*a):
    return ssd_scan_kernels(*a, EPS)


CASES = {
    # n, t, h, p, g, s: a chunk and a time block are 128 tokens
    "whole_blocks": (1, 256, 4, 64, 2, 128),
    "tail": (1, 300, 4, 64, 2, 128),
    "two_rows": (2, 256, 4, 64, 2, 128),
    "every_head_its_own_group": (1, 256, 2, 128, 2, 128),
    "one_group_of_narrow_heads": (1, 200, 8, 16, 1, 128),
    "two_rows_tail_wide_state": (2, 140, 4, 64, 1, 256),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_values_equal_the_recurrences(case):
    args = inputs(*CASES[case])
    with jax.default_matmul_precision("highest"):
        want = recurrence(*args)
        got = jax.jit(kernels)(*args)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_gradients_equal_the_recurrences(case):
    """All eight inputs, within
    ``test_chunked_gradients_are_the_recurrences``' tolerance (``a``'s, a
    sum over every token of terms that cancel inside a chunk, three times
    that: the plain form in chunks of 128 reads 4e-6 there); a tail is
    padded with tokens of step 0."""
    args = inputs(*CASES[case])

    def through(scan):
        return lambda *a: jnp.sum(jnp.sin(scan(*a)))

    with jax.default_matmul_precision("highest"):
        want = jax.grad(through(recurrence), argnums=range(8))(*args)
        got = jax.jit(jax.grad(through(kernels), argnums=range(8)))(*args)
    for name, g, r in zip(ARGS, got, want):
        assert g.shape == r.shape and g.dtype == r.dtype, name
        assert float(jnp.linalg.norm(r)) > 0, name
        assert float(jnp.linalg.norm(g - r)) < (
            3e-5 if name == "a" else 1e-5) * float(jnp.linalg.norm(r)), name


def test_kernels_differentiate_under_checkpoint_as_the_block_runs_them():
    args = inputs()
    total = lambda scan: (lambda *a: jnp.sum(jnp.sin(scan(*a))))
    with jax.default_matmul_precision("highest"):
        want = jax.grad(total(recurrence), argnums=range(8))(*args)
        got = jax.grad(total(jax.checkpoint(kernels)),
                       argnums=range(8))(*args)
    for name, g, r in zip(ARGS, got, want):
        assert float(jnp.linalg.norm(g - r)) < (
            3e-5 if name == "a" else 1e-5) * float(jnp.linalg.norm(r)), name


def test_padded_tokens_leave_the_state_alone():
    """What stands behind the sequence in the kernels' last time block is
    padding the wrapper writes (step 0: decay 1, nothing written), never
    memory nobody wrote: 140 tokens give what the first 140 of 300 give."""
    x, dt, a, b, c, z, skip, w = inputs(n=2, t=300)
    whole = kernels(x, dt, a, b, c, z, skip, w)
    cut = kernels(x[:, :140], dt[:, :140], a, b[:, :140], c[:, :140],
                  z[:, :140], skip, w)
    assert np.isfinite(np.asarray(cut)).all()
    np.testing.assert_allclose(cut, whole[:, :140], rtol=1e-6, atol=1e-6)
    assert kernel_chunks(140) == 2 and kernel_chunks(8192) == 64


def test_decays_that_underflow_leave_no_nan():
    """``test_mamba2.py``'s property at the kernels: steps so long that a
    chunk's decay underflows to 0. Every exponent is a difference of
    running sums taken forward in time, masked before the exponential, and
    nothing is divided by a decay or by a step."""
    x, dt, *rest = inputs(t=256)
    dt = (dt + 0.5) * 200.0
    y = kernels(x, dt, *rest)
    assert np.isfinite(y).all()
    want = recurrence(x, dt, *rest)
    assert np.abs(y - want).max() < 1e-4 * np.abs(want).max()
    for g in jax.grad(lambda *v: jnp.sum(kernels(*v) ** 2),
                      range(8))(x, dt, *rest):
        assert np.isfinite(g).all()


def test_bfloat16_operands_stay_as_near_float32_as_the_plain_forms():
    """The products take their operands in ``x``'s type and round them
    where ``ssd_chunked`` does: the result, bfloat16 now, is the plain
    forms' but for a last bit here and there, and each gradient is as near
    the float32 recurrence's as the plain forms'."""
    args = inputs(t=384)
    low = tuple(v.astype(jnp.bfloat16) if v.ndim >= 3 and i != 1 else v
                for i, v in enumerate(args))
    up = tuple(v.astype(jnp.float32) for v in low)
    chunked = plain(lambda *v: ssd_chunked(*v, chunk_size=128))
    got, want = kernels(*low), chunked(*low)
    assert got.dtype == want.dtype == jnp.bfloat16
    true = recurrence(*up)

    def off(v, r):
        return float(jnp.linalg.norm(v.astype(jnp.float32) - r)
                     / jnp.linalg.norm(r))

    assert off(got, want.astype(jnp.float32)) < 0.5 * off(want, true)
    assert 1e-4 < off(got, true) < 3e-2

    weights = jax.random.normal(jax.random.PRNGKey(5), got.shape)
    grads = lambda scan, v: jax.grad(
        lambda *w: jnp.sum(weights * scan(*w)), range(8))(*v)
    for name, g, p, r in zip(ARGS, grads(kernels, low),
                             grads(chunked, low), grads(recurrence, up)):
        assert g.dtype == p.dtype, name
        assert off(g, r) < max(2.0 * off(p, r), 5e-3), name


def test_the_backward_pass_keeps_border_states_and_inputs_only():
    """``test_mamba2.py``'s property for the kernels' path: what the
    differentiated program keeps between its passes are the inputs (the
    scalars as tiles) and one (H, P, S) state a chunk; no array of either
    pass has a (T, H, P, S) or a (chunks, H, Q, Q) extent, and the
    float32 ``y`` never leaves the kernels."""
    from jax._src.ad_checkpoint import saved_residuals
    n, t, h, p, g, s = 1, 512, 4, 64, 2, 128
    args = inputs(n, t, h, p, g, s)
    chunks, q = t // 128, 128
    borders = chunks * n * h * p * s
    kept = saved_residuals(lambda *a: jnp.sum(kernels(*a)), *args)
    sizes = [int(np.prod(aval.shape)) for aval, _ in kept]
    largest_input = max(int(np.prod(a.shape)) for a in args)
    assert borders in sizes
    assert max(sizes) <= max(largest_input, borders)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(kernels(*a))))(*args)
    every = [int(np.prod(v.aval.shape)) for eqn in jaxpr.jaxpr.eqns
             for v in eqn.outvars]
    assert max(every) <= max(largest_input, borders)
    assert chunks * n * h * q * q > max(every)      # no decay matrix
    assert "ssd_scan_fwd" in str(jaxpr) and "ssd_scan_bwd" in str(jaxpr)


MIXER = dict(n_in=24, n_out=24, n_heads=4, head_dim=64, n_groups=2,
             d_state=128)


def _gauge(layer):
    return default_registry().gauge(
        *pallas_ssd_scan.SSD_KERNEL_GAUGE).get(layer=layer)


def test_the_gauge_says_which_path_a_layer_traced(monkeypatch):
    """``dl4j_ssd_kernel_chunks``: 0 where the plain form was traced (the
    CPU, or a TPU at states that fill no lane tile or another chunk), the
    chunks a pass walks through the kernels where they run: 64 for 8,192
    tokens on a TPU. The test stands in for the backend; nothing is
    lowered."""
    def trace(layer, t):
        params = jax.eval_shape(
            lambda key: layer.initialize(key, RecurrentType(24, t)),
            jax.random.PRNGKey(0))
        x = jax.ShapeDtypeStruct((1, t, 24), jnp.float32)
        jax.eval_shape(lambda p, a: layer.apply(p, {}, a, LayerContext()),
                       params, x)

    wide = Mamba2Mixer(name="wide", **MIXER)
    odd = Mamba2Mixer(name="odd", **{**MIXER, "d_state": 96})
    short = Mamba2Mixer(name="short", **MIXER, chunk_size=64)
    trace(wide, 8192)
    assert _gauge("wide") == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    trace(wide, 8192)
    trace(odd, 48)
    trace(short, 256)
    assert _gauge("wide") == 64
    assert _gauge("odd") == 0 and _gauge("short") == 0


def test_the_choice_rests_on_the_inputs(monkeypatch):
    x, _, _, b, *_ = inputs(t=8)
    assert not kernels_take(x, b, 128)                      # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels_take(x, b, 128)
    assert kernels_take(x.astype(jnp.bfloat16), b, 128)
    assert not kernels_take(x, b, 64)                       # the chunk
    assert not kernels_take(x, b[..., :96], 128)            # no lane tile
    assert not kernels_take(x[..., :32], b, 128)            # 2 x 32 channels
    assert not kernels_take(x[..., :48], b[:, :, :1], 128)  # 128 % 48
    with jax.enable_x64():
        assert not kernels_take(x.astype(jnp.float64), b, 128)


def test_the_layer_runs_the_plain_form_off_the_tpu():
    """On the CPU ``ssd_scan`` is ``ssd_chunked`` under ``gated_group_norm``
    to the bit, whatever the sizes, and a ``Mamba2Mixer`` at sizes the
    kernels would take publishes 0 kernel chunks beside the chunks its
    plain form walks."""
    args = inputs(n=2, t=37)
    for chunk in (8, 128):
        np.testing.assert_array_equal(
            ssd_scan(*args, EPS, chunk_size=chunk),
            plain(lambda *v: ssd_chunked(*v, chunk_size=chunk))(*args))
    layer = Mamba2Mixer(name="plain", **MIXER)
    p = layer.initialize(jax.random.PRNGKey(1), RecurrentType(24, None))
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 21, 24))
    out, _ = layer.apply(p, {}, h, LayerContext(train=False))
    assert out.shape == (2, 21, 24) and np.isfinite(out).all()
    assert _gauge("plain") == 0
    assert default_registry().get_metric("dl4j_ssd_chunks").series()[
        (("layer", "plain"),)] == 1
