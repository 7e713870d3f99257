"""The Mamba-2 (state-space duality) scan and its mixer: the chunked
training form against the recurrence token by token (values, gradients, a
sequence that is no multiple of the chunk), a decode step continuing a
chunked prefix, decays that underflow, what the backward pass keeps, and
``Mamba2Mixer`` against the yardstick's plain reference of the layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.nn.layers.state_space import (
    Mamba2Mixer, grouped_rms_norm, ssd_chunked, ssd_recurrent, ssd_step)
from deeplearning4j_tpu.observe.registry import default_registry
from yardstick import cells

ARGS = ("x", "dt", "a", "b", "c")


def inputs(n=2, t=37, h=8, p=4, g=2, s=6, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (n, t, h, p), dtype),
            jax.nn.softplus(jax.random.normal(k[1], (n, t, h))),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (n, t, g, s), dtype),
            jax.random.normal(k[4], (n, t, g, s), dtype))


@pytest.mark.parametrize("t,chunk,groups", [
    (32, 8, 2),         # whole chunks
    (37, 8, 2),         # a tail that is padded
    (5, 8, 1),          # shorter than one chunk, one group for all heads
    (24, 24, 8),        # one chunk; every head its own group
])
def test_chunked_is_the_recurrence(t, chunk, groups):
    args = inputs(t=t, g=groups)
    with jax.default_matmul_precision("highest"):
        want, s_want = ssd_recurrent(*args)
        got, s_got = ssd_chunked(*args, chunk_size=chunk)
    assert got.shape == want.shape == (2, t, 8, 4)
    assert got.dtype == s_got.dtype == jnp.float32
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    assert np.abs(s_got - s_want).max() < 1e-5 * np.abs(s_want).max()


@pytest.mark.parametrize("wrt", range(5), ids=ARGS)
def test_chunked_gradients_are_the_recurrences(wrt):
    args = inputs()

    def loss(fn):
        def f(*a):
            y, state = fn(*a)
            return jnp.sum(jnp.sin(y)) + jnp.sum(state ** 2)
        return f

    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(ssd_recurrent), wrt)(*args)
        got = jax.grad(loss(lambda *a: ssd_chunked(*a, chunk_size=8)),
                       wrt)(*args)
    assert float(jnp.linalg.norm(want)) > 0
    assert float(jnp.linalg.norm(got - want)) < 1e-5 * float(
        jnp.linalg.norm(want))


def test_a_step_continues_a_chunked_prefix():
    """Prefill in chunks, then decode token by token from the state the
    prefix left: the (H, P, S) state is all a decode step needs."""
    x, dt, a, b, c = inputs(t=29)
    with jax.default_matmul_precision("highest"):
        whole, _ = ssd_recurrent(x, dt, a, b, c)
        _, state = ssd_chunked(x[:, :20], dt[:, :20], a, b[:, :20],
                               c[:, :20], chunk_size=8)
        for i in range(20, 29):
            state, y = ssd_step(state, x[:, i], dt[:, i], a, b[:, i], c[:, i])
            assert np.abs(y - whole[:, i]).max() < 1e-5 * np.abs(whole).max()
        # and a chunked suffix from that prefix's state
        _, mid = ssd_chunked(x[:, :20], dt[:, :20], a, b[:, :20], c[:, :20],
                             chunk_size=8)
        rest, _ = ssd_chunked(x[:, 20:], dt[:, 20:], a, b[:, 20:], c[:, 20:],
                              chunk_size=4, initial_state=mid)
    assert np.abs(rest - whole[:, 20:]).max() < 1e-5 * np.abs(whole).max()


def test_decays_that_underflow_leave_no_nan():
    """Steps so long that a chunk's decay underflows to 0: every exponent
    is a difference of cumulative sums taken forward in time, so none is
    positive, and nothing is divided by a decay."""
    x, dt, a, b, c = inputs(t=32)
    dt = dt * 200.0

    def loss(*args):
        return jnp.sum(ssd_chunked(*args, chunk_size=16)[0] ** 2)

    y, state = ssd_chunked(x, dt, a, b, c, chunk_size=16)
    assert np.isfinite(y).all() and np.isfinite(state).all()
    want, _ = ssd_recurrent(x, dt, a, b, c)
    assert np.abs(y - want).max() < 1e-4 * np.abs(want).max()
    for g in jax.grad(loss, (0, 1, 2, 3, 4))(x, dt, a, b, c):
        assert np.isfinite(g).all()


def test_the_backward_pass_keeps_the_border_states_alone():
    """What autodiff keeps of a pass over 8 chunks: the inputs and one
    (N, H, P, S) state a chunk; no (Q, Q) decay matrix."""
    from jax._src.ad_checkpoint import saved_residuals
    n, t, h, p, g, s, q = 1, 64, 4, 4, 2, 8, 8
    args = inputs(n=n, t=t, h=h, p=p, g=g, s=s)
    kept = saved_residuals(
        lambda *a: jnp.sum(ssd_chunked(*a, chunk_size=q)[0]), *args)
    sizes = [int(np.prod(aval.shape)) for aval, _ in kept]
    chunks = t // q
    assert chunks * n * h * p * s in sizes              # the border states
    # nothing as large as one decay matrix a chunk and head
    largest_input = max(int(np.prod(a.shape)) for a in args)
    assert max(sizes) <= max(largest_input, chunks * n * h * p * s)
    assert chunks * n * h * q * q > max(sizes)


def test_bfloat16_operands_stay_near_float32():
    args = inputs(t=48)
    want, _ = ssd_chunked(*args, chunk_size=16)
    low = tuple(v.astype(jnp.bfloat16) if v.ndim == 4 else v for v in args)
    got, state = ssd_chunked(*low, chunk_size=16)
    assert got.dtype == state.dtype == jnp.float32
    err = float(jnp.sqrt(jnp.mean((got - want) ** 2)) / jnp.std(want))
    assert 1e-4 < err < 3e-2


def test_grouped_rms_norm_by_hand():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, 12)).astype(np.float32)
    w = rng.normal(size=12).astype(np.float32)
    got = np.asarray(grouped_rms_norm(jnp.asarray(x), jnp.asarray(w), 3,
                                      1e-5))
    for r in range(3):
        for grp in range(3):
            part = x[r, 4 * grp:4 * grp + 4].astype(np.float64)
            want = part / np.sqrt((part ** 2).mean() + 1e-5) \
                * w[4 * grp:4 * grp + 4]
            assert np.allclose(got[r, 4 * grp:4 * grp + 4], want, rtol=1e-5)


@pytest.fixture(scope="module")
def mixer():
    layer = Mamba2Mixer(name="m2test", n_in=24, n_out=24, n_heads=8,
                        head_dim=4, n_groups=2, d_state=6, chunk_size=8)
    params = layer.initialize(jax.random.PRNGKey(3), RecurrentType(24, None))
    return layer, params


def test_the_mixers_parameters_and_initial_values(mixer):
    layer, params = mixer
    d, bc, h = 32, 12, 8
    assert {k: v.shape for k, v in params.items()} == {
        "W_in": (24, 2 * d + 2 * bc + h), "conv_w": (d + 2 * bc, 4),
        "conv_b": (d + 2 * bc,), "dt_bias": (h,), "A_log": (h,), "D": (h,),
        "norm_w": (d,), "W_out": (d, 24)}
    assert np.allclose(np.exp(params["A_log"]), np.arange(1, h + 1))
    assert (np.asarray(params["D"]) == 1).all()
    assert (np.asarray(params["norm_w"]) == 1).all()
    step = np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert (step >= 1e-3 * 0.999).all() and (step <= 0.1 * 1.001).all()
    floored = Mamba2Mixer(n_in=24, n_out=24, n_heads=8, head_dim=4,
                          n_groups=2, d_state=6, dt_min=1e-6, dt_max=1e-5,
                          dt_floor=1e-2).initialize(
        jax.random.PRNGKey(0), RecurrentType(24, None))
    assert np.allclose(jax.nn.softplus(floored["dt_bias"]), 1e-2, rtol=1e-4)
    with pytest.raises(ValueError, match="multiple"):
        Mamba2Mixer(n_in=24, n_out=24, n_heads=8, n_groups=3)


@pytest.mark.parametrize("t", [16, 21])
def test_the_mixer_is_the_references_layer(mixer, t):
    layer, params = mixer
    reference = cells.load_file_module(
        cells.ROOT / "yardstick" / "reference" / "nemotron_h.py")
    rng = np.random.default_rng(t)
    # every part away from its initial value, so that each one counts
    params = {**params,
              "conv_b": jnp.asarray(rng.normal(size=56) * 0.3, jnp.float32),
              "D": jnp.asarray(rng.normal(size=8) + 1.0, jnp.float32),
              "norm_w": jnp.asarray(rng.normal(size=32) * 0.3 + 1.0,
                                    jnp.float32),
              "W_in": params["W_in"] * 20.0}
    x = jnp.asarray(rng.normal(size=(2, t, 24)), jnp.float32)
    cfg = {"mamba_num_heads": 8, "mamba_head_dim": 4, "n_groups": 2,
           "ssm_state_size": 6, "layer_norm_epsilon": 1e-5}
    with jax.default_matmul_precision("highest"):
        got, _ = layer.apply(params, {}, x, LayerContext(train=False))
        want = reference._mamba2(cfg, x, params)
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
    gauge = default_registry().get_metric("dl4j_ssd_chunks")
    assert gauge.series()[(("layer", "m2test"),)] == -(-t // 8)
