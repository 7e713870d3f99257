"""conv_bn_stats_xla — the fused bottleneck block's conv + BN-statistics
op — against the plain XLA reference math (the accelerated-helper
validation tier — reference analog: deeplearning4j-cuda's
ValidateCudnn* tests, SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.fused_conv import (
    _conv_reference,
    conv_bn_stats_xla,
    stats_to_scale_shift,
)

RNG = np.random.default_rng(7)


def _mk(n, h, w, cin, cout, kernel):
    x = jnp.asarray(RNG.normal(0, 1, (n, h, w, cin)).astype(np.float32))
    if kernel == 1:
        wt = jnp.asarray(RNG.normal(0, 0.1, (cin, cout))
                         .astype(np.float32))
    else:
        wt = jnp.asarray(RNG.normal(0, 0.1, (3, 3, cin, cout))
                         .astype(np.float32))
    s = jnp.asarray(RNG.normal(1, 0.1, cin).astype(np.float32))
    b = jnp.asarray(RNG.normal(0, 0.1, cin).astype(np.float32))
    return x, wt, s, b


def _case(n=3, h=6, w=6, cin=8, cout=32, kernel=1, stride=1, norm=True,
          dtype="float32"):
    return dict(n=n, h=h, w=w, cin=cin, cout=cout, kernel=kernel,
                stride=stride, norm=norm, dtype=dtype)


def _traces_gram(x, wt, s, b):
    """Whether ``conv_bn_stats_xla`` takes its Gram branch for these
    shapes, read from its jaxpr: ``_gram`` is the op's only custom VJP."""
    jaxpr = jax.make_jaxpr(
        lambda x, wt: conv_bn_stats_xla(x, wt, s, b, True, True, 1))(x, wt)
    return any("custom_vjp" in str(eqn.primitive) for eqn in jaxpr.eqns)


class TestXlaGramImpl:
    """Same (y, stats) contract as the reference, Gram-matrix statistics
    for expanding 1×1 convs (Σy = colsum(e)@W, Σy² = diag(WᵀGW) with
    G=eᵀe — exact algebra, differentiable by plain autodiff)."""

    @pytest.mark.parametrize("case", [
        _case(cin=8, cout=32),                       # expand → Gram
        _case(cin=8, cout=32, stride=2),
        _case(cin=32, cout=8),                       # reduce → direct
        _case(cin=8, cout=16, kernel=3),
        _case(n=4, h=8, w=8, cin=16, cout=32),
        _case(n=4, h=8, w=8, cin=32, cout=16, stride=2),   # reduce, strided
        _case(n=2, h=33, w=5, cin=24, cout=16),      # N·H·W no tile multiple
        _case(n=4, h=6, w=6, cin=16, cout=24, kernel=3),
        _case(n=6, h=2, w=2, cin=32, cout=16, kernel=3),   # tiny planes
        # norm_in=False must skip the scale/shift on both conv shapes
        _case(n=2, h=4, w=4, cin=8, cout=16, norm=False),
        _case(n=2, h=4, w=4, cin=8, cout=16, kernel=3, norm=False),
        _case(n=2, h=4, w=4, cin=16, cout=16, dtype="bfloat16"),
    ])
    def test_matches_reference(self, case):
        x, wt, s, b = _mk(case["n"], case["h"], case["w"], case["cin"],
                          case["cout"], case["kernel"])
        x, wt = x.astype(case["dtype"]), wt.astype(case["dtype"])
        norm = case["norm"]
        y, st = conv_bn_stats_xla(x, wt, s, b, norm, norm, case["stride"])
        yr, str_ = _conv_reference(x, wt, s, b, norm, norm, case["stride"])
        assert y.dtype == x.dtype
        assert st.dtype == jnp.float32
        if case["dtype"] == "bfloat16":
            np.testing.assert_allclose(np.asarray(y, np.float32),
                                       np.asarray(yr, np.float32),
                                       rtol=0.05, atol=0.05)
            return
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(st), np.asarray(str_),
                                   rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("case", [
        _case(n=3, h=4, w=4, cin=8, cout=24),        # expand → Gram
        _case(n=3, h=4, w=4, cin=8, cout=12),
        _case(n=3, h=4, w=4, cin=8, cout=12, stride=2),
        _case(n=3, h=4, w=4, cin=8, cout=12, kernel=3),
        # forward/backward consistency without the normalize
        _case(n=2, h=4, w=4, cin=8, cout=12, norm=False),
        _case(n=2, h=4, w=4, cin=8, cout=12, kernel=3, norm=False),
    ])
    def test_grads_match_reference(self, case):
        """jax.grad through (y, stats) must equal jax.grad of the plain
        XLA composition — including the batch-stat gradient path (the
        stats outputs are differentiable)."""
        x, wt, s, b = _mk(case["n"], case["h"], case["w"], case["cin"],
                          case["cout"], case["kernel"])
        norm = case["norm"]

        def loss(f):
            def inner(x, wt, s, b):
                y, st = f(x, wt, s, b, norm, norm, case["stride"])
                # consume y AND the stats the way a downstream BN would
                inv, shift, mean, var = stats_to_scale_shift(
                    st, y.size // y.shape[-1], jnp.ones(y.shape[-1]),
                    jnp.zeros(y.shape[-1]), 1e-5)
                z = y.astype(jnp.float32) * inv + shift
                return jnp.sum(jnp.tanh(z)) + 0.1 * jnp.sum(mean * mean) \
                    + 0.1 * jnp.sum(var)
            return inner

        gf = jax.grad(loss(conv_bn_stats_xla),
                      argnums=(0, 1, 2, 3))(x, wt, s, b)
        gr = jax.grad(loss(_conv_reference),
                      argnums=(0, 1, 2, 3))(x, wt, s, b)
        for a, r, name in zip(gf, gr, "x w scale shift".split()):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), rtol=2e-4, atol=2e-4,
                err_msg=f"grad mismatch for {name}")

    @pytest.mark.parametrize("cin,gram", [(128, True), (129, False)])
    def test_gram_threshold(self, cin, gram):
        """An expanding 1×1 takes the Gram statistics up to
        cin² = 64·cout and the direct reduction past it; both equal the
        reference."""
        x, wt, s, b = _mk(2, 2, 2, cin, 256, 1)
        assert _traces_gram(x, wt, s, b) is gram
        y, st = conv_bn_stats_xla(x, wt, s, b, True, True, 1)
        yr, str_ = _conv_reference(x, wt, s, b, True, True, 1)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(st), np.asarray(str_),
                                   rtol=1e-4, atol=1e-3)


def test_block_saved_with_an_impl_still_loads():
    """A configuration saved before PR 29 carries the block's ``impl``
    field ("pallas" or "xla"); ``from_dict`` skips keys a class no longer
    has, so it loads as the one block there is."""
    from deeplearning4j_tpu.nn.layers.fused import FusedBottleneckBlock
    from deeplearning4j_tpu.utils.serde import from_dict, to_dict
    block = FusedBottleneckBlock(filters=8, stride=2, downsample=True)
    saved = to_dict(block)
    assert "impl" not in saved
    for impl in ("pallas", "xla"):
        assert from_dict({**saved, "impl": impl}) == block


def test_resnet50_refuses_a_removed_fused_impl():
    """``ResNet50.fused_impl`` selects nothing any more; a value that
    asks for the removed tier is an error, not a silent fallback."""
    from deeplearning4j_tpu.zoo.models import ResNet50
    assert ResNet50(fused_blocks=True).fused_impl == "xla"
    with pytest.raises(ValueError, match="PR 29"):
        ResNet50(fused_blocks=True, fused_impl="pallas")
