"""Flash-attention Pallas kernel vs the plain XLA attention path.

The reference validates accelerated helpers against the built-in path
(deeplearning4j-cuda tests: ValidateCudnnLSTM, CuDNNGradientChecks —
SURVEY §4 "accelerated-vs-reference validation"); same idea here, with
the kernel run in interpreter mode on the CPU mesh.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.attention import (
    scaled_dot_product_attention)
from deeplearning4j_tpu.ops.pallas_kernels import attention, flash_attention
from deeplearning4j_tpu.ops.visibility import (
    BlockDiffusion, Causal, Visibility)


def _vis(causal):
    return Causal() if causal else Visibility()


def _qkv(rng, n=2, t=48, h=4, dh=16):
    q = rng.normal(size=(n, t, h, dh)).astype(np.float32)
    k = rng.normal(size=(n, t, h, dh)).astype(np.float32)
    v = rng.normal(size=(n, t, h, dh)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_plain_forward(rng, causal):
    q, k, v = _qkv(rng)
    ref = scaled_dot_product_attention(q, k, v, visibility=_vis(causal))
    out = flash_attention(q, k, v, visibility=_vis(causal), block_q=16,
                          block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_respects_key_mask(rng):
    q, k, v = _qkv(rng, t=32)
    mask = np.ones((2, 32), np.float32)
    mask[0, 20:] = 0.0
    mask[1, 5:] = 0.0
    ref = scaled_dot_product_attention(q, k, v, mask=jnp.asarray(mask))
    out = flash_attention(q, k, v, mask=jnp.asarray(mask), block_q=8,
                          block_k=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_unaligned_lengths(rng):
    """T not a multiple of the block size exercises the padding path."""
    q, k, v = _qkv(rng, t=37)
    ref = scaled_dot_product_attention(q, k, v, visibility=Causal())
    out = flash_attention(q, k, v, visibility=Causal(), block_q=16, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match(rng, causal):
    q, k, v = _qkv(rng, n=1, t=32, h=2, dh=8)
    mask = np.ones((1, 32), np.float32)
    mask[0, 28:] = 0.0
    mask = jnp.asarray(mask)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, mask=mask, visibility=_vis(causal),
                            block_q=8, block_k=8, interpret=True)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = scaled_dot_product_attention(q, k, v, mask=mask,
                                         visibility=_vis(causal))
        return jnp.sum(o * o)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_attention_dispatch_falls_back(rng):
    """Helper-SPI: off-TPU the dispatcher uses the plain path and the
    result is identical to calling it directly."""
    q, k, v = _qkv(rng, t=16)
    out = attention(q, k, v)
    ref = scaled_dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6)


class TestFlashBlockLayout:
    """Regression for the TPU lowering constraint: the mask rides as
    (n, 1, tk) and lse as (n, h, tq, 1) so block trailing dims are legal
    (the forward and dQ kernels; the dK/dV kernel takes the mask as (n,
    tk, 1) and lse as (n, h, nq, 1, block_q)).
    On CPU this runs the same kernel in interpret mode; on TPU it must
    compile WITHOUT falling back (the silent-fallback path once hid a
    never-ran kernel)."""

    def test_flash_direct_no_fallback(self, rng):
        import jax.numpy as jnp
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention

        N, T, H, Dh = 2, 256, 4, 64
        mk = lambda: jnp.asarray(
            rng.normal(size=(N, T, H, Dh)).astype(np.float32))
        q, k, v = mk(), mk(), mk()
        # call flash_attention directly: any lowering error raises here
        o = flash_attention(q, k, v, visibility=Causal())
        s = jnp.einsum("nthd,nshd->nhts", q, k) / np.sqrt(Dh)
        m = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(m[None, None], s, -1e30)
        ref = jnp.einsum("nhts,nshd->nthd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=1e-2, atol=1e-2)

    def test_large_blocks_clamp_to_sequence(self, rng):
        import jax.numpy as jnp
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention

        # default blocks (1024) larger than T: must clamp and still work
        N, T, H, Dh = 1, 64, 2, 16
        mk = lambda: jnp.asarray(
            rng.normal(size=(N, T, H, Dh)).astype(np.float32))
        o = flash_attention(mk(), mk(), mk())
        assert o.shape == (N, T, H, Dh)
        assert np.isfinite(np.asarray(o)).all()

    def test_fully_masked_row_outputs_zero(self, rng):
        """Regression: a fully-padded sequence must produce zeros (the
        reference path's behavior), not mean(v) — the online-softmax
        accumulator sees exp(0)=1 garbage until the first valid key."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn.layers.attention import (
            scaled_dot_product_attention)
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention

        N, T, H, Dh = 3, 64, 2, 16
        mk = lambda: jnp.asarray(
            rng.normal(size=(N, T, H, Dh)).astype(np.float32))
        q, k, v = mk(), mk(), mk()
        mask = np.ones((N, T), np.float32)
        mask[1] = 0.0          # fully padded sequence
        mask[2, 20:] = 0.0     # ragged tail
        mask = jnp.asarray(mask)
        o = flash_attention(q, k, v, mask=mask)
        r = scaled_dot_product_attention(q, k, v, mask=mask)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=1e-2, atol=1e-2)
        assert np.abs(np.asarray(o[1])).max() < 1e-6
        # gradients through the masked batch match the reference too
        g1 = jax.grad(lambda v: jnp.sum(
            flash_attention(q, k, v, mask=mask) ** 2))(v)
        g2 = jax.grad(lambda v: jnp.sum(
            scaled_dot_product_attention(q, k, v, mask=mask) ** 2))(v)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-2, atol=1e-2)

    def test_masked_rows_nonzero_cotangent(self, rng):
        """Backward with sum() loss (cotangent 1 on padded-row outputs):
        grads through fully-masked rows must be zero, not exp(0) garbage."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn.layers.attention import (
            scaled_dot_product_attention)
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention

        N, T, H, Dh = 2, 64, 2, 16
        mk = lambda: jnp.asarray(
            rng.normal(size=(N, T, H, Dh)).astype(np.float32))
        q, k, v = mk(), mk(), mk()
        mask = np.ones((N, T), np.float32)
        mask[1] = 0.0
        mask = jnp.asarray(mask)
        for wrt in (0, 1, 2):
            g1 = jax.grad(lambda *a: jnp.sum(
                flash_attention(*a, mask=mask)), argnums=wrt)(q, k, v)
            g2 = jax.grad(lambda *a: jnp.sum(
                scaled_dot_product_attention(*a, mask=mask)),
                argnums=wrt)(q, k, v)
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       rtol=1e-2, atol=1e-2)


class TestFlashPallasBackward:
    """The round-4 Pallas dq/dk/dv kernels vs the jnp/scan reference VJP
    (DL4J_FLASH_BWD=xla) and vs plain-XLA attention gradients — both
    passes in kernels, reference analog: ValidateCudnnLSTM checking
    backprop too."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_xla_vjp_reference(self, rng, causal, monkeypatch):
        q, k, v = _qkv(rng, n=2, t=64, h=2, dh=16)
        mask = np.ones((2, 64), np.float32)
        mask[0, 50:] = 0.0
        mask = jnp.asarray(mask)
        do = jnp.asarray(rng.normal(size=(2, 64, 2, 16))
                         .astype(np.float32))

        def run():
            def f(q, k, v):
                o = flash_attention(q, k, v, mask=mask,
                                    visibility=_vis(causal),
                                    block_q=16, block_k=16,
                                    interpret=True)
                return jnp.sum(o * do)
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        monkeypatch.setenv("DL4J_FLASH_BWD", "pallas")
        gp = run()
        monkeypatch.setenv("DL4J_FLASH_BWD", "xla")
        gx = run()
        for a, b, name in zip(gp, gx, "q k v".split()):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                err_msg=f"d{name} mismatch vs scan reference")

    @pytest.mark.parametrize("causal", [False, True])
    def test_bwd_impl_parameter(self, rng, causal, monkeypatch):
        """Explicit bwd_impl selects the backward programmatically and
        overrides the env var (advisor r4: no ambient-state dependence).
        The pallas/xla backwards agree numerically, so the override is
        made OBSERVABLE by instrumenting the pallas entry point."""
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        q, k, v = _qkv(rng, n=2, t=32, h=2, dh=16)
        do = jnp.asarray(rng.normal(size=(2, 32, 2, 16))
                         .astype(np.float32))
        calls = []
        real = pk._flash_backward_pallas
        monkeypatch.setattr(
            pk, "_flash_backward_pallas",
            lambda *a, **kw: calls.append(1) or real(*a, **kw))
        # env says xla; the explicit param must still control the choice
        monkeypatch.setenv("DL4J_FLASH_BWD", "xla")

        def run(impl):
            def f(q, k, v):
                o = flash_attention(q, k, v, visibility=_vis(causal),
                                    block_q=16, block_k=16, interpret=True,
                                    bwd_impl=impl)
                return jnp.sum(o * do)
            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        gx = run("xla")
        assert not calls, "bwd_impl='xla' must not touch the pallas bwd"
        gp = run("pallas")
        assert calls, "bwd_impl='pallas' must override DL4J_FLASH_BWD=xla"
        for a, b, name in zip(gp, gx, "q k v".split()):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                err_msg=f"d{name} mismatch pallas vs xla bwd_impl")
        with pytest.raises(ValueError, match="bwd_impl"):
            flash_attention(q, k, v, bwd_impl="cuda")

    def test_unaligned_causal_masked_grads(self, rng):
        """Padding path + causal + key mask through the Pallas bwd."""
        q, k, v = _qkv(rng, n=1, t=37, h=2, dh=8)
        mask = np.ones((1, 37), np.float32)
        mask[0, 30:] = 0.0
        mask = jnp.asarray(mask)

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, mask=mask, visibility=Causal(),
                                block_q=8, block_k=8, interpret=True)
            return jnp.sum(jnp.tanh(o))

        def loss_ref(q, k, v):
            o = scaled_dot_product_attention(q, k, v, mask=mask,
                                             visibility=Causal())
            return jnp.sum(jnp.tanh(o))

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)

    def test_fully_masked_rows_zero_grads(self, rng):
        """lse == _NEG rows (query padding / fully-masked) must emit
        exactly zero dq and contribute nothing to dk/dv."""
        q, k, v = _qkv(rng, n=1, t=16, h=1, dh=8)
        mask = np.zeros((1, 16), np.float32)
        mask[0, :4] = 1.0
        mask = jnp.asarray(mask)

        def f(q, k, v):
            o = flash_attention(q, k, v, mask=mask,
                                block_q=8, block_k=8, interpret=True)
            return jnp.sum(o)

        dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        assert np.isfinite(np.asarray(dq)).all()
        assert np.isfinite(np.asarray(dk)).all()
        np.testing.assert_allclose(np.asarray(dk)[0, 4:], 0.0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dv)[0, 4:], 0.0, atol=1e-6)


@pytest.mark.parametrize("head_dim,blocks", [
    (64, (1024, 1024)), (128, (1024, 1024)), (256, (512, 1024)),
    (512, (256, 1024))])
def test_default_blocks_shrink_with_the_head(head_dim, blocks):
    """The tuned tiles up to a head of 128; from 256 the query tile halves
    until the dK/dV kernel's working set fits the scoped VMEM (what the
    TPU compiler accepts and refuses: tests/test_tpu_compile.py)."""
    from deeplearning4j_tpu.ops.pallas_kernels import _default_blocks
    assert _default_blocks(head_dim) == blocks


# (visibility, positions, query tile, key tile): the backward's tile is
# the forward's transposed; windows under, at and over the tile, blocks of
# a power of two and not, tiles wider on either side
TRANSPOSED_MASK_CASES = {
    "all": (Visibility(), 32, 8, 16),
    "causal": (Causal(), 32, 8, 8),
    "causal_wide_query": (Causal(), 32, 16, 8),
    "causal_wide_key": (Causal(), 32, 8, 16),
    "window_under_the_tile": (Causal(5), 32, 8, 8),
    "window_of_the_tile": (Causal(8), 32, 8, 8),
    "window_over_the_tile": (Causal(20), 48, 16, 8),
    "block_diffusion": (BlockDiffusion(32, 4), 64, 8, 8),
    "block_diffusion_wide_query": (BlockDiffusion(32, 4), 64, 16, 8),
    "block_diffusion_wide_key": (BlockDiffusion(32, 4), 64, 8, 32),
    "block_diffusion_block_of_3": (BlockDiffusion(36, 3), 72, 6, 12),
    "block_diffusion_block_of_3_wide_query": (BlockDiffusion(36, 3), 72,
                                              12, 6),
}


@pytest.mark.parametrize("case", TRANSPOSED_MASK_CASES)
def test_the_transposed_tile_mask_is_the_tile_mask_transposed(case):
    """``tile_visible_t`` (the dK/dV kernel's key-by-query tile) is
    ``tile_visible`` transposed at every (query block, key block) of the
    grid, the tiles the kernels skip too; and both are ``visible``."""
    vis, t, bq, bk = TRANSPOSED_MASK_CASES[case]
    pos = np.arange(t)
    full = vis.visible(pos[:, None], pos[None, :])
    for qi in range(t // bq):
        for ki in range(t // bk):
            seen = vis.tile_visible(qi, ki, bq, bk)
            seen_t = vis.tile_visible_t(qi, ki, bq, bk)
            if full is None:
                assert seen is None and seen_t is None
                continue
            seen, seen_t = np.asarray(seen), np.asarray(seen_t)
            assert seen_t.shape == (bk, bq)
            np.testing.assert_array_equal(seen_t, seen.T)
            want = np.asarray(full)[qi * bq:(qi + 1) * bq,
                                    ki * bk:(ki + 1) * bk]
            if not isinstance(vis, BlockDiffusion) or want.any():
                # block diffusion tells apart the tiles it visits alone
                np.testing.assert_array_equal(seen, want)


# (visibility, positions, head, value head, key mask, dtype): every kind of
# visibility, with and without a key mask, lengths that pad (250, 500 and
# the two halves of 200 under block diffusion), a value head of its own,
# and bfloat16 inputs
ONE_KERNEL_CASES = {
    "all": (Visibility(), 256, 64, 64, False, np.float32),
    "all_masked": (Visibility(), 256, 64, 64, True, np.float32),
    "all_bfloat16": (Visibility(), 256, 64, 64, True, jnp.bfloat16),
    "causal": (Causal(), 256, 64, 64, False, np.float32),
    "causal_padded_masked": (Causal(), 250, 64, 64, True, np.float32),
    "causal_wide_value": (Causal(), 256, 64, 128, False, np.float32),
    "causal_bfloat16": (Causal(), 256, 64, 64, True, jnp.bfloat16),
    "window": (Causal(100), 512, 64, 64, False, np.float32),
    "window_padded_masked": (Causal(100), 500, 64, 64, True, np.float32),
    "window_wide_value": (Causal(200), 512, 64, 128, False, np.float32),
    "window_bfloat16": (Causal(100), 500, 64, 64, True, jnp.bfloat16),
    "block_diffusion": (BlockDiffusion(128, 4), 256, 64, 64, False,
                        np.float32),
    "block_diffusion_masked": (BlockDiffusion(256, 4), 512, 64, 64, True,
                               np.float32),
    "block_diffusion_padded_wide_value": (BlockDiffusion(200, 4), 400, 64,
                                          128, True, np.float32),
    "block_diffusion_bfloat16": (BlockDiffusion(256, 4), 512, 64, 64, True,
                                 jnp.bfloat16),
}


def _one_kernel_grads(case, rng):
    """dQ, dK, dV of ``flash_attention`` in ``case`` at 128 x 128 tiles,
    and the ``pallas_call``s of its differentiated program."""
    vis, t, dh, dv, masked, dtype = ONE_KERNEL_CASES[case]
    n, h = 2, 2
    q, k = (jnp.asarray(rng.normal(size=(n, t, h, dh)), dtype)
            for _ in range(2))
    v, do = (jnp.asarray(rng.normal(size=(n, t, h, dv)), dtype)
             for _ in range(2))
    mask = None
    if masked:
        # row 0 padded at its end; row 1's leading keys masked, so that
        # its first queries see no key under every visibility but all
        mask = np.ones((n, t), np.float32)
        mask[0, t - 37:] = 0.0
        mask[1, :t // 3] = 0.0
        mask = jnp.asarray(mask)

    def f(q, k, v, impl=None):
        o = flash_attention(q, k, v, mask=mask, visibility=vis, block_q=128,
                            block_k=128, interpret=True, bwd_impl=impl)
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))

    grad = jax.grad(f, argnums=(0, 1, 2))
    launches = str(jax.make_jaxpr(grad)(q, k, v)).count("pallas_call")
    xla = jax.grad(functools.partial(f, impl="xla"), argnums=(0, 1, 2))
    return grad(q, k, v), launches, xla(q, k, v)


@pytest.mark.parametrize("case", ONE_KERNEL_CASES)
def test_the_one_kernel_backward_is_the_two_launch_backward_to_the_bit(
        monkeypatch, case):
    """The backward as one kernel (dQ kept in VMEM beside dK/dV) against
    the two launches it stands in for where a head's dQ fits, on the same
    inputs. dK and dV come from the one transposed tile on both paths, so
    they are equal to the last bit; dQ sums the same tiles in the same
    order, the one kernel's ``(dS^T)^T k`` of the dQ kernel's ``dS k``
    operands transposed exactly, so it is equal to the last bit too.
    Both paths are within the scan reference's tolerance, float32, and
    within a rounding of bfloat16's."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    one, launches, xla = _one_kernel_grads(case, np.random.default_rng(7))
    assert launches == 2                    # the forward and one backward
    monkeypatch.setattr(pk, "SCOPED_VMEM_CAP", 0)   # no dQ fits: two
    two, launches, _ = _one_kernel_grads(case, np.random.default_rng(7))
    assert launches == 3
    for a, b, name in zip(one, two, "qkv"):
        assert a.dtype == b.dtype
        assert jnp.array_equal(a, b), f"d{name} differs"
    bfloat16 = one[0].dtype == jnp.bfloat16
    for got in (one, two):
        for a, b, name in zip(got, xla, "qkv"):
            a, b = (np.asarray(x, np.float32) for x in (a, b))
            if bfloat16:        # a sum's last float32 bit may round over
                np.testing.assert_allclose(
                    a, b, rtol=2 ** -7, atol=2 ** -7 * np.abs(b).max(),
                    err_msg=f"d{name} mismatch vs scan reference")
            else:
                np.testing.assert_allclose(
                    a, b, rtol=2e-4, atol=2e-5,
                    err_msg=f"d{name} mismatch vs scan reference")


# the five language-model cells' attention calls: (positions, head, value
# head, visibility) as their layers hand them to the kernels
CELL_ATTENTION = {
    "sdar.block_diffusion": (16384, 128, 128, BlockDiffusion(8192, 4)),
    "qwen3-next.gated": (8192, 256, 256, Causal()),
    "phi4-mini-flash.full": (8192, 64, 128, Causal()),
    "phi4-mini-flash.window": (8192, 64, 128, Causal(512)),
    "nemotron3-nano.causal": (8192, 128, 128, Causal()),
    "trinity-mini.window": (8192, 128, 128, Causal(2048)),
    "trinity-mini.full": (8192, 128, 128, Causal()),
}


@pytest.mark.parametrize("cell", CELL_ATTENTION)
def test_every_cells_backward_is_one_kernel(cell):
    """A head's dQ fits in scoped VMEM beside the tile at every cell's
    shape, bfloat16 or float32: the backward is one launch there."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        SCOPED_VMEM_CAP, _bwd_vmem_need, _default_blocks)
    t, dh, dv, vis = CELL_ATTENTION[cell]
    bq, bk = _default_blocks(max(dh, dv), vis)
    for itemsize in (2, 4):
        assert _bwd_vmem_need(t, dh, dv, bq, bk, itemsize) < SCOPED_VMEM_CAP


@pytest.mark.parametrize("bwd_impl,env,transposed", [
    (None, None, 1), ("pallas", "xla", 1), (None, "xla", 0),
    ("xla", None, 0)])
def test_the_backward_layout_gauge_is_set_under_the_callers_scope(
        monkeypatch, bwd_impl, env, transposed):
    """``dl4j_flash_bwd_transposed{scope}``: 1 where the backward runs the
    Pallas kernels (their dK/dV tile is the transposed one), 0 where it
    runs the scan reference; set when the call is traced."""
    from deeplearning4j_tpu.observe.registry import default_registry
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    if env is None:
        monkeypatch.delenv("DL4J_FLASH_BWD", raising=False)
    else:
        monkeypatch.setenv("DL4J_FLASH_BWD", env)
    q, k, v = _qkv(np.random.default_rng(0), t=32)
    jax.jit(lambda *a: flash_attention(
        *a, visibility=Causal(), block_q=16, block_k=16, interpret=True,
        bwd_impl=bwd_impl, scope="attn.layout_probe")).lower(q, k, v)
    series = default_registry().get_metric(pk.FLASH_BWD_GAUGE[0]).series()
    assert [value for key, value in series.items()
            if "attn.layout_probe" in str(key)] == [float(transposed)]


@pytest.mark.parametrize("t,launches", [(8192, 2), (65536, 2),
                                        (131072, 3)])
def test_the_backward_is_two_launches_where_a_heads_dq_passes_the_cap(
        t, launches):
    """Traced at one causal head of 128, bfloat16: up to 65,536 positions
    a head's dQ (float32 scratch and the output block's two buffers) fits
    under ``SCOPED_VMEM_CAP`` with the tile; at 131,072 it is 128 MiB and
    the backward is the dK/dV kernel and the dQ kernel."""
    x = jax.ShapeDtypeStruct((1, t, 1, 128), jnp.bfloat16)
    grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, visibility=Causal(), interpret=True).astype(jnp.float32)),
        argnums=(0, 1, 2))
    assert str(jax.make_jaxpr(grad)(x, x, x)).count("pallas_call") == launches
