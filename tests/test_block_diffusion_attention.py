"""The block-diffusion visibility (``ops/visibility.py BlockDiffusion``):
the flash kernels in interpret mode, the jnp/scan backward and the plain
XLA path against the mask built from its definition, forward and all
three gradients; the tiles the grid visits; the layer and the head's loss
that train on ``[noisy | clean]``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers.attention import (
    GatedAttention, scaled_dot_product_attention)
from deeplearning4j_tpu.observe.registry import default_registry
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.ops.visibility import BlockDiffusion, Causal

H, DH = 2, 8


def literal_mask(t, b):
    """M[s, r] of the doubled sequence, rule by rule."""
    m = np.zeros((2 * t, 2 * t), bool)
    for s in range(2 * t):
        for r in range(2 * t):
            bs, br = (s % t) // b, (r % t) // b
            if s < t and r < t:
                m[s, r] = br == bs
            elif s < t:
                m[s, r] = br < bs
            elif r >= t:
                m[s, r] = br <= bs
    return m


def qkv(t, seed=0, n=1, dv=DH):
    rng = np.random.default_rng(seed)
    mk = lambda d: jnp.asarray(rng.normal(size=(n, 2 * t, H, d)), jnp.float32)
    return mk(DH), mk(DH), mk(dv)


def by_the_mask(q, k, v, mask):
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.asarray(mask)[None, None], s, -jnp.inf)
    return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1), v)


def values_and_gradients(fn, q, k, v, seed=5):
    w = jnp.asarray(np.random.default_rng(seed).normal(
        size=fn(q, k, v).shape), jnp.float32)
    return (fn(q, k, v),) + jax.grad(
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)


def test_the_visible_pairs_are_a_quarter_and_a_block_more():
    for t, b in ((16, 1), (16, 4), (64, 32)):
        m = literal_mask(t, b)
        assert m.sum() == t * (t + b)
        pos = np.arange(2 * t)
        got = BlockDiffusion(t, b).visible(pos[:, None], pos[None, :])
        np.testing.assert_array_equal(np.asarray(got), m)


# (T, B, block_q, block_k): tiles of one block and of many, a query tile
# wider and narrower than the key tile, a T no tile divides (padded)
SHAPES = [(16, 1, 4, 4), (16, 1, 1, 1), (32, 4, 4, 4), (32, 4, 8, 16),
          (32, 4, 16, 8), (32, 4, 32, 32), (64, 32, 32, 32), (24, 4, 16, 16),
          (20, 4, 8, 8), (18, 4, 8, 4)]


@pytest.mark.parametrize("t,b,bq,bk", SHAPES)
@pytest.mark.parametrize("bwd", ["pallas", "pallas_two_launches", "xla"])
def test_the_kernels_compute_the_literal_mask(t, b, bq, bk, bwd,
                                              monkeypatch):
    """The forward and the backward as one kernel, as two launches (the
    transposed dK/dV kernel, then the dQ kernel) and as the scan."""
    if bwd == "pallas_two_launches":
        monkeypatch.setattr(pk, "SCOPED_VMEM_CAP", 0)   # no dQ fits
        bwd = "pallas"
    q, k, v = qkv(t, seed=t + b)
    want = values_and_gradients(
        lambda *a: by_the_mask(*a, literal_mask(t, b)), q, k, v)
    got = values_and_gradients(lambda *a: pk.flash_attention(
        *a, visibility=BlockDiffusion(t, b), block_q=bq, block_k=bk,
        interpret=True, bwd_impl=bwd), q, k, v)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("t,b", [(16, 1), (32, 4), (64, 32)])
def test_the_plain_path_computes_the_literal_mask(t, b):
    q, k, v = qkv(t, seed=3)
    want = values_and_gradients(
        lambda *a: by_the_mask(*a, literal_mask(t, b)), q, k, v)
    got = values_and_gradients(lambda *a: scaled_dot_product_attention(
        *a, visibility=BlockDiffusion(t, b)), q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)


def test_a_key_mask_and_a_value_head_of_its_own_pass_through():
    t, b = 32, 4
    q, k, v = qkv(t, seed=9, n=2, dv=2 * DH)
    keys = np.ones((2, 2 * t), np.float32)
    keys[1, 5:9] = 0            # a few noisy keys of row 1 are padding
    keys[1, t + 20:] = 0        # and its clean tail
    want = scaled_dot_product_attention(
        q, k, v, mask=jnp.asarray(keys), visibility=BlockDiffusion(t, b))
    got = pk.flash_attention(q, k, v, mask=jnp.asarray(keys),
                             visibility=BlockDiffusion(t, b), block_q=8,
                             block_k=8, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def brute_tiles(t, b, bq, bk):
    m = literal_mask(t, b)
    tiles = m.reshape(2 * t // bq, bq, 2 * t // bk, bk).any(axis=(1, 3))
    return tiles


@pytest.mark.parametrize("t,b,bq,bk", [
    (16, 1, 4, 4), (16, 1, 1, 1), (32, 4, 4, 4), (32, 4, 8, 16),
    (32, 4, 16, 8), (64, 32, 32, 32), (64, 4, 8, 8)])
def test_the_grids_visit_the_tiles_that_hold_a_visible_pair_and_no_other(
        t, b, bq, bk):
    vis = BlockDiffusion(t, b)
    tiles = brute_tiles(t, b, bq, bk)
    nq, nk = tiles.shape
    assert pk.flash_kv_blocks(2 * t, 2 * t, bq, bk, vis) == (
        int(tiles.sum()), tiles.size)
    # the forward and dQ kernels' walk, query block by query block
    steps = vis.kv_steps(nq, nk, bq, bk)
    assert steps == tiles.sum(1).max()
    for qi in range(nq):
        walk = [vis.kv_tile(qi, kj, bq, bk) for kj in range(steps)]
        live = [ki for ki, on in walk if on]
        assert sorted(live) == list(np.flatnonzero(tiles[qi]))
        assert len(set(live)) == len(live)
        # a step past the last live block fetches the block already held
        fetched = [vis.kv_fetch(qi, kj, bq, bk) for kj in range(steps)]
        assert fetched[:len(live)] == live
        assert set(fetched[len(live):]) <= {live[-1]}
    # the dK/dV kernel's, key block by key block
    steps = vis.q_steps(nq, nk, bq, bk)
    assert steps == tiles.sum(0).max()
    for ki in range(nk):
        walk = [vis.q_tile(ki, qj, bq, bk, nq) for qj in range(steps)]
        live = [qi for qi, on in walk if on]
        assert sorted(live) == list(np.flatnonzero(tiles[:, ki]))
        fetched = [vis.q_fetch(ki, qj, bq, bk, nq) for qj in range(steps)]
        assert fetched[:len(live)] == live
        assert set(fetched[len(live):]) <= {live[-1]}


def test_at_the_cells_shapes_the_grid_visits_80_of_256_tiles():
    t, b = 8192, 4
    vis = BlockDiffusion(t, b)
    assert pk._default_blocks(128, vis) == (1024, 1024)
    assert pk.flash_kv_blocks(2 * t, 2 * t, 1024, 1024, vis) == (80, 256)
    assert vis.kv_steps(16, 16, 1024, 1024) == 9
    assert vis.q_steps(16, 16, 1024, 1024) == 16
    # the pairs are 25.0%; a causal kernel over 2T would visit 136 tiles
    assert t * (t + b) / (4 * t * t) == pytest.approx(0.25, abs=2e-4)
    assert pk.flash_kv_blocks(2 * t, 2 * t, 1024, 1024, Causal()) == (
        136, 256)


def test_with_blocks_of_one_the_clean_half_is_the_causal_kernel():
    t = 32
    q, k, v = qkv(t, seed=11)
    both = pk.flash_attention(q, k, v, visibility=BlockDiffusion(t, 1),
                              block_q=8, block_k=8, interpret=True)
    clean = pk.flash_attention(q[:, t:], k[:, t:], v[:, t:], visibility=Causal(),
                               block_q=8, block_k=8, interpret=True)
    np.testing.assert_allclose(both[:, t:], clean, rtol=1e-6, atol=1e-6)


def test_the_gauge_pair_is_set_under_the_callers_scope():
    t, b = 32, 4
    q, k, v = qkv(t)
    jax.jit(lambda *a: pk.flash_attention(
        *a, visibility=BlockDiffusion(t, b), block_q=8, block_k=8,
        interpret=True, scope="attn.block_diffusion")).lower(q, k, v)
    want = pk.flash_kv_blocks(2 * t, 2 * t, 8, 8, BlockDiffusion(t, b))
    for (name, _), value in zip(pk.FLASH_BLOCK_GAUGES, want):
        series = default_registry().get_metric(name).series()
        assert [v for key, v in series.items()
                if "attn.block_diffusion" in str(key)] == [float(value)]


def test_what_the_visibility_value_refuses():
    t = 16
    q, k, v = qkv(t)
    with pytest.raises(ValueError, match="2 \\* seq_len"):
        pk.flash_attention(q, k, v, visibility=BlockDiffusion(t + 4, 4),
                           interpret=True)
    with pytest.raises(ValueError, match="multiple of the block"):
        BlockDiffusion(t, 4).kv_steps(16, 16, 2, 2)
    assert dataclasses.replace(Causal(8), window=None) == Causal()
