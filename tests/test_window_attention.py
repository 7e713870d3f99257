"""The causal window in every attention implementation (the flash kernels
interpreted, their XLA backward, the plain XLA path), the blocks the
windowed kernels visit, and differential attention on top of them."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers.attention import (
    DifferentialAttention, differential_lambda_init,
    scaled_dot_product_attention)
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.observe.registry import default_registry
from deeplearning4j_tpu.ops import pallas_kernels as pk

T, BLOCK = 96, 16


def qkv(t=T, h=2, dh=8, dv=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, t, h, dh)),
            jax.random.normal(ks[1], (1, t, h, dh)),
            jax.random.normal(ks[2], (1, t, h, dv)))


def naive(q, k, v, window):
    """Softmax over an explicit mask, one head at a time."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    t = q.shape[1]
    pos = np.arange(t)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[None, :] > pos[:, None] - window
    out = np.zeros(q.shape[:3] + (v.shape[-1],))
    for h in range(q.shape[2]):
        s = q[0, :, h] @ k[0, :, h].T / math.sqrt(q.shape[-1])
        s = np.where(seen, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[0, :, h] = (p / p.sum(-1, keepdims=True)) @ v[0, :, h]
    return out


# one position, under a block, a block, a block's multiple, not a
# multiple, the whole sequence and more
WINDOWS = [1, 5, 16, 32, 40, T, 200]


@pytest.mark.parametrize("window", WINDOWS)
def test_window_values_in_flash_and_xla_equal_the_explicit_mask(window):
    q, k, v = qkv()
    want = naive(q, k, v, window)
    got = pk.flash_attention(q, k, v, visibility=pk.Causal(window),
                             block_q=BLOCK, block_k=BLOCK, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        scaled_dot_product_attention(q, k, v, visibility=pk.Causal(window)),
        want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [1, 5, 32, 40])
@pytest.mark.parametrize("bwd_impl", ["pallas", "pallas_two_launches",
                                      "xla"])
def test_window_gradients_in_both_backward_passes_equal_xlas(window,
                                                             bwd_impl,
                                                             monkeypatch):
    """The Pallas backward as one kernel and as two launches (the dK/dV
    kernel, then the dQ kernel), and the scan, against XLA's autodiff."""
    if bwd_impl == "pallas_two_launches":
        monkeypatch.setattr(pk, "SCOPED_VMEM_CAP", 0)   # no dQ fits
        bwd_impl = "pallas"
    q, k, v = qkv()

    def through(attn):
        return lambda *a: jnp.sum(jnp.sin(attn(*a)))

    want = jax.grad(through(lambda *a: scaled_dot_product_attention(
        *a, visibility=pk.Causal(window))), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(through(lambda *a: pk.flash_attention(
        *a, visibility=pk.Causal(window), block_q=BLOCK, block_k=BLOCK,
        interpret=True, bwd_impl=bwd_impl)), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("block_q,block_k", [(32, 16), (16, 32), (48, 16)])
def test_window_with_unequal_tiles(block_q, block_k):
    q, k, v = qkv()

    def total(*a):
        return jnp.sum(jnp.sin(pk.flash_attention(
            *a, visibility=pk.Causal(20), block_q=block_q, block_k=block_k,
            interpret=True)))

    want = jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(
        scaled_dot_product_attention(*a, visibility=pk.Causal(20)))),
        argnums=(0, 1, 2))(q, k, v)
    got = jax.value_and_grad(total, argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5)


def test_a_window_of_the_whole_sequence_is_the_causal_kernel():
    q, k, v = qkv()

    def lowered(window):
        return jax.jit(lambda *a: pk.flash_attention(
            *a, visibility=pk.Causal(window), block_q=BLOCK, block_k=BLOCK,
            interpret=True)).lower(q, k, v).as_text()

    assert lowered(T) == lowered(None) == lowered(10 * T)
    assert lowered(T - 1) != lowered(None)


def test_a_window_needs_self_attention_and_a_position():
    q, k, v = qkv()
    with pytest.raises(ValueError, match="window"):
        pk.flash_attention(q, k[:, :64], v[:, :64], visibility=pk.Causal(8),
                           interpret=True)
    with pytest.raises(ValueError, match="window >= 1"):
        pk.flash_attention(q, k, v, visibility=pk.Causal(0), interpret=True)


def brute_blocks(t, bq, bk, window):
    """Tiles that hold at least one visible (query, key) pair."""
    pos = np.arange(t)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[None, :] > pos[:, None] - window
    tiles = seen.reshape(t // bq, bq, t // bk, bk).any(axis=(1, 3))
    return int(tiles.sum()), tiles.size, int(tiles.sum(1).max()), \
        int(tiles.sum(0).max())


@pytest.mark.parametrize("t,bq,bk,window", [
    (96, 16, 16, None), (96, 16, 16, 1), (96, 16, 16, 16), (96, 16, 16, 17),
    (96, 16, 16, 40), (96, 32, 16, 20), (96, 16, 32, 20),
    (8192, 512, 512, 512), (8192, 1024, 1024, 512), (8192, 1024, 1024, None),
])
def test_the_grid_visits_the_blocks_the_window_reaches_and_no_other(
        t, bq, bk, window):
    visited, total, widest_row, widest_col = brute_blocks(t, bq, bk, window)
    assert pk.flash_kv_blocks(t, t, bq, bk, pk.Causal(window)) == (
        visited, total)
    if window is not None:
        # the inner grid axes are as long as the widest run, no longer
        nq, nk = t // bq, t // bk
        assert pk._span(lambda qi: pk._window_kv_blocks(
            qi, bq, bk, window), nq) == widest_row
        assert pk._span(lambda ki: pk._window_q_blocks(
            ki, bq, bk, window, nq), nk) == widest_col


def test_window_512_at_8192_tokens_visits_under_a_quarter_of_causal():
    bq, bk = pk._default_blocks(128, pk.Causal(512))
    assert (bq, bk) == (512, 512)           # no tile wider than the window
    assert pk._default_blocks(128, pk.Causal()) == (1024, 1024)
    assert pk._default_blocks(256, pk.Causal()) == (512, 1024)     # as before
    windowed, _ = pk.flash_kv_blocks(8192, 8192, bq, bk, pk.Causal(512))
    causal, total = pk.flash_kv_blocks(8192, 8192, 1024, 1024, pk.Causal())
    assert (windowed, causal, total) == (31, 36, 64)
    # in keys visited: 31 tiles of 512 x 512 against 36 of 1024 x 1024
    assert windowed * 512 * 512 / (causal * 1024 * 1024) < 0.22


def test_the_gauge_pair_is_set_from_the_grid_when_the_call_is_traced():
    q, k, v = qkv()
    jax.jit(lambda *a: pk.flash_attention(
        *a, visibility=pk.Causal(20), block_q=BLOCK, block_k=BLOCK,
        interpret=True, scope="attn.window")).lower(q, k, v)
    reg = default_registry()
    want = pk.flash_kv_blocks(T, T, BLOCK, BLOCK, pk.Causal(20))
    for (name, _), value in zip(pk.FLASH_BLOCK_GAUGES, want):
        series = reg.get_metric(name).series()
        assert [v for key, v in series.items()
                if "attn.window" in str(key)] == [float(value)]


# ---- differential attention --------------------------------------------------

def test_lambda_init_goes_by_the_published_index():
    assert differential_lambda_init(0) == pytest.approx(0.2)
    assert differential_lambda_init(1) == pytest.approx(
        0.8 - 0.6 * math.exp(-0.3))
    assert differential_lambda_init(17) == pytest.approx(0.796342, abs=1e-6)
    assert differential_lambda_init(19) < 0.8


def plain_differential(layer, p, x, kv=None):
    """The layer's docstring, head pair by head pair."""
    x = np.asarray(x, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    n, t, _ = x.shape
    h, hk, dh = layer.n_heads, layer.n_kv_heads, layer.head_dim
    qkv_ = x @ p["W_qkv"] + p["b_qkv"]
    if kv is None:
        q, k, v = np.split(qkv_, [h * dh, (h + hk) * dh], -1)
    else:
        q, (k, v) = qkv_, (np.asarray(a, np.float64) for a in kv)
    q, k, v = (a.reshape(n, t, -1, dh) for a in (q, k, v))
    lam0 = differential_lambda_init(layer.layer_index)
    lam = (math.exp(p["lambda_q1"] @ p["lambda_k1"])
           - math.exp(p["lambda_q2"] @ p["lambda_k2"]) + lam0)
    out = np.zeros((n, t, h // 2, 2 * dh))
    for i in range(h // 2):
        j = i // (h // hk)
        vv = np.concatenate([v[:, :, 2 * j], v[:, :, 2 * j + 1]], -1)
        maps = [naive(q[:, :, 2 * i + m:2 * i + m + 1],
                      k[:, :, 2 * j + m:2 * j + m + 1], vv[:, :, None],
                      layer.window)[:, :, 0] for m in (0, 1)]
        o = maps[0] - lam * maps[1]
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + layer.eps)
        out[:, :, i] = o * p["subln"] * (1 - lam0)
    return out.reshape(n, t, h * dh) @ p["W_o"] + p["b_o"]


def perturbed(layer, seed):
    p = layer.initialize(jax.random.PRNGKey(seed), RecurrentType(16, None))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(p))
    return {name: v + 0.3 * jax.random.normal(k, v.shape)
            for (name, v), k in zip(sorted(p.items()), keys)}


@pytest.mark.parametrize("window,index", [(None, 17), (6, 1), (1, 3)])
def test_differential_self_attention_is_the_written_one(window, index):
    layer = DifferentialAttention(n_in=16, n_out=16, n_heads=8, n_kv_heads=4,
                                  head_dim=2, window=window,
                                  layer_index=index)
    p = perturbed(layer, 3)
    assert p["W_qkv"].shape == (16, (8 + 2 * 4) * 2)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, 16))
    y, (k, v) = layer.mix(p, x)
    assert k.shape == v.shape == (1, 24, 4 * 2)
    np.testing.assert_allclose(y, plain_differential(layer, p, x),
                               rtol=5e-4, atol=5e-5)
    assert layer.scope == ("attn.full" if window is None else "attn.window")


def test_cross_attention_reads_the_keys_and_values_it_is_handed():
    emitter = DifferentialAttention(n_in=16, n_out=16, n_heads=8,
                                    n_kv_heads=4, head_dim=2, layer_index=5)
    reader = DifferentialAttention(n_in=16, n_out=16, n_heads=8,
                                   n_kv_heads=4, head_dim=2, cross=True,
                                   layer_index=7)
    pe, pr = perturbed(emitter, 1), perturbed(reader, 2)
    assert pr["W_qkv"].shape == (16, 8 * 2)         # queries alone
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 24, 16))
    _, kv = emitter.mix(pe, x)
    h = jax.random.normal(jax.random.PRNGKey(9), (1, 24, 16))
    y, _ = reader.apply(pr, {}, (h, *kv), LayerContext())
    np.testing.assert_allclose(y, plain_differential(reader, pr, h, kv),
                               rtol=5e-4, atol=5e-5)
    assert reader.scope == "attn.cross"


def test_differential_attention_on_the_flash_kernel_equals_the_xla_path(
        monkeypatch):
    layer = DifferentialAttention(n_in=16, n_out=16, n_heads=4, n_kv_heads=2,
                                  head_dim=8, window=20, layer_index=1)
    p = perturbed(layer, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, 16))
    want, _ = layer.mix(p, x)
    real = pk.attention

    def flash(q, k, v, **kw):
        kw.pop("prefer_flash", None)
        assert v.shape[-1] == 2 * q.shape[-1] and q.shape[2] == 4
        return pk.flash_attention(q, k, v, block_q=16, block_k=16,
                                  interpret=True, **kw)

    monkeypatch.setattr(pk, "attention", flash)
    got, _ = layer.mix(p, x)
    monkeypatch.setattr(pk, "attention", real)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
