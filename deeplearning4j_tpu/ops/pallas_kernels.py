"""Pallas TPU kernels — the "accelerated layer helper" tier.

The reference accelerates its hot layers with hand-written cuDNN helpers
loaded reflectively (deeplearning4j-cuda/.../BaseCudnnHelper.java:1,
ConvolutionLayer.java:75-85 — SURVEY §2.4). The TPU analog: XLA already
lowers conv/BN/LSTM onto the MXU, so helpers are only written where a
fused kernel beats XLA's default lowering. Attention is the headline case:
the blockwise (flash) kernel below keeps the running softmax in VMEM and
never materializes the (Tq, Tk) score matrix in HBM.

Layout: q/k/v are (N, H, T, Dh) inside the kernel (the layer-facing
wrapper accepts the framework-standard (N, T, H, Dh)). The grid is
(batch, head, q-block); each program streams the full K/V for its head
through VMEM in ``block_k`` chunks with an online softmax.

`attention()` chooses between the kernel and the plain XLA path from
what it can observe in its inputs (backend, sequence length); a kernel
the compiler refuses raises — there is no silent fallback.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Large-finite instead of -inf: -inf scores make softmax VJPs emit NaN for
# fully-masked rows (matches nn/layers/attention.py's choice).
_NEG = float(jnp.finfo(jnp.float32).min) / 2.0  # host-sync-ok: finfo constant

_DEF_BLOCK_Q = 1024  # tuned on v5e: 16k-seq causal attn 21.5ms vs 84ms at 128
_DEF_BLOCK_K = 1024


def _window_kv_blocks(qi, bq: int, bk: int, window: int):
    """First and last key block a causal window lets query block ``qi``
    see: keys ``qi * bq - (window - 1)`` to ``qi * bq + bq - 1``. Python
    ints or traced scalars."""
    first = qi * bq - (window - 1)
    lo = (max(first, 0) if isinstance(first, int)
          else jnp.maximum(first, 0)) // bk
    return lo, (qi * bq + bq - 1) // bk


def _window_q_blocks(ki, bq: int, bk: int, window: int, nq: int):
    """First and last query block that sees key block ``ki`` under a
    causal window: queries ``ki * bk`` to ``ki * bk + bk - 1 + window -
    1``, inside the sequence."""
    lo = (ki * bk) // bq
    last = (ki * bk + bk + window - 2) // bq
    return lo, (min(last, nq - 1) if isinstance(last, int)
                else jnp.minimum(last, nq - 1))


def _span(first_last, blocks: int) -> int:
    """The widest run of blocks ``first_last(i)`` gives over ``blocks``
    outer blocks: the extent of a windowed kernel's inner grid axis."""
    widest = 1
    for i in range(blocks):
        lo, hi = first_last(i)
        widest = max(widest, hi - lo + 1)
    return widest


def _kv_step(qi, kj, bq: int, bk: int, causal: bool,
             window: Optional[int]):
    """``(key block, whether its tile holds a visible pair)`` of step
    ``kj`` of the inner axis of a kernel that streams key blocks past
    query block ``qi``."""
    if window is not None:
        lo, hi = _window_kv_blocks(qi, bq, bk, window)
        return lo + kj, lo + kj <= hi
    # causal: tiles fully above the diagonal contribute nothing
    return kj, ((kj * bk <= (qi + 1) * bq - 1) if causal
                else (kj == kj))  # always-true traced pred


def _flash_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *, causal: bool,
                      scale: float, window: Optional[int] = None):
    """One (q-block, k-block) tile of the online softmax. The k-block
    axis is the innermost SEQUENTIAL grid dim; the running (m, l, acc)
    live in VMEM scratch across its iterations, so K/V stream from HBM
    block by block and VMEM stays O(block) at any sequence length (the
    pre-round-4 kernel kept the whole K/V resident and died at 16k).
    Under a ``window`` that axis runs over the key blocks the window
    reaches only (``_window_kv_blocks``): step ``kj`` is key block ``lo +
    kj``, and steps past the last such block do nothing and fetch
    nothing (their index is clamped to the block already held)."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ki, live = _kv_step(qi, kj, bq, bk, causal, window)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)                # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kvalid = mask_ref[0, 0] > 0.0
        s = jnp.where(kvalid[None, :], s, _NEG)
        if causal:
            qpos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(_visible(qpos, kpos, window), s, _NEG)
        m_prev = m_scr[:, :1]                              # (bq, 1)
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kj == nk - 1)
    def _finalize():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        # A row that never saw a valid key keeps m == _NEG: its p values
        # were exp(0)=1 garbage, so zero the output (matching the XLA
        # reference) rather than emitting mean(v).
        valid = m > (_NEG * 0.5)
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o = jnp.where(valid, acc_scr[...] / l_safe, 0.0)
        o_ref[0, 0] = o.astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(valid, m + jnp.log(l_safe), _NEG)


def _visible(qpos, kpos, window: Optional[int]):
    """Causal visibility: a query sees itself and what came before, under
    a ``window`` only the ``window - 1`` positions before itself."""
    seen = kpos <= qpos
    if window is not None:
        seen = seen & (kpos > qpos - window)
    return seen


def pallas_interpret() -> bool:
    """Interpret mode for every Pallas entry point in ``ops/``: compiled
    on "tpu", interpreted on "cpu" (the CPU test suite runs the same
    kernel code that way), an error anywhere else — a backend that does
    not call itself "tpu" must not quietly interpret."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for 'tpu' and interpret on 'cpu'; the "
        f"default backend is {backend!r}")


_SCOPED_VMEM_DEFAULT = 16 << 20   # Mosaic's scoped-VMEM limit unless raised
SCOPED_VMEM_CAP = 100 << 20       # of the 128 MiB a v5e/v6e core has


def scoped_vmem_limit(need: int) -> Optional[int]:
    """``vmem_limit_bytes`` for a kernel that may ask for ``need`` bytes
    of scoped VMEM: None while Mosaic's default covers it, else the need,
    capped — past the cap Mosaic's own error surfaces."""
    if need <= _SCOPED_VMEM_DEFAULT:
        return None
    return min(need, SCOPED_VMEM_CAP)


def _dim_sem(n: int, vmem_limit_bytes: Optional[int] = None):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (n - 1) + ("arbitrary",),
        vmem_limit_bytes=vmem_limit_bytes)


def _kv_index(bq: int, bk: int, window: Optional[int]):
    """Key block of grid step ``(qi, kj)`` for the index maps of the
    kernels whose inner axis runs over key blocks: ``kj`` itself, or under
    a window the ``kj``-th block the window reaches, clamped to the last
    (a repeated index fetches nothing)."""
    if window is None:
        return lambda qi, kj: kj

    def block(qi, kj):
        lo, hi = _window_kv_blocks(qi, bq, bk, window)
        return jnp.minimum(lo + kj, hi)
    return block


def _q_index(bq: int, bk: int, window: Optional[int], nq: int):
    """The same for the dK/dV kernel, whose inner axis runs over the
    query blocks that see key block ``ki``."""
    if window is None:
        return lambda ki, qj: qj

    def block(ki, qj):
        lo, hi = _window_q_blocks(ki, bq, bk, window, nq)
        return jnp.minimum(lo + qj, hi)
    return block


def _flash_forward(q, k, v, mask, causal: bool, block_q: int, block_k: int,
                   interpret: bool, window: Optional[int] = None):
    n, h, tq, dh = q.shape
    tk, dv = k.shape[2], v.shape[3]
    scale = 1.0 / float(dh) ** 0.5  # host-sync-ok: static shape
    nq, nk = tq // block_q, tk // block_k
    if window is not None:
        nk = _span(lambda qi: _window_kv_blocks(qi, block_q, block_k,
                                                window), nq)
    grid = (n, h, nq, nk)
    kb = _kv_index(block_q, block_k, window)
    vm = pl.ANY if interpret else pltpu.VMEM

    kernel = functools.partial(_flash_fwd_kernel, causal=causal,
                               scale=scale, window=window)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda i, j, qi, ki: (i, j, qi, 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda i, j, qi, ki: (i, j, kb(qi, ki), 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda i, j, qi, ki: (i, j, kb(qi, ki), 0),
                         memory_space=vm),
            # (n, 1, tk) so the block's trailing dims stay legal for the
            # TPU lowering (last two block dims divisible by (8, 128) or
            # equal to the array dims)
            pl.BlockSpec((1, 1, block_k),
                         lambda i, j, qi, ki: (i, 0, kb(qi, ki)),
                         memory_space=vm),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda i, j, qi, ki: (i, j, qi, 0),
                         memory_space=vm),
            # trailing singleton for the same block-shape constraint
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda i, j, qi, ki: (i, j, qi, 0),
                         memory_space=vm),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((n, h, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=_dim_sem(4),
        interpret=interpret,
    )(q, k, v, mask[:, None, :])
    return out, lse[..., 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_attention(q, k, v, mask, causal, block_q, block_k, interpret,
                     bwd_impl, window):
    out, _ = _flash_forward(q, k, v, mask, causal, block_q, block_k,
                            interpret, window)
    return out


def _flash_fwd_rule(q, k, v, mask, causal, block_q, block_k, interpret,
                    bwd_impl, window):
    out, lse = _flash_forward(q, k, v, mask, causal, block_q, block_k,
                              interpret, window)
    return out, (q, k, v, mask, out, lse)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                          causal: bool, scale: float,
                          window: Optional[int] = None,
                          q_blocks: int = 0):
    """dK/dV for one key block: the query-block axis is the innermost
    sequential grid dim, accumulating into VMEM scratch — P is recomputed
    from the saved logsumexp, never materialized in HBM. Under a
    ``window`` that axis runs over the query blocks that see the key
    block only (``_window_q_blocks`` of the ``q_blocks`` there are)."""
    ki = pl.program_id(2)
    qj = pl.program_id(3)
    nq = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(qj == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if window is not None:
        lo, hi = _window_q_blocks(ki, bq, bk, window, q_blocks)
        qi = lo + qj
        live = qi <= hi
    else:
        qi = qj
        live = ((qi + 1) * bq - 1 >= ki * bk) if causal else (qi == qi)

    @pl.when(live)
    def _tile():
        kb = k_ref[0, 0].astype(jnp.float32)               # (bk, dh)
        vb = v_ref[0, 0].astype(jnp.float32)
        q = q_ref[0, 0].astype(jnp.float32)                # (bq, dh)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                                # (bq, 1)
        delta = delta_ref[0, 0]
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask_ref[0, 0][None, :] > 0.0, s, _NEG)
        if causal:
            qpos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(_visible(qpos, kpos, window), s, _NEG)
        p = jnp.exp(s - lse)
        p = jnp.where(lse > (_NEG * 0.5), p, 0.0)          # (bq, bk)
        dv_scr[...] += lax.dot_general(
            p, do, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jnp.dot(do, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_scr[...] += lax.dot_general(
            ds, q, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qj == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, dq_scr, *, causal: bool,
                         scale: float, window: Optional[int] = None):
    """dQ for one query block: key blocks stream on the sequential grid
    dim (under a ``window``, those it reaches: the forward kernel's
    axis), accumulating into VMEM scratch."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    ki, live = _kv_step(qi, kj, bq, bk, causal, window)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)                # (bq, dh)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                                # (bq, 1)
        delta = delta_ref[0, 0]
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask_ref[0, 0][None, :] > 0.0, s, _NEG)
        if causal:
            qpos = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(_visible(qpos, kpos, window), s, _NEG)
        p = jnp.exp(s - lse)
        p = jnp.where(lse > (_NEG * 0.5), p, 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[...] += jnp.dot(ds, k,
                               preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_backward_pallas(q, k, v, mask, out, lse, do, causal: bool,
                           block_q: int, block_k: int, interpret: bool,
                           window: Optional[int] = None):
    """Pallas dq/dk/dv (VERDICT r3 #2 — both passes in kernels, like the
    reference's CudnnLSTMHelper accelerating fwd AND bwd). The tiny
    delta = rowsum(dO ⊙ O) precompute stays in XLA (one fused elementwise
    pass); everything matmul-shaped runs on the MXU in Pallas."""
    n, h, tq, dh = q.shape
    tk, dv = k.shape[2], v.shape[3]
    scale = 1.0 / float(dh) ** 0.5  # host-sync-ok: static shape
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                # (n, h, tq, 1)
    lse4 = lse[..., None]                                  # (n, h, tq, 1)
    mask3 = mask[:, None, :]                               # (n, 1, tk)
    vm = pl.ANY if interpret else pltpu.VMEM
    nq, nk = tq // block_q, tk // block_k
    nq_inner, nk_inner = nq, nk
    if window is not None:
        nq_inner = _span(lambda ki: _window_q_blocks(
            ki, block_q, block_k, window, nq), nk)
        nk_inner = _span(lambda qi: _window_kv_blocks(
            qi, block_q, block_k, window), nq)
    qb = _q_index(block_q, block_k, window, nq)
    kb = _kv_index(block_q, block_k, window)

    kernel = functools.partial(_flash_bwd_dkv_kernel, causal=causal,
                               scale=scale, window=window, q_blocks=nq)
    dk, dv_ = pl.pallas_call(
        kernel,
        grid=(n, h, nk, nq_inner),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda i, j, ki, qi: (i, j, qb(ki, qi), 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda i, j, ki, qi: (i, j, ki, 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda i, j, ki, qi: (i, j, ki, 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_k),
                         lambda i, j, ki, qi: (i, 0, ki),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda i, j, ki, qi: (i, j, qb(ki, qi), 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda i, j, ki, qi: (i, j, qb(ki, qi), 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda i, j, ki, qi: (i, j, qb(ki, qi), 0),
                         memory_space=vm),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda i, j, ki, qi: (i, j, ki, 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda i, j, ki, qi: (i, j, ki, 0),
                         memory_space=vm),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, tk, dh), k.dtype),
            jax.ShapeDtypeStruct((n, h, tk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, dh), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        compiler_params=_dim_sem(4),
        interpret=interpret,
    )(q, k, v, mask3, do, lse4, delta)

    kernel = functools.partial(_flash_bwd_dq_kernel, causal=causal,
                               scale=scale, window=window)
    dq = pl.pallas_call(
        kernel,
        grid=(n, h, nq, nk_inner),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda i, j, qi, ki: (i, j, qi, 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda i, j, qi, ki: (i, j, kb(qi, ki), 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda i, j, qi, ki: (i, j, kb(qi, ki), 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_k),
                         lambda i, j, qi, ki: (i, 0, kb(qi, ki)),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda i, j, qi, ki: (i, j, qi, 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda i, j, qi, ki: (i, j, qi, 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda i, j, qi, ki: (i, j, qi, 0),
                         memory_space=vm),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda i, j, qi, ki: (i, j, qi, 0),
                               memory_space=vm),
        out_shape=jax.ShapeDtypeStruct((n, h, tq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32)],
        compiler_params=_dim_sem(4),
        interpret=interpret,
    )(q, k, v, mask3, do, lse4, delta)
    return dq, dk, dv_


def _flash_bwd_rule(causal, block_q, block_k, interpret, bwd_impl, window,
                    res, do):
    """Flash backward from saved (O, logsumexp) — dq/dk/dv Pallas kernels
    (``_flash_backward_pallas``); P is recomputed from the normalizer
    instead of being saved. ``bwd_impl`` ("pallas"/"xla", the explicit
    flash_attention parameter) takes precedence; when None the
    ``DL4J_FLASH_BWD=xla`` env override selects the jnp/scan reference
    implementation (also used by equivalence tests). The env var is read
    at TRACE time — a jitted train step freezes the choice; call
    ``jax.clear_caches()`` after changing it (advisor r4: pass bwd_impl
    for programmatic control instead)."""
    import os
    q, k, v, mask, out, lse = res
    if bwd_impl is None:
        bwd_impl = os.environ.get("DL4J_FLASH_BWD", "pallas")
    if bwd_impl != "xla":
        dq, dk, dv = _flash_backward_pallas(
            q, k, v, mask, out, lse, do, causal, block_q, block_k,
            interpret, window)
        return dq, dk, dv, jnp.zeros_like(mask)
    return _flash_bwd_xla(causal, block_q, block_k, interpret, res, do,
                          window)


def _flash_bwd_xla(causal, block_q, block_k, interpret, res, do,
                   window: Optional[int] = None):
    """jnp/scan blockwise backward: the pre-round-4 VJP, kept as the
    reference implementation the Pallas kernels are tested against.
    Chunked over k blocks with lax.scan so peak memory is
    O(Tq * block_k) per (batch, head), not O(Tq * Tk)."""
    q, k, v, mask, out, lse = res
    dh = q.shape[-1]
    scale = 1.0 / float(dh) ** 0.5  # host-sync-ok: static shape
    f32 = jnp.float32
    qf, kf, vf, dof = (x.astype(f32) for x in (q, k, v, do))
    delta = jnp.sum(dof * out.astype(f32), axis=-1)        # (n, h, tq)
    tq, tk = q.shape[2], k.shape[2]

    def p_block(kb):
        """(n, h, tq, bk) probability block at k offset kb*block_k."""
        ks = lax.dynamic_slice_in_dim(kf, kb * block_k, block_k, axis=2)
        s = jnp.einsum("nhqd,nhkd->nhqk", qf, ks) * scale
        mk = lax.dynamic_slice_in_dim(mask, kb * block_k, block_k, axis=1)
        s = jnp.where(mk[:, None, None, :] > 0, s, _NEG)
        if causal:
            qpos = jnp.arange(tq)[:, None]
            kpos = kb * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where(_visible(qpos, kpos, window), s, _NEG)
        p = jnp.exp(s - lse[..., None])
        # fully-masked rows carry lse == _NEG: exp(s - lse) degenerates to
        # 1 there; their true probabilities (and grads) are zero
        p = jnp.where(lse[..., None] > (_NEG * 0.5), p, 0.0)
        return p, ks

    def scan_body(dq, kb):
        p, ks = p_block(kb)
        vs = lax.dynamic_slice_in_dim(vf, kb * block_k, block_k, axis=2)
        dp = jnp.einsum("nhqd,nhkd->nhqk", dof, vs)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("nhqk,nhkd->nhqd", ds, ks)
        dv_b = jnp.einsum("nhqk,nhqd->nhkd", p, dof)
        dk_b = jnp.einsum("nhqk,nhqd->nhkd", ds, qf)
        return dq, (dk_b, dv_b)

    nk = tk // block_k
    dq0 = jnp.zeros_like(qf)
    dq, (dk_blocks, dv_blocks) = lax.scan(scan_body, dq0, jnp.arange(nk))
    # (nk, n, h, bk, d) -> (n, h, tk, d)
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(kf.shape)
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(vf.shape)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            jnp.zeros_like(mask))


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _pad_len(t: int, block: int) -> int:
    return (-t) % block


def _default_blocks(head_dim: int, window: Optional[int] = None):
    """The tile sizes for a head of ``head_dim``: the tuned 1024 x 1024
    while the dK/dV kernel's float32 working set fits Mosaic's scoped
    VMEM, else ``block_q`` halved until it does. That working set is two
    (block_q, block_k) score tiles and about 5.5 (block, head) tiles a
    side, whatever the inputs' type (the TPU compiler, 16 heads, T =
    8,192: 18.5 MB at 1024 x 1024 x 256 and 19.8 MB at 512 x 1024 x 512
    are refused; 1024 x 1024 x 128, 512 x 1024 x 256 and 256 x 1024 x 512
    compile).

    Under a causal ``window`` neither side of the tile is wider than the
    window (in whole lanes of 128): a head of 64 with a window of 512
    takes 512 x 512. A query block of ``block_q`` rows reaches ``block_q
    + window - 1`` keys and the kernel visits the whole key blocks that
    hold them, so a key block wider than the window wastes the skip:
    1024 x 1024 would visit 2,048 keys a row where 512 are wanted (4
    times the work), 512 x 512 visits 1,024 (twice), and narrower tiles
    (256 x 256: 768) pay more grid steps and half-filled matrix units
    for what they save."""
    block_q, block_k = _DEF_BLOCK_Q, _DEF_BLOCK_K
    if window is not None:
        side = min(block_k, max(128, (window + 127) // 128 * 128))
        block_q = block_k = side

    def working_set(bq):
        return 4 * (2 * bq * block_k + 5.5 * (bq + block_k) * head_dim)

    while block_q > 128 and working_set(block_q) > _SCOPED_VMEM_DEFAULT:
        block_q //= 2
    return block_q, block_k


def flash_kv_blocks(tq: int, tk: int, block_q: int, block_k: int,
                    causal: bool, window: Optional[int] = None):
    """``(visited, total)`` key blocks of one head's forward pass over the
    kernel's own grid: the (query block, key block) tiles it computes, and
    all there are. A causal kernel skips the tiles above the diagonal; a
    windowed one also those wholly before the window."""
    nq, nk = -(-tq // block_q), -(-tk // block_k)
    if not causal:
        return nq * nk, nq * nk
    visited = 0
    for qi in range(nq):
        hi = min(nk - 1, (qi * block_q + block_q - 1) // block_k)
        lo = 0
        if window is not None:
            lo = max(qi * block_q - (window - 1), 0) // block_k
        visited += hi - lo + 1
    return visited, nq * nk


FLASH_BLOCK_GAUGES = (
    ("dl4j_flash_kv_blocks_visited",
     "key blocks one head's flash-attention forward pass computes, by the "
     "kernel's grid, as the step was last traced (label: the named scope)"),
    ("dl4j_flash_kv_blocks_total",
     "key blocks times query blocks of that pass: what a kernel that "
     "skipped nothing would compute"),
)


def _publish_kv_blocks(scope: str, visited: int, total: int) -> None:
    from deeplearning4j_tpu.observe.registry import default_registry
    reg = default_registry()
    for (name, help_text), value in zip(FLASH_BLOCK_GAUGES,
                                        (visited, total)):
        reg.gauge(name, help_text).set(value, scope=scope)


def flash_attention(q, k, v, mask=None, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    bwd_impl: Optional[str] = None,
                    window: Optional[int] = None,
                    scope: Optional[str] = None):
    """Blockwise (flash) attention on (N, T, H, Dh) tensors; ``v`` may
    have a head size of its own (N, T, H, Dv).

    Drop-in for nn.layers.attention.scaled_dot_product_attention. ``mask``
    is the (N, T_k) key-validity mask. Sequences are padded to the block
    size internally (padding is masked out, query padding sliced off).
    ``window`` (causal only) lets a query see itself and the ``window -
    1`` positions before it; key blocks wholly outside it are neither
    fetched nor computed, and a window of the whole sequence or more is
    the causal kernel. ``block_q`` / ``block_k`` default to
    ``_default_blocks`` of the head size and the window. ``interpret``
    defaults to ``pallas_interpret()``. ``bwd_impl`` selects the backward
    implementation explicitly ("pallas" kernels or the "xla" jnp/scan
    reference); None defers to the ``DL4J_FLASH_BWD`` env override
    (default pallas). ``scope`` names the caller's ``jax.named_scope``:
    with it, the visited and total key blocks of the grid are published
    as gauges when the call is traced.
    """
    if bwd_impl not in (None, "pallas", "xla"):
        raise ValueError(f"bwd_impl must be 'pallas'/'xla'/None, "
                         f"got {bwd_impl!r}")
    if interpret is None:
        interpret = pallas_interpret()
    n, tq, h, dh = q.shape
    tk = k.shape[1]
    if window is not None:
        if not causal or window < 1 or tq != tk:
            raise ValueError(
                f"window={window} needs causal self-attention (causal="
                f"{causal}, {tq} queries on {tk} keys) and a window >= 1")
        if window >= tk:
            window = None
    auto_q, auto_k = _default_blocks(max(dh, v.shape[-1]), window)
    block_q = min(block_q or auto_q, max(tq, 1))
    block_k = min(block_k or auto_k, max(tk, 1))
    if not interpret:
        # Mosaic constraints: q blocks land in the sublane dim (multiple
        # of 8); the mask's dynamic k-slice is in the lane dim (multiple
        # of 128). Sequences are padded up to the block size below.
        block_q = max(8, (block_q + 7) // 8 * 8)
        block_k = max(128, (block_k + 127) // 128 * 128)
    if scope is not None:
        _publish_kv_blocks(scope, *flash_kv_blocks(
            tq, tk, block_q, block_k, causal, window))

    # NTHD -> NHTD
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if mask is None:
        mask = jnp.ones((n, tk), jnp.float32)
    mask = mask.astype(jnp.float32)

    pq, pk = _pad_len(tq, block_q), _pad_len(tk, block_k)
    if pq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, pk)))

    out = _flash_attention(qt, kt, vt, mask, causal, block_q, block_k,
                           interpret, bwd_impl, window)
    if pq:
        out = out[:, :, :tq, :]
    return jnp.swapaxes(out, 1, 2)                          # NHTD -> NTHD


# Measured on v5e (benchmarks/attn_crossover.py, bf16 fwd+bwd, 12 heads
# Dh=64): plain XLA wins at T<=512 (the full score matrix is small and
# XLA fuses it into large batched MXU matmuls; the flash grid degenerates
# to tiny single-block programs), the streaming kernel wins from T=1024
# on. Re-measured after the head-trailing score-order change sped the
# XLA path up: 1024: 9.6 vs 9.9 ms; 2048: 13.4 vs 14.8; 4096: 20.7 vs
# 24.5 — narrower, same crossover, and plain XLA still OOMs on the
# O(T^2) scores at long T.
_FLASH_MIN_SEQ = 1024


def attention(q, k, v, mask=None, causal: bool = False,
              prefer_flash: Optional[bool] = None,
              window: Optional[int] = None, scope: Optional[str] = None):
    """Helper-SPI dispatch (the reflective cuDNN-hook analog): the
    Pallas kernel on TPU when the sequence is long enough to pay for
    streaming (``flash_attention`` pads to its block size, so any length
    is block-aligned), else the plain XLA lowering. The choice rests on
    the inputs alone; a kernel the compiler refuses raises. ``window``
    (causal only: a query sees itself and the ``window - 1`` positions
    before it) is honoured by both; ``scope`` is ``flash_attention``'s."""
    from deeplearning4j_tpu.nn.layers.attention import (
        scaled_dot_product_attention)
    if prefer_flash is None:
        prefer_flash = (jax.default_backend() == "tpu"
                        and max(q.shape[1], k.shape[1]) >= _FLASH_MIN_SEQ)
    if prefer_flash:
        return flash_attention(q, k, v, mask=mask, causal=causal,
                               window=window, scope=scope)
    return scaled_dot_product_attention(q, k, v, mask=mask, causal=causal,
                                        window=window)
