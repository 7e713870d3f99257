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
through VMEM in ``block_k`` chunks with an online softmax. The backward
is one kernel over (batch, head, k-block) that streams the query blocks
and keeps the head's dQ in VMEM beside the key block's dK/dV; where that
dQ does not fit, a second kernel computes it. The backward computes each
tile transposed, key rows by query columns (``k q^T``), so that dV and
dK are plain products and only dQ's contracts a leading axis.

`attention()` chooses between the kernel and the plain XLA path from
what it can observe in its inputs (backend, sequence length); a kernel
the compiler refuses raises — there is no silent fallback.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.visibility import (  # noqa: F401  (re-exported)
    BlockDiffusion,
    Causal,
    Visibility,
    _span,
    _window_kv_blocks,
    _window_q_blocks,
)

# Large-finite instead of -inf: -inf scores make softmax VJPs emit NaN for
# fully-masked rows (matches nn/layers/attention.py's choice).
_NEG = float(jnp.finfo(jnp.float32).min) / 2.0  # host-sync-ok: finfo constant

_DEF_BLOCK_Q = 1024  # tuned on v5e: 16k-seq causal attn 21.5ms vs 84ms at 128
_DEF_BLOCK_K = 1024

# ``checkpoint_name`` tags of the forward kernel's two results as the
# backward's residuals: a ``jax.checkpoint`` whose policy saves these names
# (the decoder blocks') keeps both and does not launch the forward kernel
# again to recompute them; anywhere else the tags are the identity. Both
# come out of one ``pallas_call``, so a policy that names one alone still
# pays the whole launch for the other.
FLASH_OUT_NAME = "flash_attention.out"
FLASH_LSE_NAME = "flash_attention.lse"


def _masked(s, vis: Visibility, qi, ki, bq: int, bk: int,
            transposed: bool = False):
    """The (bq, bk) score tile, or ``transposed`` the (bk, bq) one, with
    the pairs ``vis`` hides at ``_NEG``."""
    seen = (vis.tile_visible_t if transposed else vis.tile_visible)(
        qi, ki, bq, bk)
    return s if seen is None else jnp.where(seen, s, _NEG)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *, vis: Visibility,
                      scale: float):
    """One (q-block, k-block) tile of the online softmax. The k-block
    axis is the innermost SEQUENTIAL grid dim; the running (m, l, acc)
    live in VMEM scratch across its iterations, so K/V stream from HBM
    block by block and VMEM stays O(block) at any sequence length (the
    pre-round-4 kernel kept the whole K/V resident and died at 16k).
    That axis runs over the key blocks ``vis`` lets the query block see
    (``vis.kv_tile``: all of them, or under a window or the
    block-diffusion layout the runs that hold a visible pair); steps past
    a query block's last such block do nothing and fetch nothing (their
    index is clamped to the block already held)."""
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

    ki, live = vis.kv_tile(qi, kj, bq, bk)

    @pl.when(live)
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)                # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kvalid = mask_ref[0, 0] > 0.0
        s = jnp.where(kvalid[None, :], s, _NEG)
        s = _masked(s, vis, qi, ki, bq, bk)
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


def _dim_sem(n: int, vmem_limit_bytes: Optional[int] = None,
             sequential: int = 1):
    """``n`` grid axes, the last ``sequential`` of them in order."""
    return pltpu.CompilerParams(
        dimension_semantics=(("parallel",) * (n - sequential)
                             + ("arbitrary",) * sequential),
        vmem_limit_bytes=vmem_limit_bytes)


def _flash_forward(q, k, v, mask, vis: Visibility, block_q: int,
                   block_k: int, interpret: bool):
    n, h, tq, dh = q.shape
    tk, dv = k.shape[2], v.shape[3]
    scale = 1.0 / float(dh) ** 0.5  # host-sync-ok: static shape
    nq, nk = tq // block_q, tk // block_k
    grid = (n, h, nq, vis.kv_steps(nq, nk, block_q, block_k))
    kb = functools.partial(vis.kv_fetch, bq=block_q, bk=block_k)
    vm = pl.ANY if interpret else pltpu.VMEM

    kernel = functools.partial(_flash_fwd_kernel, vis=vis, scale=scale)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attention(q, k, v, mask, vis, block_q, block_k, interpret,
                     bwd_impl):
    out, _ = _flash_forward(q, k, v, mask, vis, block_q, block_k, interpret)
    return out


def _flash_fwd_rule(q, k, v, mask, vis, block_q, block_k, interpret,
                    bwd_impl):
    out, lse = _flash_forward(q, k, v, mask, vis, block_q, block_k,
                              interpret)
    # the tagged result is the primal too: the block's backward needs the
    # attention's output itself (the output projection's weight gradient),
    # and an untagged copy would bring the second launch back
    out = checkpoint_name(out, FLASH_OUT_NAME)
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return out, (q, k, v, mask, out, lse)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                          delta_ref, *refs, vis: Visibility, scale: float,
                          q_blocks: int, with_dq: bool):
    """dK/dV for one key block: the query-block axis is the innermost
    sequential grid dim, accumulating into VMEM scratch — P is recomputed
    from the saved logsumexp, never materialized in HBM. That axis runs
    over the query blocks that see the key block (``vis.q_tile``, of the
    ``q_blocks`` there are).

    The tile is computed transposed, key rows by query columns: ``s^T = k
    q^T`` (bk, bq), a product of the forward's form, and ``dP^T = v
    dO^T`` likewise. dV and dK sum over the tile's queries, its columns
    now, so ``dV += P^T dO`` and ``dK += dS^T q`` are plain products;
    only dQ sums over the keys, the tile's rows, and takes ``dS^T`` as an
    operand contracted over its leading axis: one transposed operand a
    tile, where a query-by-key tile makes dV's and dK's take one each.
    So the logsumexp and delta come in as rows (1, bq) and the key mask
    as a column (bk, 1), and the visibility's tile mask is
    ``tile_visible_t``. One orientation for every shape: no tile side or
    head size the cells use is faster the other way.

    ``with_dq``: the tile's ``ds`` also goes into dQ, kept for the whole
    head in a float32 scratch of (Tq, dh) and written once, at the head's
    last step, into an output block the head holds. A query block meets
    its key blocks in ascending order under every ``Visibility``, the
    order the dQ kernel walks them in, and each tile's ``(dS^T)^T k``
    takes that kernel's ``dS k`` operands, transposed exactly: dQ's sums
    are that kernel's to the last bit (on a v5e at the cells' shapes, and
    interpreted). dK and dV are this kernel's on both paths (the
    two-launch path runs it without dQ)."""
    if with_dq:
        dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = refs
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = refs
    ki = pl.program_id(2)
    qj = pl.program_id(3)
    nk = pl.num_programs(2)
    nq = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(qj == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if with_dq:
        @pl.when((ki == 0) & (qj == 0))
        def _init_dq():
            dq_scr[...] = jnp.zeros_like(dq_scr)

    qi, live = vis.q_tile(ki, qj, bq, bk, q_blocks)

    @pl.when(live)
    def _tile():
        kb = k_ref[0, 0].astype(jnp.float32)               # (bk, dh)
        vb = v_ref[0, 0].astype(jnp.float32)
        q = q_ref[0, 0].astype(jnp.float32)                # (bq, dh)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0]                             # (1, bq)
        delta = delta_ref[0, 0, 0]
        st = jnp.dot(kb, q.T, preferred_element_type=jnp.float32) * scale
        st = jnp.where(mask_ref[0] > 0.0, st, _NEG)        # (bk, 1) keys
        st = _masked(st, vis, qi, ki, bq, bk, transposed=True)
        pt = jnp.exp(st - lse)
        pt = jnp.where(lse > (_NEG * 0.5), pt, 0.0)        # (bk, bq)
        dv_scr[...] += jnp.dot(pt, do, preferred_element_type=jnp.float32)
        dpt = jnp.dot(vb, do.T, preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta) * scale
        dk_scr[...] += jnp.dot(dst, q, preferred_element_type=jnp.float32)
        if with_dq:
            rows = pl.ds(pl.multiple_of(qi * bq, bq), bq)
            dq_scr[rows, :] += lax.dot_general(
                dst, kb, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(qj == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)

    if with_dq:
        @pl.when((ki == nk - 1) & (qj == nq - 1))
        def _finalize_dq():
            dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, dq_scr, *, vis: Visibility,
                         scale: float):
    """dQ for one query block: key blocks stream on the sequential grid
    dim (the forward kernel's axis), accumulating into VMEM scratch. The
    backward's second launch, only where a head's dQ does not fit in
    scoped VMEM beside the dK/dV kernel's tile (``_bwd_vmem_need``)."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    ki, live = vis.kv_tile(qi, kj, bq, bk)

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
        s = _masked(s, vis, qi, ki, bq, bk)
        p = jnp.exp(s - lse)
        p = jnp.where(lse > (_NEG * 0.5), p, 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[...] += jnp.dot(ds, k,
                               preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _lanes(n: int) -> int:
    """``n`` in whole lanes of 128: the width a VMEM row of ``n`` takes."""
    return -(-n // 128) * 128


def _bwd_vmem_need(tq: int, dh: int, dv: int, block_q: int, block_k: int,
                   itemsize: int) -> int:
    """Scoped VMEM the one-kernel backward asks for, every row in whole
    lanes: its double-buffered blocks (a query block's q, dO, and its
    logsumexp and delta as rows of 8 sublanes; a key block's k, v and key
    mask, a column; the key block's dK and dV and the head's dQ out), its
    float32 scratch (dK, dV and the head's whole dQ) and float32
    temporaries: the four input blocks' and three score tiles (transposed,
    of the same size). More than Mosaic takes, by a score tile and more:
    at the five cells' shapes it compiles under 18.1-27.7 MiB where this
    reads 27.1-35.1 (Phi's window of 512 under 11.5, this 14.6), and
    under 49.6 where this reads 54.0 for 16 heads of 512."""
    qrow, vrow = _lanes(dh), _lanes(dv)
    blocks = ((block_q + block_k) * (qrow + vrow) * itemsize
              + 2 * 8 * _lanes(block_q) * 4 + block_k * 128 * 4)
    outputs = (block_k * (qrow + vrow) + tq * qrow) * itemsize
    scratch = (block_k * (qrow + vrow) + tq * qrow) * 4
    temps = ((block_q + block_k) * (qrow + vrow) + 3 * block_q * block_k) * 4
    return 2 * (blocks + outputs) + scratch + temps


def _flash_backward_pallas(q, k, v, mask, out, lse, do, vis: Visibility,
                           block_q: int, block_k: int, interpret: bool):
    """Pallas dq/dk/dv (VERDICT r3 #2 — both passes in kernels, like the
    reference's CudnnLSTMHelper accelerating fwd AND bwd): one kernel over
    the dK/dV grid that accumulates dQ beside dK and dV, each tile's
    scores recomputed and exponentiated once, wherever a head's dQ fits in
    scoped VMEM with the tile (``_bwd_vmem_need`` under
    ``SCOPED_VMEM_CAP``: every cell's shape, 35 MiB at 16,384 positions
    of 128, up to some 80,000 positions of 128 in bfloat16); past that
    (very long sequences, long ring-attention blocks) two launches, the
    dK/dV kernel and the dQ kernel, which computes the tile's scores
    again."""
    need = _bwd_vmem_need(q.shape[2], q.shape[3], v.shape[3], block_q,
                          block_k, q.dtype.itemsize)
    return _flash_backward_kernels(q, k, v, mask, out, lse, do, vis,
                                   block_q, block_k, interpret,
                                   with_dq=need <= SCOPED_VMEM_CAP)


def _flash_backward_kernels(q, k, v, mask, out, lse, do, vis: Visibility,
                            block_q: int, block_k: int, interpret: bool,
                            with_dq: bool):
    """The backward's launches: the dK/dV kernel, which also writes dQ
    ``with_dq``, else followed by the dQ kernel. The tiny delta =
    rowsum(dO ⊙ O) precompute stays in XLA (one fused elementwise pass);
    everything matmul-shaped runs on the MXU in Pallas."""
    n, h, tq, dh = q.shape
    tk, dv = k.shape[2], v.shape[3]
    scale = 1.0 / float(dh) ** 0.5  # host-sync-ok: static shape
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                               # (n, h, tq)
    vm = pl.ANY if interpret else pltpu.VMEM
    nq, nk = tq // block_q, tk // block_k
    # the dK/dV kernel's transposed tile takes the per-query values as
    # rows, one a query block (the block's last two dims are the array's
    # at any tile), and the key mask as a column; the dQ kernel's tile
    # takes them the other way
    lse_row, delta_row = (x.reshape(n, h, nq, 1, block_q)
                          for x in (lse, delta))
    mask_col = mask[:, :, None]                            # (n, tk, 1)
    nq_inner = vis.q_steps(nq, nk, block_q, block_k)
    nk_inner = vis.kv_steps(nq, nk, block_q, block_k)
    qb = functools.partial(vis.q_fetch, bq=block_q, bk=block_k, nq=nq)
    kb = functools.partial(vis.kv_fetch, bq=block_q, bk=block_k)

    kernel = functools.partial(_flash_bwd_dkv_kernel, vis=vis, scale=scale,
                               q_blocks=nq, with_dq=with_dq)
    out_specs = [
        pl.BlockSpec((1, 1, block_k, dh),
                     lambda i, j, ki, qi: (i, j, ki, 0), memory_space=vm),
        pl.BlockSpec((1, 1, block_k, dv),
                     lambda i, j, ki, qi: (i, j, ki, 0), memory_space=vm),
    ]
    out_shape = [jax.ShapeDtypeStruct((n, h, tk, dh), k.dtype),
                 jax.ShapeDtypeStruct((n, h, tk, dv), v.dtype)]
    scratch = [pltpu.VMEM((block_k, dh), jnp.float32),
               pltpu.VMEM((block_k, dv), jnp.float32)]
    params = _dim_sem(4)
    if with_dq:
        # the head's dQ: one block the head's steps all hold, so it goes
        # back to HBM once, when the head changes; the key-block axis is
        # sequential too, since every key block adds to it
        out_specs.insert(0, pl.BlockSpec(
            (1, 1, tq, dh), lambda i, j, ki, qi: (i, j, 0, 0),
            memory_space=vm))
        out_shape.insert(0, jax.ShapeDtypeStruct((n, h, tq, dh), q.dtype))
        scratch.insert(0, pltpu.VMEM((tq, dh), jnp.float32))
        params = _dim_sem(4, scoped_vmem_limit(_bwd_vmem_need(
            tq, dh, dv, block_q, block_k, q.dtype.itemsize)), sequential=2)
    grads = pl.pallas_call(
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
            pl.BlockSpec((1, block_k, 1),
                         lambda i, j, ki, qi: (i, ki, 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda i, j, ki, qi: (i, j, qb(ki, qi), 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, 1, 1, block_q),
                         lambda i, j, ki, qi: (i, j, qb(ki, qi), 0, 0),
                         memory_space=vm),
            pl.BlockSpec((1, 1, 1, 1, block_q),
                         lambda i, j, ki, qi: (i, j, qb(ki, qi), 0, 0),
                         memory_space=vm),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
    )(q, k, v, mask_col, do, lse_row, delta_row)
    if with_dq:
        return tuple(grads)
    dk, dv_ = grads

    kernel = functools.partial(_flash_bwd_dq_kernel, vis=vis, scale=scale)
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
    )(q, k, v, mask[:, None, :], do, lse[..., None], delta[..., None])
    return dq, dk, dv_


def _pallas_backward(bwd_impl: Optional[str]) -> bool:
    """Whether the backward runs the Pallas kernels: ``bwd_impl``, else
    the ``DL4J_FLASH_BWD`` override, else yes."""
    import os
    if bwd_impl is None:
        bwd_impl = os.environ.get("DL4J_FLASH_BWD", "pallas")
    return bwd_impl != "xla"


def _flash_bwd_rule(vis, block_q, block_k, interpret, bwd_impl, res, do):
    """Flash backward from saved (O, logsumexp) — dq/dk/dv Pallas kernels
    (``_flash_backward_pallas``: one launch where a head's dQ fits in
    VMEM, else two); P is recomputed from the normalizer instead of being
    saved. ``bwd_impl`` ("pallas"/"xla", the explicit
    flash_attention parameter) takes precedence; when None the
    ``DL4J_FLASH_BWD=xla`` env override selects the jnp/scan reference
    implementation (also used by equivalence tests). The env var is read
    at TRACE time — a jitted train step freezes the choice; call
    ``jax.clear_caches()`` after changing it (advisor r4: pass bwd_impl
    for programmatic control instead)."""
    q, k, v, mask, out, lse = res
    if _pallas_backward(bwd_impl):
        dq, dk, dv = _flash_backward_pallas(
            q, k, v, mask, out, lse, do, vis, block_q, block_k, interpret)
        return dq, dk, dv, jnp.zeros_like(mask)
    return _flash_bwd_xla(vis, block_q, block_k, interpret, res, do)


def _flash_bwd_xla(vis: Visibility, block_q, block_k, interpret, res, do):
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
        if vis != Visibility():         # something is hidden
            qpos = jnp.arange(tq)[:, None]
            kpos = kb * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where(vis.visible(qpos, kpos), s, _NEG)
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


def _default_blocks(head_dim: int, vis: Visibility = Visibility()):
    """The tile sizes for a head of ``head_dim``: the tuned 1024 x 1024
    while the dK/dV kernel's float32 working set fits Mosaic's scoped
    VMEM, else ``block_q`` halved until it does. That working set is two
    (block_q, block_k) score tiles and about 5.5 (block, head) tiles a
    side, whatever the inputs' type (the TPU compiler, 16 heads, T =
    8,192: 18.5 MB at 1024 x 1024 x 256 and 19.8 MB at 512 x 1024 x 512
    are refused; 1024 x 1024 x 128, 512 x 1024 x 256 and 256 x 1024 x 512
    compile). The tile is the two-launch backward's; the one-kernel
    backward keeps the same tile beside a head's whole dQ and asks for a
    scoped limit of its own (``_bwd_vmem_need``).

    ``vis`` may narrow the tile (``tile_side``). Under a causal window
    neither side of the tile is wider than the window (in whole lanes of
    128): a head of 64 with a window of 512 takes 512 x 512. A query
    block of ``block_q`` rows reaches ``block_q + window - 1`` keys and
    the kernel visits the whole key blocks that hold them, so a key block
    wider than the window wastes the skip: 1024 x 1024 would visit 2,048
    keys a row where 512 are wanted (4 times the work), 512 x 512 visits
    1,024 (twice), and narrower tiles (256 x 256: 768) pay more grid
    steps and half-filled matrix units for what they save. The
    block-diffusion layout keeps the tuned tile: at 8,192 positions a
    half 1024 x 1024 tiles visit 80 of 256 (31.3%, the pairs being 25.0%);
    512 x 512 would visit 28.1% in four times the grid steps."""
    block_q, block_k = _DEF_BLOCK_Q, _DEF_BLOCK_K
    side = vis.tile_side(block_k)
    if side != block_k:
        block_q = block_k = side

    def working_set(bq):
        return 4 * (2 * bq * block_k + 5.5 * (bq + block_k) * head_dim)

    while block_q > 128 and working_set(block_q) > _SCOPED_VMEM_DEFAULT:
        block_q //= 2
    return block_q, block_k


def flash_kv_blocks(tq: int, tk: int, block_q: int, block_k: int,
                    vis: Visibility):
    """``(visited, total)`` key blocks of one head's forward pass over the
    kernel's own grid: the (query block, key block) tiles it computes, by
    walking the grid as the kernel does, and all there are. A causal
    kernel skips the tiles above the diagonal; a windowed one also those
    wholly before the window; the block-diffusion one every tile but a
    noisy query block's diagonal and the clean key blocks at or before a
    query block's own."""
    nq, nk = -(-tq // block_q), -(-tk // block_k)
    visited, steps = 0, vis.kv_steps(nq, nk, block_q, block_k)
    for qi in range(nq):
        for kj in range(steps):
            ki, live = vis.kv_tile(qi, kj, block_q, block_k)
            visited += bool(live and ki < nk)
    return visited, nq * nk


FLASH_BLOCK_GAUGES = (
    ("dl4j_flash_kv_blocks_visited",
     "key blocks one head's flash-attention forward pass computes, by the "
     "kernel's grid, as the step was last traced (label: the named scope)"),
    ("dl4j_flash_kv_blocks_total",
     "key blocks times query blocks of that pass: what a kernel that "
     "skipped nothing would compute"),
)


FLASH_BWD_GAUGE = (
    "dl4j_flash_bwd_transposed",
    "1 where the scope's flash-attention backward runs the Pallas kernels, "
    "whose dK/dV tile is computed transposed (key rows by query columns), "
    "0 where it runs the scan reference; as the step was last traced")


def _publish_kv_blocks(scope: str, visited: int, total: int,
                       transposed: bool) -> None:
    from deeplearning4j_tpu.observe.registry import default_registry
    reg = default_registry()
    for (name, help_text), value in zip(
            FLASH_BLOCK_GAUGES + (FLASH_BWD_GAUGE,),
            (visited, total, int(transposed))):
        reg.gauge(name, help_text).set(value, scope=scope)


def _halves(x, seq_len: int, pad: int, axis: int):
    """``x``, whose ``axis`` holds ``[noisy | clean]`` of ``seq_len``
    each, with ``pad`` zeros after each half."""
    shape = x.shape
    x = x.reshape(shape[:axis] + (2, seq_len) + shape[axis + 1:])
    widths = [(0, 0)] * x.ndim
    widths[axis + 1] = (0, pad)
    x = jnp.pad(x, widths)
    return x.reshape(shape[:axis] + (2 * (seq_len + pad),)
                     + shape[axis + 1:])


def flash_attention(q, k, v, mask=None,
                    visibility: Visibility = Visibility(),
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    bwd_impl: Optional[str] = None,
                    scope: Optional[str] = None):
    """Blockwise (flash) attention on (N, T, H, Dh) tensors; ``v`` may
    have a head size of its own (N, T, H, Dv).

    Drop-in for nn.layers.attention.scaled_dot_product_attention. ``mask``
    is the (N, T_k) key-validity mask. Which keys a query sees is one
    value, ``visibility`` (``ops/visibility.py``): ``Visibility()`` every
    key, ``Causal()`` itself and what came before, ``Causal(window)`` of
    those itself and the ``window - 1`` before it, ``BlockDiffusion(T,
    B)`` the noisy and the clean copy of a sequence in one pass of ``2 T``
    positions. The tiles that hold no visible pair are not computed and, but for the plain causal
    kernel's, not fetched; a window of the whole sequence or more is the
    causal kernel.

    Sequences are padded to the block size internally (padding is masked
    out, query padding sliced off); under ``BlockDiffusion`` each half is
    padded. ``block_q`` / ``block_k`` default to ``_default_blocks`` of the
    head size and the visibility; under ``BlockDiffusion`` they are
    rounded to multiples of the block. ``interpret`` defaults to
    ``pallas_interpret()``. ``bwd_impl`` selects the backward
    implementation explicitly ("pallas" kernels or the "xla" jnp/scan
    reference); None defers to the ``DL4J_FLASH_BWD`` env override
    (default pallas). ``scope`` names the caller's ``jax.named_scope``:
    with it, the visited and total key blocks of the grid, and whether
    the backward's tile is the Pallas kernel's transposed one, are
    published as gauges when the call is traced.
    """
    if bwd_impl not in (None, "pallas", "xla"):
        raise ValueError(f"bwd_impl must be 'pallas'/'xla'/None, "
                         f"got {bwd_impl!r}")
    if interpret is None:
        interpret = pallas_interpret()
    vis = visibility
    n, tq, h, dh = q.shape
    tk = k.shape[1]
    if isinstance(vis, Causal) and vis.window is not None:
        if vis.window < 1 or tq != tk:
            raise ValueError(
                f"{vis} needs self-attention ({tq} queries on {tk} keys) "
                "and a window >= 1")
        if vis.window >= tk:
            vis = Causal()
    diffusion = isinstance(vis, BlockDiffusion)
    if diffusion and not tq == tk == 2 * vis.seq_len:
        raise ValueError(
            f"{vis} needs self-attention over 2 * seq_len positions; got "
            f"{tq} queries on {tk} keys")
    auto_q, auto_k = _default_blocks(max(dh, v.shape[-1]), vis)
    half_q, half_k = (tq // 2, tk // 2) if diffusion else (tq, tk)
    block_q = min(block_q or auto_q, max(half_q, 1))
    block_k = min(block_k or auto_k, max(half_k, 1))
    # Mosaic constraints: q blocks land in the sublane dim (multiple of
    # 8); the mask's dynamic k-slice is in the lane dim (multiple of
    # 128). Sequences are padded up to the block size below.
    unit_q, unit_k = (1, 1) if interpret else (8, 128)
    if diffusion:       # a tile cuts no block of the diffusion
        unit_q = math.lcm(unit_q, vis.block)
        unit_k = math.lcm(unit_k, vis.block)
    if not interpret or diffusion:
        block_q = max(unit_q, -(-block_q // unit_q) * unit_q)
        block_k = max(unit_k, -(-block_k // unit_k) * unit_k)

    # NTHD -> NHTD
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if mask is None:
        mask = jnp.ones((n, tk), jnp.float32)
    mask = mask.astype(jnp.float32)

    if diffusion:
        # both tiles divide a half: each half is padded to their multiple
        t = vis.seq_len
        pad = _pad_len(t, math.lcm(block_q, block_k))
        if pad:
            qt, kt, vt = (_halves(a, t, pad, 2) for a in (qt, kt, vt))
            mask = _halves(mask, t, pad, 1)
            vis = BlockDiffusion(t + pad, vis.block)
    else:
        pq, pk = _pad_len(tq, block_q), _pad_len(tk, block_k)
        if pq:
            qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
        if pk:
            kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
            vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
            mask = jnp.pad(mask, ((0, 0), (0, pk)))
    if scope is not None:
        _publish_kv_blocks(scope, *flash_kv_blocks(
            qt.shape[2], kt.shape[2], block_q, block_k, vis),
            transposed=_pallas_backward(bwd_impl))

    out = _flash_attention(qt, kt, vt, mask, vis, block_q, block_k,
                           interpret, bwd_impl)
    if diffusion:
        if pad:
            out = out.reshape(n, h, 2, t + pad, -1)[:, :, :, :t].reshape(
                n, h, 2 * t, -1)
    elif pq:
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


def attention(q, k, v, mask=None, visibility: Visibility = Visibility(),
              prefer_flash: Optional[bool] = None,
              scope: Optional[str] = None):
    """Helper-SPI dispatch (the reflective cuDNN-hook analog): the
    Pallas kernel on TPU when the sequence is long enough to pay for
    streaming (``flash_attention`` pads to its block size, so any length
    is block-aligned), else the plain XLA lowering. The choice rests on
    the inputs alone; a kernel the compiler refuses raises. Which keys a
    query sees (``visibility``, ``flash_attention``'s) is honoured by
    both; ``scope`` is ``flash_attention``'s."""
    from deeplearning4j_tpu.nn.layers.attention import (
        scaled_dot_product_attention)
    if prefer_flash is None:
        prefer_flash = (jax.default_backend() == "tpu"
                        and max(q.shape[1], k.shape[1]) >= _FLASH_MIN_SEQ)
    if prefer_flash:
        return flash_attention(q, k, v, mask=mask, visibility=visibility,
                               scope=scope)
    return scaled_dot_product_attention(q, k, v, mask=mask,
                                        visibility=visibility)
