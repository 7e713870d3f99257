"""Fused conv+BN+ReLU Pallas kernels for ResNet bottleneck blocks.

The accelerated-helper tier for the conv stack (reference concept: the
cuDNN per-layer helpers, CudnnConvolutionHelper.java:62 — SURVEY §2.4).
The measured ResNet50 64×64 step is HBM-bandwidth-bound
(PERF_ANALYSIS.md): XLA computes each BatchNormalization's batch
statistics in a separate pass over the conv output and applies
normalize+ReLU in another, so every activation crosses HBM ~3 extra
times per BN. benchmarks/bn_ceiling.py quantifies the ceiling: freezing
BN stats (pure elementwise) lifts 39.3k → 48.2k img/s/chip.

Design — two fusions per conv layer, both riding the one HBM pass the
conv already pays:
  * prologue: the normalize+ReLU of the PRODUCER's BatchNorm is applied
    to the input tile in VMEM right after load (BN normalize is just a
    per-channel scale+shift once stats are known), so the normalized
    activation is never materialized in HBM;
  * epilogue: per-channel (Σy, Σy²) of the conv output are accumulated
    while the output tile is still in VMEM, so the consumer's BN stats
    pass never re-reads y.

BN autodiff falls out for free: the kernels return (y, Σy, Σy²) and the
surrounding jnp code derives mean/var from the sums — the custom VJP
routes ``d(Σy)``/``d(Σy²)`` cotangents back into dy (broadcast + 2y·d),
so batch-stat gradients match jax.grad of the unfused math exactly.

1×1 convs (two of the three in every bottleneck) are matmuls over the
flattened (N·H·W, C) activation; the 3×3 runs per-image with the whole
(small) spatial plane resident in VMEM as 9 shifted matmuls. Both shapes
keep the MXU busy: at 64×64 inputs the spatial planes are tiny and the
channel counts large, exactly the regime where conv == matmul.

The Pallas path compiles on TPU and runs in interpret mode on the CPU
(``pallas_kernels.pallas_interpret``), so CPU CI exercises the same
kernel code.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas_kernels import (
    SCOPED_VMEM_CAP, pallas_interpret, scoped_vmem_limit)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


# ---------------------------------------------------------------------------
# fused matmul (1×1 conv): y = relu?(x·s + b) @ W, + per-channel stats of y
# ---------------------------------------------------------------------------

def _mm_kernel(x_ref, w_ref, s_ref, b_ref, y_ref, st_ref, *,
               relu_in: bool, want_stats: bool, norm_in: bool,
               m_valid: int, bm: int):
    i = pl.program_id(1)                       # M tile (inner)
    x = x_ref[...]
    if norm_in:
        e = x.astype(jnp.float32) * s_ref[0] + b_ref[0]
        if relu_in:
            e = jnp.maximum(e, 0.0)
        e = e.astype(x_ref.dtype)
    else:
        e = x
    y = jnp.dot(e, w_ref[...],
                preferred_element_type=jnp.float32)       # (bm, bn)
    y_ref[...] = y.astype(y_ref.dtype)
    if want_stats:
        # rows beyond m_valid are padding: relu(0·s+b) is non-zero, so
        # mask them out of the stats (their y rows are sliced off by the
        # caller anyway)
        row = i * bm + lax.broadcasted_iota(jnp.int32, y.shape, 0)
        yv = jnp.where(row < m_valid, y, 0.0)
        st_ref[0, 0] = jnp.sum(yv, axis=0)
        st_ref[0, 1] = jnp.sum(yv * yv, axis=0)


def _mm_pallas(x2d, w, scale, shift, relu_in: bool, want_stats: bool,
               norm_in: bool, interpret: bool,
               out_dtype) -> Tuple[jax.Array, jax.Array]:
    m, cin = x2d.shape
    cout = w.shape[1]
    bm = min(1024, _round_up(m, 8))
    bn = min(512, cout)
    mp = _round_up(m, bm)
    if mp != m:
        x2d = jnp.pad(x2d, ((0, mp - m), (0, 0)))
    nm, nn = mp // bm, -(-cout // bn)
    kernel = functools.partial(
        _mm_kernel, relu_in=relu_in, want_stats=want_stats,
        norm_in=norm_in, m_valid=m, bm=bm)
    y, st = pl.pallas_call(
        kernel,
        grid=(nn, nm),                        # M innermost
        in_specs=[
            pl.BlockSpec((bm, cin), lambda j, i: (i, 0)),
            pl.BlockSpec((cin, bn), lambda j, i: (0, j)),
            pl.BlockSpec((1, cin), lambda j, i: (0, 0)),
            pl.BlockSpec((1, cin), lambda j, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            # per-(i,j) partial stats; reduced over i by the caller
            pl.BlockSpec((1, 2, bn), lambda j, i: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, cout), out_dtype),
            jax.ShapeDtypeStruct((nm, 2, cout), jnp.float32),
        ],
        interpret=interpret,
    )(x2d, w, scale[None, :], shift[None, :])
    if mp != m:
        y = y[:m]
    stats = jnp.sum(st, axis=0) if want_stats else None
    return y, stats


# ---------------------------------------------------------------------------
# fused 3×3 SAME conv: y = conv3x3(relu?(x·s + b)) + stats, per-image planes
# ---------------------------------------------------------------------------

def _c3_images_per_program(n: int, h: int, wd: int, cin: int,
                           itemsize: int = 2) -> int:
    """Images per grid program: enough for ~2k matmul rows (small planes
    would leave the MXU pipeline empty), capped so the padded input
    plane (``itemsize`` bytes/element — f32 planes cost 2× bf16, advisor
    r4) stays ≈1.5 MB of VMEM, and dividing the batch."""
    cap = max(1, int(1.5e6 / ((h + 2) * (wd + 2) * cin * itemsize)))
    bi = max(1, min(n, 2048 // max(1, h * wd), cap))
    while n % bi:
        bi -= 1
    return bi


def _c3_vmem_bound(bi: int, h: int, wd: int, cin: int, cout: int,
                   itemsize: int) -> int:
    """Upper bound on the scoped VMEM any 3×3 kernel (forward, merged or
    split backward) asks Mosaic for at ``bi`` images per program: every
    block double-buffered and every full-size intermediate of the kernel
    body live at once, each padded to the (sublane, 128-lane) tile.

    Checked against the compiler on a v5e (PR 21): every ResNet-50 3×3
    at 64×64 and 224×224 inputs, bf16 and f32, compiles with this bound
    as its limit. It is 2–4× what Mosaic reports using (16–27 MiB where
    the default 16 MiB limit refused the backward: 7×7×512 in both
    dtypes, 56×56×64 f32, 112×112×64 bf16) — loose on purpose, the limit
    is a cap and not an allocation."""
    def tile(lead: int, rows: int, c: int, size: int) -> int:
        return (lead * _round_up(rows, 32 // size) * _round_up(c, 128)
                * size)

    def act(c, size):           # one (bi, h, w, c) activation
        return tile(bi * h, wd, c, size)

    def padded(c, size):        # its zero-padded (h+2, w+2) copy
        return tile(bi * (h + 2), wd + 2, c, size)

    f32 = 4
    blocks = 2 * (act(cout, itemsize) + act(cin, itemsize))   # dy y x dx
    weights = tile(1, 9 * cout, cin, itemsize) + tile(1, 9 * cin, cout, f32)
    temps = (3 * act(cout, f32) + padded(cout, itemsize)
             + 5 * act(cin, f32) + padded(cin, itemsize)
             + 2 * act(max(cin, cout), itemsize))
    return 2 * (blocks + weights) + temps


def _c3_params(bi, h, wd, cin, cout, itemsize):
    """Compiler params raising the scoped-VMEM limit to the bound."""
    return pltpu.CompilerParams(vmem_limit_bytes=scoped_vmem_limit(
        _c3_vmem_bound(bi, h, wd, cin, cout, itemsize)))


def _c3_fits_vmem(h: int, wd: int, cin: int, cout: int,
                  itemsize: int = 2) -> bool:
    """Whether a single-image 3×3 program fits VMEM, by the same bound
    the kernels pass Mosaic as their limit — so what this admits is what
    the compiler is asked to accept. Beyond it (ImageNet-size planes,
    e.g. 224×224×64) the op runs the XLA reference math, forward and
    backward. `itemsize` is the compute dtype's bytes/element."""
    return _c3_vmem_bound(1, h, wd, cin, cout, itemsize) <= SCOPED_VMEM_CAP


def _c3_kernel(x_ref, w_ref, s_ref, b_ref, y_ref, st_ref, *,
               relu_in: bool, want_stats: bool, norm_in: bool, h: int,
               wdt: int):
    if norm_in:
        x = x_ref[...].astype(jnp.float32)             # (bi, h, w, cin)
        e = x * s_ref[0, 0, 0] + b_ref[0, 0, 0]
        if relu_in:
            e = jnp.maximum(e, 0.0)
        e = e.astype(w_ref.dtype)
    else:
        e = x_ref[...].astype(w_ref.dtype)
    bi = e.shape[0]
    cin = e.shape[3]
    ep = jnp.pad(e, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros((bi * h * wdt, y_ref.shape[3]), jnp.float32)
    for di in range(3):
        for dj in range(3):
            tap = ep[:, di:di + h, dj:dj + wdt, :].reshape(-1, cin)
            acc = acc + jnp.dot(tap, w_ref[di, dj],
                                preferred_element_type=jnp.float32)
    y_ref[...] = acc.reshape(bi, h, wdt, -1).astype(y_ref.dtype)
    if want_stats:
        st_ref[0, 0] = jnp.sum(acc, axis=0)
        st_ref[0, 1] = jnp.sum(acc * acc, axis=0)


def _c3_pallas(x4d, w, scale, shift, relu_in: bool, want_stats: bool,
               norm_in: bool, interpret: bool,
               out_dtype) -> Tuple[jax.Array, jax.Array]:
    n, h, wd, cin = x4d.shape
    cout = w.shape[3]
    itemsize = max(x4d.dtype.itemsize, w.dtype.itemsize)
    bi = _c3_images_per_program(n, h, wd, cin, itemsize)
    bn = min(512, cout)
    ni, nn = n // bi, -(-cout // bn)
    kernel = functools.partial(_c3_kernel, relu_in=relu_in,
                               want_stats=want_stats, norm_in=norm_in,
                               h=h, wdt=wd)
    y, st = pl.pallas_call(
        kernel,
        grid=(nn, ni),
        in_specs=[
            pl.BlockSpec((bi, h, wd, cin), lambda j, i: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, cin, bn), lambda j, i: (0, 0, 0, j)),
            pl.BlockSpec((1, 1, 1, cin), lambda j, i: (0, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, cin), lambda j, i: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bi, h, wd, bn), lambda j, i: (i, 0, 0, j)),
            pl.BlockSpec((1, 2, bn), lambda j, i: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, wd, cout), out_dtype),
            jax.ShapeDtypeStruct((ni, 2, cout), jnp.float32),
        ],
        compiler_params=_c3_params(bi, h, wd, cin, cout, itemsize),
        interpret=interpret,
    )(x4d, w, scale[None, None, None, :], shift[None, None, None, :])
    stats = jnp.sum(st, axis=0) if want_stats else None
    return y, stats


# ---------------------------------------------------------------------------
# backward kernels. All matmul-shaped work stays in Pallas: if any saved
# activation fed an XLA dot/conv, XLA would assign it that op's preferred
# (convolution) layout and insert relayout copies around every forward
# kernel — measured at +2 GB/step before these existed.
# ---------------------------------------------------------------------------

def _dyc(dy_ref, y_ref, a_ref, b_ref):
    """Total output cotangent: dy + dΣ + 2·y·dΣ² (stats chain rule)."""
    return (dy_ref[...].astype(jnp.float32) + a_ref[0]
            + 2.0 * y_ref[...].astype(jnp.float32) * b_ref[0])


def _bwd_merged_kernel(dy_ref, y_ref, wt_ref, x_ref, a_ref, b2_ref,
                       s_ref, sh_ref, dx_ref, dw_ref, st_ref, *,
                       relu_in: bool, norm_in: bool, m_valid: int,
                       bm: int):
    """Single pass over (dy, y, x): emits BOTH dx (per M tile) and the
    dW accumulation — the split dx/dW kernels each re-read the same
    dy/y/x streams, doubling backward HBM traffic."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dyc = _dyc(dy_ref, y_ref, a_ref, b2_ref)
    row = k * bm + lax.broadcasted_iota(jnp.int32, dyc.shape, 0)
    dyc = jnp.where(row < m_valid, dyc, 0.0).astype(dy_ref.dtype)
    de = jnp.dot(dyc, wt_ref[...],
                 preferred_element_type=jnp.float32)      # (bm, bci)
    xf = x_ref[...].astype(jnp.float32)
    if norm_in:
        s = s_ref[0]
        pre = xf * s + sh_ref[0]
        e = (jnp.maximum(pre, 0.0) if relu_in else pre) \
            .astype(x_ref.dtype)
        dpre = jnp.where(pre > 0.0, de, 0.0) if relu_in else de
        st_ref[0, 0] = jnp.sum(dpre * xf, axis=0)
        st_ref[0, 1] = jnp.sum(dpre, axis=0)
        dx_ref[...] = (dpre * s).astype(dx_ref.dtype)
    else:
        rowx = k * bm + lax.broadcasted_iota(jnp.int32, xf.shape, 0)
        e = jnp.where(rowx < m_valid, xf, 0.0).astype(x_ref.dtype)
        st_ref[0, 0] = jnp.zeros_like(st_ref[0, 0])
        st_ref[0, 1] = jnp.zeros_like(st_ref[0, 1])
        dx_ref[...] = de.astype(dx_ref.dtype)
    dw_ref[...] += lax.dot_general(
        e, dyc, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_merged_pallas(dy2, y2, wt, x2, dst, scale, shift, relu_in,
                       norm_in, interpret, out_dtype):
    m, cout = dy2.shape
    cin = wt.shape[1]
    # Co=2048 layers: halve the M tile so the f32 dyc temporary + the
    # full dW accumulator stay inside VMEM
    bm = min(512 if cout <= 1024 else 256, _round_up(m, 8))
    bci = min(512, cin)
    mp = _round_up(m, bm)
    if mp != m:
        dy2 = jnp.pad(dy2, ((0, mp - m), (0, 0)))
        y2 = jnp.pad(y2, ((0, mp - m), (0, 0)))
        x2 = jnp.pad(x2, ((0, mp - m), (0, 0)))
    nm, nci = mp // bm, -(-cin // bci)
    kernel = functools.partial(_bwd_merged_kernel, relu_in=relu_in,
                               norm_in=norm_in, m_valid=m, bm=bm)
    dx, dw, st = pl.pallas_call(
        kernel,
        grid=(nci, nm),
        in_specs=[
            pl.BlockSpec((bm, cout), lambda i, k: (k, 0)),
            pl.BlockSpec((bm, cout), lambda i, k: (k, 0)),
            pl.BlockSpec((cout, bci), lambda i, k: (0, i)),
            pl.BlockSpec((bm, bci), lambda i, k: (k, i)),
            pl.BlockSpec((1, cout), lambda i, k: (0, 0)),
            pl.BlockSpec((1, cout), lambda i, k: (0, 0)),
            pl.BlockSpec((1, bci), lambda i, k: (0, i)),
            pl.BlockSpec((1, bci), lambda i, k: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bci), lambda i, k: (k, i)),
            pl.BlockSpec((bci, cout), lambda i, k: (i, 0)),
            pl.BlockSpec((1, 2, bci), lambda i, k: (k, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, cin), out_dtype),
            jax.ShapeDtypeStruct((cin, cout), jnp.float32),
            jax.ShapeDtypeStruct((nm, 2, cin), jnp.float32),
        ],
        interpret=interpret,
    )(dy2, y2, wt, x2, dst[0][None, :], dst[1][None, :],
      scale[None, :], shift[None, :])
    if mp != m:
        dx = dx[:m]
    st = jnp.sum(st, axis=0)
    return dx, dw, st[0], st[1]


def _c3_bwd_in_kernel(dy_ref, y_ref, wt_ref, x_ref, a_ref, b_ref, s_ref,
                      sh_ref, dx_ref, st_ref, *, relu_in: bool,
                      norm_in: bool, h: int, wdt: int):
    """3×3 SAME bwd-input: de = conv(dyc, flip(W)ᵀ), then BN/ReLU bwd."""
    dyc = (dy_ref[...].astype(jnp.float32) + a_ref[0, 0, 0]
           + 2.0 * y_ref[...].astype(jnp.float32) * b_ref[0, 0, 0])
    dyc = dyc.astype(dy_ref.dtype)
    bi = dyc.shape[0]
    cout = dyc.shape[3]
    dp = jnp.pad(dyc, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros((bi * h * wdt, dx_ref.shape[3]), jnp.float32)
    for di in range(3):
        for dj in range(3):
            tap = dp[:, di:di + h, dj:dj + wdt, :].reshape(-1, cout)
            acc = acc + jnp.dot(tap, wt_ref[di, dj],
                                preferred_element_type=jnp.float32)
    de = acc.reshape(bi, h, wdt, -1)
    if norm_in:
        xf = x_ref[...].astype(jnp.float32)
        s = s_ref[0, 0, 0]
        pre = xf * s + sh_ref[0, 0, 0]
        dpre = jnp.where(pre > 0.0, de, 0.0) if relu_in else de
        st_ref[0, 0] = jnp.sum(dpre * xf, axis=(0, 1, 2))
        st_ref[0, 1] = jnp.sum(dpre, axis=(0, 1, 2))
        dx_ref[...] = (dpre * s).astype(dx_ref.dtype)
    else:
        st_ref[0, 0] = jnp.zeros_like(st_ref[0, 0])
        st_ref[0, 1] = jnp.zeros_like(st_ref[0, 1])
        dx_ref[...] = de.astype(dx_ref.dtype)


def _c3_bwd_w_kernel(x_ref, dy_ref, y_ref, s_ref, b_ref, a_ref, b2_ref,
                     dw_ref, *, relu_in: bool, norm_in: bool, h: int,
                     wdt: int):
    """3×3 bwd-filter: dW[t] += shifted(e)ᵀ @ dyc, per tap."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    if norm_in:
        e = x_ref[...].astype(jnp.float32) * s_ref[0, 0, 0] \
            + b_ref[0, 0, 0]
        if relu_in:
            e = jnp.maximum(e, 0.0)
        e = e.astype(x_ref.dtype)
    else:
        e = x_ref[...]
    dyc = (dy_ref[...].astype(jnp.float32) + a_ref[0, 0, 0]
           + 2.0 * y_ref[...].astype(jnp.float32) * b2_ref[0, 0, 0])
    dyc = dyc.astype(dy_ref.dtype).reshape(-1, dy_ref.shape[3])
    cin = e.shape[3]
    ep = jnp.pad(e, ((0, 0), (1, 1), (1, 1), (0, 0)))
    for di in range(3):
        for dj in range(3):
            tap = ep[:, di:di + h, dj:dj + wdt, :].reshape(-1, cin)
            dw_ref[di, dj] += lax.dot_general(
                tap, dyc, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


def _c3_bwd_merged_kernel(dy_ref, y_ref, wt_ref, x_ref, a_ref, b_ref,
                          s_ref, sh_ref, dx_ref, dw_ref, st_ref, *,
                          relu_in: bool, h: int, wdt: int):
    """3×3 merged backward (one pass over dy/y/x): dx via 9 taps of the
    flipped-transposed filter, dW accumulated per tap, BN/ReLU backward
    in the epilogue."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dyc = (dy_ref[...].astype(jnp.float32) + a_ref[0, 0, 0]
           + 2.0 * y_ref[...].astype(jnp.float32) * b_ref[0, 0, 0])
    dyc = dyc.astype(dy_ref.dtype)
    bi = dyc.shape[0]
    cout = dyc.shape[3]
    cin = x_ref.shape[3]
    xf = x_ref[...].astype(jnp.float32)
    s = s_ref[0, 0, 0]
    pre = xf * s + sh_ref[0, 0, 0]
    e = (jnp.maximum(pre, 0.0) if relu_in else pre).astype(x_ref.dtype)

    dp = jnp.pad(dyc, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros((bi * h * wdt, cin), jnp.float32)
    for di in range(3):
        for dj in range(3):
            tap = dp[:, di:di + h, dj:dj + wdt, :].reshape(-1, cout)
            acc = acc + jnp.dot(tap, wt_ref[di, dj],
                                preferred_element_type=jnp.float32)
    de = acc.reshape(bi, h, wdt, cin)
    dpre = jnp.where(pre > 0.0, de, 0.0) if relu_in else de
    st_ref[0, 0] = jnp.sum(dpre * xf, axis=(0, 1, 2))
    st_ref[0, 1] = jnp.sum(dpre, axis=(0, 1, 2))
    dx_ref[...] = (dpre * s).astype(dx_ref.dtype)

    dyc2 = dyc.reshape(-1, cout)
    ep = jnp.pad(e, ((0, 0), (1, 1), (1, 1), (0, 0)))
    for di in range(3):
        for dj in range(3):
            tap = ep[:, di:di + h, dj:dj + wdt, :].reshape(-1, cin)
            dw_ref[di, dj] += lax.dot_general(
                tap, dyc2, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


def _c3_bwd_merged_pallas(x, dy, y, w, dst, scale, shift, relu_in,
                          interpret, out_dtype):
    n, h, wd, cin = x.shape
    cout = dy.shape[3]
    itemsize = max(x.dtype.itemsize, w.dtype.itemsize)
    bi = _c3_images_per_program(n, h, wd, cin, itemsize)
    ni = n // bi
    wt = w[::-1, ::-1].transpose(0, 1, 3, 2)
    a4 = dst[0][None, None, None, :]
    b4 = dst[1][None, None, None, :]
    s4 = scale[None, None, None, :]
    sh4 = shift[None, None, None, :]
    kernel = functools.partial(_c3_bwd_merged_kernel, relu_in=relu_in,
                               h=h, wdt=wd)
    dx, dw, st = pl.pallas_call(
        kernel,
        grid=(ni,),
        in_specs=[
            pl.BlockSpec((bi, h, wd, cout), lambda k: (k, 0, 0, 0)),
            pl.BlockSpec((bi, h, wd, cout), lambda k: (k, 0, 0, 0)),
            pl.BlockSpec((3, 3, cout, cin), lambda k: (0, 0, 0, 0)),
            pl.BlockSpec((bi, h, wd, cin), lambda k: (k, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, cout), lambda k: (0, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, cout), lambda k: (0, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, cin), lambda k: (0, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, cin), lambda k: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bi, h, wd, cin), lambda k: (k, 0, 0, 0)),
            pl.BlockSpec((3, 3, cin, cout), lambda k: (0, 0, 0, 0)),
            pl.BlockSpec((1, 2, cin), lambda k: (k, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, wd, cin), out_dtype),
            jax.ShapeDtypeStruct((3, 3, cin, cout), jnp.float32),
            jax.ShapeDtypeStruct((ni, 2, cin), jnp.float32),
        ],
        compiler_params=_c3_params(bi, h, wd, cin, cout, itemsize),
        interpret=interpret,
    )(dy, y, wt, x, a4, b4, s4, sh4)
    st = jnp.sum(st, axis=0)
    return dx, dw, st[0], st[1]


def _c3_bwd_pallas(x, dy, y, w, dst, scale, shift, relu_in, norm_in,
                   interpret, out_dtype):
    n, h, wd, cin = x.shape
    cout = dy.shape[3]
    itemsize = max(x.dtype.itemsize, w.dtype.itemsize)
    bi = _c3_images_per_program(n, h, wd, cin, itemsize)
    params = _c3_params(bi, h, wd, cin, cout, itemsize)
    ni = n // bi
    bci = min(512, cin)
    wt = w[::-1, ::-1].transpose(0, 1, 3, 2)       # flip + IO swap
    a4 = dst[0][None, None, None, :]
    b4 = dst[1][None, None, None, :]
    s4 = scale[None, None, None, :]
    sh4 = shift[None, None, None, :]

    kin = functools.partial(_c3_bwd_in_kernel, relu_in=relu_in,
                            norm_in=norm_in, h=h, wdt=wd)
    dx, st = pl.pallas_call(
        kin,
        grid=(-(-cin // bci), ni),
        in_specs=[
            pl.BlockSpec((bi, h, wd, cout), lambda i, k: (k, 0, 0, 0)),
            pl.BlockSpec((bi, h, wd, cout), lambda i, k: (k, 0, 0, 0)),
            pl.BlockSpec((3, 3, cout, bci), lambda i, k: (0, 0, 0, i)),
            pl.BlockSpec((bi, h, wd, bci), lambda i, k: (k, 0, 0, i)),
            pl.BlockSpec((1, 1, 1, cout), lambda i, k: (0, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, cout), lambda i, k: (0, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, bci), lambda i, k: (0, 0, 0, i)),
            pl.BlockSpec((1, 1, 1, bci), lambda i, k: (0, 0, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((bi, h, wd, bci), lambda i, k: (k, 0, 0, i)),
            pl.BlockSpec((1, 2, bci), lambda i, k: (k, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, wd, cin), out_dtype),
            jax.ShapeDtypeStruct((ni, 2, cin), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(dy, y, wt, x, a4, b4, s4, sh4)
    st = jnp.sum(st, axis=0)

    bco = min(256, cout)
    kw = functools.partial(_c3_bwd_w_kernel, relu_in=relu_in,
                           norm_in=norm_in, h=h, wdt=wd)
    dw = pl.pallas_call(
        kw,
        grid=(-(-cout // bco), ni),
        in_specs=[
            pl.BlockSpec((bi, h, wd, cin), lambda j, k: (k, 0, 0, 0)),
            pl.BlockSpec((bi, h, wd, bco), lambda j, k: (k, 0, 0, j)),
            pl.BlockSpec((bi, h, wd, bco), lambda j, k: (k, 0, 0, j)),
            pl.BlockSpec((1, 1, 1, cin), lambda j, k: (0, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, cin), lambda j, k: (0, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1, bco), lambda j, k: (0, 0, 0, j)),
            pl.BlockSpec((1, 1, 1, bco), lambda j, k: (0, 0, 0, j)),
        ],
        out_specs=pl.BlockSpec((3, 3, cin, bco),
                               lambda j, k: (0, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((3, 3, cin, cout), jnp.float32),
        compiler_params=params,
        interpret=interpret,
    )(x, dy, y, s4, sh4, a4, b4)
    return dx, dw, st[0], st[1]


# ---------------------------------------------------------------------------
# reference math (XLA path; also the VJP recompute)
# ---------------------------------------------------------------------------

def _norm_in(x, scale, shift, relu_in: bool, norm_in: bool):
    if not norm_in:
        return x
    e = x.astype(jnp.float32) * scale + shift
    if relu_in:
        e = jnp.maximum(e, 0.0)
    return e.astype(x.dtype)


def _conv_reference(x, w, scale, shift, relu_in, norm_in, stride):
    e = _norm_in(x, scale, shift, relu_in, norm_in)
    if w.ndim == 2:                                     # 1×1
        if stride != 1:
            e = e[:, ::stride, ::stride, :]
        y = jnp.einsum("nhwc,co->nhwo", e, w,
                       preferred_element_type=jnp.float32)
    else:                                               # 3×3 SAME, stride 1
        y = lax.conv_general_dilated(
            e, w, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)
    sums = jnp.stack([jnp.sum(y, axis=(0, 1, 2)),
                      jnp.sum(y * y, axis=(0, 1, 2))])
    return y.astype(x.dtype), sums


@jax.custom_vjp
def _gram(e):
    """G = eᵀe over all leading axes, f32-accumulated from the compute
    dtype (bf16 on the MXU). The custom VJP exists because einsum with
    ``preferred_element_type=f32`` cannot be transposed by autodiff (an
    f32 cotangent against bf16 operands); de = e·(dG + dGᵀ) is the
    exact gradient."""
    return jnp.einsum("nhwa,nhwb->ab", e, e,
                      preferred_element_type=jnp.float32)


def _gram_fwd(e):
    return _gram(e), e


def _gram_bwd(e, dg):
    d = (dg + dg.T).astype(e.dtype)
    return (jnp.einsum("ab,nhwb->nhwa", d, e),)


_gram.defvjp(_gram_fwd, _gram_bwd)


def conv_bn_stats_xla(x, w, scale, shift, relu_in: bool = True,
                      norm_in: bool = True, stride: int = 1,
                      interpret=None):
    """XLA-native sibling of ``fused_conv_bn_act`` — same
    ``(y, (Σy, Σy²))`` contract, plain jnp ops (no custom calls, no
    custom VJP), with **Gram-matrix statistics** for expanding 1×1
    convs (round 4, the measured XLA-side replacement VERDICT r3 #1
    allows):

    For ``y = e @ W``:  ``Σᵢ yᵢ = (Σᵢ eᵢ) @ W``  and
    ``Σᵢ yᵢ² = diag(Wᵀ (eᵀe) W)`` — so the batch statistics of the
    OUTPUT are computed from the (smaller) input side plus a
    weights-sized contraction, and XLA never re-reads the Cout-sized
    activation for a stats pass. Worth it exactly when Cout > Cin (the
    bottleneck's expand and downsample projections — the 4f-channel
    activations that dominate BN-stat traffic); other convs use the
    direct reduction, which autodiff also differentiates exactly.
    ``interpret`` is accepted and ignored (signature parity)."""
    e = _norm_in(x, scale, shift, relu_in, norm_in)
    f32 = jnp.float32
    w = w.astype(e.dtype)       # compute-dtype matmul/conv (MXU bf16)
    if w.ndim == 2:
        if stride != 1:
            e = e[:, ::stride, ::stride, :]
        n, h, wd, cin = e.shape
        cout = w.shape[1]
        # 4-D einsum, NOT a reshape-to-2D matmul: the flatten forces a
        # physical relayout between conv-tiled and matmul-tiled forms
        # (measured −8k img/s on the ResNet50 step). No
        # preferred_element_type — its transpose rule would pair an f32
        # cotangent with the bf16 weights and fail to differentiate.
        y = jnp.einsum("nhwc,co->nhwo", e, w)
        import os
        # DL4J_GRAM / DL4J_GRAM_T are read at TRACE time: a jitted step
        # freezes the choice — call jax.clear_caches() after changing
        # them (they exist for benchmarking sweeps, not runtime toggles)
        mode = os.environ.get("DL4J_GRAM", "auto")
        # The Gram pays an M·cin² MXU contraction to avoid an
        # M·cout·2-byte stat read. The naive roofline (bf16 183 TF/s vs
        # 819 GB/s) suggests profit until cin² ≈ 450·cout, but measured
        # e2e the wide-cin stages give the win back (T=400 → 41.4k vs
        # T=64 → 43.5-45.2k img/s — PERF_ANALYSIS.md r4): the direct
        # stat reductions XLA fuses for those stages are cheaper than
        # the extra contraction. 64 is the measured optimum.
        thresh = float(os.environ.get("DL4J_GRAM_T", "64"))  # host-sync-ok: env var
        use_gram = (mode == "always" or
                    (mode == "auto" and cout > cin
                     and cin * cin <= thresh * cout))
        if use_gram:
            wf = w.astype(f32)
            gram = _gram(e)
            colsum = jnp.sum(e.astype(f32), axis=(0, 1, 2))
            s1 = colsum @ wf
            s2 = jnp.einsum("ac,ab,bc->c", wf, gram, wf)
            sums = jnp.stack([s1, s2])
        else:
            yf = y.astype(f32)
            sums = jnp.stack([jnp.sum(yf, axis=(0, 1, 2)),
                              jnp.sum(yf * yf, axis=(0, 1, 2))])
        return y.astype(x.dtype), sums
    y = lax.conv_general_dilated(
        e, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    yf = y.astype(f32)
    sums = jnp.stack([jnp.sum(yf, axis=(0, 1, 2)),
                      jnp.sum(yf * yf, axis=(0, 1, 2))])
    return y.astype(x.dtype), sums


# ---------------------------------------------------------------------------
# public op: custom VJP, pallas fwd / XLA bwd
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def fused_conv_bn_act(x, w, scale, shift, relu_in: bool = True,
                      norm_in: bool = True, stride: int = 1,
                      interpret: Optional[bool] = None):
    """y = conv(relu?(x·scale + shift)) ⊕ per-channel (Σy, Σy²).

    ``w`` (Cin, Cout) selects the 1×1 matmul path (with optional spatial
    ``stride``); ``w`` (3, 3, Cin, Cout) the SAME 3×3 path. Returns
    ``(y, stats)`` with ``stats`` float32 (2, Cout). The stats output is
    differentiable, which is what makes the surrounding BatchNorm's
    batch-statistics gradient exact."""
    y, st = _fused_fwd_impl(x, w, scale, shift, relu_in, norm_in, stride,
                            interpret)
    return y, st


def _fused_fwd_impl(x, w, scale, shift, relu_in, norm_in, stride,
                    interpret):
    if interpret is None:
        interpret = pallas_interpret()
    if w.ndim == 2:
        if stride != 1:
            x = x[:, ::stride, ::stride, :]
        n, h, wd, cin = x.shape
        y2, st = _mm_pallas(x.reshape(-1, cin), w, scale, shift, relu_in,
                            True, norm_in, interpret, x.dtype)
        return y2.reshape(n, h, wd, -1), st
    n, h, wd, cin = x.shape
    if not _c3_fits_vmem(h, wd, cin, w.shape[3],
                         max(x.dtype.itemsize, w.dtype.itemsize)):
        return _conv_reference(x, w, scale, shift, relu_in, norm_in, 1)
    return _c3_pallas(x, w, scale, shift, relu_in, True, norm_in,
                      interpret, x.dtype)


def _fused_fwd_rule(x, w, scale, shift, relu_in, norm_in, stride,
                    interpret):
    y, st = _fused_fwd_impl(x, w, scale, shift, relu_in, norm_in, stride,
                            interpret)
    return (y, st), (x, w, scale, shift, y)


def _fused_bwd_rule(relu_in, norm_in, stride, interpret, res, cots):
    """Pallas backward: the normalized input is recomputed tile-wise
    (flash-style — it was never materialized), the stats cotangents fold
    into dy inside the kernels, and the BN/ReLU backward (mask, dγ/dβ
    sums, input rescale) rides the bwd-input matmul's epilogue. Keeping
    the backward matmuls in Pallas matters beyond the fusion itself: if
    a saved activation fed an XLA dot/conv, XLA would assign it that
    op's preferred layout and relayout-copy around every forward
    kernel."""
    x, w, scale, shift, y = res
    dy, dst = cots
    if interpret is None:
        interpret = pallas_interpret()
    if dst is None:
        dst = jnp.zeros((2, y.shape[-1]), jnp.float32)
    dst = dst.astype(jnp.float32)

    xs = x[:, ::stride, ::stride, :] if (w.ndim == 2 and stride != 1) \
        else x
    cin = xs.shape[-1]
    cout = y.shape[-1]

    if w.ndim == 4 and not _c3_fits_vmem(
            xs.shape[1], xs.shape[2], cin, cout,
            max(x.dtype.itemsize, w.dtype.itemsize)):
        # oversized spatial plane: the whole op ran on the XLA reference
        # path — differentiate that same math
        def _ref(x_, w_, s_, b_):
            return _conv_reference(x_, w_, s_, b_, relu_in, norm_in, 1)
        _, vjp = jax.vjp(_ref, x, w, scale, shift)
        return vjp((dy, dst))

    if w.ndim == 2:
        dy2 = dy.reshape(-1, cout)
        y2 = y.reshape(-1, cout)
        xs2 = xs.reshape(-1, cin)
        dxs2, dw, dscale, dshift = _bwd_merged_pallas(
            dy2, y2, w.T, xs2, dst, scale, shift, relu_in, norm_in,
            interpret, x.dtype)
        dxs = dxs2.reshape(xs.shape)
    elif cin <= 384 and norm_in:
        # merged single-pass 3×3 backward; at f=512 the full dW
        # accumulator no longer fits VMEM next to the planes → split
        dxs, dw, dscale, dshift = _c3_bwd_merged_pallas(
            xs, dy, y, w, dst, scale, shift, relu_in, interpret,
            x.dtype)
    else:
        dxs, dw, dscale, dshift = _c3_bwd_pallas(
            xs, dy, y, w, dst, scale, shift, relu_in, norm_in,
            interpret, x.dtype)

    if not norm_in:
        dscale = jnp.zeros_like(scale)
        dshift = jnp.zeros_like(shift)

    if w.ndim == 2 and stride != 1:
        dx = jnp.zeros(x.shape, x.dtype)
        dx = dx.at[:, ::stride, ::stride, :].set(dxs)
    else:
        dx = dxs
    return dx, dw.astype(w.dtype), dscale, dshift


fused_conv_bn_act.defvjp(_fused_fwd_rule, _fused_bwd_rule)


# ---------------------------------------------------------------------------
# BN helpers shared by the fused block layer
# ---------------------------------------------------------------------------

def stats_to_scale_shift(stats, count, gamma, beta, eps):
    """(Σy, Σy²) → the (scale, shift) form of BN normalize+affine, plus
    (mean, var) for the running-average update. Biased variance, exactly
    like jnp.var / the BatchNormalization layer."""
    f32 = jnp.float32
    mean = stats[0].astype(f32) / count
    var = jnp.maximum(stats[1].astype(f32) / count - mean * mean, 0.0)
    inv = gamma.astype(f32) * lax.rsqrt(var + eps)
    return inv, beta.astype(f32) - mean * inv, mean, var
