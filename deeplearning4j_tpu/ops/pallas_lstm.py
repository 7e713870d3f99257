"""Fused Pallas LSTM recurrence — the cuDNN-LSTM-helper tier for TPU.

The reference accelerates LSTM with a dedicated cuDNN helper
(deeplearning4j-cuda/.../recurrent/CudnnLSTMHelper.java) because a
per-tick recurrence dominated by dispatch/HBM overhead is the classic
case where a hand-fused kernel beats the generic compiler path. Our XLA
scan has the same gap (PERF_ANALYSIS r5: ~23 µs/tick against a ~6 µs
matmul roofline at the BASELINE TextGenerationLSTM geometry, with Wh
(2.1 MB bf16) re-streamed from HBM every tick).

The kernel here runs the whole recurrence as ONE pallas_call:

- grid = (T/block_t,) with the time axis SEQUENTIAL ("arbitrary"), so
  Wh — whose BlockSpec index map is constant — is fetched into VMEM once
  and stays pinned across all ticks;
- the (h, c) carry lives in f32 VMEM scratch, never touching HBM
  between ticks;
- per tick the kernel reads one (N, 4H) slab of the pre-projected input
  zx (the x@Wx+b hoist stays outside, where the MXU runs it at full
  tilt over all timesteps at once) and writes the hidden output plus
  the activation residuals the backward pass needs;
- the backward is a second kernel walking the grid in REVERSE via its
  index maps, with (dh, dc) and the dWh accumulator in VMEM scratch —
  wrapped as a jax.custom_vjp so training uses it too.

Masking matches the scan cell exactly: masked ticks do not advance
(h, c); output zeroing stays in the layer.

Dispatch follows the helper-SPI-with-measured-crossover discipline of
``pallas_kernels.attention``: ``choose_impl`` routes to the fused
kernel only where ``benchmarks/lstm_crossover.py`` measurements say it
wins and the compiled kernel can take the geometry, and to the
``lax.scan`` cell otherwise. A forced ``DL4J_LSTM_IMPL=fused`` with a
geometry the compiled kernel cannot take raises with the reason.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas_kernels import (
    _dim_sem, pallas_interpret, scoped_vmem_limit)

_IMPL_ENV = "DL4J_LSTM_IMPL"  # "fused" | "scan" | "auto" (default)

# Measured crossover thresholds from benchmarks/lstm_crossover.py runs on
# real hardware: rules of (min_batch, min_hidden, min_seq); the fused
# kernel is auto-selected when ANY rule is satisfied. EMPTY: the
# crossover bench has not been captured on a chip, so auto-dispatch stays
# on the scan path — thresholds here must come from measurements, not
# guesses (the attention crossover discipline). Opt in explicitly with
# DL4J_LSTM_IMPL=fused.
_MEASURED_FUSED_WINS: Tuple[Tuple[int, int, int], ...] = ()

# Runtime override installed by the autotune engine (the
# `ops.lstm_dispatch` tunable): a TunedConfig measured on THIS machine
# may carry crossover rules, and set_process_tuned() routes them here.
# None means "no tuned table installed — use the committed constant".
_runtime_rules: Optional[Tuple[Tuple[int, int, int], ...]] = None

_DEF_BLOCK_T = 1  # ticks per grid step; >1 amortizes per-step overhead
                  # at the price of VMEM (zx slab is N*4H*dtype per tick)


def dispatch_rules() -> Tuple[Tuple[int, int, int], ...]:
    """The crossover table in effect: the tuned runtime table when one
    was installed, else the committed measured constant."""
    return (_MEASURED_FUSED_WINS if _runtime_rules is None
            else _runtime_rules)


def set_dispatch_rules(rules) -> None:
    """Install (or with None, clear) a measured crossover table at
    runtime. Rules arrive from a persisted TunedConfig as lists of
    [min_batch, min_hidden, min_seq]; normalized to int tuples here."""
    global _runtime_rules
    if rules is None:
        _runtime_rules = None
        return
    _runtime_rules = tuple(
        (int(b), int(h), int(t)) for (b, h, t) in rules)


def fused_wins(batch: int, hidden: int, seq: int) -> bool:
    """True where the measured crossover table says the fused kernel
    beats the XLA scan on this (batch, hidden, seq) geometry."""
    return any(batch >= b and hidden >= h and seq >= t
               for (b, h, t) in dispatch_rules())


def compiled_geometry_error(hidden: int) -> Optional[str]:
    """Why the COMPILED kernel cannot take this geometry, or None. The
    kernels slice the (N, 4H) gate slab at lane offsets H, 2H and 3H
    (``z[:, nh:2 * nh]``); Mosaic needs those lane-aligned. Interpret
    mode (CPU) takes any H."""
    if hidden % 128:
        return (f"hidden size {hidden} is not a multiple of 128: the "
                "compiled kernel slices the (N, 4H) gate slab at lane "
                "offsets H, 2H and 3H")
    return None


def choose_impl(batch: int, hidden: int, seq: int,
                backend: Optional[str] = None) -> str:
    """Dispatch decision: 'fused' or 'scan'. An explicit
    ``DL4J_LSTM_IMPL`` is taken as given (``lstm_fused`` raises where
    the compiled kernel cannot run it); 'auto' picks the fused kernel
    only on TPU, where the measured table says it wins, at a geometry
    the compiled kernel can take."""
    mode = os.environ.get(_IMPL_ENV, "auto")
    if mode in ("fused", "scan"):
        return mode
    backend = backend or jax.default_backend()
    if (backend == "tpu" and fused_wins(batch, hidden, seq)
            and compiled_geometry_error(hidden) is None):
        return "fused"
    return "scan"


def _vmem_limit(block_t: int, n: int, h: int, itemsize: int,
                bwd: bool) -> Optional[int]:
    """Scoped-VMEM limit computed from the kernel's buffers: every
    pipelined block twice (double buffering), the scratch once, and the
    (N, 4H) f32 gate temporaries. The default limit is too small from
    about the 2xLSTM-512 geometry on: its f32 backward (N=256, H=512)
    asks for 22 MiB (chip run, PR 21)."""
    nh, n4h, h4h = block_t * n * h, block_t * n * 4 * h, 4 * h * h
    blocks = itemsize * ((4 if bwd else 3) * nh + 2 * n4h + 4 * n * h
                         + h4h + block_t * n * 128)
    scratch = 4 * 2 * n * h
    if bwd:
        blocks += 4 * h4h          # dWh out, f32
        scratch += 4 * h4h         # dWh accumulator
    return scoped_vmem_limit(2 * blocks + scratch + 4 * 4 * n4h)


def _fwd_kernel(zx_ref, h0_ref, c0_ref, wh_ref, mask_ref,
                ys_ref, gates_ref, tc_ref, cc_ref, hT_ref, cT_ref,
                h_scr, c_scr, *, block_t: int, hidden: int):
    """block_t ticks of the recurrence. Carry (h, c) persists in f32
    scratch across the sequential grid; Wh stays resident (constant
    index map). Residuals (post-activation gates, tanh(c), carried c)
    are written per tick so the backward never re-runs the matmul chain."""
    t = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)
        c_scr[...] = c0_ref[...].astype(jnp.float32)

    wh = wh_ref[...]
    nh = hidden
    for j in range(block_t):
        h_prev = h_scr[...]
        c_prev = c_scr[...]
        z = zx_ref[j].astype(jnp.float32) + jnp.dot(
            h_prev.astype(wh.dtype), wh,
            preferred_element_type=jnp.float32)
        i = jax.nn.sigmoid(z[:, :nh])
        f = jax.nn.sigmoid(z[:, nh:2 * nh])
        o = jax.nn.sigmoid(z[:, 2 * nh:3 * nh])
        g = jnp.tanh(z[:, 3 * nh:])
        c_raw = f * c_prev + i * g
        tc = jnp.tanh(c_raw)
        h_raw = o * tc
        m = mask_ref[j].astype(jnp.float32)  # (N, 1)
        h_new = m * h_raw + (1.0 - m) * h_prev
        c_new = m * c_raw + (1.0 - m) * c_prev
        h_scr[...] = h_new
        c_scr[...] = c_new
        ys_ref[j] = h_new.astype(ys_ref.dtype)
        gates_ref[j] = jnp.concatenate([i, f, o, g],
                                       axis=1).astype(gates_ref.dtype)
        tc_ref[j] = tc.astype(tc_ref.dtype)
        cc_ref[j] = c_new.astype(cc_ref.dtype)

    @pl.when(t == nt - 1)
    def _fin():
        hT_ref[...] = h_scr[...].astype(hT_ref.dtype)
        cT_ref[...] = c_scr[...].astype(cT_ref.dtype)


def _fused_forward(zx, h0, c0, wh, mask, block_t: int, interpret: bool):
    """zx (T, N, 4H) pre-projected inputs, mask (T, N, 1). T must be a
    multiple of block_t (the wrapper pads). Returns ys/hT/cT plus the
    backward residuals."""
    t_pad, n, g4 = zx.shape
    h = g4 // 4
    nt = t_pad // block_t
    vm = pl.ANY if interpret else pltpu.VMEM
    dt = zx.dtype

    kernel = functools.partial(_fwd_kernel, block_t=block_t, hidden=h)
    const2 = lambda t: (0, 0)
    tick3 = lambda t: (t, 0, 0)
    return pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((block_t, n, g4), tick3, memory_space=vm),
            pl.BlockSpec((n, h), const2, memory_space=vm),
            pl.BlockSpec((n, h), const2, memory_space=vm),
            pl.BlockSpec((h, g4), const2, memory_space=vm),
            # (T, N, 1): trailing block dims equal the array dims, and m
            # broadcasts along lanes against the (N, H) carry
            pl.BlockSpec((block_t, n, 1), tick3, memory_space=vm),
        ],
        out_specs=[
            pl.BlockSpec((block_t, n, h), tick3, memory_space=vm),
            pl.BlockSpec((block_t, n, g4), tick3, memory_space=vm),
            pl.BlockSpec((block_t, n, h), tick3, memory_space=vm),
            pl.BlockSpec((block_t, n, h), tick3, memory_space=vm),
            pl.BlockSpec((n, h), const2, memory_space=vm),
            pl.BlockSpec((n, h), const2, memory_space=vm),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_pad, n, h), dt),      # ys
            jax.ShapeDtypeStruct((t_pad, n, g4), dt),     # gates i|f|o|g
            jax.ShapeDtypeStruct((t_pad, n, h), dt),      # tanh(c_raw)
            jax.ShapeDtypeStruct((t_pad, n, h), dt),      # carried c
            jax.ShapeDtypeStruct((n, h), h0.dtype),       # hT
            jax.ShapeDtypeStruct((n, h), c0.dtype),       # cT
        ],
        scratch_shapes=[
            pltpu.VMEM((n, h), jnp.float32),
            pltpu.VMEM((n, h), jnp.float32),
        ],
        compiler_params=_dim_sem(
            1, _vmem_limit(block_t, n, h, dt.itemsize, bwd=False)),
        interpret=interpret,
    )(zx, h0, c0, wh, mask)


def _bwd_kernel(dys_ref, dhT_ref, dcT_ref, gates_ref, tc_ref, cprev_ref,
                hprev_ref, mask_ref, wh_ref,
                dzx_ref, dwh_ref, dh0_ref, dc0_ref,
                dh_scr, dc_scr, dwh_scr, *, block_t: int, hidden: int):
    """Reverse-time VJP of ``_fwd_kernel``. The grid's index maps walk T
    backwards; (dh, dc) and the dWh accumulator live in f32 scratch.
    Masked ticks pass (dh, dc) through untouched and contribute zero to
    dzx/dWh — the exact transpose of the carry-freezing forward."""
    k = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(k == 0)
    def _init():
        dh_scr[...] = dhT_ref[...].astype(jnp.float32)
        dc_scr[...] = dcT_ref[...].astype(jnp.float32)
        dwh_scr[...] = jnp.zeros_like(dwh_scr)

    wh = wh_ref[...]
    nh = hidden
    for j in reversed(range(block_t)):
        m = mask_ref[j].astype(jnp.float32)  # (N, 1)
        dh = dh_scr[...] + dys_ref[j].astype(jnp.float32)
        dc = dc_scr[...]
        gts = gates_ref[j].astype(jnp.float32)
        i = gts[:, :nh]
        f = gts[:, nh:2 * nh]
        o = gts[:, 2 * nh:3 * nh]
        g = gts[:, 3 * nh:]
        tc = tc_ref[j].astype(jnp.float32)
        cp = cprev_ref[j].astype(jnp.float32)

        dh_raw = m * dh
        do = dh_raw * tc
        dc_raw = m * dc + dh_raw * o * (1.0 - tc * tc)
        di = dc_raw * g
        df = dc_raw * cp
        dg = dc_raw * i
        dz = jnp.concatenate([
            di * i * (1.0 - i),
            df * f * (1.0 - f),
            do * o * (1.0 - o),
            dg * (1.0 - g * g),
        ], axis=1)
        dzx_ref[j] = dz.astype(dzx_ref.dtype)
        hp = hprev_ref[j]
        dwh_scr[...] += jax.lax.dot_general(
            hp, dz.astype(hp.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dh_scr[...] = (1.0 - m) * dh + jax.lax.dot_general(
            dz.astype(wh.dtype), wh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dc_scr[...] = (1.0 - m) * dc + dc_raw * f

    @pl.when(k == nt - 1)
    def _fin():
        dwh_ref[...] = dwh_scr[...]
        dh0_ref[...] = dh_scr[...].astype(dh0_ref.dtype)
        dc0_ref[...] = dc_scr[...].astype(dc0_ref.dtype)


def _fused_backward(dys, dhT, dcT, gates, tcs, cprev, hprev, mask, wh,
                    block_t: int, interpret: bool):
    t_pad, n, h = dys.shape
    g4 = 4 * h
    nt = t_pad // block_t
    vm = pl.ANY if interpret else pltpu.VMEM

    kernel = functools.partial(_bwd_kernel, block_t=block_t, hidden=h)
    const2 = lambda k: (0, 0)
    rev3 = lambda k: (nt - 1 - k, 0, 0)
    return pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((block_t, n, h), rev3, memory_space=vm),
            pl.BlockSpec((n, h), const2, memory_space=vm),
            pl.BlockSpec((n, h), const2, memory_space=vm),
            pl.BlockSpec((block_t, n, g4), rev3, memory_space=vm),
            pl.BlockSpec((block_t, n, h), rev3, memory_space=vm),
            pl.BlockSpec((block_t, n, h), rev3, memory_space=vm),
            pl.BlockSpec((block_t, n, h), rev3, memory_space=vm),
            pl.BlockSpec((block_t, n, 1), rev3, memory_space=vm),
            pl.BlockSpec((h, g4), const2, memory_space=vm),
        ],
        out_specs=[
            pl.BlockSpec((block_t, n, g4), rev3, memory_space=vm),
            pl.BlockSpec((h, g4), const2, memory_space=vm),
            pl.BlockSpec((n, h), const2, memory_space=vm),
            pl.BlockSpec((n, h), const2, memory_space=vm),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_pad, n, g4), dys.dtype),  # dzx
            jax.ShapeDtypeStruct((h, g4), jnp.float32),       # dWh
            jax.ShapeDtypeStruct((n, h), dhT.dtype),          # dh0
            jax.ShapeDtypeStruct((n, h), dcT.dtype),          # dc0
        ],
        scratch_shapes=[
            pltpu.VMEM((n, h), jnp.float32),
            pltpu.VMEM((n, h), jnp.float32),
            pltpu.VMEM((h, g4), jnp.float32),
        ],
        compiler_params=_dim_sem(
            1, _vmem_limit(block_t, n, h, dys.dtype.itemsize, bwd=True)),
        interpret=interpret,
    )(dys, dhT, dcT, gates, tcs, cprev, hprev, mask, wh)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _lstm_fused_core(zx, h0, c0, wh, mask, block_t, interpret):
    ys, _, _, _, hT, cT = _fused_forward(zx, h0, c0, wh, mask,
                                         block_t, interpret)
    return ys, hT, cT


def _core_fwd(zx, h0, c0, wh, mask, block_t, interpret):
    ys, gates, tcs, ccs, hT, cT = _fused_forward(zx, h0, c0, wh, mask,
                                                 block_t, interpret)
    return (ys, hT, cT), (h0, c0, wh, mask, ys, gates, tcs, ccs)


def _core_bwd(block_t, interpret, res, cts):
    h0, c0, wh, mask, ys, gates, tcs, ccs = res
    dys, dhT, dcT = cts
    # previous-tick carries, built once in XLA: prev(0) is the initial
    # state, prev(t) the tick-(t-1) outputs
    hprev = jnp.concatenate([h0[None].astype(ys.dtype), ys[:-1]], axis=0)
    cprev = jnp.concatenate([c0[None].astype(ccs.dtype), ccs[:-1]], axis=0)
    dzx, dwh, dh0, dc0 = _fused_backward(
        dys.astype(ys.dtype), dhT, dcT, gates, tcs, cprev, hprev, mask,
        wh, block_t, interpret)
    return (dzx, dh0, dc0, dwh.astype(wh.dtype), jnp.zeros_like(mask))


_lstm_fused_core.defvjp(_core_fwd, _core_bwd)


def lstm_fused(zx, h0, c0, wh, mask=None, *, block_t: int = _DEF_BLOCK_T,
               interpret: Optional[bool] = None):
    """Run the fused recurrence over pre-projected inputs.

    zx: (T, N, 4H) time-major ``x@Wx + b`` with gate-major [i|f|o|g]
    columns; h0/c0: (N, H); wh: (H, 4H); mask: optional (T, N) with the
    scan cell's semantics (masked ticks keep the previous carry).
    Returns (ys (T, N, H), hT, cT). Differentiable via a custom VJP
    whose backward is itself a fused reverse-time kernel.
    """
    if interpret is None:
        interpret = pallas_interpret()
    if not interpret:
        why = compiled_geometry_error(h0.shape[1])
        if why is not None:
            raise ValueError(f"lstm_fused: {why}")
    t = zx.shape[0]
    n = zx.shape[1]
    if mask is None:
        mask3 = jnp.ones((t, n, 1), zx.dtype)
    else:
        mask3 = mask[:, :, None].astype(zx.dtype)
    pad = (-t) % block_t
    if pad:
        zx = jnp.pad(zx, ((0, pad), (0, 0), (0, 0)))
        # padded ticks are fully masked: carries pass through unchanged
        mask3 = jnp.pad(mask3, ((0, pad), (0, 0), (0, 0)))
    ys, hT, cT = _lstm_fused_core(zx, h0, c0, wh, mask3, block_t,
                                  interpret)
    return ys[:t], hT, cT
