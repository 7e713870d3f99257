"""The selective scan as Pallas TPU kernels, backward by hand.

What ``nn.layers.state_space.selective_scan_chunked`` computes with a
``lax.scan`` over chunks of a ``lax.scan`` over tokens, and differentiates
by ``jax.checkpoint`` and autodiff, runs here as one kernel a pass: from a
zero state, in float32 whatever comes in,

    s_t = exp(dt_t (x) A) * s_(t-1) + B_t (x) (dt_t x_t)
    y_t = sum_n C_t[n] s_t[n, :]

The state of a block of channels, ``(S, lanes)`` with the states on the
sublanes as ``selective_scan_step`` lays it out, stays in VMEM scratch for
the whole walk along a grid axis marked ``arbitrary``; the channel blocks
are independent and ``parallel``. ``x``, ``dt`` and ``y`` are read and
written where the layer has them, ``(N, T, D)``: a loop iteration takes the
eight rows of one sublane tile and unrolls their tokens. ``B`` and ``C``
come transposed 128 tokens at a time, ``(N, T / 128, S, 128)`` (XLA's,
outside: 0.5 MB each at the cell's shapes), so that a token's sixteen
numbers are a lane column of a ``(S, 128)`` tile; the tile travels through
the loop rolled by eight lanes an iteration, which keeps the columns an
iteration reads static.

The forward writes, when asked, the state at every chunk border. The
backward walks the time blocks in reverse with ``ds`` in scratch: for a
chunk it computes the chunk's states again from its border into VMEM
scratch, then walks the chunk's tokens backwards (``e_t = exp(dt_t (x)
A)``):

    ds += C_t (x) dy_t          dC_t = sum_d dy_t s_t
    dB_t = sum_d ds (dt_t x_t)  r = sum_n ds B_t
    dx_t = dt_t r               ds <- ds e_t
    g = ds s_(t-1)              dA += g dt_t
    ddt_t = x_t r + sum_n g A

``dA`` accumulates over the walk; ``dB`` and ``dC`` leave as one partial a
channel block, transposed like their inputs, and XLA sums them. Neither
pass puts a ``(T, D, S)`` array in HBM.

``selective_scan`` chooses between the kernels and the plain chunked form
from what it is handed (backend, sizes, chunk); a kernel the compiler
refuses raises.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nn.layers.state_space import (
    CHUNK, selective_scan_chunked)
from deeplearning4j_tpu.ops.pallas_kernels import (
    _dim_sem, pallas_interpret, scoped_vmem_limit)

_F32 = jnp.float32
_GROUP = 8       # tokens a loop iteration unrolls: the rows of a sublane tile
_TILE = 128      # tokens a lane tile of B^T and C^T holds

# ``checkpoint_name`` tags of what the forward kernel writes when
# differentiated: ``y`` and the float32 states at the chunk borders. A
# ``jax.checkpoint`` whose policy saves these names (the decoder blocks')
# keeps both and does not launch the forward kernel again; anywhere else the
# tags are the identity. Both come out of one ``pallas_call``.
SCAN_Y_NAME = "ssm_selective_scan.y"
SCAN_STATES_NAME = "ssm_selective_scan.states"

SSM_KERNEL_GAUGE = (
    "dl4j_ssm_kernel_chunks",
    "chunks one pass of the selective scan walks through the Pallas "
    "kernels, as the step was last traced; 0 where the plain chunked form "
    "was traced (label: the layer)")

# tokens a grid step takes. On the v5e at the Phi-4-mini-flash cell's shapes
# (my chip runs, PR 36) time blocks of 128 / 256 / 512 at 512 lanes read
# forward 2.23 / 2.18 / 2.16 ms and backward 10.39 / 10.34 / 10.31; channel
# blocks of 256 / 512 / 1024 lanes at 256 tokens forward 3.42 / 2.18 / 1.96
# and backward 19.3 / 10.3 / 27.4 (at 1024 the walk's values spill)
_TIME_BLOCK = 256


# ---- inside the kernels --------------------------------------------------

def _rolled(tile, lanes: int):
    """``tile`` with lane ``i`` moved to lane ``i + lanes`` (mod 128)."""
    lanes %= _TILE
    return tile if lanes == 0 else pltpu.roll(tile, lanes, 1)


def _group_rows(group):
    """The eight rows of the time block's ``group``-th sublane tile."""
    return pl.ds(pl.multiple_of(group * _GROUP, _GROUP), _GROUP)


def _token(state, a, dt_r, dtx_r, b_col):
    """One token's state: ``a`` (S, lanes), the token's rows (1, lanes)
    and its column of ``B`` (S, 1)."""
    return jnp.exp(dt_r * a) * state + b_col * dtx_r


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, *rest,
                chunk: int, save_borders: bool):
    if save_borders:
        borders_ref, s_scr, dtx_scr = rest
    else:
        s_scr, dtx_scr = rest

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    dtx_scr[...] = dt_ref[0] * x_ref[0].astype(_F32)
    a = a_ref[...]
    chunks, groups = _TILE // chunk, chunk // _GROUP

    def tile_walk(tile, state):
        def chunk_walk(k, carry):
            first = tile * chunks + k
            if save_borders:
                borders_ref[0, first] = carry[0]

            def group(g, carry):
                state, b_t, c_t = carry
                rows = _group_rows(first * groups + g)
                dt_g, dtx_g = dt_ref[0, rows, :], dtx_scr[rows, :]
                ys = []
                for j in range(_GROUP):
                    state = _token(state, a, dt_g[j:j + 1], dtx_g[j:j + 1],
                                   b_t[:, j:j + 1])
                    ys.append(jnp.sum(c_t[:, j:j + 1] * state, axis=0,
                                      keepdims=True))
                y_ref[0, rows, :] = jnp.concatenate(ys, axis=0)
                return (state, _rolled(b_t, -_GROUP), _rolled(c_t, -_GROUP))

            return lax.fori_loop(0, groups, group, carry)

        return lax.fori_loop(0, chunks, chunk_walk,
                             (state, b_ref[0, tile], c_ref[0, tile]))[0]

    s_scr[...] = lax.fori_loop(0, dt_ref.shape[1] // _TILE, tile_walk,
                               s_scr[...])


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, borders_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                ds_scr, s_scr, x_scr, *, chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    x_scr[...] = x_ref[0].astype(_F32)
    a = a_ref[...]
    tiles = dt_ref.shape[1] // _TILE
    chunks, groups = _TILE // chunk, chunk // _GROUP
    lane = lax.broadcasted_iota(jnp.int32, (a.shape[0], _TILE), 1)

    def tile_walk(i, carry):
        tile = tiles - 1 - i
        b_t, c_t = b_ref[0, tile], c_ref[0, tile]

        def chunk_walk(k, carry):
            dstate, da, b_f, *walk = carry
            first = tile * chunks + chunks - 1 - k

            def again(g, carry):
                state, b_f = carry
                rows = _group_rows(first * groups + g)
                dt_g, x_g = dt_ref[0, rows, :], x_scr[rows, :]
                for j in range(_GROUP):
                    s_scr[g * _GROUP + j] = state
                    dt_r = dt_g[j:j + 1]
                    state = _token(state, a, dt_r, dt_r * x_g[j:j + 1],
                                   b_f[:, j:j + 1])
                return state, _rolled(b_f, -_GROUP)

            s_scr[chunk] = lax.fori_loop(
                0, groups, again, (borders_ref[0, first], b_f))[0]

            def back(i, carry):
                dstate, da = carry[:2]
                b_r, c_r, db_t, dc_t = (_rolled(v, _GROUP)
                                        for v in carry[2:])
                g = groups - 1 - i
                rows = _group_rows(first * groups + g)
                dt_g, x_g = dt_ref[0, rows, :], x_scr[rows, :]
                dy_g = dy_ref[0, rows, :]
                dxs, ddts = [None] * _GROUP, [None] * _GROUP
                for j in reversed(range(_GROUP)):
                    token = g * _GROUP + j
                    dt_r, x_r, dy_r = (v[j:j + 1] for v in (dt_g, x_g, dy_g))
                    dstate = dstate + c_r[:, j:j + 1] * dy_r
                    dc_col = jnp.sum(dy_r * s_scr[token + 1], axis=1,
                                     keepdims=True)
                    db_col = jnp.sum(dstate * (dt_r * x_r), axis=1,
                                     keepdims=True)
                    r = jnp.sum(dstate * b_r[:, j:j + 1], axis=0,
                                keepdims=True)
                    dstate = dstate * jnp.exp(dt_r * a)
                    gm = dstate * s_scr[token]
                    da = da + gm * dt_r
                    ddts[j] = x_r * r + jnp.sum(gm * a, axis=0,
                                                keepdims=True)
                    dxs[j] = dt_r * r
                    db_t = jnp.where(lane == j, db_col, db_t)
                    dc_t = jnp.where(lane == j, dc_col, dc_t)
                # the group's rows of x are read: dx takes their place
                x_scr[rows, :] = jnp.concatenate(dxs, 0)
                ddt_ref[0, rows, :] = jnp.concatenate(ddts, 0)
                return dstate, da, b_r, c_r, db_t, dc_t

            dstate, da, *walk = lax.fori_loop(0, groups, back,
                                              (dstate, da, *walk))
            return (dstate, da, _rolled(b_f, chunk), *walk)

        # the walk's frame (``b_t``, ``c_t`` and the columns of ``dB``,
        # ``dC`` written so far): an iteration of ``back`` first rolls it by
        # eight lanes, so that its group's columns stand in lanes 0-7;
        # after the tile's sixteen groups it is the tile's own again.
        # ``b_f`` is ``b_t`` with the chunk's first column in lane 0.
        dstate, da, _, _, _, db_t, dc_t = lax.fori_loop(
            0, chunks, chunk_walk,
            (*carry, _rolled(b_t, chunk - _TILE), b_t, c_t,
             jnp.zeros_like(b_t), jnp.zeros_like(c_t)))
        db_ref[0, 0, tile] = db_t
        dc_ref[0, 0, tile] = dc_t
        return dstate, da

    dstate, da = lax.fori_loop(0, tiles, tile_walk,
                               (ds_scr[...], jnp.zeros_like(a)))
    ds_scr[...] = dstate
    da_ref[0] += da
    dx_ref[0] = x_scr[...].astype(dx_ref.dtype)


# ---- the calls -----------------------------------------------------------

def _lanes(d: int) -> int:
    """The channel block: the widest of 512, 256, 128 lanes that divides
    ``d``."""
    return next(w for w in (512, 256, 128) if d % w == 0)


def _specs(x, s: int, chunk: int, reverse: bool):
    """``(grid, channel block, block specs)`` both passes share, from ``x``
    (N, Tp, D): the grid is ``(batch, channel block, time block)``, the
    last axis sequential (``reverse``: from the sequence's end). The specs:
    rows of ``x``'s kind, ``A^T``, tiles of ``B``'s kind, the border
    states, and the backward's partial ``dA`` and ``dB``."""
    n, tp, d = x.shape
    bd, bt = _lanes(d), _TIME_BLOCK
    steps = tp // bt
    step = (lambda t: steps - 1 - t) if reverse else (lambda t: t)
    rows = pl.BlockSpec((1, bt, bd), lambda i, j, t: (i, step(t), j))
    rates = pl.BlockSpec((s, bd), lambda i, j, t: (0, j))
    columns = pl.BlockSpec((1, bt // _TILE, s, _TILE),
                           lambda i, j, t: (i, step(t), 0, 0))
    borders = pl.BlockSpec((1, bt // chunk, s, bd),
                           lambda i, j, t: (i, step(t), 0, j))
    partial_rates = pl.BlockSpec((1, s, bd), lambda i, j, t: (i, 0, j))
    partial_columns = pl.BlockSpec((1, 1, bt // _TILE, s, _TILE),
                                   lambda i, j, t: (i, j, step(t), 0, 0))
    return (n, d // bd, steps), bd, (rows, rates, columns, borders,
                                     partial_rates, partial_columns)


def _vmem_need(s: int, bd: int, chunk: int, backward: bool) -> int:
    """Scoped VMEM a pass may ask for: its double-buffered blocks and its
    scratch."""
    bt = _TIME_BLOCK
    rows = bt * bd * 4
    blocks = (5 if backward else 3) * rows + 4 * s * bt * 4 \
        + (bt // chunk + 2) * s * bd * 4
    scratch = rows + (chunk + 2 if backward else 1) * s * bd * 4
    return 2 * blocks + scratch


def _forward(x, dt, a_t, b_t, c_t, chunk: int, save_borders: bool,
             interpret: bool):
    n, tp, d = x.shape
    s = a_t.shape[0]
    grid, bd, (rows, rates, columns, borders, _, _) = _specs(
        x, s, chunk, False)
    out_specs, out_shape = [rows], [jax.ShapeDtypeStruct((n, tp, d), _F32)]
    if save_borders:
        out_specs.append(borders)
        out_shape.append(
            jax.ShapeDtypeStruct((n, tp // chunk, s, d), _F32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk,
                          save_borders=save_borders),
        grid=grid,
        in_specs=[rows, rows, rates, columns, columns],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((s, bd), _F32),
                        pltpu.VMEM((_TIME_BLOCK, bd), _F32)],
        compiler_params=_dim_sem(3, scoped_vmem_limit(
            _vmem_need(s, bd, chunk, backward=False))),
        name="ssm_selective_scan_fwd",
        interpret=interpret,
    )(x, dt, a_t, b_t, c_t)


def _backward(x, dt, a_t, b_t, c_t, states, dy, chunk: int,
              interpret: bool):
    n, tp, d = x.shape
    s = a_t.shape[0]
    grid, bd, (rows, rates, columns, borders, partial_rates,
               partial_columns) = _specs(x, s, chunk, True)
    blocks = grid[1]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[rows, rows, rates, columns, columns, borders, rows],
        out_specs=[rows, rows, partial_rates, partial_columns,
                   partial_columns],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct((n, s, d), _F32),
                   jax.ShapeDtypeStruct((n, blocks) + b_t.shape[1:], _F32),
                   jax.ShapeDtypeStruct((n, blocks) + c_t.shape[1:], _F32)],
        scratch_shapes=[pltpu.VMEM((s, bd), _F32),
                        pltpu.VMEM((chunk + 1, s, bd), _F32),
                        pltpu.VMEM((_TIME_BLOCK, bd), _F32)],
        compiler_params=_dim_sem(3, scoped_vmem_limit(
            _vmem_need(s, bd, chunk, backward=True))),
        name="ssm_selective_scan_bwd",
        interpret=interpret,
    )(x, dt, a_t, b_t, c_t, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(x, dt, a_t, b_t, c_t, chunk, interpret):
    return _forward(x, dt, a_t, b_t, c_t, chunk, False, interpret)[0]


def _scan_fwd(x, dt, a_t, b_t, c_t, chunk, interpret):
    y, states = _forward(x, dt, a_t, b_t, c_t, chunk, True, interpret)
    # the tagged result is the primal too (``_flash_fwd_rule``'s reason)
    y = checkpoint_name(y, SCAN_Y_NAME)
    states = checkpoint_name(states, SCAN_STATES_NAME)
    return y, (x, dt, a_t, b_t, c_t, states)


def _scan_bwd(chunk, interpret, res, dy):
    dx, ddt, da, db, dc = _backward(*res, dy.astype(_F32), chunk,
                                    interpret)
    return dx, ddt, jnp.sum(da, 0), jnp.sum(db, 1), jnp.sum(dc, 1)


_scan.defvjp(_scan_fwd, _scan_bwd)


def kernel_chunks(t: int, chunk: int) -> int:
    """Chunks one pass walks through the kernels for ``t`` tokens: the
    sequence padded to whole time blocks."""
    return -(-t // _TIME_BLOCK) * _TIME_BLOCK // chunk


def selective_scan_kernels(x, dt, a, b, c, chunk_size: int = CHUNK,
                           interpret: Optional[bool] = None):
    """The selective scan through the Pallas kernels. ``x``, ``dt`` (N, T,
    D), ``D`` a multiple of 128; ``a`` (D, S), ``S`` a multiple of 8;
    ``b``, ``c`` (N, T, S); ``chunk_size`` a multiple of 8 that divides
    128. Returns ``y`` (N, T, D) in float32 from a zero state.
    ``interpret`` defaults to ``pallas_interpret()``."""
    if interpret is None:
        interpret = pallas_interpret()
    t = x.shape[1]
    chunk = int(chunk_size)
    pad = kernel_chunks(t, chunk) * chunk - t

    def rows(v):            # tokens of step 0 behind: the state stays
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v

    def columns(v):         # (N, T, S) -> (N, Tp / 128, S, 128)
        v = rows(v.astype(_F32))
        return jnp.swapaxes(v.reshape(v.shape[0], -1, _TILE, v.shape[2]),
                            2, 3)

    y = _scan(rows(x), rows(dt.astype(_F32)), a.astype(_F32).T, columns(b),
              columns(c), chunk, bool(interpret))
    return y[:, :t]


def kernels_take(x, a, chunk_size: int) -> bool:
    """Whether the kernels run what ``selective_scan`` is handed: the
    backend a TPU, the channels a multiple of 128 and the states of 8,
    float32 the type the plain form would compute in, the chunk a multiple
    of 8 that divides 128."""
    chunk = int(chunk_size)
    return (jax.default_backend() == "tpu" and x.shape[-1] % 128 == 0
            and a.shape[-1] % 8 == 0
            and jnp.promote_types(_F32, x.dtype) == _F32
            and chunk % _GROUP == 0 and _TILE % chunk == 0)


def selective_scan(x, dt, a, b, c, chunk_size: int = CHUNK,
                   layer: Optional[str] = None):
    """``selective_scan_chunked``'s ``y`` for ``x``, ``dt`` (N, T, D), ``a``
    (D, S) and ``b``, ``c`` (N, T, S): the Pallas kernels where
    ``kernels_take``, else the plain chunked form. The choice rests on the
    inputs alone; a kernel the compiler refuses raises. With ``layer``,
    the caller's name, the chunks a pass walks through the kernels (0 for
    the plain form) are published as the gauge ``dl4j_ssm_kernel_chunks``
    at trace time."""
    takes = kernels_take(x, a, chunk_size)
    if layer is not None:
        from deeplearning4j_tpu.observe.registry import default_registry
        default_registry().gauge(*SSM_KERNEL_GAUGE).set(
            kernel_chunks(x.shape[1], int(chunk_size)) if takes else 0,
            layer=layer)
    if takes:
        return selective_scan_kernels(x, dt, a, b, c, chunk_size)
    return selective_scan_chunked(x, dt, a, b, c, chunk_size)
