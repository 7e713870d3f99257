"""The Mamba-2 (state-space duality) scan as Pallas TPU kernels, backward
by hand, with the mixer's skip, gate and grouped norm inside.

What ``nn.layers.state_space.ssd_chunked`` computes with a ``lax.scan``
over chunks of batched XLA products, and differentiates by
``jax.checkpoint`` and autodiff, and what ``gated_group_norm`` makes of
its result, runs here as one kernel a pass, from a zero state. Per chunk of ``Q = 128`` tokens and head (``cum`` the running
sum of ``dt A`` inside the chunk, ``L[i, j] = e^(cum_i - cum_j)`` for ``j
<= i``, masked before the exponential; ``S`` the head's (P, S) state as
the chunk finds it):

    M  = (C B^T) o L o dt_j                  y  = M x + e^cum (C S^T)
    S' = e^cum_last S + (x e^(cum_last - cum) dt)^T B
    o  = GroupRMSNorm((y + skip x) silu(z))

A grid step owns **one group's heads**, which share ``B`` and ``C`` and
are one group of the norm: ``C B^T`` is one product a chunk for all of
them, ``x``, ``z`` and ``o`` are read and written where the layer has
them, ``(N, T, H P)`` with the group's ``(H /
G) P`` channels as a lane-dense column block, and the group's float32
state ``((H / G) P, S)`` stays in VMEM scratch for the whole walk along a
grid axis marked ``arbitrary``. Neither a decay matrix nor ``M`` ever
leaves VMEM. Heads narrower than a lane tile (``P`` = 64: two a tile)
never meet a half-tile operation: a head's product takes the tile of ``x``
with the other heads' lanes zeroed, which costs the matrix unit the same
pass, and the tile's heads add up; the products over the state take all
the group's heads at once (``C S^T``: 128 x S x (H / G) P).

The per-token scalars travel as one ``(rows, 2 Q)`` float32 block a chunk
and group, ``[cum | dt]`` with a head on each sublane and the tokens on the
lanes: the form a decay matrix needs along its lanes; the kernel
transposes the block for the form down the sublanes, and broadcasts a
head's column over the lanes once for its decay matrix and its channels
alike. The running sum itself is XLA's, outside, in float32: a product
with a triangle of ones at ``highest`` precision, which also turns the
tokens to the lanes (2 MB a layer).

The forward writes ``o`` once, in the compute type (the float32 ``y``
never leaves VMEM), and, when differentiated, the state at every chunk
border. The backward walks the chunks in reverse with ``dS`` in scratch;
for a chunk it takes ``C B^T``, the decay matrices and ``y`` again from
the inputs and the border state, goes back through the norm and the gate
from ``do`` to ``dy`` (and ``dz``, and the skip's and the norm weight's
gradients as one row a batch row, summed along the walk), and with ``Z = M'^T dy + e^(cum_last - cum) (B dS'^T)``
(``M' = (C B^T) o L``) the gradients are

    dx = dt Z + skip dy             ddt_i  = sum_p x_ip Z_ip
    dC = (sum_h dM o L o dt_j) B + (e^cum dy) S
    dB = (sum_h dM o L o dt_j)^T C + (x e^(cum_last - cum) dt) dS'
    dS = e^cum_last dS' + (e^cum dy)^T C
    dcum_i = sum_j (dM o M)_ij + sum_p dy_ip e^cum_i (C S^T)_ip - dt_i ddt_i
             + [i = Q - 1] (sum_j dt_j w_j + e^cum_last <dS', S>)

with ``dM = dy x^T`` and ``w_i = sum_p x_ip e^(cum_last - cum_i) (B
dS'^T)_ip``, the share of ``ddt_i`` that came through the state the chunk
leaves. ``x`` and ``dt`` enter ``y`` through their product alone, which
gives ``ddt``; what ``cum_j`` loses of ``dM o M`` is its column sum, ``dt_j``
times ``dt_j``'s own. ``dA`` weighs ``dcum_i`` with ``cum_i``, a large
number, and lives on what cancels between a row sum and a column sum: both
are taken over the same float32 products ``dM o M'`` (sixteen registers a
head, reduced down and across), as autodiff takes them in the plain form;
sums that each round their own operands (``sum_p dy y`` for the rows) read
``dA`` ten times further from float32 under bfloat16 operands. ``dB`` and
``dC`` are sums over the group's heads, which one grid step holds. The
residuals are the inputs and the border states. The gradient through the
running sum (a reverse running sum) and ``dA`` are autodiff's over the
wrapper's XLA lines. Neither pass puts a ``(T, H, P,
S)`` or a ``(T / Q, H, Q, Q)`` array in HBM.

Types as in the plain forms: ``dt``, ``A``, the running sums, the state,
``y``, the gate and the norm float32; matrix products take their operands
in the compute type (``x``'s), rounded where ``ssd_chunked`` rounds them,
and accumulate in float32; ``y`` is not rounded before the norm.

``ssd_scan`` chooses between the kernels and the plain forms from what it
is handed (backend, sizes, chunk); a kernel the compiler refuses raises.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.nn.layers.state_space import (
    SSD_CHUNK, gated_group_norm, ssd_chunked)
from deeplearning4j_tpu.ops.pallas_delta_rule import (
    _Products, _dot, _dot_nt, _dot_tn)
from deeplearning4j_tpu.ops.pallas_kernels import (
    _dim_sem, pallas_interpret, scoped_vmem_limit)

_F32 = jnp.float32
_Q = 128         # tokens a chunk: a decay matrix is one (128, 128) tile
_TILE = 128      # lanes

# ``checkpoint_name`` tags of what the forward kernel writes when
# differentiated: the normed rows ``o`` (compute type) and the float32
# states at the chunk borders. A ``jax.checkpoint`` whose policy saves these
# names (the decoder blocks') keeps both and does not launch the forward
# kernel again; anywhere else the tags are the identity. Both come out of
# one ``pallas_call``.
SSD_OUT_NAME = "ssd_scan.o"
SSD_BORDERS_NAME = "ssd_scan.borders"

SSD_KERNEL_GAUGE = (
    "dl4j_ssd_kernel_chunks",
    "chunks one pass of the Mamba-2 scan walks through the Pallas kernels, "
    "as the step was last traced; 0 where the plain chunked form was "
    "traced (label: the layer)")

# A grid step takes one chunk. On the v5e at the Nemotron 3 Nano cell's
# shapes (my chip runs, PR 38, the scan alone, before the norm moved in)
# 1 / 2 / 4 chunks a step (a loop inside the step) read forward 0.713 / 0.696 / 0.691 ms, forward with the border
# states 0.813 / 0.731 / 0.717 and backward 1.055 / 1.024 / 1.013: 0.16 ms
# a layer of 2.58, for a loop the kernels are simpler without. The lane
# block is the group's (H / G) P channels: what shares B and C.


class _Heads(NamedTuple):
    """A group's heads: ``count`` of ``width`` channels each."""
    count: int
    width: int

    @property
    def channels(self) -> int:
        return self.count * self.width

    @property
    def a_tile(self) -> int:
        """Heads a lane tile holds."""
        return _TILE // self.width

    @property
    def rows(self) -> int:
        """Rows of a scalar tile: the heads padded to whole sublane tiles."""
        return -(-self.count // 8) * 8


# ---- inside the kernels --------------------------------------------------

class _Scalars(NamedTuple):
    """A chunk's per-token scalars, both ways round. ``cum_rows`` and
    ``dt_rows`` are (rows, Q), head ``h`` of the group in row ``h``, as
    they came; ``cum`` and ``dt`` hold a (Q, 128) value a head, every
    lane its token's: a lane broadcast is the dearest thing a chunk does
    sixteen times over, so each is made once and used for the head's decay
    matrix and for its lanes of the group's channels alike."""
    cum_rows: jax.Array
    dt_rows: jax.Array
    cum: tuple
    dt: tuple


def _scalars(block, heads: _Heads) -> _Scalars:
    """From the block as it came, (rows, 2 Q): ``[cum | dt]``."""
    rows = block.shape[0]
    cum_rows, dt_rows = block[:, :_Q], block[:, _Q:]
    cols = jnp.concatenate(
        [cum_rows, dt_rows, jnp.zeros((_TILE - 2 * rows, _Q), _F32)],
        0).T                    # (Q, 128): cum in lanes [0, rows), then dt

    def lanes(h):
        return jnp.broadcast_to(cols[:, h:h + 1], (_Q, _TILE))

    every = range(heads.count)
    return _Scalars(cum_rows, dt_rows, tuple(lanes(h) for h in every),
                    tuple(lanes(rows + h) for h in every))


def _tile_heads(k: int, heads: _Heads):
    """``[(head, lanes it fills, or None for all)]`` of lane tile ``k`` of
    the group's channels."""
    per = heads.a_tile
    if per == 1:
        return [(k, None)]
    lane = lax.broadcasted_iota(jnp.int32, (_Q, _TILE), 1)
    return [(k * per + j, (lane >= j * heads.width)
             & (lane < (j + 1) * heads.width)) for j in range(per)]


def _spread(values, k: int, heads: _Heads):
    """Lane tile ``k`` of the group's channels, each lane holding its
    head's (Q, 128) value of ``values``."""
    (h, _), *others = _tile_heads(k, heads)
    out = values[h]
    for h, own in others:
        out = jnp.where(own, values[h], out)
    return out


class _Spread(NamedTuple):
    """What the rows of lane tile ``k`` are scaled by, (Q, 128) each."""
    dt: jax.Array        # dt_i
    began: jax.Array     # e^cum_i
    ends: jax.Array      # e^(cum_last - cum_i)


def _spreads(sc: _Scalars, k: int, heads: _Heads) -> _Spread:
    cum = _spread(sc.cum, k, heads)
    return _Spread(_spread(sc.dt, k, heads), jnp.exp(cum),
                   jnp.exp(cum[_Q - 1:_Q, :] - cum))


def _own(x, lanes):
    """``x`` (Q, 128) with the lanes of the tile's other heads zeroed."""
    return x if lanes is None else jnp.where(lanes, x, 0.0)


def _decay(sc: _Scalars, h: int, lower):
    """``L`` of head ``h``: masked before the exponential, whose argument
    above the diagonal is positive."""
    return jnp.exp(jnp.where(
        lower, sc.cum[h] - sc.cum_rows[h:h + 1, :], -jnp.inf))


def _lower():
    return (lax.broadcasted_iota(jnp.int32, (_Q, _Q), 1)
            <= lax.broadcasted_iota(jnp.int32, (_Q, _Q), 0))


def _lanes(k: int):
    """Lane tile ``k`` of the group's channels."""
    return slice(k * _TILE, (k + 1) * _TILE)


def _head_rows(h: int, heads: _Heads):
    return slice(h * heads.width, (h + 1) * heads.width)


def _carried(sc: _Scalars, h: int, width: int):
    """``e^cum_last`` of head ``h`` as a (1, width) row (a (1, 1) value
    does not broadcast both ways at once)."""
    return jnp.exp(jnp.broadcast_to(sc.cum[h][_Q - 1:_Q, 0:1], (1, width)))


def _carry_on(scr, sc: _Scalars, state, added, heads: _Heads):
    """``scr <- e^cum_last state + added``, head by head, ((H / G) P, S):
    the state to the chunk's end, or its gradient to the chunk's start."""
    for h in range(heads.count):
        r = _head_rows(h, heads)
        scr[r, :] = _carried(sc, h, scr.shape[1]) * state[r] + added[r]


def _per_head(values, k: int, heads: _Heads):
    """``sum_p`` over each head's channels of ``values`` (Q, 128), lane
    tile ``k``: ``[(head, (1, Q) row)]``."""
    t = values.T
    return [(k * heads.a_tile + j, jnp.sum(
        t[j * heads.width:(j + 1) * heads.width], axis=0, keepdims=True))
        for j in range(heads.a_tile)]


def _tile_of(rows, height: int):
    """(1, width) rows as a (height, width) tile, zeros below."""
    pad = height - len(rows)
    return jnp.concatenate(
        rows + ([jnp.zeros((pad, rows[0].shape[1]), _F32)] if pad else []),
        0)


class _Tile(NamedTuple):
    """A lane tile of a chunk's rows up to the norm, (Q, 128) float32 each."""
    x: jax.Array
    by: _Spread
    v: jax.Array         # y + skip x
    z: jax.Array
    sig: jax.Array       # sigmoid(z)
    g: jax.Array         # v silu(z)


def _gated_rows(x_ref, z_ref, skip_ref, sc: _Scalars, scores, began, lower,
                heads: _Heads, mm):
    """``(lane tiles, {head: its decay matrix})`` of the chunk: the scan's
    ``y`` with the skip and the gate, as both passes need it."""
    tiles, decays = [], {}
    for t in range(heads.channels // _TILE):
        lanes = _lanes(t)
        x = x_ref[0, :, lanes].astype(_F32)
        by = _spreads(sc, t, heads)
        # what the state the chunk began with adds to its rows
        y = by.began * began[:, lanes]
        for h, own in _tile_heads(t, heads):
            decays[h] = _decay(sc, h, lower)
            m = scores * decays[h] * sc.dt_rows[h:h + 1, :]
            y = y + _dot(m, _own(x, own), mm)
        z = z_ref[0, :, lanes].astype(_F32)
        sig = 1.0 / (1.0 + jnp.exp(-z))
        v = y + skip_ref[:, lanes] * x
        tiles.append(_Tile(x, by, v, z, sig, v * (z * sig)))
    return tiles, decays


def _across(values, channels: int):
    """The mean over the group's channels of ``values`` (a (Q, 128) value a
    lane tile), broadcast over a tile's lanes."""
    return jnp.broadcast_to(
        jnp.sum(functools.reduce(jnp.add, values), axis=1, keepdims=True)
        / channels, (_Q, _TILE))


def _left(tiles):
    """Each token's write decayed to the chunk's end, (Q, R P)."""
    return jnp.concatenate([t.x * (t.by.ends * t.by.dt) for t in tiles], 1)


def _fwd_kernel(x_ref, z_ref, b_ref, c_ref, sc_ref, skip_ref, w_ref, o_ref,
                *rest, heads: _Heads, eps: float, precision,
                save_borders: bool):
    if save_borders:
        borders_ref, s_scr = rest
    else:
        (s_scr,) = rest

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    mm = _Products(x_ref.dtype, precision)
    state = s_scr[...]
    if save_borders:
        borders_ref[0, 0, 0] = state
    sc = _scalars(sc_ref[0, 0, 0], heads)
    b, c = b_ref[0], c_ref[0]
    tiles, _ = _gated_rows(x_ref, z_ref, skip_ref, sc, _dot_nt(c, b, mm),
                           _dot_nt(c, state, mm), _lower(), heads, mm)
    # the grouped norm: a group's channels are this grid step's
    scale = lax.rsqrt(_across([t.g * t.g for t in tiles], heads.channels)
                      + eps)
    for t, tile in enumerate(tiles):
        lanes = _lanes(t)
        o_ref[0, :, lanes] = (tile.g * scale * w_ref[:, lanes]).astype(
            o_ref.dtype)
    # the state the chunk leaves
    _carry_on(s_scr, sc, state, _dot_tn(_left(tiles), b, mm), heads)


def _bwd_kernel(x_ref, z_ref, b_ref, c_ref, sc_ref, skip_ref, w_ref,
                borders_ref, do_ref, dx_ref, dz_ref, db_ref, dc_ref,
                sums_ref, carried_ref, dskip_ref, dw_ref, ds_scr,
                *, heads: _Heads, eps: float, precision):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    mm = _Products(x_ref.dtype, precision)
    states = ds_scr.shape[1]
    every = range(heads.count)
    height = sc_ref.shape[3]
    lane = lax.broadcasted_iota(jnp.int32, (_Q, _TILE), 1)
    state, dstate = borders_ref[0, 0, 0], ds_scr[...]
    sc = _scalars(sc_ref[0, 0, 0], heads)
    b, c = b_ref[0], c_ref[0]
    scores = _dot_nt(c, b, mm)                                  # (Q, Q)
    began = _dot_nt(c, state, mm)                               # (Q, R P)
    dleft = _dot_nt(b, dstate, mm)                              # (Q, R P)
    tiles, decays = _gated_rows(x_ref, z_ref, skip_ref, sc, scores, began,
                                _lower(), heads, mm)

    # through the norm and the gate, to dy
    scale = lax.rsqrt(_across([t.g * t.g for t in tiles], heads.channels)
                      + eps)
    normed, dnormed, dws = [], [], []
    for t, tile in enumerate(tiles):
        lanes = _lanes(t)
        do = do_ref[0, :, lanes].astype(_F32)
        normed.append(tile.g * scale)
        dnormed.append(do * w_ref[:, lanes])
        dws.append(jnp.sum(do * normed[t], axis=0, keepdims=True))
    dw_ref[0] += jnp.concatenate(dws, 1)
    along = _across([d * n for d, n in zip(dnormed, normed)], heads.channels)
    dys, dskips = [], []
    for t, tile in enumerate(tiles):
        lanes = _lanes(t)
        dg = scale * (dnormed[t] - normed[t] * along)
        dz_ref[0, :, lanes] = (dg * tile.v * tile.sig * (
            1.0 + tile.z * (1.0 - tile.sig))).astype(dz_ref.dtype)
        dys.append(dg * (tile.z * tile.sig))
        dskips.append(jnp.sum(dys[t] * tile.x, axis=0, keepdims=True))
    dskip_ref[0] += jnp.concatenate(dskips, 1)

    # through the scan
    dscores = jnp.zeros((_Q, _Q), _F32)
    row_sums = jnp.zeros((_Q, _TILE), _F32)
    dbegan = []
    col_sums, began_sums, wrote_sums = {}, {}, {}
    for t, (tile, dy) in enumerate(zip(tiles, dys)):
        lanes = _lanes(t)
        wrote = tile.by.ends * dleft[:, lanes]
        z = wrote
        for h, own in _tile_heads(t, heads):
            step = sc.dt_rows[h:h + 1, :]
            dy_h = _own(dy, own)
            ungated = scores * decays[h]                        # M'
            z = z + _dot_tn(ungated, dy_h, mm)
            dm = _dot_nt(dy_h, tile.x, mm)                      # (Q, Q)
            dscores = dscores + dm * decays[h] * step
            # dM o M' summed down its columns (dt's) and, with dt, along
            # its rows (cum_i's; cum_j's is dt's times dt): the same
            # products on both sides, so that what cancels does
            through = dm * ungated
            col_sums[h] = jnp.sum(through, axis=0, keepdims=True)
            row_sums = jnp.where(
                lane == h,
                jnp.sum(through * step, axis=1, keepdims=True), row_sums)
        dx_ref[0, :, lanes] = (tile.by.dt * z + skip_ref[:, lanes] * dy
                               ).astype(dx_ref.dtype)
        gated = tile.by.began * dy
        wrote_sums.update(_per_head(tile.x * wrote, t, heads))
        began_sums.update(_per_head(gated * began[:, lanes], t, heads))
        dbegan.append(gated)
    dbegan = jnp.concatenate(dbegan, 1)
    dc_ref[0] = (_dot(dscores, b, mm)
                 + _dot(dbegan, state, mm)).astype(dc_ref.dtype)
    db_ref[0] = (_dot_tn(dscores, c, mm)
                 + _dot(_left(tiles), dstate, mm)).astype(db_ref.dtype)
    row_sums = row_sums.T
    sums_ref[0, 0, 0] = jnp.concatenate([
        _tile_of([row_sums[h:h + 1] + began_sums[h] for h in every], height),
        _tile_of([col_sums[h] for h in every], height),
        _tile_of([wrote_sums[h] for h in every], height)], 1)
    # e^cum_last <dS', S> a head, its lanes still to be summed
    through = dstate * state
    carried_ref[0, 0, 0] = _tile_of(
        [_carried(sc, h, states) * jnp.sum(
            through[_head_rows(h, heads)], axis=0, keepdims=True)
         for h in every], height)
    _carry_on(ds_scr, sc, dstate, _dot_tn(dbegan, c, mm), heads)


# ---- the calls -----------------------------------------------------------

def _specs(x, s: int, heads: _Heads, reverse: bool):
    """``(grid, block specs)`` both passes share, from ``x`` (N, Tp, H P):
    the grid is ``(batch, group, time block)``, the last axis sequential
    (``reverse``: from the sequence's end). The specs: rows of ``x``'s
    kind, rows of ``B``'s kind, a chunk's and group's scalars, border
    state, the backward's three sums a token and its one a chunk, a row a
    channel (the skip, the norm's weight) and its gradient a batch row."""
    n, tp, d = x.shape
    steps = tp // _Q
    step = (lambda t: steps - 1 - t) if reverse else (lambda t: t)
    channels = pl.BlockSpec((1, _Q, heads.channels),
                            lambda i, j, t: (i, step(t), j))
    shared = pl.BlockSpec((1, _Q, s), lambda i, j, t: (i, step(t), j))

    def per_chunk(*tile):
        return pl.BlockSpec((1, 1, 1) + tile,
                            lambda i, j, t: (i, step(t), j, 0, 0))

    a_row = pl.BlockSpec((1, heads.channels), lambda i, j, t: (0, j))
    partial_row = pl.BlockSpec((1, 1, heads.channels),
                               lambda i, j, t: (i, 0, j))
    return (n, d // heads.channels, steps), (
        channels, shared, per_chunk(heads.rows, 2 * _Q),
        per_chunk(heads.channels, s), per_chunk(heads.rows, 3 * _Q),
        per_chunk(heads.rows, s), a_row, partial_row)


def _vmem_need(s: int, heads: _Heads, backward: bool) -> int:
    """Scoped VMEM a pass may ask for: its double-buffered blocks, its
    scratch and the float32 temporaries of one chunk."""
    rows = _Q * heads.channels * 4
    state = heads.channels * s * 4
    blocks = (5 if backward else 3) * rows + 4 * _Q * s * 4 \
        + state + 5 * heads.rows * _Q * 4
    temps = (24 if backward else 8) * rows + 4 * state \
        + (2 * heads.count * _Q * _Q * 4 if backward else 0)
    return 2 * blocks + state + temps


# Both calls are jitted so that a model's layers, whose shapes are the
# same, trace a kernel's body once and not once a layer: eight heads a
# chunk unrolled are a few thousand equations, and four layers' twelve
# traces were seconds of every process's set-up.
@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "precision", "save_borders", "interpret"))
def _forward(x, z, b, c, sc, skip, w, heads: _Heads, eps: float, precision,
             save_borders: bool, interpret: bool):
    n, tp, d = x.shape
    s = b.shape[2] // (d // heads.channels)
    grid, (channels, shared, scalars, borders, _, _, a_row, _) = _specs(
        x, s, heads, reverse=False)
    # the normed rows and, when differentiated, the state a chunk found
    states = jax.ShapeDtypeStruct(
        (n, tp // _Q, grid[1], heads.channels, s), _F32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, eps=eps,
                          precision=precision, save_borders=save_borders),
        grid=grid,
        in_specs=[channels, channels, shared, shared, scalars, a_row, a_row],
        out_specs=[channels] + [borders] * save_borders,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)]
        + [states] * save_borders,
        scratch_shapes=[pltpu.VMEM((heads.channels, s), _F32)],
        compiler_params=_dim_sem(3, scoped_vmem_limit(
            _vmem_need(s, heads, backward=False))),
        name="ssd_scan_fwd",
        interpret=interpret,
    )(x, z, b, c, sc, skip, w)


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "precision", "interpret"))
def _backward(x, z, b, c, sc, skip, w, borders, do, heads: _Heads,
              eps: float, precision, interpret: bool):
    n, _, d = x.shape
    s = borders.shape[-1]
    grid, (channels, shared, scalars, border_blocks, sums, carried, a_row,
           partial_row) = _specs(x, s, heads, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, eps=eps,
                          precision=precision),
        grid=grid,
        in_specs=[channels, channels, shared, shared, scalars, a_row, a_row,
                  border_blocks, channels],
        out_specs=[channels, channels, shared, shared, sums, carried,
                   partial_row, partial_row],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype)]
        + [jax.ShapeDtypeStruct(sc.shape[:3] + spec.block_shape[3:], _F32)
           for spec in (sums, carried)]
        + [jax.ShapeDtypeStruct((n, 1, d), _F32)] * 2,
        scratch_shapes=[pltpu.VMEM((heads.channels, s), _F32)],
        compiler_params=_dim_sem(3, scoped_vmem_limit(
            _vmem_need(s, heads, backward=True))),
        name="ssd_scan_bwd",
        interpret=interpret,
    )(x, z, b, c, sc, skip, w, borders, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _scan(x, z, b, c, sc, skip, w, heads, eps, precision, interpret):
    return _forward(x, z, b, c, sc, skip, w, heads, eps, precision, False,
                    interpret)[0]


def _scan_fwd(x, z, b, c, sc, skip, w, heads, eps, precision, interpret):
    o, borders = _forward(x, z, b, c, sc, skip, w, heads, eps, precision,
                          True, interpret)
    # the tagged result is the primal too (``_flash_fwd_rule``'s reason)
    o = checkpoint_name(o, SSD_OUT_NAME)
    borders = checkpoint_name(borders, SSD_BORDERS_NAME)
    return o, (x, z, b, c, sc, skip, w, borders)


def _scan_bwd(heads, eps, precision, interpret, res, do):
    sc = res[4]
    dx, dz, db, dc, sums, carried, dskip, dw = _backward(
        *res, do, heads, eps, precision, interpret)
    step = sc[..., _Q:]
    # a token's three sums: what cum_i gains (the rows of dM o M and the
    # state the chunk began with), dt's gradient through M and through the
    # state the chunk leaves; cum loses dt's times dt, and cum_last gains
    # what the tokens' writes lost and e^cum_last <dS', S>
    gains, through_m, through_left = (
        sums[..., i * _Q:(i + 1) * _Q] for i in range(3))
    ddt = through_m + through_left
    last = (jnp.sum(step * through_left, -1, keepdims=True)
            + jnp.sum(carried, -1, keepdims=True))
    dcum = gains - step * ddt + last * (jnp.arange(_Q) == _Q - 1)
    return (dx, dz, db, dc, jnp.concatenate([dcum, ddt], -1),
            jnp.sum(dskip, 0), jnp.sum(dw, 0))


_scan.defvjp(_scan_fwd, _scan_bwd)


def kernel_chunks(t: int) -> int:
    """Chunks one pass walks through the kernels for ``t`` tokens."""
    return -(-t // _Q)


def ssd_scan_kernels(x, dt, a, b, c, z, skip, norm_w, eps: float,
                     interpret: Optional[bool] = None):
    """The Mamba-2 scan with the mixer's skip, gate and grouped norm
    through the Pallas kernels, in chunks of 128. ``x`` (N, T, H, P) in the
    compute type, ``P`` a divisor of 128 and the ``H / G`` heads of a
    group a multiple of 128 channels; ``dt`` (N, T, H); ``a``, ``skip``
    (H,); ``b``, ``c`` (N, T, G, S), ``S`` a multiple of 128; ``z`` (N, T,
    H P); ``norm_w`` (H P,). Returns ``gated_group_norm`` of the scan's
    ``y`` from a zero state, (N, T, H P) in ``x``'s type. ``interpret``
    defaults to ``pallas_interpret()``."""
    if interpret is None:
        interpret = pallas_interpret()
    n, t, h, p = x.shape
    g, s = b.shape[2:]
    heads = _Heads(h // g, p)
    chunks = kernel_chunks(t)
    pad = chunks * _Q - t
    mm = x.dtype

    def rows(v):            # (N, T, ...) -> (N, Tp, -1), tokens of step 0
        v = v.reshape(n, t, -1)                      # behind: the state stays
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v

    # the scalars a chunk and group, (N, chunks, G, rows, 2 Q): heads on
    # the sublanes, ``[cum | dt]`` along the lanes. The running sum inside
    # a chunk is a float32 product with a triangle of ones (``highest``:
    # the summands are not rounded), which also turns the tokens to the
    # lanes; XLA's own cumulative sum took 1.25 ms a pass on the v5e
    step = rows(dt.astype(_F32)).reshape(n, chunks, _Q, g, heads.count)
    cum = jnp.einsum(
        "ncqgr,qk->ncgrk", step * a.astype(_F32).reshape(g, heads.count),
        jnp.triu(jnp.ones((_Q, _Q), _F32)), precision=lax.Precision.HIGHEST)
    sc = jnp.concatenate([cum, jnp.moveaxis(step, 2, 4)], 4)
    if heads.rows > heads.count:
        sc = jnp.pad(sc, ((0, 0),) * 3 + ((0, heads.rows - heads.count),
                                          (0, 0)))
    o = _scan(rows(x), rows(z.astype(mm)), rows(b.astype(mm)),
              rows(c.astype(mm)), sc,
              jnp.repeat(skip.astype(_F32), p)[None],
              norm_w.astype(_F32)[None], heads, eps,
              jax.config.jax_default_matmul_precision, bool(interpret))
    return o[:, :t]


def kernels_take(x, b, chunk_size: int) -> bool:
    """Whether the kernels run what ``ssd_scan`` is handed: the backend a
    TPU, the chunk 128, the states and a group's channels multiples of 128
    with a head a divisor of a lane tile (and a multiple of 8), a group's
    scalars one tile, float32 the type the plain form would
    compute in."""
    h, p = x.shape[-2:]
    g, s = b.shape[-2:]
    return (jax.default_backend() == "tpu" and int(chunk_size) == _Q
            and s % _TILE == 0 and (h // g) * p % _TILE == 0
            and p % 8 == 0 and _TILE % p == 0
            and 2 * _Heads(h // g, p).rows <= _TILE
            and jnp.promote_types(_F32, x.dtype) == _F32)


def ssd_scan(x, dt, a, b, c, z, skip, norm_w, eps: float,
             chunk_size: int = SSD_CHUNK, layer: Optional[str] = None):
    """A ``Mamba2Mixer``'s scan from a zero state with its skip, gate and
    grouped norm, ``gated_group_norm(ssd_chunked(...)[0], ...)``, for ``x``
    (N, T, H, P), ``dt`` (N, T, H), ``a`` and ``skip`` (H,), ``b`` and
    ``c`` (N, T, G, S), ``z`` (N, T, H P) and ``norm_w`` (H P,): the Pallas
    kernels where ``kernels_take``, else the plain forms (``ssd_chunked``
    is also the one that continues from a state). The choice rests on the
    inputs alone; a kernel the compiler refuses raises. With ``layer``,
    the caller's name, the chunks a pass walks through the kernels (0 for
    the plain form) are published as the gauge ``dl4j_ssd_kernel_chunks``
    at trace time."""
    takes = kernels_take(x, b, chunk_size)
    if layer is not None:
        from deeplearning4j_tpu.observe.registry import default_registry
        default_registry().gauge(*SSD_KERNEL_GAUGE).set(
            kernel_chunks(x.shape[1]) if takes else 0, layer=layer)
    if takes:
        return ssd_scan_kernels(x, dt, a, b, c, z, skip, norm_w, eps)
    y, _ = ssd_chunked(x, dt, a, b, c, chunk_size=chunk_size)
    return gated_group_norm(y, x, z, skip, norm_w, b.shape[-2], eps)
