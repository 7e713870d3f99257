"""The chunked gated delta rule as Pallas TPU kernels, backward by hand.

What ``nn.layers.linear_attention.chunk_gated_delta_rule`` computes with
batched XLA products and a ``lax.scan`` over chunks runs here as one
kernel a pass. Per chunk (``C`` tokens of one value head; ``gc`` the
running sum of ``g`` inside the chunk, ``D[i, j] = e^(gc_i - gc_j)`` for
``j <= i``):

    A = strict_lower((beta k) k^T * D)      Tm = (I + A)^-1
    u = Tm (beta v)                         w = Tm (beta k e^gc)
    P = tril(q k^T * D)
    v_new = u - w S
    o     = (q e^gc) S + P v_new
    S'    = e^gc_last S + (k e^(gc_last - gc))^T v_new

Everything but ``S`` is local to the chunk. The kernels take a
*super-chunk* of ``W`` tokens a time (``W = 128`` for chunks that divide
128, else one chunk) and treat its chunks' ``A``, ``Tm`` and ``P`` as one
block-diagonal ``W x W`` matrix, so that the inverse (sum_k (-A)^k by
doubling: ``[P_j; R_j] P_j`` gives ``P_(j+1) = P_j^2`` and ``R_(j+1) =
R_j + R_j P_j`` in one product) and the WY products fill the matrix
unit's tile; the state then walks the chunks one by one, ``S`` in VMEM
scratch along a grid axis marked ``arbitrary``. A grid step takes one
key head and the value heads it serves, so ``q`` and ``k`` are read where
the layer's convolution left them, ``(N, T, H * D)`` with a head as a
column block, and are never repeated.

The backward walks the chunks in reverse with ``dS`` in scratch. It is
handed the states at the chunk borders (float32) and the inverses ``Tm``
(compute type), the forward's residuals besides its inputs, computes the
other chunk-local quantities again, and uses ``dA = -Tm^T dTm Tm^T`` for
the inverse (inverting again instead of reading ``Tm`` back cost 6.2 ms
against 4.0 a layer on the v5e). The per-token scalars travel as
``(8, W)`` tiles ``[gc, gc_last, beta, 0...]`` and their gradients come
back the same way; the running sum and its transpose are XLA's, outside.

Types as in the plain form: gates, decay sums and the carried state
float32; matrix products take their operands in the compute type
(``v``'s) and accumulate in float32.

``gated_delta_rule`` chooses between the kernels and the plain form from
what it is handed (backend, head sizes, chunk); a kernel the compiler
refuses raises.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas_kernels import (
    _dim_sem, pallas_interpret, scoped_vmem_limit)

_F32 = jnp.float32
_ROWS = 8        # rows of a scalar tile: gc, gc_last, beta, then zeros

# ``checkpoint_name`` tags of what the forward kernel writes when
# differentiated: the result and the backward's two residuals (the states at
# the chunk borders, float32, and the inverses ``Tm``, compute type). A
# ``jax.checkpoint`` whose policy saves these names (the decoder blocks')
# keeps all three and does not launch the forward kernel again; anywhere
# else the tags are the identity. All three come out of one ``pallas_call``.
DELTA_RULE_OUT_NAME = "gdn_delta_rule.o"
DELTA_RULE_STATES_NAME = "gdn_delta_rule.states"
DELTA_RULE_TM_NAME = "gdn_delta_rule.tm"

GDN_KERNEL_GAUGE = (
    "dl4j_gdn_kernel_chunks",
    "chunks one head's pass of the gated delta rule walks through the "
    "Pallas kernels, as the step was last traced; 0 where the plain "
    "chunked form was traced (label: the layer)")


# ---- inside the kernels --------------------------------------------------

class _Products(NamedTuple):
    """How the kernels' matrix products take their operands: the compute
    type and the precision the forward was traced under (handed to the
    backward too: a ``custom_vjp``'s backward is traced outside a
    ``jax.default_matmul_precision`` block round the forward)."""
    dtype: Any
    precision: Optional[str]


def _dot_general(a, b, contract, mm: _Products):
    return lax.dot_general(a.astype(mm.dtype), b.astype(mm.dtype),
                           (contract, ((), ())),
                           precision=mm.precision,
                           preferred_element_type=_F32)


def _dot(a, b, mm):
    return _dot_general(a, b, ((1,), (0,)), mm)


def _dot_nt(a, b, mm):
    """``a b^T``."""
    return _dot_general(a, b, ((1,), (1,)), mm)


def _dot_tn(a, b, mm):
    """``a^T b``."""
    return _dot_general(a, b, ((0,), (0,)), mm)


def _columns(tile):
    """An ``(8, W)`` tile of per-token rows as ``(W, 128)``: lane ``j``
    holds row ``j % 8`` as a column."""
    return jnp.concatenate([tile] * (128 // _ROWS), axis=0).T


def _rows(columns):
    """The inverse: lanes 0-7 of ``(W, 128)`` as an ``(8, W)`` tile."""
    return columns.T[:_ROWS]


def _masks(w: int, c: int):
    """``(lower, strictly lower, diagonal)`` of the block-diagonal ``W x
    W`` pattern whose blocks are chunks of ``c``."""
    row = lax.broadcasted_iota(jnp.int32, (w, w), 0)
    col = lax.broadcasted_iota(jnp.int32, (w, w), 1)
    same = None
    for j in range(w // c):
        block = ((row >= j * c) & (row < (j + 1) * c)
                 & (col >= j * c) & (col < (j + 1) * c))
        same = block if same is None else same | block
    return same & (col <= row), same & (col < row), row == col


def _unit_lower_inverse(a, eye, c: int, mm):
    """``(I + a)^-1`` for ``a`` strictly lower triangular in blocks of
    ``c``: with ``b = -a`` nilpotent, ``sum_(k < c) b^k`` by doubling."""
    w = a.shape[0]
    power = -a
    inv = jnp.where(eye, 1.0, power)                 # sum_(k < 2) b^k
    levels = max(1, (c - 1).bit_length())
    if levels == 1:
        return inv
    power = _dot(power, power, mm)                   # b^2
    for j in range(1, levels):
        if j < levels - 1:
            x = _dot(jnp.concatenate([power, inv], 0), power, mm)
            power, inv = x[:w], inv + x[w:]
        else:
            inv = inv + _dot(inv, power, mm)
    return inv


class _Local(NamedTuple):
    """What a super-chunk holds besides the state (float32)."""
    gl: jax.Array        # (W, 1) columns of the scalar tile
    beta: jax.Array
    e: jax.Array         # e^gc (W, 1)
    el: jax.Array        # e^(gc_last - gc) (W, 1)
    decay: jax.Array     # (W, W), 0 outside the chunks' lower triangles
    kb: jax.Array        # beta k
    vb: jax.Array        # beta v
    kbg: jax.Array       # beta k e^gc
    qg: jax.Array        # q e^gc
    kd: jax.Array        # k e^(gc_last - gc)
    a: jax.Array         # (W, W)
    p: jax.Array         # (W, W)
    tm: jax.Array        # (W, W)
    u: jax.Array         # (W, Dv)
    w: jax.Array         # (W, Dk)


def _local(q, k, v, tile, masks, c: int, mm, tm=None) -> _Local:
    low, slow, eye = masks
    w = q.shape[0]
    dv = v.shape[1]
    cols = _columns(tile)
    gc, gl, beta = cols[:, 0:1], cols[:, 1:2], cols[:, 2:3]
    # masked before the exponential, whose argument above the diagonal
    # (and between two chunks) is no decay
    decay = jnp.exp(jnp.where(low, gc - tile[0:1, :], -jnp.inf))
    e = jnp.exp(gc)
    el = jnp.exp(gl - gc)
    kf = k.astype(_F32)
    kb = kf * beta
    vb = v.astype(_F32) * beta
    kbg = kb * e
    gram = _dot_nt(jnp.concatenate([kb.astype(mm.dtype), q], 0), k, mm)
    a = jnp.where(slow, gram[:w] * decay, 0.0)
    p = gram[w:] * decay
    if tm is None:
        tm = _unit_lower_inverse(a, eye, c, mm)
    uw = _dot(tm, jnp.concatenate([vb, kbg], 1), mm)
    return _Local(gl, beta, e, el, decay, kb, vb, kbg,
                  q.astype(_F32) * e, kf * el, a, p, tm,
                  uw[:, :dv], uw[:, dv:])


def _carry(gl, token: int, width: int):
    """``e^gc_last`` of the chunk that holds ``token`` as a ``(1, width)``
    row (a ``(1, 1)`` value does not broadcast both ways at once)."""
    return jnp.exp(jnp.broadcast_to(gl[token:token + 1], (1, width)))


def _place(x, j: int, chunks: int):
    """``x`` (C, D) as rows of chunk ``j`` of a super-chunk, zeros in the
    others: the operand a block-diagonal matrix's rows meet."""
    if chunks == 1:
        return x
    zero = jnp.zeros_like(x)
    return jnp.concatenate([x if i == j else zero for i in range(chunks)], 0)


def _fwd_kernel(q_ref, k_ref, v_ref, sc_ref, s0_ref, o_ref, sT_ref, *rest,
                chunk: int, rep: int, precision, save_residuals: bool):
    if save_residuals:
        states_ref, tm_ref, s_scr = rest
    else:
        (s_scr,) = rest
    ts = pl.program_id(2)

    @pl.when(ts == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    mm = _Products(v_ref.dtype, precision)
    w = sc_ref.shape[-1]
    supers = q_ref.shape[1] // w
    chunks = w // chunk
    dv = v_ref.shape[2] // rep
    masks = _masks(w, chunk)
    for r in range(rep):
        heads = slice(r * dv, (r + 1) * dv)
        for s in range(supers):
            rows = slice(s * w, (s + 1) * w)
            loc = _local(q_ref[0, rows, :], k_ref[0, rows, :],
                         v_ref[0, rows, heads], sc_ref[0, r, s], masks,
                         chunk, mm)
            if save_residuals:
                tm_ref[0, r, s] = loc.tm.astype(tm_ref.dtype)
            state = s_scr[r]
            outs = []
            for j in range(chunks):
                cr = slice(j * chunk, (j + 1) * chunk)
                if save_residuals:
                    states_ref[0, r, s * chunks + j] = state
                ws = _dot(jnp.concatenate([loc.w[cr], loc.qg[cr]], 0),
                          state, mm)
                v_new = loc.u[cr] - ws[:chunk]
                outs.append(ws[chunk:] + _dot(
                    loc.p[cr], _place(v_new, j, chunks), mm))
                state = (state * _carry(loc.gl, j * chunk, dv)
                         + _dot_tn(loc.kd[cr], v_new, mm))
            s_scr[r] = state
            o_ref[0, rows, heads] = (outs[0] if chunks == 1
                                     else jnp.concatenate(outs, 0))

    @pl.when(ts == pl.num_programs(2) - 1)
    def _final():
        sT_ref[0] = s_scr[...]


def _bwd_kernel(q_ref, k_ref, v_ref, sc_ref, states_ref, tm_ref, do_ref,
                dsT_ref, dq_ref, dk_ref, dv_ref, dsc_ref, ds0_ref, ds_scr,
                *, chunk: int, rep: int, precision):
    ts = pl.program_id(2)

    @pl.when(ts == 0)
    def _init():
        ds_scr[...] = dsT_ref[0]

    mm = _Products(v_ref.dtype, precision)
    w = sc_ref.shape[-1]
    supers = q_ref.shape[1] // w
    chunks = w // chunk
    dv = v_ref.shape[2] // rep
    masks = _masks(w, chunk)
    low, slow, _ = masks
    lane = lax.broadcasted_iota(jnp.int32, (w, 128), 1)
    first = None                       # rows that open a chunk, (W, 1)
    token = lax.broadcasted_iota(jnp.int32, (w, 1), 0)
    for j in range(chunks):
        first = (token == j * chunk) if first is None \
            else first | (token == j * chunk)

    for s in reversed(range(supers)):
        rows = slice(s * w, (s + 1) * w)
        q, k = q_ref[0, rows, :], k_ref[0, rows, :]
        dq_sum = dk_sum = None
        for r in range(rep):
            heads = slice(r * dv, (r + 1) * dv)
            v = v_ref[0, rows, heads]
            do = do_ref[0, rows, heads]
            loc = _local(q, k, v, sc_ref[0, r, s], masks, chunk, mm,
                         tm=tm_ref[0, r, s])
            p_t_do = _dot_tn(loc.p, do, mm)                    # (W, Dv)
            # the walk, in reverse
            dstate = ds_scr[r]
            v_new, dv_new, dkd, dws, carried = ([None] * chunks
                                                for _ in range(5))
            for j in reversed(range(chunks)):
                cr = slice(j * chunk, (j + 1) * chunk)
                state = states_ref[0, r, s * chunks + j]
                v_new[j] = loc.u[cr] - _dot(loc.w[cr], state, mm)
                dv_new[j] = p_t_do[cr] + _dot(loc.kd[cr], dstate, mm)
                dkd[j] = _dot_nt(v_new[j], dstate, mm)         # (C, Dk)
                carry = _carry(loc.gl, j * chunk, dv)
                # d e^gc_last = <dS', S>, times e^gc_last for gc_last's
                carried[j] = carry[:, 0:1] * jnp.sum(
                    jnp.sum(dstate * state, axis=1, keepdims=True),
                    axis=0, keepdims=True)
                # [dw; d(q e^gc)] = [-dv_new; do] S^T
                dws[j] = _dot_nt(
                    jnp.concatenate([-dv_new[j], do[cr]], 0), state, mm)
                dstate = dstate * carry + _dot_tn(
                    jnp.concatenate([loc.qg[cr], -loc.w[cr]], 0),
                    jnp.concatenate([do[cr], dv_new[j]], 0), mm)
            ds_scr[r] = dstate

            def whole(parts):
                return (parts[0] if chunks == 1
                        else jnp.concatenate(parts, 0))

            v_new, du, dkd = whole(v_new), whole(dv_new), whole(dkd)
            dw = whole([x[:chunk] for x in dws])
            dqg = whole([x[chunk:] for x in dws])
            dgl_first = whole([jnp.broadcast_to(x, (chunk, 1))
                               for x in carried])
            # chunk-local gradients
            duw = jnp.concatenate([du, dw], 1)
            dp = jnp.where(low, _dot_nt(do, v_new, mm), 0.0)
            dtm = _dot_nt(duw, jnp.concatenate([loc.vb, loc.kbg], 1), mm)
            da = -_dot_nt(_dot_tn(loc.tm, dtm, mm), loc.tm, mm)
            da = jnp.where(slow, da, 0.0)
            dvb_dkbg = _dot_tn(loc.tm, duw, mm)
            dvb, dkbg = dvb_dkbg[:, :dv], dvb_dkbg[:, dv:]
            dgram = jnp.concatenate([da * loc.decay, dp * loc.decay], 0)
            through = da * loc.a + dp * loc.p          # d decay * decay
            dkb_dq = _dot(dgram, k, mm)
            dkb = dkb_dq[:w] + dkbg * loc.e
            dq = dkb_dq[w:] + dqg * loc.e
            dk = (_dot_tn(dgram, jnp.concatenate(
                      [loc.kb.astype(mm.dtype), q], 0), mm)
                  + dkd * loc.el + dkb * loc.beta)
            dq_sum = dq if dq_sum is None else dq_sum + dq
            dk_sum = dk if dk_sum is None else dk_sum + dk
            dv_ref[0, rows, heads] = (dvb * loc.beta).astype(dv_ref.dtype)

            def lanes(x):
                return jnp.sum(x, axis=1, keepdims=True)

            kd_side = lanes(dkd * loc.kd)
            dgc = (lanes(through) + lanes(dqg * loc.qg) - kd_side
                   + lanes(dkbg * loc.kbg))
            dgl = kd_side + jnp.where(first, dgl_first, 0.0)
            dbeta = (lanes(dkb * k.astype(_F32))
                     + lanes(dvb * v.astype(_F32)))
            tile = _rows(jnp.where(
                lane == 0, dgc, jnp.where(
                    lane == 1, dgl, jnp.where(lane == 2, dbeta, 0.0))))
            row = lax.broadcasted_iota(jnp.int32, (_ROWS, w), 0)
            dsc_ref[0, r, s] = tile - jnp.where(
                row == 0, jnp.sum(through, axis=0, keepdims=True), 0.0)
        dq_ref[0, rows, :] = dq_sum.astype(dq_ref.dtype)
        dk_ref[0, rows, :] = dk_sum.astype(dk_ref.dtype)

    @pl.when(ts == pl.num_programs(2) - 1)
    def _final():
        ds0_ref[0] = ds_scr[...]


# ---- the calls -----------------------------------------------------------

# super-chunks a grid step takes; on the v5e at the Qwen3-Next cell's shapes
# (my chip runs, PR 32) 1 / 2 / 4 / 8 read forward 5.21 / 5.08 / 5.01 / 4.98 ms
# and backward 6.20 / 5.88 / 5.79 / 5.61 (with the inverse read back 1 / 2:
# 3.97 / 3.64); the kernel's text and its compile time grow with it
_SUPERS_A_STEP = 2


def _super_width(chunk: int) -> int:
    return 128 if 128 % chunk == 0 else chunk


def _specs(q, border_like, chunk: int, reverse: bool):
    """``(grid, value heads a key head, block specs)`` every pass shares,
    from ``q`` (N, Tp, Hk * Dk) and a state (N, Hv, Dk, Dv): the grid is
    ``(batch, key head, step)``, the last axis sequential (``reverse``:
    from the sequence's end)."""
    n, tp, kd = q.shape
    hv, dk, dv = border_like.shape[1:]
    hk = kd // dk
    rep = hv // hk
    w = _super_width(chunk)
    bt = w * _SUPERS_A_STEP
    steps = tp // bt
    step = (lambda t: steps - 1 - t) if reverse else (lambda t: t)
    tokens = lambda width: pl.BlockSpec(
        (1, bt, width), lambda i, j, t: (i, step(t), j))
    scalars = pl.BlockSpec((1, rep, _SUPERS_A_STEP, _ROWS, w),
                           lambda i, j, t: (i, j, step(t), 0, 0))
    border = pl.BlockSpec((1, rep, dk, dv), lambda i, j, t: (i, j, 0, 0))
    states = pl.BlockSpec((1, rep, bt // chunk, dk, dv),
                          lambda i, j, t: (i, j, step(t), 0, 0))
    inverses = pl.BlockSpec((1, rep, _SUPERS_A_STEP, w, w),
                            lambda i, j, t: (i, j, step(t), 0, 0))
    return (n, hk, steps), rep, (tokens, scalars, border, states, inverses)


def _forward(q, k, v, sc, s0, chunk: int, precision,
             save_residuals: bool, interpret: bool):
    n, tp, _ = q.shape
    hv, dk, dv = s0.shape[1:]
    grid, rep, (tokens, scalars, border, states, inverses) = _specs(
        q, s0, chunk, reverse=False)
    out_specs = [tokens(rep * dv), border]
    out_shape = [jax.ShapeDtypeStruct((n, tp, hv * dv), _F32),
                 jax.ShapeDtypeStruct(s0.shape, _F32)]
    if save_residuals:
        w = _super_width(chunk)
        out_specs += [states, inverses]
        out_shape += [
            jax.ShapeDtypeStruct((n, hv, tp // chunk, dk, dv), _F32),
            jax.ShapeDtypeStruct((n, hv, tp // w, w, w), v.dtype)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, rep=rep,
                          precision=precision,
                          save_residuals=save_residuals),
        grid=grid,
        in_specs=[tokens(dk), tokens(dk), tokens(rep * dv), scalars,
                  border],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), _F32)],
        compiler_params=_dim_sem(3, scoped_vmem_limit(_vmem_need(
            rep, dk, dv, chunk, backward=False))),
        name="gdn_delta_rule_fwd",
        interpret=interpret,
    )(q, k, v, sc, s0)


def _backward(q, k, v, sc, states, tm, do, ds_final, chunk: int,
              precision, interpret: bool):
    dk, dv = ds_final.shape[2:]
    grid, rep, (tokens, scalars, border, state_blocks, inverses) = _specs(
        q, ds_final, chunk, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, rep=rep,
                          precision=precision),
        grid=grid,
        in_specs=[tokens(dk), tokens(dk), tokens(rep * dv), scalars,
                  state_blocks, inverses, tokens(rep * dv), border],
        out_specs=[tokens(dk), tokens(dk), tokens(rep * dv), scalars,
                   border],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(sc.shape, _F32),
                   jax.ShapeDtypeStruct(ds_final.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), _F32)],
        compiler_params=_dim_sem(3, scoped_vmem_limit(_vmem_need(
            rep, dk, dv, chunk, backward=True))),
        name="gdn_delta_rule_bwd",
        interpret=interpret,
    )(q, k, v, sc, states, tm, do, ds_final)


def _vmem_need(rep: int, dk: int, dv: int, chunk: int,
               backward: bool) -> int:
    """Scoped VMEM a pass may ask for: its double-buffered blocks, the
    state scratch and the float32 temporaries of one super-chunk."""
    w = _super_width(chunk)
    bt = w * _SUPERS_A_STEP
    blocks = bt * (2 * dk + rep * dv) * 4 * (3 if backward else 2)
    states = rep * ((bt // chunk + 3) * dk * dv * 4 + bt * w * 2)
    temps = (24 if backward else 12) * w * max(w, dk + dv) * 4
    return 2 * (blocks + states) + rep * temps


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _delta_rule(q, k, v, sc, s0, chunk, precision, interpret):
    return tuple(_forward(q, k, v, sc, s0, chunk, precision, False,
                          interpret))


def _delta_rule_fwd(q, k, v, sc, s0, chunk, precision, interpret):
    o, s_final, states, tm = _forward(q, k, v, sc, s0, chunk, precision,
                                      True, interpret)
    # the tagged result is the primal too (``_flash_fwd_rule``'s reason)
    o = checkpoint_name(o, DELTA_RULE_OUT_NAME)
    states = checkpoint_name(states, DELTA_RULE_STATES_NAME)
    tm = checkpoint_name(tm, DELTA_RULE_TM_NAME)
    return (o, s_final), (q, k, v, sc, states, tm)


def _delta_rule_bwd(chunk, precision, interpret, res, cts):
    q, k, v, sc, states, tm = res
    do, ds_final = cts
    return tuple(_backward(q, k, v, sc, states, tm, do.astype(_F32),
                           ds_final.astype(_F32), chunk, precision,
                           interpret))


_delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd)


def kernel_chunks(t: int, chunk: int) -> int:
    """Chunks one head's pass walks through the kernels for ``t`` tokens:
    the sequence padded to whole grid steps."""
    bt = _super_width(chunk) * _SUPERS_A_STEP
    return -(-t // bt) * bt // chunk


def gated_delta_rule_kernels(q, k, v, g, beta, chunk_size: int = 64,
                             initial_state=None,
                             interpret: Optional[bool] = None):
    """The gated delta rule through the Pallas kernels. ``q``, ``k`` (N,
    T, Hk, Dk) and ``v`` (N, T, Hv, Dv) in the compute type, ``Hv`` a
    multiple of ``Hk`` (value head ``h`` reads key head ``h // (Hv /
    Hk)``), ``Dk`` and ``Dv`` multiples of 128; ``g`` = log alpha and
    ``beta`` (N, T, Hv). Returns ``(o (N, T, Hv, Dv), final state (N, Hv,
    Dk, Dv))`` in float32. ``interpret`` defaults to
    ``pallas_interpret()``."""
    if interpret is None:
        interpret = pallas_interpret()
    n, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    c = int(chunk_size)
    w = _super_width(c)
    tp = kernel_chunks(t, c) * c
    mm = v.dtype

    def tokens(a):          # (N, T, H, D) -> (N, Tp, H * D), zeros behind
        a = a.reshape(n, t, -1)
        return jnp.pad(a, ((0, 0), (0, tp - t), (0, 0))) if tp > t else a

    def per_chunk(a):       # (N, T, Hv) -> (N, Hv, chunks, C), zeros behind
        a = jnp.moveaxis(a.astype(_F32), 1, 2)
        if tp > t:          # beta = 0, g = 0: the state is left alone
            a = jnp.pad(a, ((0, 0), (0, 0), (0, tp - t)))
        return a.reshape(n, hv, tp // c, c)

    gc = jnp.cumsum(per_chunk(g), -1)
    rows = [gc, jnp.broadcast_to(gc[..., -1:], gc.shape), per_chunk(beta)]
    rows = [a.reshape(n, hv, tp // w, 1, w) for a in rows]
    sc = jnp.concatenate(
        rows + [jnp.zeros((n, hv, tp // w, _ROWS - len(rows), w), _F32)], 3)
    s0 = (jnp.zeros((n, hv, dk, dv), _F32) if initial_state is None
          else initial_state.astype(_F32))
    o, s_final = _delta_rule(tokens(q.astype(mm)), tokens(k.astype(mm)),
                             tokens(v), sc, s0, c,
                             jax.config.jax_default_matmul_precision,
                             bool(interpret))
    return o[:, :t].reshape(n, t, hv, dv), s_final


def kernels_take(q, v, chunk_size: int) -> bool:
    """Whether the kernels run what ``gated_delta_rule`` is handed: the
    backend a TPU, both head sizes multiples of 128, the chunk a multiple
    of 16."""
    return (jax.default_backend() == "tpu" and q.shape[-1] % 128 == 0
            and v.shape[-1] % 128 == 0 and int(chunk_size) % 16 == 0)


def gated_delta_rule(q, k, v, g, beta, chunk_size: int = 64,
                     initial_state=None, layer: Optional[str] = None):
    """The chunked gated delta rule for ``q``, ``k`` (N, T, Hk, Dk) and
    ``v`` (N, T, Hv, Dv): the Pallas kernels where ``kernels_take``, else
    ``chunk_gated_delta_rule`` with ``q`` and ``k`` repeated to the value
    heads. The choice rests on the inputs alone; a kernel the compiler
    refuses raises. With ``layer``, the caller's name, the chunks a head's
    pass walks through the kernels (0 for the plain form) are published
    as the gauge ``dl4j_gdn_kernel_chunks`` at trace time."""
    from deeplearning4j_tpu.nn.layers.linear_attention import (
        chunk_gated_delta_rule)
    takes = kernels_take(q, v, chunk_size)
    if layer is not None:
        from deeplearning4j_tpu.observe.registry import default_registry
        default_registry().gauge(*GDN_KERNEL_GAUGE).set(
            kernel_chunks(q.shape[1], int(chunk_size)) if takes else 0,
            layer=layer)
    if takes:
        return gated_delta_rule_kernels(q, k, v, g, beta, chunk_size,
                                        initial_state)
    rep = v.shape[2] // q.shape[2]
    if rep > 1:
        q = jnp.repeat(q, rep, axis=2)
        k = jnp.repeat(k, rep, axis=2)
    return chunk_gated_delta_rule(q, k, v, g, beta, chunk_size=chunk_size,
                                  initial_state=initial_state)
