"""Ring attention: exact attention over sequence-sharded inputs.

The reference's longest-sequence story is truncated BPTT (SURVEY §5.7);
sequence/context parallelism is ABSENT there and is designed fresh here
(SURVEY §7.2 stage 7, §7.3 item 4): each device in a mesh axis holds a
T/P slice of the sequence; K/V blocks rotate around the ring via
``lax.ppermute`` (ICI neighbor exchange) while each device accumulates
its queries' attention with a numerically-stable online softmax
(flash-attention style running max/denominator). After P steps every
query has seen every key — EXACT attention, O(T/P) memory per chip,
compute/communication overlapped by XLA.

``ring_self_attention`` matches nn/layers/attention.py's
``scaled_dot_product_attention`` bit-for-all-practical-purposes (f32
softmax accumulation) — asserted by tests/test_attention.py.

Masking uses large-FINITE score floors (not -inf): -inf produces NaN in
the softmax/exp VJPs for fully-masked rows, which would poison batch
gradients (same rationale as scaled_dot_product_attention).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

SEQ_AXIS = "sp"

_NEG = float(jnp.finfo(jnp.float32).min) / 2  # host-sync-ok: trace-time Python constant


def _ring_attention_local(q, k, v, mask, axis_name: str, causal: bool):
    """Per-device body (runs under shard_map).

    q, k, v: (N, Tl, H, Dh) local sequence shards.
    mask:    (N, Tl) local key-validity shard, or None (statically known:
             the mask carry/permute/where work is skipped entirely).
    """
    n_dev = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    tl = q.shape[1]
    dh = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    qf = q.astype(jnp.float32)
    has_mask = mask is not None

    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    m0 = jnp.full(q.shape[:1] + (q.shape[2], tl), _NEG, jnp.float32)
    l0 = jnp.zeros_like(m0)                       # (N, H, Tq)
    acc0 = jnp.zeros(q.shape, jnp.float32)        # (N, Tq, H, Dh)

    def loop_body(i, carry):
        if has_mask:
            m, l, acc, k_c, v_c, mask_c = carry
        else:
            m, l, acc, k_c, v_c = carry
        src = (my - i) % n_dev                    # owner of this K/V block
        s = jnp.einsum("nqhd,nkhd->nhqk", qf,
                       k_c.astype(jnp.float32)) * scale
        if causal:
            qpos = my * tl + jnp.arange(tl)
            kpos = src * tl + jnp.arange(tl)
            s = jnp.where(kpos[None, None, None, :]
                          <= qpos[None, None, :, None], s, _NEG)
        if has_mask:
            s = jnp.where(mask_c[:, None, None, :].astype(bool), s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        # masked entries: s == _NEG underflows exp to exact 0 for any
        # m_new ≥ O(1); for all-masked rows (m_new == _NEG) zero explicitly
        p = jnp.where(s <= _NEG, 0.0, p)
        l_new = l * corr + p.sum(-1)
        acc_new = (acc * corr.transpose(0, 2, 1)[..., None]
                   + jnp.einsum("nhqk,nkhd->nqhd", p,
                                v_c.astype(jnp.float32)))
        k_c = lax.ppermute(k_c, axis_name, perm)
        v_c = lax.ppermute(v_c, axis_name, perm)
        if has_mask:
            mask_c = lax.ppermute(mask_c, axis_name, perm)
            return m_new, l_new, acc_new, k_c, v_c, mask_c
        return m_new, l_new, acc_new, k_c, v_c

    init = ((m0, l0, acc0, k, v, mask) if has_mask
            else (m0, l0, acc0, k, v))
    out_carry = lax.fori_loop(0, n_dev, loop_body, init)
    l, acc = out_carry[1], out_carry[2]
    # (N, H, Tq) → (N, Tq, H); fully-masked rows (l == 0) emit zeros
    denom = l.transpose(0, 2, 1)[..., None]
    out = jnp.where(denom > 0, acc / jnp.maximum(denom, 1e-30), 0.0)
    return out.astype(q.dtype)


def _shard_attention(local_fn, q, k, v, mask, mesh: Mesh, axis: str,
                     batch_axis: Optional[str]):
    """Shared shard_map dispatch for sequence-parallel attention bodies:
    q/k/v sharded (batch, time) over the mesh, mask optional (statically
    absent → the body skips all mask work)."""
    bspec = batch_axis if batch_axis else None
    spec_qkv = P(bspec, axis, None, None)
    spec_mask = P(bspec, axis)
    if mask is None:
        shard_fn = jax.shard_map(
            lambda q_, k_, v_: local_fn(q_, k_, v_, None),
            mesh=mesh, in_specs=(spec_qkv,) * 3, out_specs=spec_qkv,
            check_vma=False)
        return shard_fn(q, k, v)
    shard_fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, spec_mask),
        out_specs=spec_qkv, check_vma=False)
    return shard_fn(q, k, v, mask)


def ring_self_attention(q, k, v, mesh: Mesh, *, axis: str = SEQ_AXIS,
                        mask: Optional[jax.Array] = None,
                        causal: bool = False,
                        batch_axis: Optional[str] = None):
    """Exact attention with q/k/v sharded along time over ``mesh[axis]``.

    q, k, v: (N, T, H, Dh) GLOBAL shapes; T must divide by the axis size.
    mask:    (N, T) key-validity mask (or None).
    Returns the (N, T, H, Dh) attention output, same sharding as q.
    """
    fn = functools.partial(_ring_attention_local, axis_name=axis,
                           causal=causal)
    return _shard_attention(fn, q, k, v, mask, mesh, axis, batch_axis)


def _ulysses_local(q, k, v, mask, axis_name: str, causal: bool):
    """Per-device body: all-to-all head-scatter/sequence-gather, full-
    sequence attention on the local head shard, all-to-all back."""
    a2a = functools.partial(lax.all_to_all, axis_name=axis_name,
                            tiled=True)
    qg = a2a(q, split_axis=2, concat_axis=1)   # (N, T, H/P, Dh)
    kg = a2a(k, split_axis=2, concat_axis=1)
    vg = a2a(v, split_axis=2, concat_axis=1)
    mg = (None if mask is None
          else lax.all_gather(mask, axis_name, axis=1, tiled=True))
    from deeplearning4j_tpu.ops.pallas_kernels import attention
    from deeplearning4j_tpu.ops.visibility import Causal, Visibility
    o = attention(qg, kg, vg, mask=mg,
                  visibility=Causal() if causal else Visibility())
    return a2a(o, split_axis=1, concat_axis=2)  # (N, T/P, H, Dh)


def ulysses_self_attention(q, k, v, mesh: Mesh, *, axis: str = SEQ_AXIS,
                           mask: Optional[jax.Array] = None,
                           causal: bool = False,
                           batch_axis: Optional[str] = None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style): the
    alternative SP strategy to the ring. Two all-to-alls swap the
    sequence sharding for a HEAD sharding, each device runs full-
    sequence attention (through the flash-kernel dispatch) on H/P heads,
    and a third all-to-all restores the sequence sharding.

    Trade-off vs the ring: Ulysses moves O(T·H·Dh/P) per device through
    three all-to-alls and needs ``H % P == 0``, but runs the unmodified
    single-device kernel (no online-softmax carry) and has no P-step
    serial dependency; the ring streams K/V in P hops with compute
    overlap and supports any H. Same math either way — both are asserted
    equal to ``scaled_dot_product_attention`` in tests/test_attention.py.

    q, k, v: (N, T, H, Dh) GLOBAL shapes; T and H must divide by the
    axis size. mask: (N, T) key-validity mask (or None).
    """
    p = int(mesh.shape[axis])
    if q.shape[2] % p:
        raise ValueError(f"ulysses needs heads ({q.shape[2]}) divisible"
                         f" by the {axis!r} axis ({p})")
    fn = functools.partial(_ulysses_local, axis_name=axis, causal=causal)
    return _shard_attention(fn, q, k, v, mask, mesh, axis, batch_axis)
