"""Which keys a query attends to, as one value.

The attention paths (the three flash kernels and their index maps, the
jnp/scan backward, the plain XLA lowering, the tile chooser and the
counter of visited tiles in ``ops/pallas_kernels.py``;
``nn.layers.attention.scaled_dot_product_attention``) are handed one
``Visibility`` and ask it two things: whether a pair is visible
(``visible``, ``tile_visible``; ``tile_visible_t`` for the dK/dV kernel's
tile, which is transposed), and which tiles of the (query block, key
block) grid hold a visible pair at all, so that the others are neither
fetched nor computed (``kv_tile`` / ``kv_fetch`` / ``kv_steps`` for the
kernels that stream key blocks past a query block, ``q_tile`` / ``q_fetch``
/ ``q_steps`` for the dK/dV kernel, which streams query blocks past a key
block). The instances:

- ``Visibility()``: every pair (an encoder's attention);
- ``Causal()``: a query sees itself and what came before;
- ``Causal(window)``: of those, itself and the ``window - 1`` before it;
- ``BlockDiffusion(seq_len, block)``: the training layout of a masked
  diffusion over blocks (BD3-LM, arXiv:2503.09573; SDAR,
  arXiv:2510.06303): the sequence is ``[noisy | clean]``, two copies of
  ``seq_len`` positions cut into blocks of ``block``.

The tile functions take Python ints (counting, grid extents) or traced
scalars (inside a kernel or an index map) alike.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
from jax import lax


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


def _where(cond, a, b):
    if isinstance(cond, bool):
        return a if cond else b
    return jnp.where(cond, a, b)


def _tile_positions(qi, ki, bq: int, bk: int, transposed: bool = False):
    """Query and key positions of the (bq, bk) tile, or ``transposed`` of
    its (bk, bq) transpose: key rows by query columns."""
    shape, q_axis = ((bk, bq), 1) if transposed else ((bq, bk), 0)
    qpos = qi * bq + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = ki * bk + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return qpos, kpos


@dataclasses.dataclass(frozen=True)
class Visibility:
    """Every query sees every key: the grid's inner axis runs over all the
    blocks, in order, and no tile is masked. The base of the others, which
    override what differs."""

    def visible(self, qpos, kpos):
        """Whether query position ``qpos`` sees key position ``kpos``
        (arrays that broadcast); None where every pair is visible."""
        return None

    def tile_visible(self, qi, ki, bq: int, bk: int):
        """``visible`` for the (bq, bk) tile of query block ``qi`` and key
        block ``ki``, inside a kernel."""
        return None

    def tile_visible_t(self, qi, ki, bq: int, bk: int):
        """``tile_visible`` transposed: the (bk, bq) tile, key rows by
        query columns, as the dK/dV kernel computes it."""
        return None

    # ---- kernels whose inner axis streams key blocks past query block qi
    def kv_steps(self, nq: int, nk: int, bq: int, bk: int) -> int:
        """Extent of that axis: the most key blocks a query block visits."""
        return nk

    def kv_tile(self, qi, kj, bq: int, bk: int):
        """``(key block, whether its tile holds a visible pair)`` of step
        ``kj``."""
        return kj, kj == kj         # traced: an always-true predicate

    def kv_fetch(self, qi, kj, bq: int, bk: int):
        """The key block the index maps fetch at step ``kj``: that of
        ``kv_tile`` or, where the step is past the last live block, the
        block already held (a repeated index fetches nothing)."""
        return kj

    # ---- the dK/dV kernel: query blocks stream past key block ki
    def q_steps(self, nq: int, nk: int, bq: int, bk: int) -> int:
        return nq

    def q_tile(self, ki, qj, bq: int, bk: int, nq: int):
        return qj, qj == qj

    def q_fetch(self, ki, qj, bq: int, bk: int, nq: int):
        return qj

    def tile_side(self, side: int) -> int:
        """The widest tile side worth taking when ``side`` is the tuned
        one."""
        return side


@dataclasses.dataclass(frozen=True)
class Causal(Visibility):
    """A query sees itself and what came before; under a ``window`` only
    itself and the ``window - 1`` positions before it. The causal kernels
    compute no tile above the diagonal (they still fetch it); the
    windowed ones run their inner axis over the blocks the window reaches
    only."""
    window: Optional[int] = None

    def visible(self, qpos, kpos):
        seen = kpos <= qpos
        if self.window is not None:
            seen = seen & (kpos > qpos - self.window)
        return seen

    def tile_visible(self, qi, ki, bq, bk):
        return self.visible(*_tile_positions(qi, ki, bq, bk))

    def tile_visible_t(self, qi, ki, bq, bk):
        return self.visible(*_tile_positions(qi, ki, bq, bk, transposed=True))

    def kv_steps(self, nq, nk, bq, bk):
        if self.window is None:
            return nk
        return _span(lambda qi: _window_kv_blocks(qi, bq, bk, self.window),
                     nq)

    def kv_tile(self, qi, kj, bq, bk):
        if self.window is None:
            # tiles fully above the diagonal contribute nothing
            return kj, kj * bk <= (qi + 1) * bq - 1
        lo, hi = _window_kv_blocks(qi, bq, bk, self.window)
        return lo + kj, lo + kj <= hi

    def kv_fetch(self, qi, kj, bq, bk):
        if self.window is None:
            return kj
        lo, hi = _window_kv_blocks(qi, bq, bk, self.window)
        return jnp.minimum(lo + kj, hi)

    def q_steps(self, nq, nk, bq, bk):
        if self.window is None:
            return nq
        return _span(lambda ki: _window_q_blocks(ki, bq, bk, self.window,
                                                 nq), nk)

    def q_tile(self, ki, qj, bq, bk, nq):
        if self.window is None:
            return qj, (qj + 1) * bq - 1 >= ki * bk
        lo, hi = _window_q_blocks(ki, bq, bk, self.window, nq)
        qi = lo + qj
        return qi, qi <= hi

    def q_fetch(self, ki, qj, bq, bk, nq):
        if self.window is None:
            return qj
        lo, hi = _window_q_blocks(ki, bq, bk, self.window, nq)
        return jnp.minimum(lo + qj, hi)

    def tile_side(self, side):
        """Neither side of the tile wider than the window, in whole lanes
        of 128: a key block wider than the window wastes the skip."""
        if self.window is None:
            return side
        return min(side, max(128, (self.window + 127) // 128 * 128))


def _run_step(step, a_lo, a_n, b_lo, b_n):
    """Block ``step`` of two runs of blocks walked one after the other
    (``a_n`` from ``a_lo``, then ``b_n`` from ``b_lo``), and whether the
    step is inside them."""
    return (_where(step < a_n, a_lo + step, b_lo + step - a_n),
            step < a_n + b_n)


def _run_fetch(step, a_lo, a_n, b_lo, b_n):
    last = a_n + b_n - 1
    return _run_step(_where(step < last, step, last),
                     a_lo, a_n, b_lo, b_n)[0]


@dataclasses.dataclass(frozen=True)
class BlockDiffusion(Visibility):
    """Masked diffusion over blocks, trained on the noisy and the clean
    copy of a sequence in one pass: slot ``s`` of ``2 * seq_len`` has
    position ``s mod seq_len`` and block ``position // block``, and is
    noisy below ``seq_len``, clean from there. A noisy query sees the
    noisy keys of its own block (both directions) and the clean keys of
    earlier blocks; a clean query sees the clean keys of its own and
    earlier blocks; no query sees a noisy key of another block, and a
    clean query sees no noisy key. ``seq_len * (seq_len + block)`` of the
    ``4 * seq_len^2`` pairs.

    The kernels need tiles that are multiples of ``block`` and divide
    ``seq_len`` (``flash_attention`` sees to both), so that a tile lies in
    one half and cuts no block. A noisy query block then visits the key
    block(s) on its own diagonal and the clean key blocks before its own
    last block; a clean one the clean key blocks up to its own; the dK/dV
    kernel walks the transposed sets."""
    seq_len: int
    block: int

    def visible(self, qpos, kpos):
        t, b = self.seq_len, self.block
        q_noisy, k_noisy = qpos < t, kpos < t
        qb = jnp.where(q_noisy, qpos, qpos - t) // b
        kb = jnp.where(k_noisy, kpos, kpos - t) // b
        return jnp.where(k_noisy, q_noisy & (kb == qb),
                         jnp.where(q_noisy, kb < qb, kb <= qb))

    def _block_of(self, pos):
        b = self.block
        if b & (b - 1) == 0:        # a shift, where the block allows one
            return pos >> (b.bit_length() - 1)
        return pos // b

    def tile_visible(self, qi, ki, bq, bk):
        return self._tile_mask(qi, ki, bq, bk, transposed=False)

    def tile_visible_t(self, qi, ki, bq, bk):
        return self._tile_mask(qi, ki, bq, bk, transposed=True)

    def _tile_mask(self, qi, ki, bq, bk, transposed):
        """A tile lies in one half on each side, so which of the three
        rules holds is a scalar; the one tile kind that is never visited
        (clean queries on noisy keys) is not told apart. Either
        orientation: the bounds are scalars, so only the positions'
        axes differ."""
        half_q, half_k = self.seq_len // bq, self.seq_len // bk
        q_noisy, k_noisy = qi < half_q, ki < half_k
        qpos, kpos = _tile_positions(
            jnp.where(q_noisy, qi, qi - half_q),
            jnp.where(k_noisy, ki, ki - half_k), bq, bk, transposed)
        # by how many blocks the query is ahead of the key: 0 for a noisy
        # key; for a clean one at least 1, and 0 too for a clean query
        # (scalar bounds: Mosaic selects no vector of booleans)
        ahead = self._block_of(qpos) - self._block_of(kpos)
        least = jnp.where(k_noisy | jnp.logical_not(q_noisy), 0, 1)
        most = jnp.where(k_noisy, 0, self.seq_len)
        return (ahead >= least) & (ahead <= most)

    def _check(self, bq, bk):
        for side in (bq, bk):
            if side % self.block or self.seq_len % side:
                raise ValueError(
                    f"a {bq} x {bk} tile under {self}: each side a multiple "
                    "of the block that divides seq_len")

    def _kv_runs(self, qi, bq, bk):
        """The two runs of key blocks query block ``qi`` visits."""
        half_q, half_k = self.seq_len // bq, self.seq_len // bk
        noisy = qi < half_q
        end = (_where(noisy, qi, qi - half_q) + 1) * bq   # in its half
        diagonal = (end - bq) // bk
        return (_where(noisy, diagonal, half_k),
                _where(noisy, (end - 1) // bk - diagonal + 1,
                       (end - 1) // bk + 1),
                half_k,
                _where(noisy, (end - self.block + bk - 1) // bk, 0))

    def kv_steps(self, nq, nk, bq, bk):
        self._check(bq, bk)
        runs = [self._kv_runs(qi, bq, bk) for qi in range(nq)]
        return max(a_n + b_n for _, a_n, _, b_n in runs)

    def kv_tile(self, qi, kj, bq, bk):
        return _run_step(kj, *self._kv_runs(qi, bq, bk))

    def kv_fetch(self, qi, kj, bq, bk):
        return _run_fetch(kj, *self._kv_runs(qi, bq, bk))

    def _q_runs(self, ki, bq, bk):
        """The two runs of query blocks that see key block ``ki``: a
        noisy one its diagonal; a clean one the noisy query blocks from
        the block after its first and the clean ones from its own."""
        half_q, half_k = self.seq_len // bq, self.seq_len // bk
        noisy = ki < half_k
        start = _where(noisy, ki, ki - half_k) * bk        # in its half
        diagonal = start // bq
        after = (start + self.block) // bq
        return (_where(noisy, diagonal, after),
                _where(noisy, (start + bk - 1) // bq - diagonal + 1,
                       half_q - after),
                half_q + diagonal,
                _where(noisy, 0, half_q - diagonal))

    def q_steps(self, nq, nk, bq, bk):
        self._check(bq, bk)
        runs = [self._q_runs(ki, bq, bk) for ki in range(nk)]
        return max(a_n + b_n for _, a_n, _, b_n in runs)

    def q_tile(self, ki, qj, bq, bk, nq):
        return _run_step(qj, *self._q_runs(ki, bq, bk))

    def q_fetch(self, ki, qj, bq, bk, nq):
        return _run_fetch(qj, *self._q_runs(ki, bq, bk))
