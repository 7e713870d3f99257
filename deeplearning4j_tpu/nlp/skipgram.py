"""Batched SkipGram / CBOW device kernels.

TPU-native replacement for the reference's native aggregate ops: the
reference batches (center, context) pairs into ``AggregateSkipGram`` /
``AggregateCBOW`` and executes them in C++ via
``Nd4j.getExecutioner().exec(batches)``
(models/embeddings/learning/impl/elements/SkipGram.java:176,271; CBOW.java).

Here the same batching idea becomes ONE jitted step per batch: gather the
center rows from syn0 and the target rows (negative samples or Huffman
inner nodes) from syn1, compute the sigmoid-gradient for every pair at
once on the MXU, and scatter the updates back. Buffers are donated so
the embedding tables are updated in place on device.

Duplicate rows in a batch scatter-add as usual but each row's TOTAL
accumulated update is norm-clipped: word2vec's sequential (hogwild)
updates are self-limiting — each saturating step sees the previous one's
result — but a batched scatter-add applies k duplicate updates computed
from the SAME pre-update row. For frequent words (or tiny vocabularies)
k is large; once row norms grow, the summed step overshoots and the
feedback loop diverges to overflow as batch size grows. Clipping the
per-row accumulated update norm (at 1.0 — well above any healthy
per-batch step, far below the runaway regime) bounds the feedback loop
at any batch size — which the dispatch-overhead economics push toward
64k+ (PERF_ANALYSIS.md). This is a deliberate, small semantic deviation
from word2vec.c's sequential updates: sub-threshold batches differ from
a sequential replay only by float summation order, and a frequent word
whose legitimate accumulated update exceeds the threshold takes a
direction-preserving, norm-1 step instead (word2vec.c, applying the
same pairs one at a time through a saturating sigmoid, also never moves
a row by more than O(1) per batch — the clip restores that property,
it does not add a new one).

The clip works on the B·K update rows directly (sort by index +
segment sums), NOT by materializing a dense [V, D] accumulator — per
step cost stays O(B·K·D + B·K log B·K) regardless of vocab size.

The math (per pair, label y ∈ {0,1}, lr α):
    g = (y − σ(syn0[c]·syn1[t])) · α
    syn1[t] += g · syn0[c]
    syn0[c] += g · syn1[t]        (pre-update value, as in word2vec.c)
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _sg_update(syn0: jax.Array, syn1: jax.Array,
               centers: jax.Array,      # [B] int32
               targets: jax.Array,      # [B, K] int32
               labels: jax.Array,       # [B, K] float32 (1=pos, 0=neg)
               mask: jax.Array,         # [B, K] float32
               lr: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One batched SkipGram update (negative sampling or hierarchical
    softmax — identical math, different targets/labels)."""
    h = syn0[centers]                                  # [B, D]
    w = syn1[targets]                                  # [B, K, D]
    logits = jnp.einsum("bd,bkd->bk", h, w)
    g = (labels - jax.nn.sigmoid(logits)) * mask * lr  # [B, K]
    dh = jnp.einsum("bk,bkd->bd", g, w)                # grad wrt syn0 rows
    dw = g[..., None] * h[:, None, :]                  # [B, K, D]
    d = syn0.shape[1]
    mr = _max_row_norm(lr, d)
    syn1 = _clipped_scatter(syn1, targets.reshape(-1), dw.reshape(-1, d),
                            mr)
    syn0 = _clipped_scatter(syn0, centers, dh, mr)
    return syn0, syn1


skipgram_step = functools.partial(jax.jit, donate_argnums=(0, 1))(
    _sg_update)


# Divergence-guard clip, scaled with lr and layer size: at word2vec.c
# defaults (lr=0.025, D=100) this reproduces the old absolute threshold
# of 1.0, but high-lr or large-D configs no longer have legitimate
# per-chunk updates silently clipped (advisor r2).
_CLIP_COEF = 4.0


def _max_row_norm(lr: jax.Array, d: int) -> jax.Array:
    return _CLIP_COEF * lr * jnp.sqrt(jnp.float32(d))


def _clipped_scatter(table: jax.Array, idx: jax.Array,
                     upd: jax.Array, max_norm: jax.Array) -> jax.Array:
    """table[idx] += updates, with each destination row's accumulated
    update norm-clipped (see module docstring). Segment-sum over the
    sorted update rows — no dense [V, D] temporaries, so cost scales
    with the batch, not the vocabulary.

    Every step here is duplicate-free by construction: segment bounds
    come from cummax/cummin over the sorted order (a scatter-max with
    duplicate indices lowers to a SERIAL per-element loop on TPU —
    profiled at ~48 ms per 64k-pair chunk, 50× the rest of the step),
    and the final scatter-add lands each segment total on its unique
    destination row while every other element targets its own slot in
    a dump area past the table, so XLA vectorizes the scatter AND the
    result stays bitwise deterministic (exactly one add per live row)."""
    b = idx.shape[0]
    order = jnp.argsort(idx)
    sid = jnp.take(idx, order)
    supd = jnp.take(upd, order, axis=0).astype(jnp.float32)
    pos = jnp.arange(b)
    first = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    is_last = jnp.concatenate([sid[1:] != sid[:-1],
                               jnp.ones((1,), bool)])
    # ``total`` only has to be right at each segment's LAST element (all
    # other elements land in the dump area below), so the segment sum is
    # cs - cs[segment start - 1] evaluated elementwise: one cummax for
    # the start positions and ONE row gather — (b, D) gathers are the
    # dominant cost of this kernel on TPU
    seg_start = jax.lax.cummax(jnp.where(first, pos, -1))
    cs = jnp.cumsum(supd, axis=0)
    lo = jnp.where((seg_start > 0)[:, None],
                   jnp.take(cs, jnp.maximum(seg_start - 1, 0), axis=0),
                   0.0)
    total = cs - lo          # segment sum, valid at segment-last rows
    norm = jnp.linalg.norm(total, axis=-1, keepdims=True)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    nrows = table.shape[0]
    scatter_idx = jnp.where(is_last, sid, nrows + pos)
    padded = jnp.concatenate(
        [table, jnp.zeros((b,) + table.shape[1:], table.dtype)], axis=0)
    padded = padded.at[scatter_idx].add(
        (total * scale).astype(table.dtype), unique_indices=True)
    return padded[:nrows]


@functools.partial(jax.jit, donate_argnums=(0, 1))
def skipgram_hs_step(syn0: jax.Array, syn1: jax.Array,
                     centers: jax.Array,      # [B] int32
                     contexts: jax.Array,     # [B] int32
                     points_mat: jax.Array,   # [V, L] int32 Huffman nodes
                     labels_mat: jax.Array,   # [V, L] float32 (1 - code)
                     hs_mask: jax.Array,      # [V, L] float32 path length
                     row_valid: jax.Array,    # [B] float32 batch padding
                     lr: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Hierarchical-softmax SkipGram step with the Huffman-path gathers
    done ON DEVICE: targets/labels/mask come from per-word matrices, so
    the host loop ships only (center, context) index pairs — the same
    batching economics as the negative-sampling path."""
    targets = points_mat[contexts]                 # [B, L]
    labels = labels_mat[contexts]
    mask = hs_mask[contexts] * row_valid[:, None]
    return skipgram_step(syn0, syn1, centers, targets, labels, mask, lr)


def partial_mask(full_dev: jax.Array, n_valid: int) -> jax.Array:
    """All-ones device mask when the chunk is full; else a zero-padded
    host-built mask of the same shape — the one home for the padded-tail
    logic shared by every vectorized flush path."""
    shape = full_dev.shape
    if n_valid == shape[0]:
        return full_dev
    m = np.zeros(shape, np.float32)
    m[:n_valid] = 1.0
    return jnp.asarray(m)


def build_hs_matrices(vocab_words, max_len: int
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(points, labels=1-codes, mask) matrices padded to ``max_len`` for
    the device-side HS gather (rows indexed by word index)."""
    v = len(vocab_words)
    points = np.zeros((v, max_len), np.int32)
    labels = np.zeros((v, max_len), np.float32)
    mask = np.zeros((v, max_len), np.float32)
    for i, vw in enumerate(vocab_words):
        n = min(len(vw.points), max_len)
        points[i, :n] = vw.points[:n]
        labels[i, :n] = 1.0 - np.asarray(vw.codes[:n], np.float32)
        mask[i, :n] = 1.0
    return points, labels, mask


def _cbow_update(syn0: jax.Array, syn1: jax.Array,
                 context: jax.Array,       # [B, W] int32 context word rows
                 context_mask: jax.Array,  # [B, W] float32
                 targets: jax.Array,       # [B, K] int32
                 labels: jax.Array,        # [B, K] float32
                 mask: jax.Array,          # [B, K] float32
                 lr: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One batched CBOW update: h = mean(context rows); the syn0 gradient
    is broadcast back to every context word (reference: CBOW.java via
    AggregateCBOW)."""
    cvecs = syn0[context]                               # [B, W, D]
    denom = jnp.maximum(context_mask.sum(-1, keepdims=True), 1.0)
    h = (cvecs * context_mask[..., None]).sum(1) / denom  # [B, D]
    w = syn1[targets]
    logits = jnp.einsum("bd,bkd->bk", h, w)
    g = (labels - jax.nn.sigmoid(logits)) * mask * lr
    dh = jnp.einsum("bk,bkd->bd", g, w) / denom          # [B, D]
    dw = g[..., None] * h[:, None, :]
    d = syn0.shape[1]
    mr = _max_row_norm(lr, d)
    syn1 = _clipped_scatter(syn1, targets.reshape(-1), dw.reshape(-1, d),
                            mr)
    dctx = (dh[:, None, :] * context_mask[..., None]).reshape(-1, d)
    syn0 = _clipped_scatter(syn0, context.reshape(-1), dctx, mr)
    return syn0, syn1


cbow_step = functools.partial(jax.jit, donate_argnums=(0, 1))(
    _cbow_update)


# ---- scanned multi-chunk steps -------------------------------------------
# One dispatch applies D sequential chunk updates via lax.scan: the
# per-dispatch overhead is amortized D×, and the host builds the next
# superchunk while the device drains this one (async dispatch — the
# double-buffering the reference gets from its trainer threads feeding
# one fat native op per batch, SkipGram.java:176).

def _row_mask(b: int, k: int, nv: jax.Array) -> jax.Array:
    """(B, K) float mask of rows below the chunk's valid count."""
    return jnp.broadcast_to(
        (jnp.arange(b)[:, None] < nv).astype(jnp.float32), (b, k))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def skipgram_scan_step(syn0, syn1,
                       centers,   # [D, B] int32
                       targets,   # [D, B, K] int32 (col 0 = positive)
                       n_valid,   # [D] int32
                       lrs):      # [D] float32
    b, k = targets.shape[1], targets.shape[2]
    labels = jnp.zeros((b, k), jnp.float32).at[:, 0].set(1.0)

    def body(carry, chunk):
        s0, s1 = carry
        cen, tgt, nv, lr = chunk
        s0, s1 = _sg_update(s0, s1, cen, tgt, labels,
                            _row_mask(b, k, nv), lr)
        return (s0, s1), None

    (syn0, syn1), _ = jax.lax.scan(
        body, (syn0, syn1), (centers, targets, n_valid, lrs))
    return syn0, syn1


@functools.partial(jax.jit, donate_argnums=(0, 1))
def skipgram_hs_scan_step(syn0, syn1,
                          centers,     # [D, B] int32
                          contexts,    # [D, B] int32
                          points_mat, labels_mat, hs_mask,
                          n_valid, lrs):
    b = centers.shape[1]
    k = points_mat.shape[1]

    def body(carry, chunk):
        s0, s1 = carry
        cen, ctx, nv, lr = chunk
        targets = points_mat[ctx]
        labels = labels_mat[ctx]
        mask = hs_mask[ctx] * _row_mask(b, k, nv)
        s0, s1 = _sg_update(s0, s1, cen, targets, labels, mask, lr)
        return (s0, s1), None

    (syn0, syn1), _ = jax.lax.scan(
        body, (syn0, syn1), (centers, contexts, n_valid, lrs))
    return syn0, syn1


@functools.partial(jax.jit, donate_argnums=(0, 1))
def cbow_scan_step(syn0, syn1,
                   context,       # [D, B, W] int32
                   context_mask,  # [D, B, W] float32
                   targets,       # [D, B, K] int32 (col 0 = positive)
                   n_valid, lrs):
    b, k = targets.shape[1], targets.shape[2]
    labels = jnp.zeros((b, k), jnp.float32).at[:, 0].set(1.0)

    def body(carry, chunk):
        s0, s1 = carry
        ctx, cm, tgt, nv, lr = chunk
        s0, s1 = _cbow_update(s0, s1, ctx, cm, tgt, labels,
                              _row_mask(b, k, nv), lr)
        return (s0, s1), None

    (syn0, syn1), _ = jax.lax.scan(
        body, (syn0, syn1), (context, context_mask, targets, n_valid,
                             lrs))
    return syn0, syn1


@functools.partial(jax.jit, donate_argnums=(0, 1))
def cbow_hs_scan_step(syn0, syn1,
                      context,       # [D, B, W] int32
                      context_mask,  # [D, B, W] float32
                      centers,       # [D, B] int32
                      points_mat, labels_mat, hs_mask,
                      n_valid, lrs):
    b = centers.shape[1]
    k = points_mat.shape[1]

    def body(carry, chunk):
        s0, s1 = carry
        ctx, cm, cen, nv, lr = chunk
        targets = points_mat[cen]
        labels = labels_mat[cen]
        mask = hs_mask[cen] * _row_mask(b, k, nv)
        s0, s1 = _cbow_update(s0, s1, ctx, cm, targets, labels, mask,
                              lr)
        return (s0, s1), None

    (syn0, syn1), _ = jax.lax.scan(
        body, (syn0, syn1), (context, context_mask, centers, n_valid,
                             lrs))
    return syn0, syn1


@functools.partial(jax.jit, donate_argnums=(0, 1))
def cbow_hs_step(syn0: jax.Array, syn1: jax.Array,
                 context: jax.Array,       # [B, W] int32
                 context_mask: jax.Array,  # [B, W] float32
                 centers: jax.Array,       # [B] int32 (Huffman lookup)
                 points_mat: jax.Array,    # [V, L] int32
                 labels_mat: jax.Array,    # [V, L] float32
                 hs_mask: jax.Array,       # [V, L] float32
                 row_valid: jax.Array,     # [B] float32
                 lr: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Hierarchical-softmax CBOW with the Huffman-path gather ON DEVICE
    (mirrors skipgram_hs_step): the host ships context ids + center ids
    only, instead of re-uploading gathered (B, L) target/label/mask
    arrays every chunk."""
    targets = points_mat[centers]
    labels = labels_mat[centers]
    mask = hs_mask[centers] * row_valid[:, None]
    return cbow_step(syn0, syn1, context, context_mask, targets, labels,
                     mask, lr)


@functools.partial(jax.jit, donate_argnums=(0, 1),
                   static_argnames=("window", "n_neg"))
def skipgram_token_step(syn0: jax.Array, syn1: jax.Array,
                        tokens: jax.Array,    # (S, L) int32, padded
                        lengths: jax.Array,   # (S,) int32 valid lengths
                        table: jax.Array,     # unigram^0.75 table, int32
                        key: jax.Array, lr: jax.Array,
                        *, window: int, n_neg: int
                        ) -> Tuple[jax.Array, jax.Array]:
    """SGNS over raw token-id sentences with pair generation ON DEVICE.

    The host pipeline (word→id lookup aside) caps tokens/s at what numpy
    window expansion + negative gathers can produce (~120k tokens/s
    measured). Here the (center, context) grid, the per-center effective
    window draw (word2vec.c's ``b``), the negative samples, and the
    update all happen inside one jitted step: the host ships only padded
    int32 sentence matrices. Same math as skipgram_step (shared tail,
    incl. the clipped scatter); RNG is jax-side instead of host-side.
    """
    s, l = tokens.shape
    kb, kn = jax.random.split(key)
    pos = jnp.arange(l)
    offs = jnp.concatenate([jnp.arange(-window, 0),
                            jnp.arange(1, window + 1)])      # (2W,)
    b = jax.random.randint(kb, (s, l), 1, window + 1)
    grid = jnp.broadcast_to(pos[None, :, None] + offs[None, None, :],
                            (s, l, 2 * window))
    valid = ((jnp.abs(offs)[None, None, :] <= b[..., None])
             & (grid >= 0) & (grid < lengths[:, None, None])
             & (pos[None, :, None] < lengths[:, None, None]))
    centers = jnp.broadcast_to(tokens[:, :, None],
                               valid.shape).reshape(-1)
    ctx_idx = jnp.clip(grid, 0, l - 1)          # (S, L, 2W) positions
    contexts = jnp.take_along_axis(
        tokens, ctx_idx.reshape(s, -1), axis=1).reshape(-1)
    mask_row = valid.reshape(-1).astype(jnp.float32)

    p = centers.shape[0]
    negs = table[jax.random.randint(kn, (p, n_neg), 0, table.shape[0])]
    # a negative colliding with the positive would train the same target
    # toward both labels: cycle it (word2vec.c skips; same effect). The
    # vocab bound is syn1's static row count — free at trace time.
    vmax = max(syn1.shape[0], 2)
    negs = jnp.where(negs == contexts[:, None],
                     (negs + 1) % vmax, negs)
    targets = jnp.concatenate([contexts[:, None], negs], axis=1)
    labels = jnp.zeros((p, 1 + n_neg),
                       jnp.float32).at[:, 0].set(1.0)
    mask = jnp.broadcast_to(mask_row[:, None], (p, 1 + n_neg))
    return skipgram_step(syn0, syn1, centers, targets, labels, mask, lr)


@functools.partial(jax.jit, donate_argnums=(0,))
def infer_step(docvec: jax.Array,        # [D] the one trainable vector
               syn1: jax.Array,          # frozen
               targets: jax.Array,       # [P, K]
               labels: jax.Array,
               mask: jax.Array,
               lr: jax.Array) -> jax.Array:
    """ParagraphVectors.inferVector inner step: train a single new doc
    vector against a frozen syn1 (reference: ParagraphVectors.java
    inferVector)."""
    w = syn1[targets]                                   # [P, K, D]
    logits = jnp.einsum("d,pkd->pk", docvec, w)
    g = (labels - jax.nn.sigmoid(logits)) * mask * lr
    upd = jnp.einsum("pk,pkd->d", g, w).astype(jnp.float32)
    # the whole P*K pair sum lands on ONE row computed from the same
    # pre-update docvec — the worst case of the duplicate-sum divergence
    # _clipped_scatter guards against; clip it the same way
    norm = jnp.maximum(jnp.linalg.norm(upd), 1e-12)
    upd = upd * jnp.minimum(1.0, _max_row_norm(lr, docvec.shape[0]) / norm)
    return docvec + upd.astype(docvec.dtype)


class PairBatcher:
    """Host-side accumulator of (center, targets, labels) rows, flushed to
    the device kernel when full — the analog of the reference's batch list
    handed to the native executioner (SkipGram.java:176-186)."""

    def __init__(self, batch_size: int, k: int):
        self.batch_size = batch_size
        self.k = k
        self.centers = np.zeros(batch_size, np.int32)
        self.targets = np.zeros((batch_size, k), np.int32)
        self.labels = np.zeros((batch_size, k), np.float32)
        self.mask = np.zeros((batch_size, k), np.float32)
        self.n = 0

    def add(self, center: int, targets: np.ndarray, labels: np.ndarray):
        i = self.n
        kk = min(len(targets), self.k)
        self.centers[i] = center
        self.targets[i, :kk] = targets[:kk]
        self.labels[i, :kk] = labels[:kk]
        self.mask[i, :kk] = 1.0
        if kk < self.k:
            self.targets[i, kk:] = 0
            self.labels[i, kk:] = 0.0
            self.mask[i, kk:] = 0.0
        self.n += 1
        return self.n >= self.batch_size

    def take(self):
        out = (self.centers.copy(), self.targets.copy(),
               self.labels.copy(), self.mask.copy(), self.n)
        # zero masks beyond fill point so a partial flush is inert
        if self.n < self.batch_size:
            out[3][self.n:] = 0.0
        self.n = 0
        self.mask[:] = 0.0
        return out


def draw_negatives(rng: np.random.Generator, table: np.ndarray,
                   pos: np.ndarray, n_neg: int,
                   n_words: int) -> np.ndarray:
    """(n, n_neg) negatives from the unigram^0.75 table for positive
    column ``pos`` (n, 1): collisions with the positive are redrawn
    once, then cycled to (pos+1) mod vocab — the single home of the
    collision policy shared by the SGNS and CBOW fast paths."""
    n = pos.shape[0]
    # uint32 draws: ~2x faster than the int64 default in numpy's
    # Lemire path, and table indices always fit
    negs = table[rng.integers(0, len(table), (n, n_neg),
                              dtype=np.uint32)]
    bad = np.nonzero(negs == pos)
    if bad[0].size:
        # redraw/cycle only the colliding cells (~1 in vocab^0.25 of
        # pairs) — a second full-width compare cost more than all the
        # collisions combined at the 500k-pair chunk size
        redraw = table[rng.integers(0, len(table), bad[0].size)]
        pb = pos[bad[0], 0]
        still = redraw == pb
        redraw[still] = (pb[still] + 1) % max(n_words, 2)
        negs[bad] = redraw
    return negs


def window_grid(n: int, window: int, rng: np.random.Generator
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Randomized-effective-window offsets grid (word2vec.c's ``b``):
    returns (grid positions (n, 2W), validity mask (n, 2W)) shared by
    the SGNS and CBOW fast paths."""
    offsets = np.concatenate([np.arange(-window, 0),
                              np.arange(1, window + 1)])
    eff = (rng.integers(1, window + 1, n) if window > 1
           else np.ones(n, np.int64))
    grid = np.arange(n)[:, None] + offsets[None, :]
    valid = ((np.abs(offsets)[None, :] <= eff[:, None])
             & (grid >= 0) & (grid < n))
    return grid, valid


def negative_sample_targets(pos: int, table: np.ndarray, n_neg: int,
                            rng: np.random.Generator
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """1 positive + n_neg negatives drawn from the unigram^0.75 table.
    Negatives colliding with the positive are redrawn (word2vec.c skips
    target==word), so a row never trains the same target toward both
    labels at once."""
    negs = table[rng.integers(0, len(table), n_neg)]
    for _ in range(4):
        bad = negs == pos
        if not bad.any():
            break
        negs[bad] = table[rng.integers(0, len(table), int(bad.sum()))]
    if (negs == pos).any():  # tiny vocab: fall back to cycling indices
        n_words = int(table.max()) + 1
        negs[negs == pos] = (pos + 1) % max(n_words, 2)
    targets = np.concatenate(([pos], negs)).astype(np.int32)
    labels = np.zeros(1 + n_neg, np.float32)
    labels[0] = 1.0
    return targets, labels


def hs_targets(vw, max_len: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Hierarchical-softmax targets: Huffman inner nodes with label
    1−code (word2vec convention)."""
    points = np.asarray(vw.points, np.int32)
    labels = 1.0 - np.asarray(vw.codes, np.float32)
    if max_len is not None:
        points, labels = points[:max_len], labels[:max_len]
    return points, labels


# ---------------------------------------------------------------------------
# shared-negative-sample SkipGram (round 4)
# ---------------------------------------------------------------------------

SHARED_NEG_GROUP = 512


def _sg_update_shared(syn0, syn1,
                      centers,     # [B] int32
                      contexts,    # [B] int32
                      negs,        # [G, NEG] int32, B % G == 0
                      nv,          # scalar int32 valid rows
                      lr):
    """SkipGram update with PER-GROUP shared negative samples.

    Per-pair negative rows are the gather/scatter bound of the exact
    batched step (K+1 random ~512-byte row ops each way per pair —
    latency-, not bandwidth-, limited on TPU). Sharing one negative set
    across a group of ``B/G`` consecutive pairs turns the negative
    work into three batched MXU matmuls (logits, dh, dW) over [G,
    group, D] blocks, leaving only the positive context + center rows
    to gather/scatter. This is the published shared-negative-sampling
    batching (e.g. Ji et al., "Parallelizing Word2Vec in Shared and
    Distributed Memory", whose negative sharing this mirrors) — the
    negatives are i.i.d. draws either way; sharing them within a group
    changes which random negatives each pair sees, not their
    distribution. The reference's exact per-pair semantics remain
    available via shared_negatives=False.

    Negatives are drawn WITHOUT excluding each pair's positive (a
    collision demotes one true context draw to ~uniform noise at
    unigram-table probability — word2vec.c itself merely skips such
    draws). Row updates still go through the clipped deduplicating
    scatter, so determinism and the divergence guard are unchanged."""
    b = centers.shape[0]
    d = syn0.shape[1]
    g, n_neg = negs.shape
    group = b // g
    valid = (jnp.arange(b) < nv).astype(jnp.float32)
    h = syn0[centers]                                  # [B, D]
    wt = syn1[contexts]                                # [B, D]
    # positive pair
    lp = jnp.sum(h * wt, axis=-1)
    gp = (1.0 - jax.nn.sigmoid(lp)) * valid * lr       # [B]
    dh = gp[:, None] * wt
    dwt = gp[:, None] * h
    # shared negatives: batched matmuls over [G, group, D]
    wn = syn1[negs.reshape(-1)].reshape(g, n_neg, d)   # [G, NEG, D]
    hg = h.reshape(g, group, d)
    ln = jnp.einsum("gbd,gnd->gbn", hg, wn)
    gn = (-jax.nn.sigmoid(ln)) * valid.reshape(g, group, 1) * lr
    dh = dh + jnp.einsum("gbn,gnd->gbd", gn, wn).reshape(b, d)
    dwn = jnp.einsum("gbn,gbd->gnd", gn, hg)           # [G, NEG, D]
    mr = _max_row_norm(lr, d)
    syn1 = _clipped_scatter(syn1, contexts, dwt, mr)
    syn1 = _clipped_scatter(syn1, negs.reshape(-1),
                            dwn.reshape(-1, d), mr)
    syn0 = _clipped_scatter(syn0, centers, dh, mr)
    return syn0, syn1


@functools.partial(jax.jit, donate_argnums=(0, 1))
def skipgram_scan_step_shared(syn0, syn1,
                              centers,   # [D, B] int32
                              contexts,  # [D, B] int32
                              negs,      # [D, G, NEG] int32
                              n_valid,   # [D] int32
                              lrs):      # [D] float32
    def body(carry, chunk):
        s0, s1 = carry
        cen, ctx, ng, nv, lr = chunk
        s0, s1 = _sg_update_shared(s0, s1, cen, ctx, ng, nv, lr)
        return (s0, s1), None

    (syn0, syn1), _ = jax.lax.scan(
        body, (syn0, syn1), (centers, contexts, negs, n_valid, lrs))
    return syn0, syn1
