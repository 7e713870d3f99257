"""Noise for a masked diffusion over blocks, drawn per batch.

A diffusion language model of the BD3-LM / SDAR kind (arXiv:2503.09573,
arXiv:2510.06303) trains on a clean row ``x0`` and a noisy copy ``xt`` of
it in which each position is replaced by ``[MASK]`` with the row's noise
level ``t_n``, and weights a masked position's loss by ``1 / t_n`` (the
linear schedule of LLaDA's ``forward_process``). The noise belongs to the
batch, not to the set: a trainer's collator draws it anew every time, and
a set noised once would train on as many fixed masks as it has rows. So
the noiser is a ``DataSetPreProcessor`` (``iterator.set_pre_processor``),
run where the batch is produced: under ``fit()`` on the prefetch thread,
inside the ``produce`` span.
"""

from __future__ import annotations

import time

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, DataSetPreProcessor
from deeplearning4j_tpu.nn.layers.decoder import IGNORE_LABEL
from deeplearning4j_tpu.observe.registry import default_registry
from deeplearning4j_tpu.observe.tracer import thread_tracer

MASKED_SHARE_GAUGE = (
    "dl4j_diffusion_masked_share",
    "share of the positions of the last batch a BlockDiffusionNoiser "
    "handed out that it replaced by [MASK]")


class BlockDiffusionNoiser(DataSetPreProcessor):
    """From a ``DataSet`` of clean token ids ``x0`` (N, T), the batch a
    block-diffusion model trains on:

    - features ``[xt | x0]`` (N, 2T) int32: per row ``t_n = (1 - eps) u +
      eps`` with ``u ~ U(0, 1)``, each position of ``xt`` independently
      ``mask_id`` with probability ``t_n`` and ``x0``'s id otherwise;
    - labels (N, T, 2) float32, the layout that selects
      ``CausalLMOutputLayer``'s masked-diffusion loss: ``[..., 0]`` the
      id a masked position hides (``IGNORE_LABEL`` where none is hidden;
      ids below 2**24 are exact in float32), ``[..., 1]`` its weight ``1 /
      t_n`` (0 where none is hidden). The weights travel in the labels
      because features and labels are all a loss is handed everywhere.

    The generator is seeded by ``(seed, epoch, index)`` of the batch in
    its iterator: another draw every epoch, the same draw for the same
    three. ``mask_id`` is no datum: a batch that holds it is refused.
    A features mask (N, T) is doubled; a labels mask passes.

    Each batch sets the gauge ``dl4j_diffusion_masked_share`` and, on a
    thread whose tracer is enabled (the prefetch worker of a traced
    ``fit()``), records a ``noise`` span (cat ``data``; ``seq`` = T,
    ``masked`` positions, ``rows``, ``epoch``, ``index``)."""

    def __init__(self, mask_id: int, eps: float = 1e-3, seed: int = 0,
                 registry=None):
        if not 0.0 < eps < 1.0:
            raise ValueError(f"eps={eps}: the least noise level, in (0, 1)")
        if not 0 <= mask_id < 2 ** 24:
            raise ValueError(f"mask_id={mask_id}: an id float32 labels hold "
                             "exactly, in [0, 2**24)")
        self.mask_id = int(mask_id)
        self.eps = float(eps)  # host-sync-ok: ctor arg
        self.seed = int(seed)
        self._share = (registry or default_registry()).gauge(
            *MASKED_SHARE_GAUGE)

    def draw(self, rows: int, seq: int, epoch: int = 0, index: int = 0):
        """``(t_n (rows,), masked (rows, seq) bool)`` of one batch."""
        rng = np.random.default_rng((self.seed, epoch, index))
        level = (1.0 - self.eps) * rng.random(rows) + self.eps
        return level, rng.random((rows, seq)) < level[:, None]

    def pre_process(self, batch: DataSet, epoch: int = 0,
                    index: int = 0) -> DataSet:
        start = time.perf_counter()
        x0 = np.asarray(batch.features)  # host-sync-ok: host-side batch production
        if x0.ndim != 2 or not np.issubdtype(x0.dtype, np.integer):
            raise ValueError("BlockDiffusionNoiser takes integer token ids "
                             f"(N, T); got {x0.dtype} {x0.shape}")
        if (x0 == self.mask_id).any():
            raise ValueError(f"the data holds mask_id={self.mask_id}: "
                             "[MASK] is reserved, no datum")
        rows, seq = x0.shape
        level, masked = self.draw(rows, seq, epoch, index)
        features = np.empty((rows, 2 * seq), np.int32)
        np.copyto(features[:, :seq], x0)
        features[:, :seq][masked] = self.mask_id
        np.copyto(features[:, seq:], x0)
        labels = np.empty((rows, seq, 2), np.float32)
        labels[..., 0] = np.where(masked, x0, IGNORE_LABEL)
        labels[..., 1] = masked / level[:, None]
        fmask = batch.features_mask
        if fmask is not None:
            fmask = np.concatenate([fmask, fmask], axis=1)
        n_masked = int(masked.sum())
        self._share.set(n_masked / masked.size)
        tracer = thread_tracer()
        if tracer.enabled:
            tracer.add_span("noise", start, time.perf_counter(), cat="data",
                            seq=seq, masked=n_masked, rows=rows,
                            epoch=epoch, index=index)
        return DataSet(features, labels, fmask, batch.labels_mask)
