"""DeviceFeeder: device-side input prefetch + K-step batch staging.

The reference keeps the accelerator fed by wrapping every
``fit(DataSetIterator)`` in an AsyncDataSetIterator thread that stages
minibatches into device workspaces (MultiLayerNetwork.java:1273, SURVEY
§2.3). The JAX analog has TWO gaps to close, both measured in
PERF_ANALYSIS:

1. **Transfer on the critical path.** ``jnp.asarray(batch)`` inside the
   step loop serializes host→device wire time with compute. The feeder
   instead issues ``jax.device_put`` for batches *i+1 / i+2* while the
   (asynchronously dispatched) step *i* still computes, holding up to
   ``depth`` staged batches in a bounded double-buffer (default 2
   slots, optional byte budget). The feeder makes no host copy of its
   own: ``put`` is handed the arrays the source produced, and the
   runtime keeps each until its transfer is done (which is how a source
   that uses its buffers again, ``ArrayDataSetIterator``, knows when it
   may).
2. **Per-dispatch overhead.** Each dispatch carries a fixed cost;
   ``k_steps > 1`` groups K prefetched batches into ONE stacked device
   array and the fit loop runs ``make_scan_train_step`` over it — the
   exact mechanism bench.py hand-rolls, promoted to the user-facing
   ``fit()``.

To keep the K-step path (and, opted in, the per-batch path) at ONE
compiled signature, the feeder normalizes ragged batches: every batch
gets an explicit labels mask (ones where it had none) and the final
partial batch is padded to the bucket size with duplicated zero-weight
rows — the masked loss mean ignores them, so the trajectory matches the
unpadded dispatch bitwise while the RecompileWatchdog sees zero new
signatures (it used to count every ragged tail as a storm).

Observability: ``dl4j_feed_depth`` (staged batches at last hand-off)
and ``dl4j_etl_stall_ms`` (cumulative ms the step loop actually waited
for data) ride the process registry; the tracer gets ``etl`` spans for
host-side batch production, ``host_to_device`` spans for the staging
issue, ``resident`` spans from that issue until the batch is on
the device (see ``_ResidentWatcher``), and ``feed_stall`` spans whenever
the queue ran dry — so overlap (or its absence) is visible in the
Perfetto timeline. ``host_to_device``, ``resident`` and the consumer's
``dispatch`` span of one batch share its ``seq``.
"""

from __future__ import annotations

import queue
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Iterable, List, NamedTuple, Optional

import jax
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.observe.registry import default_registry
from deeplearning4j_tpu.observe.tracer import NULL_TRACER

DEFAULT_DEPTH = 2


# ---- ragged-batch normalization (shared with parallel/wrapper.py) ------

def ones_labels_mask(batch: DataSet) -> np.ndarray:
    """The all-ones labels mask matching this batch's label rank — the
    identity element of the masked loss mean (ops/losses._masked_mean
    divides by sum(mask), so ones reproduce the plain mean bitwise)."""
    lab = np.asarray(batch.labels)  # host-sync-ok: host-side batch staging before transfer
    n = batch.num_examples()
    if lab.ndim <= 2:
        # (N,) sparse or (N, C) dense labels → per-example weights
        return np.ones((n,), np.float32)
    if lab.ndim == 3 and batch.features_mask is not None:
        # variable-length sequences: the loss would have used the
        # features mask — keep those semantics explicit
        return np.asarray(batch.features_mask, np.float32)  # host-sync-ok: host-side batch staging before transfer
    # (N, T, C) → (N, T); (N, H, W, C) → (N, H, W)
    return np.ones(lab.shape[:-1], np.float32)


def ensure_labels_mask(batch: DataSet) -> DataSet:
    """Attach an explicit (all-ones) labels mask when the batch carries
    none, so full and padded batches share one compile signature."""
    if batch.labels_mask is not None or batch.labels is None:
        return batch
    return DataSet(batch.features, batch.labels, batch.features_mask,
                   ones_labels_mask(batch))


def pad_rows(batch: DataSet, pad: int) -> DataSet:
    """Append ``pad`` zero-weight rows: features/labels/features-mask
    duplicate the last row (finite activations — a zeroed row could
    still NaN through log/normalization paths), the labels mask extends
    with zeros so the masked loss mean and its gradients ignore them.
    The one caveat is BatchNormalization batch statistics, which see the
    duplicated rows (mask-free batch moments) — same bounded
    perturbation the parallel wrapper's padding has always accepted."""
    if pad <= 0:
        return batch

    def rep(a):
        if a is None:
            return None
        a = np.asarray(a)  # host-sync-ok: host-side batch staging before transfer
        return np.concatenate([a, np.repeat(a[-1:], pad, axis=0)], axis=0)

    lmask = batch.labels_mask
    if lmask is None:
        lmask = ones_labels_mask(batch)
    lmask = np.asarray(lmask)  # host-sync-ok: host-side batch staging before transfer
    zeros = np.zeros((pad,) + lmask.shape[1:], lmask.dtype)
    return DataSet(rep(batch.features), rep(batch.labels),
                   rep(batch.features_mask),
                   np.concatenate([lmask, zeros], axis=0))


def pad_to_bucket(batch: DataSet, bucket: int) -> DataSet:
    """Normalize one batch to exactly ``bucket`` examples with an
    explicit labels mask (see ``pad_rows``). Bitwise-neutral for masked
    losses; raises when the batch is LARGER than the bucket (a growing
    batch is a data-pipeline bug, not a ragged tail)."""
    n = batch.num_examples()
    if n > bucket:
        raise ValueError(
            f"batch of {n} examples exceeds the feed bucket size "
            f"{bucket}; ragged-batch padding only shrinks tails")
    return pad_rows(ensure_labels_mask(batch), bucket - n)


# ---- staged items -------------------------------------------------------

class FeedItem(NamedTuple):
    """One staged hand-off from the feeder to the fit loop. Arrays are
    device-resident (already ``device_put``). ``k == 0`` marks a
    passthrough batch the feeder does not understand (e.g. a
    MultiDataSet) — ``raw`` then holds the untouched host object and the
    fit loop takes its unfed path for it."""
    features: Any
    labels: Any
    features_mask: Any
    labels_mask: Any
    k: int                  # inner optimizer steps this item carries
    n_examples: int         # REAL examples (pre-padding), for listeners
    queue_wait_ms: float    # time the consumer stalled for this item
    nbytes: int
    raw: Any = None
    seq: int = -1           # the feeder's count of staged items (k > 0)

    def as_dataset(self) -> DataSet:
        return DataSet(self.features, self.labels, self.features_mask,
                       self.labels_mask)


class _HostItem(NamedTuple):
    """Host-side prepared arrays, pre-staging."""
    arrays: tuple           # (features, labels, fmask, lmask) numpy/None
    k: int
    n_examples: int
    raw: Any = None


class _ResidentWatcher:
    """Records the ``resident`` span (cat ``data``) of every staged item:
    from the reading its ``host_to_device`` span starts at until
    ``jax.block_until_ready`` returns on the staged arrays, which is when
    the batch is on the device and a step can use it. ``device_put``
    returns long before (the runtime lays the batch out on its own host
    threads), so this is the hand-off that ``host_to_device`` cannot see.

    One daemon thread per feeder, made only by an enabled tracer, ended
    by ``DeviceFeeder.close()`` or with the feeder itself. Items are
    waited for in the order they were staged, so one that became resident
    out of order reads late, never early. An item's arrays are let go as
    soon as they are ready, which is before the step that uses them can
    run: the watcher keeps no batch alive. A transfer that failed leaves
    no span; the step that uses it raises on the loop's thread."""

    def __init__(self, tracer):
        self._tracer = tracer
        self._jobs: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="dl4j-feed-resident")
        self._thread.start()

    def watch(self, seq, start, nbytes, k, arrays):
        self._jobs.put((seq, start, nbytes, k, arrays))

    def _run(self):
        while True:
            job = self._jobs.get()
            if job is None:
                return
            seq, start, nbytes, k = job[:4]
            try:
                jax.block_until_ready(job[4])
                end = time.perf_counter()
            except RuntimeError:    # raised again where the batch is used
                end = None
            del job                 # the arrays go before the next wait
            if end is not None:
                self._tracer.add_span("resident", start, end, cat="data",
                                      seq=seq, bytes=nbytes, k=k)

    def stop(self):
        """End the thread once it has recorded what it was handed."""
        self._jobs.put(None)

    def close(self):
        self.stop()
        self._thread.join()


class DeviceFeeder:
    """Bounded device-side prefetch queue over an iterable of DataSets.

    Parameters
    ----------
    source : iterable of DataSet (foreign objects pass through unstaged)
    depth : staged batches held ahead of the consumer (default 2 — the
        classic double buffer)
    byte_budget : optional soft cap on staged bytes; refill stops above
        it (at least one item is always staged)
    k_steps : >1 groups K batches into one stacked (K, B, ...) device
        array for the scanned multi-step dispatch; the remainder of an
        epoch not filling a group is yielded as per-batch items at the
        same bucket shape (no K-recompile, no dummy optimizer steps)
    pad_ragged : normalize every batch to the bucket size (first batch's
        example count) with an explicit labels mask. Defaults to True
        when ``k_steps > 1`` (stacking requires it), else False.
    prepare : optional host-side hook ``DataSet -> DataSet`` applied
        before normalization/stacking (the parallel wrapper pads to its
        worker multiple here)
    group_prepare : optional hook ``[DataSet] -> (f, l, fm, lm)``
        overriding the default stack of a K-group (the wrapper's
        AVERAGING round staging)
    group_remainder : "split" (default) yields a short tail group as
        per-batch items; "pad" repeats the last batch to a full group —
        the AVERAGING-round contract, where the round is the unit
    put : staging function ``np.ndarray -> jax.Array`` (default
        ``jax.device_put``; the wrapper passes its sharded staging). It
        is handed the very arrays the source produced (the stacked ones
        of a K-group): the feeder makes no host copy of its own
    """

    def __init__(self, source: Iterable, *, depth: Optional[int] = None,
                 byte_budget: Optional[int] = None, k_steps: int = 1,
                 pad_ragged: Optional[bool] = None,
                 prepare: Optional[Callable[[DataSet], DataSet]] = None,
                 group_prepare: Optional[Callable[[List[DataSet]], tuple]]
                 = None,
                 group_remainder: str = "split",
                 put: Optional[Callable] = None,
                 tracer=None, registry=None, session_id: str = "train"):
        if depth is None:
            # direct constructions (fit() resolves its own): measured
            # tuned depth when a process TunedConfig is installed, else
            # the committed double buffer
            from deeplearning4j_tpu.optimize.autotune import tuned_value
            tuned = tuned_value("feeder.depth")
            depth = DEFAULT_DEPTH if tuned is None else int(tuned)
        if depth < 1:
            raise ValueError("feeder depth must be >= 1")
        if k_steps < 1:
            raise ValueError("k_steps must be >= 1")
        if group_remainder not in ("split", "pad"):
            raise ValueError("group_remainder must be 'split' or 'pad'")
        self.source = source
        self.depth = int(depth)
        self.byte_budget = byte_budget
        self.k_steps = int(k_steps)
        self.pad_ragged = (self.k_steps > 1 if pad_ragged is None
                           else bool(pad_ragged))
        self.prepare = prepare
        self.group_prepare = group_prepare
        self.group_remainder = group_remainder
        self.put = put if put is not None else jax.device_put
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.session_id = session_id
        reg = registry if registry is not None else default_registry()
        self._g_depth = reg.gauge(
            "dl4j_feed_depth", "device batches staged ahead of the step "
            "loop by the input feeder")
        self._g_stall = reg.gauge(
            "dl4j_etl_stall_ms", "cumulative ms the step loop waited on "
            "the input feeder (0 = ETL fully hidden behind compute)")
        # bucket = the normalized example count; seeded from the
        # source's declared batch size so a tiny first pass (ragged
        # FIRST batch) can't lock in an undersized bucket
        bs = getattr(source, "batch_size", None)
        self.bucket_size: Optional[int] = (int(bs) if isinstance(bs, int)
                                           and bs > 0 else None)
        self.stall_ms = 0.0
        self.max_depth_seen = 0
        self._staged_bytes = 0
        self._seq = 0                   # staged items over the feeder's life
        self._watcher: Optional[_ResidentWatcher] = None

    # ---- host-side production -------------------------------------------
    def _normalize(self, batch: DataSet) -> DataSet:
        if self.bucket_size is None:
            self.bucket_size = batch.num_examples()
        return pad_to_bucket(batch, self.bucket_size)

    def _arrays_of(self, batch: DataSet) -> tuple:
        return (batch.features, batch.labels, batch.features_mask,
                batch.labels_mask)

    def _make_group(self, group: List[DataSet]) -> _HostItem:
        """Stack a K-group of RAW batches into (K, B, ...) host arrays.
        Real example counts are taken before the prepare hooks run —
        listeners must see genuine counts, not padded ones."""
        n_real = sum(b.num_examples() for b in group)
        prepared = [self.prepare(b) if self.prepare is not None else b
                    for b in group]
        if self.group_prepare is not None:
            arrays = self.group_prepare(prepared)
        else:
            norm = [self._arrays_of(self._normalize(b)) for b in prepared]
            arrays = tuple(
                None if any(a[i] is None for a in norm)
                else np.stack([np.asarray(a[i]) for a in norm])  # host-sync-ok: host-side batch staging before transfer
                for i in range(4))
        return _HostItem(arrays, len(group), n_real)

    def _make_single(self, batch: DataSet, normalize: bool) -> _HostItem:
        n_real = batch.num_examples()
        if self.prepare is not None:
            batch = self.prepare(batch)
        if normalize:
            batch = self._normalize(batch)
        return _HostItem(self._arrays_of(batch), 1, n_real)

    def _host_items(self):
        """Generator of host-prepared items: per-batch DataSets (k=1),
        stacked K-groups (k=K), or passthrough foreign objects (k=0)."""
        group: List[DataSet] = []
        for b in self.source:
            if not isinstance(b, DataSet):
                for item in self._flush_group(group):
                    yield item
                group = []
                yield _HostItem((None,) * 4, 0, 0, raw=b)
                continue
            if self.k_steps > 1 or self.group_prepare is not None:
                # a group_prepare hook defines the staged LAYOUT (e.g.
                # the wrapper's stacked (K, B, ...) AVERAGING rounds),
                # so it must run even for K=1 groups
                group.append(b)
                if len(group) == self.k_steps:
                    yield self._make_group(group)
                    group = []
            else:
                yield self._make_single(b, normalize=self.pad_ragged)
        for item in self._flush_group(group):
            yield item

    def _flush_group(self, group: List[DataSet]):
        if not group:
            return
        if self.group_remainder == "pad" and len(group) < self.k_steps:
            # the round is the unit: repeat the tail batch to a full
            # group (the AVERAGING contract — ParallelWrapper has always
            # padded short rounds this way, counting the repeats)
            padded = group + [group[-1]] * (self.k_steps - len(group))
            yield self._make_group(padded)
            return
        if len(group) == self.k_steps:
            yield self._make_group(group)
            return
        # short tail, "split": per-batch items at the SAME bucket shape
        # the K-group members were padded to — the per-batch step keeps
        # its one signature and no dummy optimizer steps run
        for b in group:
            yield self._make_single(b, normalize=True)

    # ---- staging ---------------------------------------------------------
    def _stage(self, item: _HostItem) -> FeedItem:
        if item.k == 0:
            return FeedItem(None, None, None, None, 0, item.n_examples,
                            0.0, 0, raw=item.raw)
        start = time.perf_counter()
        staged = []
        nbytes = 0
        for a in item.arrays:
            if a is None:
                staged.append(None)
                continue
            a = np.asarray(a)  # host-sync-ok: host-side batch staging before transfer
            nbytes += a.nbytes
            staged.append(self.put(a))
        seq = self._seq
        self._seq += 1
        if self.tracer.enabled:
            self.tracer.add_span("host_to_device", start,
                                 time.perf_counter(), cat="data",
                                 k=item.k, bytes=nbytes, seq=seq)
            if self._watcher is None:
                self._watcher = _ResidentWatcher(self.tracer)
                # a feeder dropped without close() still ends its thread
                weakref.finalize(self, self._watcher.stop)
            self._watcher.watch(seq, start, nbytes, item.k, tuple(staged))
        self._staged_bytes += nbytes
        return FeedItem(staged[0], staged[1], staged[2], staged[3],
                        item.k, item.n_examples, 0.0, nbytes, seq=seq)

    def close(self):
        """End the ``resident`` watcher once its spans are recorded.
        ``fit()`` calls this after the last epoch and not between epochs:
        waiting there would move the epoch boundary that a traced run is
        made to show. No-op when tracing is off."""
        if self._watcher is not None:
            self._watcher.close()
            self._watcher = None

    # ---- the prefetch loop ----------------------------------------------
    def __iter__(self):
        src = self._host_items()
        pending: deque = deque()
        exhausted = False
        self.stall_ms = 0.0
        self._staged_bytes = 0
        while True:
            wait_ms = 0.0
            while not exhausted and len(pending) < self.depth and (
                    not pending or self.byte_budget is None
                    or self._staged_bytes < self.byte_budget):
                t0 = time.perf_counter()
                try:
                    item = next(src)
                except StopIteration:
                    exhausted = True
                    break
                t1 = time.perf_counter()
                self.tracer.add_span("etl", t0, t1, cat="data")
                staged = self._stage(item)
                if not pending:
                    # queue ran dry: the consumer genuinely waited for
                    # host production + staging issue of THIS item
                    stall = (time.perf_counter() - t0) * 1000.0
                    wait_ms += stall
                    self.stall_ms += stall
                    self.tracer.add_span("feed_stall", t0,
                                         time.perf_counter(), cat="data")
                pending.append(staged)
            if not pending:
                break
            self.max_depth_seen = max(self.max_depth_seen, len(pending))
            self._g_depth.set(len(pending), session=self.session_id)
            self._g_stall.set(self.stall_ms, session=self.session_id)
            out = pending.popleft()
            self._staged_bytes -= out.nbytes
            yield out._replace(queue_wait_ms=wait_ms)
