"""In-step device telemetry: metric spec + on-device ring buffer.

The reference's BaseStatsListener reads score and parameter statistics
from the host after every iteration — each read is a device→host sync
that drains the dispatch pipeline (SURVEY §2.12). Here the metrics are
computed INSIDE the jitted train step, where the loss/grads/updates
already live in registers, and appended to a fixed-size on-device ring
buffer carried in the TrainState. The host fetches the whole buffer in
ONE transfer every ``flush_interval`` steps; between flushes, training
performs zero telemetry-induced syncs.

Metric rows are f32: loss, global grad-norm, non-finite count across
gradients+loss, and (optionally) one update:param mean-magnitude ratio
per layer. Iterations ride in a parallel int32 ring so rows stay exact
past 2^24 steps.
"""

from __future__ import annotations

import logging
import math
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.observe.registry import (
    MetricsRegistry,
    default_registry,
)

log = logging.getLogger(__name__)

# metrics always present, in row order, ahead of per-layer ratios
BASE_METRICS = ("loss", "grad_norm", "nonfinite_count")

# histogram kinds, in storage order along the hist ring's third axis
HIST_KINDS = ("param", "grad", "update")


class HistRing(NamedTuple):
    """Device-resident histogram ring: ``counts[i % capacity]`` holds the
    fixed-bin per-layer param/grad/update histograms of the i-th recorded
    histogram step. Rides inside the TelemetryBuffer pytree so it is
    fetched in the SAME single device_get as the metric rows."""
    counts: jnp.ndarray   # f32[capacity, n_layers, len(HIST_KINDS), bins]
    ranges: jnp.ndarray   # f32[capacity, n_layers, len(HIST_KINDS), 2]
    iters: jnp.ndarray    # i32[capacity]
    count: jnp.ndarray    # i32 scalar


class ReplicaRing(NamedTuple):
    """Per-device rows from the parallel wrapper's step (loss/grad-norm
    per worker in AVERAGING mode, a param-norm fingerprint per replica in
    sync DP). Also part of the one-fetch TelemetryBuffer pytree."""
    rows: jnp.ndarray     # f32[capacity, n_workers, n_replica_metrics]
    iters: jnp.ndarray    # i32[capacity]
    count: jnp.ndarray    # i32 scalar


class TelemetryBuffer(NamedTuple):
    """Device-resident ring: ``rows[i % capacity]`` is the metric row of
    the i-th recorded step; ``count`` is the total rows ever written.
    ``hist`` and ``replica`` default to empty pytrees so 3-field
    constructions (and old checkpoints) keep working."""
    rows: jnp.ndarray    # f32[capacity, n_metrics]
    iters: jnp.ndarray   # i32[capacity]
    count: jnp.ndarray   # i32 scalar
    hist: Any = ()       # HistRing when histograms are enabled
    replica: Any = ()    # ReplicaRing when replica rows are enabled


def has_buffer(telemetry) -> bool:
    """True when a TrainState.telemetry slot actually carries a ring
    buffer (the slot defaults to an empty pytree)."""
    return isinstance(telemetry, TelemetryBuffer)


class TelemetrySpec:
    """Compiled-in metric catalog: knows the row layout and how to append
    one row from inside the traced step."""

    def __init__(self, layer_names: Tuple[str, ...] = (),
                 capacity: int = 128, per_layer: bool = True,
                 histograms: bool = False, hist_bins: int = 16,
                 hist_interval: int = 10, hist_capacity: int = 8,
                 replicas: int = 0,
                 replica_metrics: Tuple[str, ...] = ("loss", "grad_norm")):
        if capacity < 1:
            raise ValueError("telemetry capacity must be >= 1")
        if hist_bins < 2 or hist_capacity < 1 or hist_interval < 1:
            raise ValueError("histogram config must be positive "
                             "(bins >= 2)")
        self.capacity = int(capacity)
        self.per_layer = per_layer
        self.layer_names = tuple(layer_names) if per_layer else ()
        self.metric_names: Tuple[str, ...] = BASE_METRICS + tuple(
            f"update_ratio/{n}" for n in self.layer_names)
        # histograms need named layers to bucket by
        self.histograms = bool(histograms) and bool(self.layer_names)
        self.hist_bins = int(hist_bins)
        self.hist_interval = int(hist_interval)
        self.hist_capacity = int(hist_capacity)
        self.replicas = int(replicas)
        self.replica_metrics = tuple(replica_metrics)

    def init(self) -> TelemetryBuffer:
        n = len(self.metric_names)
        hist: Any = ()
        if self.histograms:
            nl, nk = len(self.layer_names), len(HIST_KINDS)
            hist = HistRing(
                counts=jnp.zeros((self.hist_capacity, nl, nk,
                                  self.hist_bins), jnp.float32),
                ranges=jnp.zeros((self.hist_capacity, nl, nk, 2),
                                 jnp.float32),
                iters=jnp.full((self.hist_capacity,), -1, jnp.int32),
                count=jnp.zeros((), jnp.int32))
        replica: Any = ()
        if self.replicas > 1:
            replica = ReplicaRing(
                rows=jnp.zeros((self.capacity, self.replicas,
                                len(self.replica_metrics)), jnp.float32),
                iters=jnp.full((self.capacity,), -1, jnp.int32),
                count=jnp.zeros((), jnp.int32))
        return TelemetryBuffer(
            rows=jnp.zeros((self.capacity, n), jnp.float32),
            iters=jnp.full((self.capacity,), -1, jnp.int32),
            count=jnp.zeros((), jnp.int32),
            hist=hist, replica=replica)

    # ---- traced: runs inside the jitted train step ----------------------
    def record(self, buf: TelemetryBuffer, *, loss, grads, params,
               prev_params, iteration) -> TelemetryBuffer:
        """Append one metric row. All inputs are traced values already in
        flight inside the step — recording adds a handful of reductions
        and one dynamic row write, no host interaction.

        The update:param ratio is ``mean|new - prev| / mean|new|`` per
        layer over bounded prefix samples — computed from the parameter
        DELTA, not the optimizer's update tree: depending on the update
        tree would force XLA to materialize it as a buffer instead of
        fusing it into the parameter add (measured at ~8% step time on
        the CPU tier-1 path). The delta also folds in constraint
        projections, matching ui/stats.py's update-statistics convention.
        """
        gleaves = jax.tree_util.tree_leaves(grads)
        loss32 = loss.astype(jnp.float32)
        sumsq = sum(
            (jnp.sum(jnp.square(g.astype(jnp.float32))) for g in gleaves),
            jnp.zeros((), jnp.float32))
        gnorm = jnp.sqrt(sumsq)

        # The elementwise non-finite count is an O(params) pass that the
        # squared-norm already screens for free: any NaN/Inf gradient
        # element makes ``sumsq`` non-finite (squares are >= 0, so no
        # finite cancellation can produce NaN). Steady state takes the
        # zero branch; the full count only runs — and is exact — once
        # training has actually blown up.
        def _count_nonfinite():
            return sum(
                (jnp.sum(~jnp.isfinite(g)).astype(jnp.float32)
                 for g in gleaves), jnp.zeros((), jnp.float32))

        nonfinite = jax.lax.cond(
            jnp.isfinite(sumsq),
            lambda: jnp.zeros((), jnp.float32),
            _count_nonfinite) + (~jnp.isfinite(loss32)).astype(
            jnp.float32)
        vals = [loss32, gnorm, nonfinite]
        for name in self.layer_names:
            new = jax.tree_util.tree_leaves(_subtree(params, name))
            old = jax.tree_util.tree_leaves(_subtree(prev_params, name))
            if not new or len(new) != len(old):
                vals.append(jnp.zeros((), jnp.float32))
                continue
            umag = _mean_abs([n - o for n, o in
                              zip(_samples(new), _samples(old))])
            pmag = _mean_abs(_samples(new))
            vals.append(umag / (pmag + jnp.float32(1e-12)))
        row = jnp.stack(vals)
        idx = buf.count % self.capacity
        new_buf = buf._replace(
            rows=buf.rows.at[idx].set(row),
            iters=buf.iters.at[idx].set(iteration.astype(jnp.int32) + 1),
            count=buf.count + 1)
        if self.histograms and isinstance(buf.hist, HistRing):
            new_buf = new_buf._replace(hist=self._record_hist(
                buf.hist, buf.count, nonfinite, grads=grads,
                params=params, prev_params=prev_params,
                iteration=iteration))
        return new_buf

    def _record_hist(self, hist: HistRing, step_count, nonfinite, *,
                     grads, params, prev_params, iteration) -> HistRing:
        """Fixed-bin per-layer param/grad/update histograms, written every
        ``hist_interval`` recorded steps — and unconditionally on a
        blown-up step (non-finite seen), so the post-mortem dump always
        carries the histograms of the step that died. The bucketing runs
        inside a ``lax.cond`` branch: amortized steady-state cost is the
        sampling slices plus one predicate."""
        samples = []
        for name in self.layer_names:
            p = jax.tree_util.tree_leaves(_subtree(params, name))
            o = jax.tree_util.tree_leaves(_subtree(prev_params, name))
            g = jax.tree_util.tree_leaves(_subtree(grads, name))
            ps = _concat_samples(p)
            gs = _concat_samples(g) if g else jnp.zeros((1,), jnp.float32)
            us = (ps - _concat_samples(o)
                  if o and len(o) == len(p) else
                  jnp.zeros_like(ps))
            samples.append((ps, gs, us))

        def _update(h: HistRing) -> HistRing:
            per_layer_counts, per_layer_ranges = [], []
            for ps, gs, us in samples:
                kc, kr = [], []
                for x in (ps, gs, us):
                    c, lo, hi = _hist_counts(x, self.hist_bins)
                    kc.append(c)
                    kr.append(jnp.stack([lo, hi]))
                per_layer_counts.append(jnp.stack(kc))
                per_layer_ranges.append(jnp.stack(kr))
            hidx = h.count % self.hist_capacity
            return HistRing(
                counts=h.counts.at[hidx].set(
                    jnp.stack(per_layer_counts)),
                ranges=h.ranges.at[hidx].set(
                    jnp.stack(per_layer_ranges)),
                iters=h.iters.at[hidx].set(
                    iteration.astype(jnp.int32) + 1),
                count=h.count + 1)

        due = (step_count % self.hist_interval == 0) | (nonfinite > 0)
        return jax.lax.cond(due, _update, lambda h: h, hist)

    def record_replica(self, buf: TelemetryBuffer, *, values,
                       iteration) -> TelemetryBuffer:
        """Append one per-device row (``values``: f32[n_workers,
        n_replica_metrics], identical on every device — e.g. the result
        of an ``all_gather``). Traced; called from the parallel wrapper's
        step function."""
        rep = buf.replica
        if not isinstance(rep, ReplicaRing):
            return buf
        idx = rep.count % self.capacity
        return buf._replace(replica=ReplicaRing(
            rows=rep.rows.at[idx].set(values.astype(jnp.float32)),
            iters=rep.iters.at[idx].set(iteration.astype(jnp.int32) + 1),
            count=rep.count + 1))


def _subtree(tree, key):
    if isinstance(tree, dict):
        return tree.get(key, {})
    return {}


# Per-leaf sample cap for the update:param ratio estimate. Full
# reductions over every parameter tensor measured +16% step time on the
# CPU tier-1 path (benchmarks/telemetry_overhead.py) — the ratio is a
# monitoring signal, so bound the work: tensors larger than the cap
# contribute a prefix sample (a 64Ki-element mean is statistically
# indistinguishable for health monitoring). Tensors at or under the cap
# are reduced exactly.
_MEAN_ABS_SAMPLE = 65536


def _samples(leaves):
    """Flattened bounded prefix of each leaf (static slice: no gather)."""
    out = []
    for l in leaves:
        flat = l.reshape(-1)
        if int(np.prod(l.shape)) > _MEAN_ABS_SAMPLE:
            flat = flat[:_MEAN_ABS_SAMPLE]
        out.append(flat)
    return out


def _mean_abs(leaves) -> jnp.ndarray:
    total = jnp.zeros((), jnp.float32)
    n = 0
    for l in leaves:
        total = total + jnp.sum(jnp.abs(l.astype(jnp.float32)))
        n += int(np.prod(l.shape))
    return total / jnp.float32(max(n, 1))


# Histograms use a tighter per-leaf sample cap than the ratio estimate:
# the scatter-add bucketing is a gather-heavy pass, and a 16Ki sample per
# tensor is ample for a 16-bin shape signal.
_HIST_SAMPLE = 16384


def _concat_samples(leaves) -> jnp.ndarray:
    """One flat f32 vector of bounded prefix samples over the leaves."""
    flat = [l.reshape(-1)[:_HIST_SAMPLE].astype(jnp.float32)
            for l in leaves]
    if not flat:
        return jnp.zeros((1,), jnp.float32)
    return jnp.concatenate(flat) if len(flat) > 1 else flat[0]


def _hist_counts(x: jnp.ndarray, bins: int):
    """Fixed-bin histogram of ``x``: (counts[bins], min, max). Non-finite
    elements are zeroed before bucketing (the ``nonfinite_count`` row
    already counts them exactly; a NaN range would poison every bin)."""
    x = jnp.where(jnp.isfinite(x), x, jnp.zeros_like(x))
    lo = jnp.min(x)
    hi = jnp.max(x)
    span = jnp.maximum(hi - lo, jnp.float32(1e-30))
    idx = jnp.clip(((x - lo) / span * bins).astype(jnp.int32), 0, bins - 1)
    counts = jnp.zeros((bins,), jnp.float32).at[idx].add(1.0)
    return counts, lo, hi


class TelemetryCollector:
    """Host side: owns the spec, decides when to flush, decodes rows, and
    publishes to the Prometheus registry.

    Attach with ``model.set_telemetry(TelemetryCollector(...))``; the
    model compiles the spec into its train step and calls ``on_step``
    after each dispatch. Every ``flush_interval`` recorded steps the
    collector performs exactly ONE device fetch (``fetch_count`` counts
    them — the property the acceptance test asserts). Listener-visible
    values (``last('loss')`` etc.) therefore lag up to one flush
    interval; that staleness is the price of a stall-free pipeline.
    """

    def __init__(self, flush_interval: int = 50,
                 capacity: Optional[int] = None, per_layer: bool = True,
                 registry: Optional[MetricsRegistry] = None,
                 session_id: str = "train",
                 histograms: bool = False, hist_bins: int = 16,
                 hist_interval: int = 10, hist_capacity: int = 8):
        if flush_interval < 1:
            raise ValueError("flush_interval must be >= 1")
        self.flush_interval = int(flush_interval)
        self.capacity = int(capacity) if capacity is not None else max(
            2 * self.flush_interval, 64)
        if self.capacity < self.flush_interval:
            raise ValueError(
                f"capacity {self.capacity} < flush_interval "
                f"{self.flush_interval}: rows would be overwritten "
                "before they are ever fetched")
        self.per_layer = per_layer
        self.session_id = session_id
        self.registry = registry if registry is not None else \
            default_registry()
        self.histograms = bool(histograms)
        self.hist_bins = int(hist_bins)
        self.hist_interval = int(hist_interval)
        self.hist_capacity = int(hist_capacity)
        self.spec: Optional[TelemetrySpec] = None
        self.history: List[dict] = []
        self.hist_history: List[dict] = []
        self.replica_history: List[dict] = []
        self.fetch_count = 0
        self.dropped_rows = 0
        self._read_count = 0
        self._hist_read = 0
        self._replica_read = 0
        self._pending = 0
        self._last_flush_time: Optional[float] = None

    # ---- wiring ---------------------------------------------------------
    def spec_for(self, model) -> TelemetrySpec:
        """The spec is built once per collector, from the model's layer
        names — reusing one collector across models with different layers
        would mislabel rows, so it is rejected."""
        names = tuple(getattr(model, "layer_names", ()))
        if self.spec is None:
            self.spec = TelemetrySpec(
                names, capacity=self.capacity, per_layer=self.per_layer,
                histograms=self.histograms, hist_bins=self.hist_bins,
                hist_interval=self.hist_interval,
                hist_capacity=self.hist_capacity)
        elif self.per_layer and self.spec.layer_names != names:
            raise ValueError(
                "TelemetryCollector is already bound to layers "
                f"{self.spec.layer_names}; use a fresh collector for a "
                "model with different layers")
        return self.spec

    def enable_replicas(self, n_workers: int,
                        metrics: Tuple[str, ...] = ("loss", "grad_norm")
                        ) -> bool:
        """Turn on the per-device row ring (the parallel wrapper calls
        this before its first dispatch). Returns True when the spec
        changed — the caller must then re-init any existing buffer so the
        new pytree slot exists."""
        if self.spec is None:
            raise RuntimeError("spec_for(model) must run before "
                               "enable_replicas")
        n = int(n_workers)
        metrics = tuple(metrics)
        changed = (self.spec.replicas != n
                   or self.spec.replica_metrics != metrics)
        self.spec.replicas = n
        self.spec.replica_metrics = metrics
        return changed

    def rebind_buffer(self, train_state):
        """Replace the buffer after a spec change (``enable_replicas``
        altered the pytree): flush whatever the old ring still holds,
        re-init to the new layout and reset the read cursors. One extra
        fetch + one recompile, both before the next monitored dispatch."""
        if self.spec is None:
            raise RuntimeError("spec_for(model) must run before "
                               "rebind_buffer")
        if has_buffer(train_state.telemetry):
            self.flush(train_state)
        self._read_count = 0
        self._hist_read = 0
        self._replica_read = 0
        self._pending = 0
        if self._last_flush_time is None:
            self._last_flush_time = time.perf_counter()
        return train_state._replace(telemetry=self.spec.init())

    def ensure_buffer(self, train_state):
        """Attach the ring buffer into a TrainState that doesn't carry
        one yet (changes the pytree structure → one recompile, before the
        first monitored dispatch)."""
        if has_buffer(train_state.telemetry):
            return train_state
        if self.spec is None:
            raise RuntimeError("spec_for(model) must run before "
                               "ensure_buffer")
        if self._last_flush_time is None:
            self._last_flush_time = time.perf_counter()
        return train_state._replace(telemetry=self.spec.init())

    # ---- steady-state hook ----------------------------------------------
    def will_flush(self, steps: int = 1) -> bool:
        """Whether the next ``on_step(..., steps)`` will fetch."""
        return self._pending + int(steps) >= self.flush_interval

    def on_step(self, train_state, steps: int = 1):
        """Called after each dispatched train step (``steps`` > 1 for the
        scanned multi-step). Flushes when a full interval has
        accumulated; otherwise free — no device interaction."""
        self._pending += int(steps)  # graftlint: disable=release-discipline: flush-interval accumulator reset by flush(), not a capacity claim
        if self._pending >= self.flush_interval:
            self.flush(train_state)

    def flush(self, train_state) -> List[dict]:
        """ONE device fetch: pull the whole ring + counters, decode every
        row not yet seen, publish the newest values to the registry.
        Returns the newly decoded records."""
        buf = train_state.telemetry
        if not has_buffer(buf):
            return []
        host = jax.device_get(buf)       # the single transfer
        self.fetch_count += 1
        self._pending = 0
        now = time.perf_counter()
        total = int(host.count)
        new = total - self._read_count
        records: List[dict] = []
        if new > 0:
            dropped = max(0, new - self.spec.capacity)
            if dropped:
                self.dropped_rows += dropped
                self.registry.counter(
                    "dl4j_telemetry_dropped_rows_total",
                    "ring rows overwritten before flush").inc(
                    dropped, session=self.session_id)
                log.warning("telemetry ring overwrote %d rows before "
                            "flush (capacity %d); flush more often or "
                            "grow the ring", dropped, self.spec.capacity)
            for j in range(self._read_count + dropped, total):
                idx = j % self.spec.capacity
                rec: Dict[str, Any] = {"iteration": int(host.iters[idx])}
                for m, name in enumerate(self.spec.metric_names):
                    rec[name] = float(host.rows[idx, m])
                records.append(rec)
            self._read_count = total
            self.history.extend(records)
        # hist/replica rings advance on their own cadence (the parallel
        # wrapper's AVERAGING step records ONLY replica rows) — decode
        # them even when no new base rows landed
        self._decode_hist(host)
        rep_records = self._decode_replica(host)
        if records:
            self._publish(records, new, now)
        self._publish_replica(rep_records)
        self._last_flush_time = now
        return records

    def _decode_hist(self, host) -> List[dict]:
        """Decode new histogram-ring entries from an already-fetched
        buffer (no device interaction — ``host`` is the flush's one
        transfer)."""
        if not isinstance(host.hist, HistRing) or self.spec is None:
            return []
        h = host.hist
        total = int(h.count)
        new = total - self._hist_read
        if new <= 0:
            return []
        start = self._hist_read + max(0, new - self.spec.hist_capacity)
        out = []
        for j in range(start, total):
            idx = j % self.spec.hist_capacity
            layers: Dict[str, dict] = {}
            for li, lname in enumerate(self.spec.layer_names):
                layers[lname] = {
                    kind: {
                        "counts": h.counts[idx, li, ki].tolist(),
                        "min": float(h.ranges[idx, li, ki, 0]),
                        "max": float(h.ranges[idx, li, ki, 1]),
                    } for ki, kind in enumerate(HIST_KINDS)}
            out.append({"iteration": int(h.iters[idx]),
                        "layers": layers})
        self._hist_read = total
        self.hist_history.extend(out)
        return out

    def _decode_replica(self, host) -> List[dict]:
        """Decode new per-device rows from the fetched buffer."""
        if not isinstance(host.replica, ReplicaRing) or self.spec is None:
            return []
        rep = host.replica
        total = int(rep.count)
        new = total - self._replica_read
        if new <= 0:
            return []
        start = self._replica_read + max(0, new - self.spec.capacity)
        out = []
        for j in range(start, total):
            idx = j % self.spec.capacity
            rec: Dict[str, Any] = {"iteration": int(rep.iters[idx])}
            for m, name in enumerate(self.spec.replica_metrics):
                rec[name] = [float(v) for v in rep.rows[idx, :, m]]
            out.append(rec)
        self._replica_read = total
        self.replica_history.extend(out)
        return out

    def _publish(self, records: List[dict], n_steps: int, now: float):
        r = self.registry
        s = self.session_id
        last = records[-1]
        r.gauge("dl4j_loss", "training loss (flushed from the device "
                "ring)").set(last["loss"], session=s)
        r.gauge("dl4j_grad_norm", "global gradient L2 norm").set(
            last["grad_norm"], session=s)
        r.gauge("dl4j_iteration", "latest flushed iteration").set(
            last["iteration"], session=s)
        nonfinite = sum(rec["nonfinite_count"] for rec in records)
        r.counter("dl4j_nonfinite_values_total", "non-finite values seen "
                  "in gradients/loss").inc(nonfinite, session=s)
        if self._last_flush_time is not None:
            dt = now - self._last_flush_time
            if dt > 0:
                r.gauge("dl4j_steps_per_second", "optimizer steps per "
                        "second over the last flush window").set(
                    n_steps / dt, session=s)
        r.counter("dl4j_telemetry_flushes_total", "device fetches "
                  "performed by the telemetry collector").inc(session=s)
        for name in self.spec.layer_names:
            r.gauge("dl4j_update_ratio", "mean |update| / mean |param| "
                    "per layer").set(last[f"update_ratio/{name}"],
                                     session=s, layer=name)

    def _publish_replica(self, records: List[dict]):
        """Per-device gauges + the cross-replica divergence metric: the
        relative spread (max − min over workers, over the mean magnitude)
        of the divergence column — ``grad_norm`` when present, else the
        last replica metric. ~0 on healthy synchronous replicas; a
        desynced/straggling worker pushes it up before the averaged
        parameters are corrupted."""
        if not records or self.spec is None:
            return
        r = self.registry
        s = self.session_id
        names = self.spec.replica_metrics
        last = records[-1]
        nonfinite = 0
        for rec in records:
            for name in names:
                nonfinite += sum(1 for v in rec[name]
                                 if not math.isfinite(v))
        if nonfinite:
            r.counter("dl4j_nonfinite_values_total", "non-finite values "
                      "seen in gradients/loss").inc(nonfinite, session=s)
        for name in names:
            g = r.gauge(f"dl4j_replica_{name}",
                        f"per-device {name} from the parallel wrapper")
            for w, v in enumerate(last[name]):
                g.set(v, session=s, replica=str(w))
        div_col = "grad_norm" if "grad_norm" in names else names[-1]
        div = 0.0
        for rec in records:
            vals = [v for v in rec[div_col] if math.isfinite(v)]
            if len(vals) >= 2:
                scale = sum(abs(v) for v in vals) / len(vals)
                div = max(div,
                          (max(vals) - min(vals)) / (scale + 1e-12))
            elif len(vals) < len(rec[div_col]):
                div = float("inf")   # a non-finite replica IS divergence
        r.gauge("dl4j_replica_divergence", "relative max pairwise "
                "spread of per-replica grad norms (0 = replicas in "
                "sync)").set(div, session=s)

    # ---- read side ------------------------------------------------------
    def last_record(self) -> Optional[dict]:
        return self.history[-1] if self.history else None

    def last(self, metric: str) -> Optional[float]:
        rec = self.last_record()
        return None if rec is None else rec.get(metric)

    def last_histograms(self) -> Optional[dict]:
        """Latest decoded per-layer histograms
        (``{"iteration": i, "layers": {name: {param/grad/update:
        {counts, min, max}}}}``), or None before the first flush of a
        histogram-enabled ring."""
        return self.hist_history[-1] if self.hist_history else None

    def last_replica_record(self) -> Optional[dict]:
        return self.replica_history[-1] if self.replica_history else None


# ---- routing counters of expert layers ------------------------------------

ROUTING_GAUGES = (
    ("dl4j_moe_assignments_held",
     "token-to-expert assignments of the last step that landed on experts "
     "this chip holds"),
    ("dl4j_moe_expert_load_max",
     "largest number of assignments one held expert took in the last step"),
    ("dl4j_moe_expert_load_mean",
     "mean number of assignments a held expert took in the last step"),
    ("dl4j_moe_dropped_assignments",
     "assignments to held experts that were not computed in the last step "
     "(the held-experts path drops none: always 0)"),
    ("dl4j_moe_dispatch_blocks",
     "blocks of rows the held experts' dispatch loop ran in the last step: "
     "the held assignments over the block, rounded up"),
    ("dl4j_moe_router_bias_absmax",
     "largest |b_e| of a sigmoid router's score-correction bias after the "
     "last step: how far the load has moved it (layers whose router has "
     "none publish no such series)"),
)


LOSS_TERM_GAUGE = (
    "dl4j_loss_term",
    "one term of the last step's training loss, before its weight, as the "
    "head that computes it leaves it in its state (labels: layer, term)")
LOSS_TERMS = ("next_token", "mtp")


def publish_loss_terms(rows: Dict[str, Any],
                       registry: Optional[MetricsRegistry] = None) -> None:
    """``rows``: layer name -> the ``lm_loss_terms`` a head with a
    multi-token-prediction term leaves in its state (next-token mean, then
    the multi-token mean), on the host; a gauge each, labelled by layer
    and ``term``."""
    reg = registry if registry is not None else default_registry()
    gauge = reg.gauge(*LOSS_TERM_GAUGE)
    for layer, row in rows.items():
        for term, value in zip(LOSS_TERMS, np.asarray(row)):
            gauge.set(float(value), layer=layer, term=term)


def publish_routing(rows: Dict[str, Any],
                    registry: Optional[MetricsRegistry] = None) -> None:
    """``rows``: layer name -> the row a ``HeldExpertsMoE`` leaves in its
    state (``parallel.moe.ROUTING_COUNTERS``' order, then the dispatch
    loop's blocks, then, under a sigmoid router, its bias's largest
    magnitude), on the host. Each value becomes a gauge with a
    ``layer`` label."""
    reg = registry if registry is not None else default_registry()
    for layer, row in rows.items():
        for (name, help_text), value in zip(ROUTING_GAUGES, np.asarray(row)):
            reg.gauge(name, help_text).set(float(value), layer=layer)
