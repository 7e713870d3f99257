"""Driver ``fit_loop``: a training job through the entry point a user
calls, ``model.fit(iterator, epochs)`` or, with ``workers`` above one in
the traffic file, ``ParallelWrapper.fit(iterator, epochs)``.

A closed loop over the configuration's in-memory set: ``fit`` is called
with ``epochs_per_call`` epochs again and again until the window is
spent. Epoch boundaries (the iterator's reshuffle, the feeder's restart)
and call boundaries (the loop re-reads the device's iteration count)
belong to a real job and stay inside the measurement. The set is wrapped
so that it hands out no batch after the deadline, which ends the window
within a few steps of ``--seconds`` whatever an epoch lasts. Listeners
never sync: losses stay on the device until the window is over.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

from deeplearning4j_tpu.datasets.dataset import (DataSetIterator,
                                                 MultiDataSet)
from deeplearning4j_tpu.optimize.listeners import TrainingListener
from yardstick import cells
from yardstick.cells import Cell
from yardstick.compiles import Compiles
from yardstick.observed import Outcome
from yardstick.tracing import Window
from yardstick.weights import init_on_device

STALL_GAUGE = "dl4j_etl_stall_ms"


class _Until:
    """The set a user would hand to ``fit``, which stops handing out
    batches at a deadline or after a number of them."""

    def __init__(self, base):
        self.base = base
        self.deadline = math.inf
        self.batches_left = math.inf

    def _spent(self) -> bool:
        return (self.batches_left <= 0
                or time.perf_counter() >= self.deadline)

    def __iter__(self):
        if self._spent():       # before the base is asked: starting a pass
            return              # can cost it a reshuffle of the whole set
        for batch in self.base:
            if self._spent():
                return
            self.batches_left -= 1
            yield batch

    def reset(self):
        if hasattr(self.base, "reset"):
            self.base.reset()

    @property
    def batch_size(self):
        return getattr(self.base, "batch_size", None)


class _UntilIterator(_Until, DataSetIterator):
    """The same over a ``DataSetIterator``, so that ``fit`` gives it the
    prefetch thread it gives the iterator inside."""


def _until(base) -> _Until:
    return (_UntilIterator if isinstance(base, DataSetIterator)
            else _Until)(base)


class _Tap(TrainingListener):
    """Counts steps and examples, keeps each loss on the device, and
    sums the feeder's stall gauge, which the program resets every
    epoch."""

    def __init__(self, staged_shape, watch_shards: bool):
        self.losses: List[Any] = []
        self.examples = 0
        self.etl_stall_ms = 0.0
        self.watch_shards = watch_shards
        self.shard_rows: List[Dict[int, int]] = []
        self._staged_shape = tuple(staged_shape)
        self._steps_this_epoch = 0

    def iteration_done(self, model, iteration, epoch, loss, etl_ms,
                       batch_size):
        self.losses.append(loss)
        self.examples += batch_size
        self._steps_this_epoch += 1
        if self.watch_shards:
            import jax
            self.shard_rows += [
                {s.device.id: s.data.shape[0] for s in a.addressable_shards}
                for a in jax.live_arrays()
                if a.shape == self._staged_shape]

    def on_epoch_end(self, model, epoch):
        # the feeder writes the gauge as it hands out a batch, so an
        # epoch without one (after the deadline) still shows the last's
        from deeplearning4j_tpu.observe.registry import default_registry
        gauge = default_registry().get_metric(STALL_GAUGE)
        if gauge is not None and self._steps_this_epoch:
            self.etl_stall_ms += sum(gauge.series().values())
        self._steps_this_epoch = 0

    def restart(self):
        self.losses, self.examples, self.etl_stall_ms = [], 0, 0.0
        self.watch_shards = False


def _as_tuples(batch):
    if isinstance(batch, MultiDataSet):
        return tuple(batch.features), tuple(batch.labels)
    return (batch.features,), (batch.labels,)


def _resolved_tunables() -> Dict[str, Any]:
    """What ``fit()`` will resolve ``k_steps`` and ``prefetch`` to, by the
    program's own functions."""
    from deeplearning4j_tpu.datasets.feeder import DEFAULT_DEPTH
    from deeplearning4j_tpu.optimize.autotune import (process_tuned,
                                                      tuned_value)
    k, depth = tuned_value("fit.k_steps"), tuned_value("feeder.depth")
    return {"tuned_config": None if process_tuned() is None else "installed",
            "fit.k_steps": 1 if k is None else int(k),
            "feeder.depth": DEFAULT_DEPTH if depth is None else int(depth)}


def _replicas_agree(model, workers: int) -> bool:
    """Every parameter leaf is held whole by each worker's chip, and the
    copies are equal bit for bit."""
    import jax
    for leaf in jax.tree_util.tree_leaves(model.train_state.params):
        shards = leaf.addressable_shards
        if len(shards) != workers:
            return False
        first = np.asarray(shards[0].data)
        if first.shape != leaf.shape or any(
                not np.array_equal(first, np.asarray(s.data))
                for s in shards[1:]):
            return False
    return True


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        compiles: Compiles, devices, t_process: float) -> Outcome:
    import jax
    cfg, traffic = cell.config, cell.traffic
    build = cells.load_build(cell)
    reference = cells.load_reference(cell)
    workers = int(traffic["workers"])
    batch = int(cfg["batch"]) * workers      # the iterator's batch
    notes: Dict[str, Any] = {"driver": "fit_loop", "iterator_batch": batch,
                             **_resolved_tunables()}

    # ---- set-up: model, weights from the seed, data, one comparison with
    # the plain reference, and a warm-up through the path the window uses
    phases, last = {}, [t_process]

    def lap(name):
        now = time.perf_counter()
        phases[name] = round(now - last[0], 3)
        last[0] = now

    lap("imports")
    graph = build.build(cfg, seed)
    lap("graph")
    model = init_on_device(graph, seed)
    jax.block_until_ready(model.train_state)
    lap("weights")
    check = build.check_batch(cfg, seed, int(traffic["check_rows"]))
    feats, labels = _as_tuples(check)
    ts = model.train_state
    want = float(reference.loss(cfg, ts.params, ts.model_state, feats, labels))
    got = float(model.score(check))
    tol = float(cfg["loss_tolerance"])
    loss_err = abs(got - want) / abs(want)
    notes.update(reference_loss=want, system_loss=got,
                 loss_rel_err=loss_err, loss_tolerance=tol)
    lap("reference_check")

    data = _until(build.train_set(cfg, seed, batch))
    lap("data")
    tap = _Tap((batch,) + feats[0].shape[1:], watch_shards=workers > 1)
    model.set_listeners(tap)
    window = Window(cell, trace, compiles)
    model.tracer = window.tracer            # None leaves the program's off
    if workers > 1:
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        trainer = ParallelWrapper.builder(model).workers(workers).build()
    else:
        trainer = model
    # at least two steps: ParallelWrapper's second step sees a train state
    # laid out by its first and is another program
    data.batches_left = int(traffic["warmup_steps"])
    while data.batches_left > 0:
        before = len(tap.losses)
        trainer.fit(data, epochs=1)
        if len(tap.losses) == before:
            raise ValueError("the configuration's train_set is empty")
    jax.block_until_ready(model.train_state)
    warm_losses = np.asarray(jax.device_get(tap.losses), np.float32)
    shards_ok = True
    if workers > 1:
        want_rows = {d.id: batch // workers for d in devices}
        shards_ok = bool(tap.shard_rows) and all(
            rows == want_rows for rows in tap.shard_rows)
        notes["shard_rows"] = tap.shard_rows[:1]
    tap.restart()
    data.batches_left = math.inf
    lap("warmup")
    notes["setup_phases_s"] = phases

    # ---- the window
    epochs = int(traffic["epochs_per_call"])
    length = float(traffic["trace_seconds"]) if trace else float(seconds)

    with window:
        data.deadline = time.perf_counter() + length
        while time.perf_counter() < data.deadline:
            trainer.fit(data, epochs=epochs)
        jax.block_until_ready(model.train_state)

    # ---- after the window: losses to the host, the checks
    losses = np.asarray(jax.device_get(tap.losses), np.float32)
    steps = len(losses)
    failed = int(np.sum(~np.isfinite(losses)))
    # over the whole run, warm-up steps included: a traced window of five
    # steps is too short for its own first and last loss to say anything
    run_losses = np.concatenate([warm_losses, losses])
    quarter = max(1, len(run_losses) // 4)
    fell = bool(steps > 0 and np.mean(run_losses[-quarter:])
                < np.mean(run_losses[:quarter]))
    replicas_ok = workers == 1 or _replicas_agree(model, workers)
    correct = bool(steps > 0 and failed == 0
                   and np.isfinite(warm_losses).all()
                   and compiles.in_window == 0 and loss_err <= tol
                   and fell and shards_ok and replicas_ok)
    notes.update(steps=steps, window_s=window.seconds,
                 memory_stats=devices[0].memory_stats(),
                 loss_first_quarter=float(np.mean(run_losses[:quarter])),
                 loss_last_quarter=float(np.mean(run_losses[-quarter:])),
                 loss_fell=fell, compiles_in_window=compiles.in_window,
                 shards_ok=shards_ok, replicas_agree=replicas_ok)
    end_to_end = {
        "train_examples_per_s_per_chip":
            tap.examples / window.seconds / workers,
        "setup_s": window.opened_at - t_process,
    }
    observed = window.observed(devices, {
        "steps": steps, "examples": tap.examples, "workers": workers,
        "flops_per_step_per_chip":
            build.train_flops_per_example(cfg) * cfg["batch"],
        "etl_stall_ms": tap.etl_stall_ms})
    return Outcome(correct=correct, attempted=steps, failed=failed,
                   end_to_end=end_to_end, notes=notes, observed=observed)
