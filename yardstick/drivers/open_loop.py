"""Driver ``open_loop``: a prediction service under independent clients.

``ServingEngine(model, batch_limit, feature_shape)`` as a user builds it,
fed by ``yardstick/arrivals.py``: Poisson arrivals from the seed at the
rate fixed in the traffic file, rows per request from its mixture, every
request timed from when it was due. Nothing is trained first: the weights
are the seed's, and where the configuration's reference offers
``batch_statistics`` the running statistics are set from a seeded batch,
as training would have left them.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from yardstick import arrivals, cells
from yardstick.cells import Cell
from yardstick.compiles import Compiles
from yardstick.observed import Outcome
from yardstick.tracing import Window
from yardstick.weights import init_on_device

WARM_REQUESTS = 32


def _rel_err(got, want) -> float:
    """The largest difference between two sets of class probabilities, in
    log space and relative to the spread of the wanted ones: with random
    weights a rounding moves a probability by a factor, and the spread of
    the logits is the scale that factor is measured against."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    tiny = 1e-30
    diff = np.abs(np.log(got + tiny) - np.log(want + tiny))
    return float(np.max(diff) / np.std(np.log(want + tiny)))


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        compiles: Compiles, devices, t_process: float) -> Outcome:
    from deeplearning4j_tpu.parallel.serving import ServingEngine
    cfg, traffic = cell.config, cell.traffic
    build = cells.load_build(cell)
    reference = cells.load_reference(cell)
    notes: Dict[str, Any] = {"driver": "open_loop",
                             "rate_per_s": traffic["rate_per_s"],
                             "limit_ms": traffic["limit_ms"],
                             "batch_limit": traffic["batch_limit"]}

    # ---- set-up: model, weights and statistics, engine (its constructor
    # compiles the bucket ladder), payloads, schedule, a few warm requests
    model = init_on_device(build.build(cfg, seed), seed)
    pool = build.request_rows(cfg, seed, int(traffic["request_pool_rows"]))
    if hasattr(reference, "batch_statistics"):
        ts = model.train_state
        calib = pool[:int(traffic["calibration_rows"])]
        model.train_state = ts._replace(model_state=reference.batch_statistics(
            cfg, ts.params, ts.model_state, (calib,)))
    window = Window(cell, trace, compiles)
    engine = ServingEngine(model, batch_limit=int(traffic["batch_limit"]),
                           feature_shape=build.feature_shape(cfg),
                           tracer=window.tracer)    # None is the engine's off
    try:
        length = float(traffic["trace_seconds"]) if trace else float(seconds)
        due, rows, where = arrivals.schedule(
            seed, float(traffic["rate_per_s"]), length, traffic["rows"])
        offsets = (where * (len(pool) - rows)).astype(int)
        payloads = [pool[o:o + r] for o, r in zip(offsets, rows)]
        rng = np.random.default_rng(seed)
        checked = rng.choice(len(due), size=min(len(due), int(
            traffic["check_requests"])), replace=False)
        for x in payloads[:WARM_REQUESTS]:
            engine.output(x)

        with window:
            sent = arrivals.drive(engine.submit, payloads, due, checked)

        # ---- after the window: a sample of the answers against the plain
        # reference and against the engine's answer to the same rows alone
        ts = model.train_state
        tol = float(cfg["output_tolerance"])
        ref_err = alone_err = 0.0
        kept = [int(i) for i in checked if int(i) in sent.results]
        if kept:
            # one reference program whatever the seed drew: the checked
            # requests' rows side by side, padded to a fixed count
            rows_all = np.concatenate([payloads[i] for i in kept])
            fixed = int(traffic["check_requests"]) * max(
                int(m["high"]) for m in traffic["rows"])
            padded = np.resize(rows_all, (fixed,) + rows_all.shape[1:])
            want_all = np.asarray(reference.predict(
                cfg, ts.params, ts.model_state, (padded,)))
            at = 0
            for i in kept:
                got, n_rows = sent.results[i], len(payloads[i])
                ref_err = max(ref_err, _rel_err(got,
                                                want_all[at:at + n_rows]))
                alone_err = max(alone_err, _rel_err(
                    got, engine.output(payloads[i])))
                at += n_rows
        live_compiles = engine.recompiles_after_warmup
    finally:
        engine.shutdown()

    latency = sent.latency_ms
    n = len(due)
    correct = bool(n > 0 and sent.failed == 0 and compiles.in_window == 0
                   and live_compiles == 0 and ref_err <= tol
                   and alone_err <= tol / 10)
    notes.update(requests=n, rows_mean=float(np.mean(rows)) if n else None,
                 window_s=window.seconds,
                 samples_beyond_p99=int(len(latency) * 0.01),
                 reference_rel_err=ref_err, alone_rel_err=alone_err,
                 output_tolerance=tol, compiles_in_window=compiles.in_window,
                 engine_live_compiles=live_compiles,
                 generator_lag_p99_ms=float(np.percentile(sent.lag_ms, 99))
                 if n else None)
    end_to_end = {"setup_s": window.opened_at - t_process}
    if len(latency):
        end_to_end.update(
            serve_latency_p50_ms=float(np.percentile(latency, 50)),
            serve_latency_p99_ms=float(np.percentile(latency, 99)),
            serve_goodput_per_s=sent.within(float(traffic["limit_ms"]))
            / length)
    observed = window.observed(devices, {"requests": n,
                                         "lags_ms": sent.lag_ms})
    return Outcome(correct=correct, attempted=n, failed=sent.failed,
                   end_to_end=end_to_end, notes=notes, observed=observed)
