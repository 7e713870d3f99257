"""Serving engine: pipelined vs blocking dispatcher under load.

The claim under test (parallel/serving.py): the seed dispatcher's fixed
aggregation window + inline host-sync fetch put a floor of
``timeout_ms + device_roundtrip`` under every request; the pipelined
engine's backpressure aggregation (coalesce only while the device is
busy) and completion-thread fetch remove both, so closed-loop
throughput rises and the latency tail collapses. On a 1-core CPU box
the window elimination dominates; on a real accelerator the
dispatch/fetch overlap is the bigger half — PERF_ANALYSIS r8 records
the decomposition.

Two load shapes:
- **closed-loop**: N client threads, each issuing its next request the
  moment the previous answer lands — throughput-bound, the arm ratio is
  the A/B headline.
- **open-loop**: Poisson arrivals at a target rate, submitted without
  waiting — latency-bound; the p50/p95/p99 table is the story (a
  closed loop can't see coordinated omission).

Arms alternate per round (A/B interleaved via benchmarks/ab.py, the
shared harness the autotuner reuses) so machine-load drift hits both
equally.

PR 6 adds two multi-process modes:

- **--cold-start**: subprocess A/B of cold-start-to-``assert_warm()``
  with and without the persisted AOT executable cache
  (parallel/aot_cache.py). Each arm is a FRESH python process (the only
  honest way to measure a cold start); the cached arm must also produce
  bitwise-identical outputs to the uncached arm.
- **--smoke-fleet / --soak-fleet**: open-loop soak against the fleet
  front door (parallel/fleet.py). The parent hosts a warmed FleetRouter
  behind the UI HTTP surface; worker SUBPROCESSES drive Poisson
  arrivals at a target aggregate QPS through ``POST /api/predict`` and
  count ok / shed (HTTP 503) / error. Gates: zero post-warmup
  recompiles (watchdog-asserted), shed rate < 100%, served p99 under a
  CPU-calibrated bound, achieved arrival rate near target.

Usage:
    python benchmarks/serving.py                   # timed A/B + curve
    python benchmarks/serving.py --rate 500        # open-loop point
    python benchmarks/serving.py --smoke           # CI gate: bitwise vs
        # direct model.output, zero recompiles after warmup, pipelined
        # >= 1.3x blocking closed-loop
    python benchmarks/serving.py --precision-ab    # f32/bf16/int8 $/req
    python benchmarks/serving.py --precision-ab --smoke  # CI gate:
        # int8 within top-1 budget of f32, all arms warm, int8 bytes
        # proxy strictly below bf16
    python benchmarks/serving.py --cold-start      # cached vs uncached
    python benchmarks/serving.py --smoke-fleet     # CI fleet gate
    python benchmarks/serving.py --soak-fleet --rate 150 --duration 10
"""

from __future__ import annotations

import argparse
import json
import os
import random
import statistics
import subprocess
import sys
import threading
import time

import numpy as np

from benchmarks import ab
from deeplearning4j_tpu.observe.latency import LatencyRing
from deeplearning4j_tpu.observe.registry import MetricsRegistry
from deeplearning4j_tpu.parallel.serving import ServingEngine

FEATURES = 128


def build_model(seed: int = 7, width: int = 1024):
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer
    from deeplearning4j_tpu.nn.layers.output import OutputLayer
    from deeplearning4j_tpu.ops.losses import LossFunction
    from deeplearning4j_tpu.optimize.updaters import Adam
    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater(Adam(1e-3)).list()
            .layer(DenseLayer(n_out=width))
            .layer(OutputLayer(n_out=10, loss=LossFunction.MCXENT))
            .set_input_type(InputType.feed_forward(FEATURES)).build())
    return MultiLayerNetwork(conf).init()


def make_engine(model, *, pipelined: bool, session: str,
                batch_limit: int = 32, timeout_ms: float = 5.0,
                replicas=1, aot_cache_dir=None,
                precision=None) -> ServingEngine:
    # isolated registry per arm: the A/B must not share counters
    return ServingEngine(
        model, batch_limit=batch_limit, timeout_ms=timeout_ms,
        pipelined=pipelined, replicas=replicas,
        feature_shape=(FEATURES,), registry=MetricsRegistry(),
        session_id=session, aot_cache_dir=aot_cache_dir,
        model_version="bench", precision=precision)


def closed_loop(engine: ServingEngine, n_clients: int, n_requests: int,
                req_size: int, seed: int = 0):
    """N clients, each firing its next request on completion. Returns
    (throughput req/s, LatencyRing of client-observed latencies)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(req_size, FEATURES)).astype(np.float32)
    ring = LatencyRing(capacity=n_clients * n_requests)
    barrier = threading.Barrier(n_clients + 1)
    errors = []

    def client():
        barrier.wait()
        try:
            for _ in range(n_requests):
                t0 = time.perf_counter()
                engine.output(x)
                ring.record(time.perf_counter() - t0)
        except Exception as e:      # surface, don't hang the barrier
            errors.append(e)

    threads = [threading.Thread(target=client) for _ in range(n_clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return (n_clients * n_requests) / wall, ring


def open_loop(engine: ServingEngine, rate_hz: float, duration_s: float,
              req_size: int, seed: int = 0):
    """Poisson arrivals at ``rate_hz``, submitted without waiting for
    completions. Returns (achieved req/s, LatencyRing)."""
    rng = np.random.default_rng(seed)
    arrival = random.Random(seed)
    x = rng.normal(size=(req_size, FEATURES)).astype(np.float32)
    ring = LatencyRing(capacity=int(rate_hz * duration_s) + 64)
    pending = []
    t_start = time.perf_counter()
    deadline = t_start + duration_s
    while time.perf_counter() < deadline:
        t0 = time.perf_counter()
        f = engine.submit(x)
        f.add_done_callback(
            lambda _f, t0=t0: ring.record(time.perf_counter() - t0))
        pending.append(f)
        time.sleep(arrival.expovariate(rate_hz))
    for f in pending:
        f.result()
    wall = time.perf_counter() - t_start
    return len(pending) / wall, ring


def run_timed(args) -> int:
    model = build_model(width=args.width)
    arms = {}
    for name, pipelined in (("blocking", False), ("pipelined", True)):
        arms[name] = make_engine(
            model, pipelined=pipelined, session=name,
            batch_limit=args.batch_limit, timeout_ms=args.timeout_ms,
            replicas=args.replicas)
    try:
        rings = {name: LatencyRing(capacity=1 << 16) for name in arms}

        def _arm(name, eng):
            def go(r):
                t, ring = closed_loop(eng, args.clients, args.requests,
                                      args.req_size, seed=r)
                for v in ring.snapshot():
                    rings[name].record(v)
                return t
            return go

        tput = ab.interleaved({n: _arm(n, e) for n, e in arms.items()},
                              args.rounds)
        med = ab.median_of(tput)
        print(f"closed-loop: {args.clients} clients x {args.requests} "
              f"requests x{args.req_size}, median of {args.rounds} "
              "rounds:")
        for name in arms:
            print(f"  {name:9s} {med[name]:9.1f} req/s   "
                  f"{ab.fmt_quantiles(rings[name])}")
        speedup = med["pipelined"] / med["blocking"]
        print(f"pipelined speedup: {speedup:.2f}x")

        if args.rate:
            t, ring = open_loop(arms["pipelined"], args.rate,
                                args.open_duration, args.req_size)
            print(f"open-loop (Poisson {args.rate:.0f} req/s target): "
                  f"{t:9.1f} req/s achieved   {ab.fmt_quantiles(ring)}")
        for name, eng in arms.items():
            eng.assert_warm()
        if args.assert_speedup and speedup < args.assert_speedup:
            print(f"FAIL: pipelined speedup {speedup:.2f}x below the "
                  f"{args.assert_speedup:.2f}x floor")
            return 1
        return 0
    finally:
        for eng in arms.values():
            eng.shutdown()


def run_smoke(args) -> int:
    """CI gate: (1) serving output bitwise-equal to direct
    ``model.output`` across request sizes (including padded, split and
    co-batched ones); (2) zero recompiles after the warmup sweep,
    watchdog-asserted; (3) pipelined >= 1.3x blocking closed-loop
    throughput. The margin measured on a 1-core CPU box is ~10x
    (PERF_ANALYSIS r8), so the 1.3x floor keeps noise headroom."""
    model = build_model(width=64)
    rng = np.random.default_rng(0)
    eng = make_engine(model, pipelined=True, session="smoke",
                      batch_limit=16)
    try:
        for n in (1, 2, 3, 5, 8, 16, 37):   # 37 > batch_limit: splits
            x = rng.normal(size=(n, FEATURES)).astype(np.float32)
            got = eng.output(x)
            want = np.asarray(model.output(x))
            if got.shape != want.shape or not np.array_equal(got, want):
                print(f"FAIL: serving output diverged from direct "
                      f"model.output at request size {n} "
                      f"(max abs diff "
                      f"{np.max(np.abs(got - want)):.3e})")
                return 1
        # concurrent co-batched requests must slice back bitwise too
        t, _ring = closed_loop(eng, 4, 25, 2)
        got = eng.output(rng.normal(size=(3, FEATURES))
                         .astype(np.float32))
        eng.assert_warm()       # zero recompiles after warmup
        stats = eng.stats()
    finally:
        eng.shutdown()

    # A/B throughput gate on fresh engines (isolated counters)
    arms = {}
    for name, pipelined in (("blocking", False), ("pipelined", True)):
        arms[name] = make_engine(model, pipelined=pipelined,
                                 session=f"smoke-{name}", batch_limit=16)
    try:
        rings = {name: LatencyRing(capacity=1 << 14) for name in arms}

        def _arm(name, e):
            def go(r):
                tp, ring = closed_loop(e, 4, 30, 1, seed=r)
                for v in ring.snapshot():
                    rings[name].record(v)
                return tp
            return go

        tput = ab.interleaved({n: _arm(n, e) for n, e in arms.items()},
                              3)
        med = ab.median_of(tput)
        speedup = med["pipelined"] / med["blocking"]
        for name in arms:
            print(f"  {name:9s} {med[name]:9.1f} req/s   "
                  f"{ab.fmt_quantiles(rings[name])}")
        arms["pipelined"].assert_warm()
    finally:
        for e in arms.values():
            e.shutdown()

    if speedup < 1.3:
        print(f"FAIL: pipelined speedup {speedup:.2f}x below the 1.3x "
              "floor")
        return 1
    print(f"serving smoke: bitwise vs direct output, "
          f"{stats['recompiles_after_warmup']} recompiles after warmup, "
          f"pipelined {speedup:.2f}x blocking")
    return 0


# ---- precision A/B: $/req proxy across f32 / bf16 / int8 -----------------

def run_precision_ab(args, smoke: bool = False) -> int:
    """A/B the serving PrecisionPolicy arms on a $/req cost proxy next
    to the latency columns. Dollar cost on a rented accelerator tracks
    device-seconds and bytes moved, so per completed request we report:

    - **bytes/req** — params-resident bytes x (device batches / requests)
      plus the request's own feature/output payload: the per-request
      share of weight traffic the matmuls pull through the memory
      hierarchy. Int8 holds a quarter of f32's weight bytes (bf16 half),
      so this is the column quantization is buying down.
    - **devms/req** — engine-measured device milliseconds (dispatch to
      ready) per request.
    - **params MB** — resident committed weights (the HBM rent).

    ``--smoke`` gates: int8 answers like f32 (top-1 agreement within
    budget), every arm warm (zero post-warmup recompiles), and int8's
    bytes/req strictly below bf16's — the headline the quantization
    path must actually deliver.
    """
    from deeplearning4j_tpu.parallel.quant import PrecisionPolicy
    width = 64 if smoke else args.width
    batch_limit = 16 if smoke else args.batch_limit
    clients = 4 if smoke else args.clients
    requests = 25 if smoke else args.requests
    rounds = 2 if smoke else args.rounds
    model = build_model(width=width)
    rng = np.random.default_rng(11)
    calib = rng.normal(size=(256, FEATURES)).astype(np.float32)
    eval_x = rng.normal(size=(batch_limit, FEATURES)).astype(np.float32)
    policies = {
        "f32": PrecisionPolicy.f32(),
        "bf16": PrecisionPolicy.bf16(),
        "int8": PrecisionPolicy.int8(calib),
    }
    rows = {}
    outputs = {}
    failures = []
    engines = {}
    base = {}
    rings = {}
    try:
        # every arm alive before timing starts: the interleaved rounds
        # see identical machine load (benchmarks/ab.py methodology)
        for name, policy in policies.items():
            eng = make_engine(model, pipelined=True,
                              session=f"prec-{name}",
                              batch_limit=batch_limit,
                              timeout_ms=args.timeout_ms,
                              precision=policy)
            engines[name] = eng
            outputs[name] = np.asarray(eng.output(eval_x))
            base[name] = (eng.dispatch_count, eng.device_ms_total)
            rings[name] = LatencyRing(capacity=1 << 16)

        def _arm(name, eng):
            def go(r):
                tp, rg = closed_loop(eng, clients, requests,
                                     args.req_size, seed=r)
                for v in rg.snapshot():
                    rings[name].record(v)
                return tp
            return go

        meds = ab.median_of(ab.interleaved(
            {n: _arm(n, e) for n, e in engines.items()}, rounds))

        for name, eng in engines.items():
            d0, ms0 = base[name]
            n_req = clients * requests * rounds
            batches = eng.dispatch_count - d0
            dev_ms = eng.device_ms_total - ms0
            pbytes = eng.params_resident_bytes
            io_bytes = (args.req_size * FEATURES * 4
                        + args.req_size * outputs[name].shape[-1] * 4)
            q = rings[name].quantiles((0.5, 0.99))
            try:
                eng.assert_warm()
            except Exception as e:
                failures.append(f"{name} arm not warm: {e}")
            rows[name] = {
                "tput": meds[name],
                "p50_ms": q[0.5] * 1e3, "p99_ms": q[0.99] * 1e3,
                "params_bytes": pbytes,
                "bytes_per_req": pbytes * (batches / n_req) + io_bytes,
                "devms_per_req": dev_ms / n_req,
            }
    finally:
        for eng in engines.values():
            eng.shutdown()

    print(f"precision A/B: width={width}, {clients} clients x "
          f"{requests} requests x{args.req_size}, median of {rounds} "
          "rounds:")
    print(f"  {'arm':5s} {'req/s':>9s} {'p50':>9s} {'p99':>9s} "
          f"{'paramsMB':>9s} {'bytes/req':>11s} {'devms/req':>10s}")
    for name, r in rows.items():
        print(f"  {name:5s} {r['tput']:9.1f} {r['p50_ms']:8.2f}m "
              f"{r['p99_ms']:8.2f}m {r['params_bytes'] / 1e6:9.3f} "
              f"{r['bytes_per_req']:11.0f} {r['devms_per_req']:10.3f}")

    a_f32 = outputs["f32"].argmax(axis=-1).reshape(-1)
    a_int8 = outputs["int8"].argmax(axis=-1).reshape(-1)
    agreement = float((a_f32 == a_int8).mean())
    print(f"  int8 top-1 agreement vs f32: {agreement:.4f}  "
          f"bytes/req vs bf16: {rows['int8']['bytes_per_req']:.0f} "
          f"vs {rows['bf16']['bytes_per_req']:.0f}")
    if smoke:
        if agreement < 1.0 - args.top1_budget:
            failures.append(
                f"int8 top-1 agreement {agreement:.4f} below the "
                f"{1.0 - args.top1_budget:.4f} floor")
        if not rows["int8"]["bytes_per_req"] < \
                rows["bf16"]["bytes_per_req"]:
            failures.append(
                "int8 bytes/req "
                f"{rows['int8']['bytes_per_req']:.0f} not strictly "
                f"below bf16 {rows['bf16']['bytes_per_req']:.0f}")
    for f in failures:
        print(f"FAIL: {f}")
    return 1 if failures else 0


# ---- cold start: persisted AOT cache A/B (subprocess arms) ---------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_child(extra, timeout=600):
    """Run this benchmark in a fresh process, parse the last stdout line
    as JSON (child modes print exactly one JSON line)."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.serving"] + extra,
        cwd=_ROOT, capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"child {extra[:2]} failed rc={proc.returncode}:\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_cold_child(args) -> int:
    """One cold-start arm: fresh process builds the model, stands up a
    warmed engine (optionally against a persisted cache), and reports
    the warmup-sweep seconds + an output digest for bitwise comparison.
    Prints exactly one JSON line."""
    import hashlib
    model = build_model(width=args.width)
    t0 = time.perf_counter()
    eng = make_engine(model, pipelined=True, session="cold",
                      batch_limit=16, aot_cache_dir=args.aot_cache_dir)
    build_s = time.perf_counter() - t0
    try:
        eng.assert_warm()
        rng = np.random.default_rng(123)
        x = rng.normal(size=(5, FEATURES)).astype(np.float32)
        out = eng.output(x)
        digest = hashlib.sha256(
            np.ascontiguousarray(out).tobytes()).hexdigest()
        stats = eng.stats()
    finally:
        eng.shutdown()
    print(json.dumps({
        "warmup_s": stats["warmup_s"], "build_s": build_s,
        "out_sha256": digest,
        "aot": stats.get("aot_cache"),
        "recompiles": stats["recompiles_after_warmup"]}))
    return 0


def run_cold_start(args) -> int:
    """Cold-start-to-``assert_warm()``: median over ``--cold-runs``
    fresh processes, uncached vs persisted-cache-warm. The first cached
    process pays the save (reported separately); every later one loads.
    Outputs must be bitwise-identical across every arm."""
    import shutil
    import tempfile
    cache = args.aot_cache_dir or tempfile.mkdtemp(prefix="dl4j-aot-")
    owned = args.aot_cache_dir is None
    base = ["--cold-start-child", "--width", str(args.width)]
    try:
        uncached = [_run_child(base) for _ in range(args.cold_runs)]
        # seed process: state "cold" -> warms live, saves the cache
        seed_run = _run_child(base + ["--aot-cache-dir", cache])
        cached = [_run_child(base + ["--aot-cache-dir", cache])
                  for _ in range(args.cold_runs)]
    finally:
        if owned:
            shutil.rmtree(cache, ignore_errors=True)

    digests = {r["out_sha256"] for r in uncached + [seed_run] + cached}
    med_un = statistics.median(r["warmup_s"] for r in uncached)
    med_ca = statistics.median(r["warmup_s"] for r in cached)
    speedup = med_un / med_ca if med_ca > 0 else float("inf")
    states = [r["aot"]["state"] if r["aot"] else "?" for r in cached]
    print(f"cold start to assert_warm(), width={args.width}, median of "
          f"{args.cold_runs} fresh processes:")
    print(f"  uncached       {med_un * 1e3:8.1f} ms")
    print(f"  cache save     {seed_run['warmup_s'] * 1e3:8.1f} ms "
          "(first process: live warmup + export)")
    print(f"  cache warm     {med_ca * 1e3:8.1f} ms   "
          f"states={states}")
    print(f"  speedup        {speedup:8.2f}x   bitwise-equal outputs: "
          f"{len(digests) == 1}")
    if len(digests) != 1:
        print("FAIL: cached arm output diverged from uncached")
        return 1
    if any(s != "warm" for s in states):
        print("FAIL: a cached arm did not load the persisted table")
        return 1
    if args.assert_cold_speedup and speedup < args.assert_cold_speedup:
        print(f"FAIL: cached cold-start speedup {speedup:.2f}x below "
              f"the {args.assert_cold_speedup:.2f}x floor")
        return 1
    return 0


# ---- fleet soak: multi-process open loop against the front door ----------

def run_soak_worker(args) -> int:
    """One load-generating subprocess: Poisson arrivals at ``--rate``
    against ``--url``/api/predict for ``--duration`` seconds, open-loop
    (arrivals never wait for completions). Prints one JSON line with
    ok/shed/error counts and served latencies."""
    import urllib.error
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor
    rng = np.random.default_rng(args.seed)
    arrival = random.Random(args.seed)
    x = rng.normal(size=(args.req_size, FEATURES)).astype(np.float32)
    body = json.dumps({"features": x.tolist()}).encode()
    url = args.url.rstrip("/") + "/api/predict"
    counts = {"ok": 0, "shed": 0, "error": 0}
    lat = []
    lock = threading.Lock()

    def one():
        t0 = time.perf_counter()
        try:
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                r.read()
            dt = time.perf_counter() - t0
            with lock:
                counts["ok"] += 1
                lat.append(dt)
        except urllib.error.HTTPError as e:
            e.read()
            with lock:
                counts["shed" if e.code == 503 else "error"] += 1
        except Exception:
            with lock:
                counts["error"] += 1

    attempts = 0
    t_start = time.perf_counter()
    deadline = t_start + args.duration
    with ThreadPoolExecutor(max_workers=64) as pool:
        futs = []
        while time.perf_counter() < deadline:
            futs.append(pool.submit(one))
            attempts += 1
            time.sleep(arrival.expovariate(args.rate))
        for f in futs:
            f.result()
    wall = time.perf_counter() - t_start
    print(json.dumps({
        "attempts": attempts, "wall_s": wall,
        "latencies_ms": [round(v * 1e3, 3) for v in lat], **counts}))
    return 0


def run_fleet(args, smoke: bool) -> int:
    """Parent of the multi-process soak: host a warmed FleetRouter
    behind the UI HTTP surface, fan ``--workers`` load-generating
    subprocesses at it, aggregate, gate."""
    from deeplearning4j_tpu.parallel.fleet import FleetRouter
    from deeplearning4j_tpu.ui.server import UIServer
    from deeplearning4j_tpu.ui.serving_module import FleetModule
    from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage

    width = 64 if smoke else args.width
    rate = args.rate or (60.0 if smoke else 150.0)
    duration = args.duration
    model = build_model(width=width)
    fleet = FleetRouter(slo_ms=args.slo_ms, window_s=0.5)
    fleet.add_pool("bench", model, pool_size=args.pool_size,
                   batch_limit=16, feature_shape=(FEATURES,),
                   aot_cache_dir=args.aot_cache_dir)
    server = UIServer(port=0)
    server.attach(InMemoryStatsStorage())
    server.register_module(FleetModule(fleet))
    server.start()
    try:
        fleet.assert_warm()         # warm BEFORE traffic
        per_worker = rate / args.workers
        cmd = [sys.executable, "-m", "benchmarks.serving",
               "--soak-worker", "--url", server.url,
               "--rate", str(per_worker),
               "--duration", str(duration),
               "--req-size", str(args.req_size)]
        procs = [subprocess.Popen(cmd + ["--seed", str(i)], cwd=_ROOT,
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.PIPE, text=True)
                 for i in range(args.workers)]
        results = []
        for p in procs:
            out, err = p.communicate(timeout=duration * 10 + 120)
            if p.returncode != 0:
                raise RuntimeError(
                    f"soak worker rc={p.returncode}:\n{err[-2000:]}")
            results.append(json.loads(out.strip().splitlines()[-1]))

        ok = sum(r["ok"] for r in results)
        shed = sum(r["shed"] for r in results)
        errors = sum(r["error"] for r in results)
        attempts = sum(r["attempts"] for r in results)
        lat = sorted(v for r in results for v in r["latencies_ms"])
        wall = max(r["wall_s"] for r in results)
        achieved = attempts / wall

        def q(p):
            return lat[min(len(lat) - 1,
                           int(np.ceil(p * len(lat))) - 1)] if lat else 0

        shed_rate = shed / attempts if attempts else 1.0
        pst = fleet.stats()["pools"]["bench"]
        import urllib.request
        with urllib.request.urlopen(server.url + "/metrics") as r:
            server_metrics = r.read().decode()
        fleet.assert_warm()         # zero recompiles under traffic
        print(f"fleet soak: {args.workers} worker processes, Poisson "
              f"{rate:.0f} req/s aggregate target x {duration:.0f}s, "
              f"slo={args.slo_ms:.0f}ms, pool_size={args.pool_size}:")
        print(f"  attempts={attempts} ({achieved:.1f} req/s achieved)  "
              f"ok={ok}  shed={shed} ({shed_rate * 100:.1f}%)  "
              f"errors={errors}")
        if lat:
            print(f"  served: p50={q(.5):7.2f}ms  p95={q(.95):7.2f}ms  "
                  f"p99={q(.99):7.2f}ms")
        print(f"  router: shed_fraction={pst['shed_fraction']:.3f}  "
              f"windowed_p99={pst['windowed_p99_ms']:.1f}ms  "
              "post-warmup recompiles=0 (watchdog-asserted)")
        failures = []
        if errors:
            failures.append(f"{errors} worker errors (non-shed)")
        if shed_rate >= 1.0:
            failures.append("every request shed")
        if lat and q(.99) > args.fleet_p99_ms:
            failures.append(f"served p99 {q(.99):.1f}ms over the "
                            f"{args.fleet_p99_ms:.0f}ms bound")
        if achieved < 0.5 * rate:
            failures.append(f"achieved arrival rate {achieved:.1f} "
                            f"req/s under half the {rate:.0f} target")
        if "dl4j_fleet_admitted_total" not in server_metrics:
            failures.append("dl4j_fleet_* series missing from /metrics")
        for f in failures:
            print(f"FAIL: {f}")
        return 1 if failures else 0
    finally:
        server.stop()
        fleet.shutdown()


# ---- cluster chaos soak: node kill / rejoin through the remote tier ------

def _start_node(model_zip, node_id, reg_dir, store_dir, log_path, env,
                slo_ms=1000.0):
    """Spawn one worker node subprocess (the real CLI path: ``serve
    --join``) under ``env``. Output goes to a log file — tail printed on
    failure."""
    cmd = [sys.executable, "-m", "deeplearning4j_tpu", "serve",
           "--model", model_zip, "--inference-mode", "batched",
           "--batch-limit", "16", "--warmup-shape", str(FEATURES),
           "--ui-port", "0", "--join", reg_dir,
           "--artifact-store", store_dir, "--model-key", "bench",
           "--node-id", node_id, "--slo-ms", str(slo_ms),
           "--drain-timeout", "20"]
    log = open(log_path, "w")
    proc = subprocess.Popen(cmd, cwd=_ROOT, env=env, stdout=log,
                            stderr=subprocess.STDOUT)
    return proc, log


def _wait_node(registry, node_id, pid, timeout_s=240.0):
    """Wait for THIS process's registry record (pid-matched, so a
    rejoining node with a crashed predecessor's stale file doesn't
    count until the new process actually published)."""
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        rec = registry.read_all().get(node_id)
        if rec and rec.get("pid") == pid:
            return rec
        time.sleep(0.2)
    raise RuntimeError(f"node {node_id} (pid {pid}) never registered")


def _tail(path, n=2000):
    try:
        with open(path) as f:
            return f.read()[-n:]
    except OSError:
        return "<no log>"


def run_cluster(args, smoke: bool) -> int:
    """Chaos soak through the cluster tier (parallel/node.py +
    parallel/remote.py): two worker-node subprocesses join a shared
    registry and warm from one shared artifact store; the parent drives
    Poisson traffic through a RemoteDispatcher while node "a" is
    SIGKILLed mid-soak and a replacement (SAME node id) joins.

    Gates:
    - client-visible errors <= the killed node's in-flight count at the
      kill (everything else retries onto the survivor);
    - served p99 under ``--cluster-p99-ms`` THROUGH the kill+join;
    - node "a"'s circuit breaker opened at least once and is closed
      again at the end (half-open probe recovered onto the rejoiner);
    - the rejoined node warmed from the shared store: AOT state "warm",
      zero recompiles after warmup, and it actually served requests;
    - SIGTERM drain on node "b": exit 0, record deregistered.
    """
    import shutil
    import signal as _signal
    import tempfile
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from benchmarks import cpu_only_children_env
    child_env = cpu_only_children_env("benchmarks.serving cluster soak")

    from deeplearning4j_tpu.models.serialization import save_model
    from deeplearning4j_tpu.parallel.aot_cache import ArtifactStore
    from deeplearning4j_tpu.parallel.node import NodeRegistry
    from deeplearning4j_tpu.parallel.remote import RemoteDispatcher

    width = 64 if smoke else args.width
    rate = args.rate or (40.0 if smoke else 120.0)
    kill_after = 4.0 if smoke else max(4.0, args.duration * 0.3)
    tail_s = 6.0 if smoke else max(8.0, args.duration * 0.3)

    work = tempfile.mkdtemp(prefix="dl4j-cluster-")
    reg_dir = os.path.join(work, "registry")
    store_dir = os.path.join(work, "store")
    model_zip = os.path.join(work, "model.zip")
    save_model(build_model(width=width), model_zip)
    registry = NodeRegistry(reg_dir, stale_after_s=1.0, dead_after_s=2.5)
    procs = {}
    logs = {}
    handles = []
    failures = []

    def start(node_id):
        p, log = _start_node(model_zip, node_id, reg_dir, store_dir,
                             os.path.join(work, f"{node_id}.log"),
                             child_env, slo_ms=args.slo_ms)
        procs.setdefault(node_id, []).append(p)
        handles.append(log)
        logs[node_id] = os.path.join(work, f"{node_id}.log")
        return p

    try:
        # serial start: node "a" pays the warmup sweep and publishes the
        # shared store; "b" (and the rejoiner) must warm from it
        pa = start("a")
        _wait_node(registry, "a", pa.pid)
        if ArtifactStore(store_dir).manifest("bench") is None:
            failures.append("node a did not publish the artifact store")
        pb = start("b")
        rec_b = _wait_node(registry, "b", pb.pid)

        disp = RemoteDispatcher(
            registry, timeout_s=10.0, retries=3, backoff_s=0.05,
            breaker_failures=3, breaker_reset_s=1.0, hedge_after_s=0.5)
        counts = {"ok": 0, "error": 0}
        lat = []
        lock = threading.Lock()
        rng = np.random.default_rng(args.seed)
        x = rng.normal(size=(args.req_size, FEATURES)).astype(np.float32)
        stop = threading.Event()

        def one():
            t0 = time.perf_counter()
            try:
                disp.predict(x)
                dt = time.perf_counter() - t0
                with lock:
                    counts["ok"] += 1
                    lat.append(dt)
            except Exception:   # RemoteError / NoNodesError / transport
                with lock:
                    counts["error"] += 1

        pool = ThreadPoolExecutor(max_workers=64)
        futs = []
        arrival = random.Random(args.seed)

        def drive():
            while not stop.is_set():
                futs.append(pool.submit(one))
                time.sleep(arrival.expovariate(rate))

        driver = threading.Thread(target=drive, daemon=True)
        driver.start()

        # ---- chaos: SIGKILL node a mid-soak --------------------------
        time.sleep(kill_after)
        gossip_a = registry.read_all().get("a", {}).get("stats", {})
        pa.kill()                                      # SIGKILL
        inflight_at_kill = (disp.inflight().get("a", 0)
                            + int(gossip_a.get("pending") or 0)
                            + int(gossip_a.get("inflight") or 0))
        t_kill = time.time()
        # replacement joins under the SAME identity: exercises the
        # stale-record overwrite AND lets the breaker genuinely recover
        pa2 = start("a")
        rec_a2 = _wait_node(registry, "a", pa2.pid)
        rejoin_s = time.time() - t_kill
        time.sleep(tail_s)              # traffic over the full fleet
        stop.set()
        driver.join(timeout=10)
        for f in futs:
            f.result()

        # post-soak probes: make sure the breaker's half-open window
        # has traffic to recover through, and the rejoiner serves
        for _ in range(20):
            try:
                disp.predict(x)
            except Exception:
                pass
            if disp.breaker_state("a") == "closed":
                break
            time.sleep(0.2)

        ok, errors = counts["ok"], counts["error"]
        lat_ms = sorted(v * 1e3 for v in lat)

        def q(p):
            return lat_ms[min(len(lat_ms) - 1,
                              int(np.ceil(p * len(lat_ms))) - 1)] \
                if lat_ms else 0.0

        br = disp._breaker("a")
        with urllib.request.urlopen(
                rec_a2["url"] + "/api/serving/stats", timeout=10) as r:
            stats_a2 = json.loads(r.read())
        served_a2 = int(registry.read_all().get("a", {})
                        .get("stats", {}).get("requests") or 0)
        aot = stats_a2.get("aot_cache") or {}

        print(f"cluster soak: 2 nodes, Poisson {rate:.0f} req/s, "
              f"SIGKILL node a at {kill_after:.0f}s, rejoin in "
              f"{rejoin_s:.1f}s (same id, shared store):")
        print(f"  ok={ok}  errors={errors} "
              f"(bound: in-flight at kill = {inflight_at_kill})")
        print(f"  served: p50={q(.5):7.2f}ms  p95={q(.95):7.2f}ms  "
              f"p99={q(.99):7.2f}ms  (bound {args.cluster_p99_ms:.0f}ms)")
        print(f"  breaker a: opened_total={br.opened_total}  "
              f"state={br.state}")
        print(f"  rejoined a: aot_state={aot.get('state')}  "
              f"recompiles_after_warmup="
              f"{stats_a2.get('recompiles_after_warmup')}  "
              f"served={served_a2}")

        if ok == 0:
            failures.append("no request succeeded")
        if errors > inflight_at_kill:
            failures.append(
                f"{errors} client-visible errors exceed the killed "
                f"node's in-flight window ({inflight_at_kill})")
        if lat_ms and q(.99) > args.cluster_p99_ms:
            failures.append(f"served p99 {q(.99):.1f}ms over the "
                            f"{args.cluster_p99_ms:.0f}ms bound")
        if br.opened_total < 1:
            failures.append("breaker for the killed node never opened")
        if br.state != "closed":
            failures.append(
                f"breaker for node a did not recover (state={br.state})")
        if aot.get("state") != "warm":
            failures.append(
                f"rejoined node not warm from the shared store "
                f"(aot state={aot.get('state')!r}, "
                f"reason={aot.get('reason')!r})")
        if stats_a2.get("recompiles_after_warmup"):
            failures.append(
                f"rejoined node recompiled "
                f"{stats_a2['recompiles_after_warmup']}x after warmup")
        if served_a2 < 1:
            failures.append("rejoined node never served a request")

        # ---- graceful drain: SIGTERM node b --------------------------
        pb.send_signal(_signal.SIGTERM)
        try:
            rc_b = pb.wait(timeout=40)
        except subprocess.TimeoutExpired:
            rc_b = None
        if rc_b != 0:
            failures.append(
                f"SIGTERM drain on node b exited rc={rc_b} "
                f"(want 0):\n{_tail(logs['b'])}")
        if "b" in registry.read_all():
            failures.append(
                "node b's registry record survived its drain")
        else:
            print(f"  drain b: rc=0, deregistered "
                  f"(was {rec_b['url']})")

        pool.shutdown(wait=False)
        disp.shutdown()
        for f in failures:
            print(f"FAIL: {f}")
        if failures:
            for nid, path in logs.items():
                print(f"--- node {nid} log tail ---\n{_tail(path)}")
        return 1 if failures else 0
    finally:
        for plist in procs.values():
            for p in plist:
                if p.poll() is None:
                    p.kill()
        for h in handles:
            h.close()
        shutil.rmtree(work, ignore_errors=True)


# ---- chaos smoke: armed fault plan + deadline propagation ----------------

_CHAOS_PLAN = ("seed={seed};"
               "registry.write:torn_write(count=1,arg=node-a);"
               "store.save:corrupt(count=1,arg=blob);"
               "remote.send:delay(p=0.5,ms=5);"
               "broker.publish:error(count=2)")


def _chaos_pass(work, seed, model):
    """One deterministic sweep over the four fault seams under an armed
    plan; returns (observations, replay signature). Two passes with the
    same seed must agree bitwise on both."""
    from deeplearning4j_tpu.chaos import plan as chaosplan
    from deeplearning4j_tpu.parallel.node import NodeRegistry
    from deeplearning4j_tpu.parallel.remote import RemoteDispatcher
    from deeplearning4j_tpu.streaming.broker import TcpTransport

    plan = chaosplan.arm(
        chaosplan.parse_plan(_CHAOS_PLAN.format(seed=seed)))
    obs = {}
    try:
        # registry: torn heartbeat record -> classified dead, next
        # clean beat heals it
        nreg = NodeRegistry(os.path.join(work, "reg"))
        nreg.write("node-a", "http://a")            # torn (count=1)
        rec = nreg.snapshot()["node-a"]
        nreg.write("node-a", "http://a")            # clean overwrite
        obs["registry"] = (rec["health"], bool(rec.get("corrupt")),
                           nreg.snapshot()["node-a"]["health"])

        # store: first process saves the AOT cache with one blob
        # corrupted in flight; a joining process must quarantine it,
        # live-compile that bucket, and still answer bitwise-correctly
        cache = os.path.join(work, "aot")
        e1 = make_engine(model, pipelined=True, session="chaos-save",
                         batch_limit=4, aot_cache_dir=cache)
        try:
            e1.assert_warm()
        finally:
            e1.shutdown()
        e2 = make_engine(model, pipelined=True, session="chaos-join",
                         batch_limit=4, aot_cache_dir=cache)
        try:
            e2.assert_warm()
            rng = np.random.default_rng(0)
            x = rng.normal(size=(4, FEATURES)).astype(np.float32)
            bitwise = np.array_equal(np.asarray(e2.output(x)),
                                     np.asarray(model.output(x)))
            st = e2.stats()["aot_cache"]
            obs["store"] = (st["quarantined"], st["state"], bitwise)
        finally:
            e2.shutdown()

        # remote: a chaos-delayed node is absorbed by the dispatcher —
        # every client call still succeeds (zero-error budget)
        nreg.write("n1", "http://n1")
        nreg.write("n2", "http://n2")
        calls = []
        ok_body = json.dumps({"output": [[0.0]], "n": 1}).encode()

        def transport(url, body, timeout_s):
            calls.append(url)
            return 200, {}, ok_body

        disp = RemoteDispatcher(nreg, transport=transport,
                                metrics=MetricsRegistry(),
                                snapshot_ttl_s=0.0,
                                sleep=lambda s: None, seed=0, retries=2)
        try:
            served = sum(disp.predict([[1.0]])["n"] for _ in range(20))
        finally:
            disp.shutdown()
        obs["remote"] = (served, len(calls))

        # broker: injected connection drops ride the reconnect path;
        # then a REAL broker restart on the same port is survived too
        t = TcpTransport(backoff_base_s=0.01, registry=MetricsRegistry())
        t.serve()
        try:
            t.publish("chaos", b"m1")       # 2 injected drops, lands
            got1 = t.poll("chaos", timeout=2.0)
            rec_injected = t.reconnects
            port = t.port
            t._server.shutdown()            # kill the broker...
            t._server.server_close()
            t._server = None
            restarted = TcpTransport(port=port)
            restarted.serve()               # ...and restart, same port
            try:
                t.poll("chaos", timeout=0.05)  # flush the stale conn
                t.publish("chaos", b"m2")
                got2 = t.poll("chaos", timeout=2.0)
            finally:
                restarted.close()
            obs["broker"] = (got1, rec_injected, got2)
        finally:
            t.close()

        return obs, plan.replay_signature()
    finally:
        chaosplan.disarm()


def run_chaos(args, smoke: bool = True) -> int:
    """CI chaos gate: deterministic fault sweep (armed plan over the
    registry / artifact-store / remote-dispatch / broker seams, replayed
    bitwise), deadline propagation through the HTTP front door (expired
    -> 504, never dispatched), and an empty graftlint baseline."""
    import shutil
    import tempfile
    import urllib.error
    import urllib.request
    from deeplearning4j_tpu.parallel.fleet import FleetRouter
    from deeplearning4j_tpu.ui.server import UIServer
    from deeplearning4j_tpu.ui.serving_module import FleetModule
    from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage

    width = 32 if smoke else args.width
    seed_a, seed_b = 42 + args.seed, 43 + args.seed
    model = build_model(width=width)
    work = tempfile.mkdtemp(prefix="dl4j-chaos-")
    failures = []
    try:
        print(f"chaos smoke: plan '{_CHAOS_PLAN.format(seed=seed_a)}'")
        obs1, sig1 = _chaos_pass(os.path.join(work, "p1"), seed_a, model)
        obs2, sig2 = _chaos_pass(os.path.join(work, "p2"), seed_a, model)
        obs3, sig3 = _chaos_pass(os.path.join(work, "p3"), seed_b, model)

        torn, corrupt, healed = obs1["registry"]
        quarantined, state, bitwise = obs1["store"]
        served, calls = obs1["remote"]
        got1, rec_injected, got2 = obs1["broker"]
        fired = {(s, k) for s, k, _, _ in sig1}
        print(f"  registry: torn record -> {torn} (corrupt={corrupt}), "
              f"next beat -> {healed}")
        print(f"  store:    quarantined={quarantined} "
              f"state={state} bitwise={bitwise}")
        print(f"  remote:   {served}/20 served across {calls} sends "
              "(delays absorbed, zero client errors)")
        print(f"  broker:   injected drops -> {rec_injected} reconnects"
              f", delivered={got1 == b'm1'}; restart survived="
              f"{got2 == b'm2'}")
        print(f"  replay:   {len(sig1)} injections; same-seed pass "
              f"identical={(obs1, sig1) == (obs2, sig2)}; "
              f"seed+1 differs={sig3 != sig1}")
        if (torn, corrupt, healed) != ("dead", True, "alive"):
            failures.append(
                f"torn registry record not dead->alive: {obs1['registry']}")
        if quarantined != 1 or state != "warm" or not bitwise:
            failures.append(
                "joining engine did not quarantine the corrupt blob and "
                f"live-compile warm: {obs1['store']}")
        if served != 20:
            failures.append(
                f"remote tier lost requests under injected delay: "
                f"{served}/20")
        if got1 != b"m1" or rec_injected != 2 or got2 != b"m2":
            failures.append(
                f"broker drops/restart not absorbed: {obs1['broker']}")
        if (obs1, sig1) != (obs2, sig2):
            failures.append("same-seed chaos pass not bitwise identical")
        if sig3 == sig1:
            failures.append("different seed replayed the same signature")
        missing = {("registry.write", "torn_write"),
                   ("store.save", "corrupt"), ("remote.send", "delay"),
                   ("broker.publish", "error")} - fired
        if missing:
            failures.append(f"plan clauses never fired: {sorted(missing)}")

        # deadline propagation through the real front door (disarmed)
        reg = MetricsRegistry()
        fleet = FleetRouter(slo_ms=args.slo_ms, window_s=0.5,
                            registry=reg)
        fleet.add_pool("bench", model, pool_size=1, batch_limit=4,
                       feature_shape=(FEATURES,))
        server = UIServer(port=0)
        server.attach(InMemoryStatsStorage())
        server.register_module(FleetModule(fleet))
        server.start()
        try:
            fleet.assert_warm()
            url = server.url + "/api/predict"
            rng = np.random.default_rng(1)
            body = json.dumps({"features": rng.normal(
                size=(1, FEATURES)).tolist()}).encode()

            def post(deadline_ms):
                req = urllib.request.Request(
                    url, data=body,
                    headers={"Content-Type": "application/json",
                             "X-Deadline-Ms": deadline_ms})
                try:
                    with urllib.request.urlopen(req, timeout=30) as r:
                        return r.status, r.read()
                except urllib.error.HTTPError as e:
                    return e.code, e.read()

            def admitted():
                m = reg.get_metric("dl4j_fleet_admitted_total")
                return sum(m.series().values()) if m is not None else 0.0

            before = admitted()
            code, payload = post("0.000001")       # expired at ingress
            expired_ok = (code == 504
                          and json.loads(payload).get("error")
                          == "deadline" and admitted() == before)
            code2, _ = post("30000")               # generous budget
            print(f"  deadline: expired -> HTTP {code} "
                  f"(dispatched={admitted() != before and code != 504}),"
                  f" fresh budget -> HTTP {code2}")
            if not expired_ok:
                failures.append(
                    f"expired deadline not shed pre-dispatch: HTTP "
                    f"{code}, admitted {before}->{admitted()}")
            if code2 != 200:
                failures.append(
                    f"request with fresh budget failed: HTTP {code2}")
            shed = reg.get_metric("dl4j_fleet_shed_total")
            if shed is None or shed.get(model="bench",
                                        reason="deadline") != 1.0:
                failures.append(
                    "dl4j_fleet_shed_total{reason=deadline} != 1")
        finally:
            server.stop()
            fleet.shutdown()

        # hot paths must stay chaos-clean (zero-overhead contract)
        lint = subprocess.run(
            [sys.executable, "-m", "tools.graftlint", "--baseline",
             os.path.join("tools", "graftlint", "baseline.json")],
            cwd=_ROOT, capture_output=True, text=True, timeout=900)
        print("  graftlint: baseline "
              + ("empty" if lint.returncode == 0 else "VIOLATED"))
        if lint.returncode != 0:
            failures.append("graftlint baseline not empty:\n"
                            + lint.stdout[-2000:] + lint.stderr[-2000:])

        for f in failures:
            print(f"FAIL: {f}")
        return 1 if failures else 0
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--clients", type=int, default=8,
                    help="closed-loop client threads")
    ap.add_argument("--requests", type=int, default=100,
                    help="requests per client per round")
    ap.add_argument("--req-size", type=int, default=1,
                    help="examples per request")
    ap.add_argument("--rounds", type=int, default=5,
                    help="interleaved A/B rounds")
    ap.add_argument("--batch-limit", type=int, default=32)
    ap.add_argument("--timeout-ms", type=float, default=5.0,
                    help="aggregation upper bound (the blocking arm's "
                    "fixed window)")
    ap.add_argument("--replicas", default=1,
                    help="device replicas (int or 'auto')")
    ap.add_argument("--width", type=int, default=1024,
                    help="hidden width of the benchmark model")
    ap.add_argument("--rate", type=float, default=None,
                    help="add an open-loop (Poisson) point at this "
                    "req/s target")
    ap.add_argument("--open-duration", type=float, default=5.0,
                    help="open-loop measurement window, seconds")
    ap.add_argument("--assert-speedup", type=float, default=None,
                    help="exit 1 when pipelined/blocking falls below")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: bitwise outputs, zero post-warmup "
                    "recompiles, >=1.3x closed-loop")
    # precision A/B ($/req proxy across serving precisions)
    ap.add_argument("--precision-ab", action="store_true",
                    help="A/B f32 / bf16 / int8 serving arms on a "
                    "$/req proxy (bytes moved, device ms, resident "
                    "params) next to p50/p99; with --smoke also gates "
                    "int8 accuracy + bytes strictly below bf16")
    ap.add_argument("--top1-budget", type=float, default=0.02,
                    help="--precision-ab --smoke: max tolerated int8 "
                    "top-1 disagreement vs f32")
    # cold start (persisted AOT cache A/B)
    ap.add_argument("--cold-start", action="store_true",
                    help="subprocess A/B: cold-start-to-assert_warm "
                    "with vs without the persisted AOT cache")
    ap.add_argument("--cold-runs", type=int, default=3,
                    help="fresh processes per cold-start arm (median)")
    ap.add_argument("--assert-cold-speedup", type=float, default=None,
                    help="exit 1 when cached/uncached cold-start falls "
                    "below this ratio")
    ap.add_argument("--aot-cache-dir", default=None,
                    help="persisted AOT cache location (default: a "
                    "temp dir, removed afterwards)")
    # fleet soak (multi-process open loop)
    ap.add_argument("--smoke-fleet", action="store_true",
                    help="CI gate: short multi-process Poisson soak "
                    "through the fleet front door")
    ap.add_argument("--soak-fleet", action="store_true",
                    help="longer fleet soak at --rate/--duration")
    ap.add_argument("--workers", type=int, default=2,
                    help="load-generating worker subprocesses")
    ap.add_argument("--duration", type=float, default=3.0,
                    help="soak measurement window, seconds")
    ap.add_argument("--slo-ms", type=float, default=1000.0,
                    help="router p99 SLO for the soak")
    ap.add_argument("--fleet-p99-ms", type=float, default=750.0,
                    help="served-p99 gate for the soak (CPU-calibrated)")
    ap.add_argument("--pool-size", type=int, default=1,
                    help="engines in the soak's replica pool")
    # cluster chaos soak (worker-node subprocesses + kill/rejoin)
    ap.add_argument("--smoke-cluster", action="store_true",
                    help="CI gate: 2 worker nodes join a gossiped "
                    "registry + shared artifact store; SIGKILL one "
                    "mid-soak, rejoin same-id, SIGTERM-drain the other")
    ap.add_argument("--soak-cluster", action="store_true",
                    help="longer cluster chaos soak at --rate/--duration")
    ap.add_argument("--cluster-p99-ms", type=float, default=2000.0,
                    help="served-p99 gate through the kill+join "
                    "(CPU-calibrated; retries ride the backoff curve)")
    # fault-injection smoke (deterministic armed chaos plan)
    ap.add_argument("--smoke-chaos", action="store_true",
                    help="CI gate: deterministic fault sweep under an "
                    "armed DL4J_CHAOS plan (torn registry record, "
                    "corrupted AOT blob, delayed remote sends, broker "
                    "drops + restart), bitwise same-seed replay, "
                    "expired-deadline -> 504 without device dispatch, "
                    "empty graftlint baseline")
    ap.add_argument("--seed", type=int, default=0)
    # internal child modes (spawned by --cold-start / --*-fleet)
    ap.add_argument("--cold-start-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--soak-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--url", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.replicas != "auto":
        args.replicas = int(args.replicas)
    if args.soak_worker:
        return run_soak_worker(args)
    if args.cold_start_child:
        return run_cold_child(args)
    if args.cold_start:
        return run_cold_start(args)
    if args.precision_ab:
        return run_precision_ab(args, smoke=args.smoke)
    if args.smoke_fleet or args.soak_fleet:
        return run_fleet(args, smoke=args.smoke_fleet)
    if args.smoke_cluster or args.soak_cluster:
        return run_cluster(args, smoke=args.smoke_cluster)
    if args.smoke_chaos:
        return run_chaos(args, smoke=True)
    return run_smoke(args) if args.smoke else run_timed(args)


if __name__ == "__main__":
    import sys
    sys.exit(main())
