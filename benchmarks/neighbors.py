"""Nearest-neighbor retrieval: interleaved A/B + cluster chaos soak.

The claims under test (retrieval/):

- **throughput**: the jitted fused distance+top-k path (one matmul +
  in-graph ``lax.top_k``; only k ids + k distances leave the device)
  beats the host VPTree walk — >= 10x queries/s at batch >= 64 in the
  full run on CPU, >= 1x in the CI smoke. The comparison is
  worst-case to worst-case over the SAME corpus: the tree's latency
  is query-dependent (near-duplicate probes of a well-separated
  corpus prune superbly; a query without that structure collapses the
  triangle-inequality bound and the walk degenerates to the O(corpus)
  Python scan), while the fused scan is query-invariant by
  construction. A serving tier provisions for the query that prunes
  nothing, so the gated pair is (host walk, fused scan) on
  pruning-hostile out-of-distribution queries; the tree's
  easy-probe qps is reported alongside, ungated, to show the spread.
- **recall**: the int8 arm (4x denser corpus + exact f32 host refine)
  and the IVF arm (nprobe routed clusters) both hold recall@10 >= 0.95
  against the exact f32 oracle — quality is a gate, not a footnote.
- **determinism**: repeated queries are bitwise identical, including
  distance ties (the (distance, id) merge order).
- **compile discipline**: zero live compiles after the warmup sweep
  across every arm and batch bucket (watchdog-asserted).
- **bytes/query**: the corpus bytes a query's distance pass must read
  (the memory-bound term): int8 strictly under 0.3x of f32 brute, IVF
  strictly under brute (nprobe/K of the corpus + centroids).

--smoke-cluster adds the multi-node chaos case: two ``serve
--neighbors-index`` subprocesses own disjoint shard slices of one
published index; mid-soak one is SIGKILLed. Gates: every in-flight and
subsequent query is answered — full while both live, ``partial: true``
(never an exception) while the killed node's shards have no owner; the
rejoined node (same id) warms from the shared ArtifactStore with zero
live compiles and full answers resume; the second node SIGTERM-drains
to exit 0 with its record deregistered.

Usage:
    python benchmarks/neighbors.py                 # full A/B table
        # (1M-vector corpus, host VPTree built on ALL of it; the
        # speedup gate is 10x on worst-case queries)
    python benchmarks/neighbors.py --smoke         # CI gate
    python benchmarks/neighbors.py --smoke-cluster # CI chaos gate
"""

from __future__ import annotations

import argparse
import json
import os
import random
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from benchmarks import ab

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def blob_corpus(n, dim, k_blobs, seed=0, spread=0.15):
    """Seeded mixture-of-gaussians corpus — the clustered geometry of
    real embedding spaces (and what IVF routing exists for)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k_blobs, dim)).astype(np.float32) * 3.0
    assign = rng.integers(k_blobs, size=n)
    pts = centers[assign] + \
        rng.normal(size=(n, dim)).astype(np.float32) * spread
    return pts.astype(np.float32)


def exact_oracle(corpus, queries, k, block=4096):
    """Exact f32 top-k by blocked full scan (the recall ground truth;
    blocked so the 1M full run fits in ram)."""
    b = queries.shape[0]
    best_d = np.full((b, k), np.inf, np.float32)
    best_i = np.full((b, k), -1, np.int64)
    q2 = np.sum(queries ** 2, axis=1, keepdims=True)
    for lo in range(0, corpus.shape[0], block):
        c = corpus[lo:lo + block]
        d2 = q2 - 2.0 * (queries @ c.T) + np.sum(c ** 2, axis=1)[None]
        d = np.concatenate([best_d, d2.astype(np.float32)], axis=1)
        i = np.concatenate(
            [best_i, np.arange(lo, lo + c.shape[0])[None].repeat(
                b, axis=0)], axis=1)
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        best_d = np.take_along_axis(d, order, axis=1)
        best_i = np.take_along_axis(i, order, axis=1)
    return best_d, best_i


def recall_at(found, oracle):
    hits = sum(len(set(int(v) for v in f if v >= 0)
                   & set(int(v) for v in o))
               for f, o in zip(found, oracle))
    return hits / float(oracle.size)


def _bytes_per_query(index, mode):
    """Corpus bytes the distance pass reads per query — the
    memory-bound cost term (metadata like scales/ids excluded; they
    are O(R) vs the O(R*D) row term)."""
    elt = 1 if index.precision == "int8" else 4
    rows_bytes = index.shard_rows * index.dim * elt
    n_shards = len(index.shards)
    if mode == "brute":
        return n_shards * rows_bytes
    probe = min(index.ivf.get("nprobe_hint", 8), index.ivf["clusters"])
    per_shard = (index.ivf["clusters"] * index.dim * 4      # centroids
                 + probe * index.ivf["cap"] * index.dim * elt)
    return n_shards * per_shard


# ---- single-process A/B ---------------------------------------------------

def run_ab(args, smoke: bool) -> int:
    from deeplearning4j_tpu.clustering.vptree import VPTree
    from deeplearning4j_tpu.retrieval.engine import RetrievalEngine
    from deeplearning4j_tpu.retrieval.index import ShardedCorpusIndex

    n = 20000 if smoke else args.vectors
    dim = 32 if smoke else args.dim
    batch = args.batch
    k = 10
    rounds = 3 if smoke else args.rounds
    # nprobe must scale with the blob/cluster ratio to hold the recall
    # gate: the full corpus packs ~488 blobs into 256 clusters/shard,
    # so the capacity-balanced assignment spills dense-blob fringe rows
    # into neighboring clusters and shallow probing misses them
    # (measured on the 1M index: recall@10 0.941 at nprobe=32, 0.991
    # at 64). 8 of 64 clusters suffices on the small smoke corpus.
    nprobe = 8 if smoke else 64

    print(f"neighbors A/B: corpus {n}x{dim}, batch {batch}, k={k}, "
          f"{rounds} interleaved rounds")
    corpus = blob_corpus(n, dim, k_blobs=max(16, n // 2048),
                         seed=args.seed)
    rng = np.random.default_rng(args.seed + 1)
    probes = corpus[rng.integers(n, size=batch)] + rng.normal(
        size=(batch, dim)).astype(np.float32) * 0.05
    # pruning-hostile queries for the worst-case pair: scaled like the
    # blob centers but unrelated to any of them, so the walk's tau
    # never collapses and the tree degenerates to the O(corpus) scan
    worst = rng.normal(size=(batch, dim)).astype(np.float32) * 3.0
    _, oracle = exact_oracle(corpus, probes, k)

    shard_rows = min(n, 8192 if smoke else 262144)
    ivf_clusters = 64 if smoke else 256
    print("building indexes (f32, int8, ivf, ivf-int8)...")
    arms = {}
    for name, precision, ivf in (
            ("brute-f32", "f32", 0), ("brute-int8", "int8", 0),
            ("ivf-f32", "f32", ivf_clusters),
            ("ivf-int8", "int8", ivf_clusters)):
        idx = ShardedCorpusIndex.build(
            corpus, shard_rows=shard_rows, precision=precision,
            ivf_clusters=ivf, nprobe_hint=nprobe, seed=args.seed)
        eng = RetrievalEngine(idx, k_ladder=(10, 40), max_batch=batch,
                              session_id=f"bench-{name}")
        eng.warmup()
        mode = "ivf" if ivf else "brute"
        arms[name] = (eng, mode, _bytes_per_query(idx, mode))

    # the host baseline walks the SAME corpus — no subsampling
    t0 = time.perf_counter()
    tree = VPTree(corpus)
    print(f"  host VPTree built on all {n} rows in "
          f"{time.perf_counter() - t0:.1f}s")

    # interleaved rounds (benchmarks/ab.py): arm order rotates so drift
    # (thermal, page cache) spreads across arms instead of biasing the
    # last one
    def _engine_arm(name):
        eng, mode, _ = arms[name]

        def go(_r):
            t0 = time.perf_counter()
            eng.search(probes, k, mode=mode)
            return batch / (time.perf_counter() - t0)
        return go

    def _host_arm(_r):
        t0 = time.perf_counter()
        for qv in probes:
            tree.search(qv, k)
        return batch / (time.perf_counter() - t0)

    ab_arms = {name: _engine_arm(name) for name in arms}
    ab_arms["host-vptree"] = _host_arm
    order = list(ab_arms)
    stats = ab.interleaved(ab_arms, rounds)

    # the gated worst-case pair: same pruning-hostile queries through
    # both arms. The fused scan's cost is query-invariant (same matmul
    # regardless of the query); the tree's is not — this is the number
    # a serving tier provisions for.
    n_worst = 8 if smoke else 4
    t0 = time.perf_counter()
    for qv in worst[:n_worst]:
        tree.search(qv, k)
    host_worst_qps = n_worst / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    arms["brute-f32"][0].search(worst, k, mode="brute")
    fused_worst_qps = batch / (time.perf_counter() - t0)

    failures = []
    rows = []
    host_qps = float(np.median(stats["host-vptree"]))
    for name in order:
        qps = float(np.median(stats[name]))
        if name == "host-vptree":
            rows.append((name, qps, None, n * dim * 8, 1.0))
            continue
        eng, mode, bpq = arms[name]
        d1, i1 = eng.search(probes, k, mode=mode)
        d2, i2 = eng.search(probes, k, mode=mode)
        if not (np.asarray(d1).tobytes() == np.asarray(d2).tobytes()
                and np.asarray(i1).tobytes()
                == np.asarray(i2).tobytes()):
            failures.append(f"{name}: repeat not bitwise identical")
        rec = recall_at(np.asarray(i1), oracle)
        rows.append((name, qps, rec, bpq, qps / host_qps))
        if rec < 0.95:
            failures.append(
                f"{name}: recall@10 {rec:.3f} below the 0.95 gate")
        if eng.recompiles_after_warmup:
            failures.append(
                f"{name}: {eng.recompiles_after_warmup} live "
                f"compile(s) after warmup")
        p = eng.query_ring.quantiles((0.5, 0.99))
        print(f"  {name:<12} qps={qps:10.1f}  "
              f"p50={p[0.5] * 1e3:7.2f}ms  p99={p[0.99] * 1e3:7.2f}ms"
              f"  recall@10={rec:.3f}  bytes/q={bpq / 1e6:8.2f}MB"
              f"  vs-host={qps / host_qps:6.1f}x")
    print(f"  {'host-vptree':<12} qps={host_qps:10.1f}  "
          f"(exact walk, easy in-distribution probes — ungated)")
    print(f"  worst-case queries: host walk {host_worst_qps:8.2f} q/s"
          f"  vs fused scan {fused_worst_qps:8.1f} q/s "
          f"({fused_worst_qps / host_worst_qps:.1f}x)")

    speedup_gate = 1.0 if smoke else 10.0
    if fused_worst_qps < speedup_gate * host_worst_qps:
        failures.append(
            f"fused scan {fused_worst_qps:.0f} q/s under "
            f"{speedup_gate}x the host walk ({host_worst_qps:.2f} "
            f"q/s) on worst-case queries")
    f32_bytes = arms["brute-f32"][2]
    if arms["brute-int8"][2] > 0.3 * f32_bytes:
        failures.append("int8 bytes/query not under 0.3x of f32")
    if arms["ivf-f32"][2] >= f32_bytes:
        failures.append("IVF bytes/query not under brute f32")

    label = "smoke" if smoke else "full"
    if failures:
        print(f"neighbors {label}: FAIL")
        for f in failures:
            print(f"  - {f}")
        return 1
    if not smoke:
        # the full acceptance serves the 1M index through the real
        # HTTP ingress: same engine behind a FleetRouter pool +
        # /api/neighbors, answers must match the direct search
        from deeplearning4j_tpu.parallel.fleet import FleetRouter
        from deeplearning4j_tpu.ui.neighbors_module import \
            NeighborsModule
        from deeplearning4j_tpu.ui.server import UIServer
        from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
        eng = arms["brute-f32"][0]
        router = FleetRouter(session_id="nn-bench")
        router.add_retrieval_pool("neighbors", eng)
        server = UIServer(port=0)
        server.attach(InMemoryStatsStorage())
        server.register_module(NeighborsModule(router))
        server.start()
        try:
            body = json.dumps({"queries": probes.tolist(),
                               "k": k}).encode()
            req = urllib.request.Request(
                f"{server.url}/api/neighbors", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                out = json.loads(r.read())
            d_ref, i_ref = eng.search(probes, k, mode="brute")
            if not np.array_equal(np.asarray(out["ids"]),
                                  np.asarray(i_ref)):
                failures.append("/api/neighbors ids diverge from the "
                                "direct engine search")
            else:
                print(f"  /api/neighbors served the {n}-vector index: "
                      f"{out['n']} queries, index_version="
                      f"{out['index_version']}")
        finally:
            server.stop()
        if failures:
            print(f"neighbors {label}: FAIL")
            for f in failures:
                print(f"  - {f}")
            return 1

    print(f"neighbors {label}: PASS — fused >= {speedup_gate}x host, "
          f"recall gates held, bitwise-deterministic, zero live "
          f"compiles after warmup")
    return 0


# ---- cluster chaos smoke --------------------------------------------------

def _start_nn_node(node_id, shards, reg_dir, store_dir, key, log_path,
                   env):
    cmd = [sys.executable, "-m", "deeplearning4j_tpu", "serve",
           "--neighbors-index", key, "--artifact-store", store_dir,
           "--neighbors-shards", ",".join(str(s) for s in shards),
           "--neighbors-k-ladder", "10,40", "--neighbors-batch", "16",
           "--ui-port", "0", "--join", reg_dir, "--node-id", node_id,
           "--drain-timeout", "20"]
    log = open(log_path, "w")
    return subprocess.Popen(cmd, cwd=_ROOT, env=env, stdout=log,
                            stderr=subprocess.STDOUT), log


def _wait_nn_node(registry, node_id, pid, timeout_s=240.0):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        rec = registry.read_all().get(node_id)
        if rec and rec.get("pid") == pid \
                and (rec.get("stats") or {}).get("shards"):
            return rec
        time.sleep(0.2)
    raise RuntimeError(f"node {node_id} (pid {pid}) never gossiped "
                       f"its shards")


def _tail(path, n=2000):
    try:
        with open(path) as f:
            return f.read()[-n:]
    except OSError:
        return "<no log>"


def run_cluster(args, smoke: bool = True) -> int:
    """Mid-query node-SIGKILL chaos through the scatter-gather tier
    (the module docstring's --smoke-cluster contract)."""
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from benchmarks import cpu_only_children_env
    child_env = cpu_only_children_env("benchmarks.neighbors cluster smoke")

    from deeplearning4j_tpu.parallel.aot_cache import ArtifactStore
    from deeplearning4j_tpu.parallel.node import NodeRegistry
    from deeplearning4j_tpu.retrieval.cluster import NeighborsDispatcher
    from deeplearning4j_tpu.retrieval.index import ShardedCorpusIndex

    n, dim, k = 8192, 32, 10
    kill_after = 3.0 if smoke else 8.0
    dead_tail_s = 4.0
    rejoin_tail_s = 5.0

    work = tempfile.mkdtemp(prefix="dl4j-nn-cluster-")
    reg_dir = os.path.join(work, "registry")
    store_dir = os.path.join(work, "store")
    corpus = blob_corpus(n, dim, k_blobs=32, seed=args.seed)
    ShardedCorpusIndex.build(corpus, shard_rows=2048,
                             precision="int8").save(
        ArtifactStore(store_dir), "nnbench")
    registry = NodeRegistry(reg_dir, stale_after_s=1.0,
                            dead_after_s=2.5)
    rng = np.random.default_rng(args.seed)
    probes = corpus[rng.integers(n, size=8)] + rng.normal(
        size=(8, dim)).astype(np.float32) * 0.05

    logs = {"a": os.path.join(work, "a.log"),
            "b": os.path.join(work, "b.log")}
    handles = []
    failures = []
    pa, log = _start_nn_node("a", [0, 1], reg_dir, store_dir,
                             "nnbench", logs["a"], child_env)
    handles.append(log)
    pb = None
    try:
        _wait_nn_node(registry, "a", pa.pid)
        pb, log = _start_nn_node("b", [2, 3], reg_dir, store_dir,
                                 "nnbench", logs["b"], child_env)
        handles.append(log)
        rec_b = _wait_nn_node(registry, "b", pb.pid)

        disp = NeighborsDispatcher(
            registry, timeout_s=10.0, retries=2, backoff_s=0.05,
            breaker_failures=3, breaker_reset_s=1.0)
        counts = {"full": 0, "partial": 0, "error": 0}
        lock = threading.Lock()
        stop = threading.Event()

        def one():
            try:
                out = disp.search(probes, k)
                with lock:
                    counts["partial" if out["partial"]
                           else "full"] += 1
            except Exception:
                with lock:
                    counts["error"] += 1

        pool = ThreadPoolExecutor(max_workers=16)
        futs = []
        arrival = random.Random(args.seed)

        def drive():
            while not stop.is_set():
                futs.append(pool.submit(one))
                time.sleep(arrival.expovariate(30.0))

        driver = threading.Thread(target=drive, daemon=True)
        driver.start()

        time.sleep(kill_after)
        before_kill = dict(counts)
        pa.kill()                                    # SIGKILL node a
        print(f"  SIGKILL node a at t={kill_after}s "
              f"(answers so far: {before_kill})")
        time.sleep(dead_tail_s)
        during = {kk: counts[kk] - before_kill[kk] for kk in counts}
        if during["error"]:
            failures.append(
                f"{during['error']} queries raised during the dead "
                f"window — contract is full or partial, never an "
                f"exception")
        if not during["partial"]:
            failures.append(
                "no partial answers during the dead window — the "
                "degradation path never exercised")

        # rejoin under the SAME id: stale-record overwrite + warm from
        # the shared store
        t_join = time.time()
        pa2, log = _start_nn_node("a", [0, 1], reg_dir, store_dir,
                                  "nnbench", logs["a"] + ".2", child_env)
        handles.append(log)
        rec_a2 = _wait_nn_node(registry, "a", pa2.pid)
        rejoin_s = time.time() - t_join
        time.sleep(rejoin_tail_s)
        stop.set()
        driver.join(timeout=10)
        for f in futs:
            f.result()

        # the rejoined node must answer full again and be warm with
        # zero live compiles (the store's XLA cache fed its warmup)
        out = disp.search(probes, k)
        if out["partial"]:
            failures.append("post-rejoin query still partial")
        with urllib.request.urlopen(
                rec_a2["url"] + "/api/neighbors/stats",
                timeout=10) as r:
            st = json.loads(r.read())["engine"]
        if not st["warm"] or st["recompiles_after_warmup"]:
            failures.append(
                f"rejoined node not cleanly warm: warm={st['warm']} "
                f"recompiles={st['recompiles_after_warmup']}")
        oracle_d, oracle_i = exact_oracle(corpus, probes, k)
        rec = recall_at(np.asarray(out["ids"]), oracle_i)
        if rec < 0.95:
            failures.append(f"post-rejoin recall {rec:.3f} < 0.95")

        # SIGTERM drain on b: finish in-flight, deregister, exit 0
        pb.terminate()
        rc = pb.wait(timeout=30)
        if rc != 0:
            failures.append(f"node b drain exited {rc}")
        if "b" in registry.read_all():
            failures.append("node b record not deregistered")
        disp.shutdown()

        print(f"  answers: {counts}  (dead window: {during}, "
              f"rejoin {rejoin_s:.1f}s)")
        if failures:
            print("neighbors cluster smoke: FAIL")
            for f in failures:
                print(f"  - {f}")
            for nid, p in logs.items():
                print(f"--- tail {nid} ---\n{_tail(p)}")
            return 1
        print("neighbors cluster smoke: PASS — every query answered "
              "full or flagged-partial through the SIGKILL, rejoiner "
              "warm from the store with zero live compiles, drain "
              "clean")
        return 0
    finally:
        for p in (pa, pb, locals().get("pa2")):
            if p is not None and p.poll() is None:
                p.kill()
        for h in handles:
            h.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--smoke-cluster", action="store_true")
    ap.add_argument("--vectors", type=int, default=1_000_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.smoke_cluster:
        return run_cluster(args, smoke=True)
    return run_ab(args, smoke=args.smoke)


if __name__ == "__main__":
    sys.exit(main())
