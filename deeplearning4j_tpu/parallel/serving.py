"""ServingEngine: pipelined batched inference with warmed bucket
executables and multi-replica fan-out.

The seed dispatcher (parallel/inference.py pre-PR5) host-synced on
the model output fetch inside its batching loop, so the queue
drained at device-roundtrip latency, every bucket paid first-request
compile cost, and a request larger than ``batch_limit`` minted an
unbounded set of pow2 executables. This engine replaces it with five
coordinated pieces:

1. **Pipelined dispatch.** The dispatcher thread issues the compiled
   forward and hands the still-on-device result (plus its waiters) to a
   completion thread over a bounded pipe; JAX async dispatch means batch
   N+1 is being formed and issued while batch N computes and its
   device→host fetch completes — the same double-buffer discipline as
   ``datasets/feeder.py``. The pipe's bound doubles as the aggregation
   policy: while the device is busy (pipe full) the dispatcher keeps
   coalescing arrivals up to ``timeout_ms``; the moment a slot frees it
   dispatches what it has. The seed's fixed aggregation window — which
   idled the device for the full ``timeout_ms`` whenever offered load
   sat below ``batch_limit`` — survives only as the upper bound.
2. **Committed inference params.** Parameters and model state are
   ``device_put`` once at engine start (optionally cast to bf16), per
   replica and — for the sharded path — replicated over the mesh. No
   per-call reliance on the global trace cache keyed off
   ``model.train_state``: the engine owns an explicit per-bucket
   executable table (AOT ``jit.lower(...).compile()``; a bucket that
   will not compile raises at warmup).
3. **Bounded bucket ladder + request splitting.** Batches pad to the
   smallest power-of-two bucket in ``[min_bucket, batch_limit]``;
   oversized requests are split across dispatches at ``output()`` and
   reassembled, so the executable table is bounded by the ladder no
   matter what arrives. A warmup sweep over the ladder at start means
   no live request ever pays a compile (``recompiles_after_warmup``
   asserts it; the RecompileWatchdog sees every dispatch signature).
4. **Multi-replica fan-out.** With R > 1 visible devices, full
   ``batch_limit`` buckets shard data-parallel across the mesh
   (parallel/mesh.py); partial buckets round-robin whole replicas.
   Per-replica dispatch and busy-time counters feed utilization gauges.
5. **Tail-latency observability.** Per-request ``queue_wait`` and
   per-batch ``batch_form``/``dispatch``/``device``/``fetch`` spans ride
   the SpanTracer; streaming p50/p95/p99 (observe/latency.py), in-flight
   depth, queue depth, batch occupancy and ``dl4j_serving_*`` series
   publish to the Prometheus registry scraped at ``/metrics``.

The reference analog is ParallelInference.java:35 (SURVEY §2.11) — its
model-per-GPU workers become replicas here; ``parallel/inference.py``
keeps the ParallelInference facade on top of this engine.

Numerical contract: a request's rows are computed at the bucket shape
and sliced back, so padded and split requests are bitwise-equal to the
direct ``model.output`` call. A request CO-BATCHED with other callers
runs at whatever bucket the batch lands in; on backends whose matmul
kernel selection depends on the batch dimension (CPU gemv vs gemm)
that can shift results by ~1 ulp vs the exact-size direct call.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, NamedTuple, Optional, Tuple, Union

import numpy as np

from deeplearning4j_tpu.chaos.hook import chaos_site
from deeplearning4j_tpu.observe.latency import LatencyRing
from deeplearning4j_tpu.observe.recompile import RecompileWatchdog
from deeplearning4j_tpu.observe.registry import default_registry
from deeplearning4j_tpu.observe.tracer import NULL_TRACER
from deeplearning4j_tpu.parallel.deadline import (Deadline,
                                                  DeadlineExceeded)

MESH = "mesh"            # dispatch-target key for the sharded full bucket


class _Request(NamedTuple):
    """One enqueued chunk: host features, its waiter, arrival time,
    and the caller's remaining-budget deadline (None = unbounded)."""
    x: np.ndarray
    future: Future
    t_enqueue: float
    deadline: Optional[Deadline] = None


class _InFlight(NamedTuple):
    """A dispatched batch travelling dispatcher -> completion thread."""
    out: Any                 # device-resident result (un-fetched)
    requests: List[_Request]
    n_real: int
    bucket: int
    where: Union[int, str]
    t_dispatched: float


class ServingEngine:
    """Thread-safe batched inference over one model's committed params.

    Parameters
    ----------
    model : MultiLayerNetwork / single-io ComputationGraph (must expose
        ``build_inference_fn``)
    batch_limit : max examples per dispatch; also the ladder's top bucket
    queue_limit : bound on queued request chunks (producers block)
    timeout_ms : UPPER bound on batch aggregation; the pipelined engine
        only waits at all while the completion pipe is full
    depth : in-flight batches handed to the completion thread (the
        double-buffer depth; 1 = aggregate exactly while device is busy)
    pipelined : False reproduces the seed's blocking dispatcher (fixed
        aggregation window + inline fetch) — kept for the benchmark A/B
    replicas : device count to serve on; "auto" = all visible devices
    feature_shape : per-example feature shape (no batch dim); providing
        it (with ``dtype``) enables the warmup sweep at start
    dtype : feature dtype requests are cast to (default float32)
    precision : a ``PrecisionPolicy`` (or its mode string) selecting the
        committed-params precision: "f32" (default), "bf16" (cast the
        inference copy to bfloat16), or "int8" (post-training quantized
        via parallel/quant.py — the policy must carry calibration
        ``samples``; the model's train_state is untouched in all modes)
    bf16 : DEPRECATED — the pre-PrecisionPolicy spelling of
        ``precision=PrecisionPolicy.bf16()``; passing it warns
    warmup : compile the whole bucket ladder at start (default: True
        when ``feature_shape`` is known)
    aot_cache_dir : persist the warmed executable table here
        (parallel/aot_cache.py): the first process exports + saves the
        ladder after its sweep; later processes reach ``assert_warm()``
        in a fraction of the sweep time by deserializing StableHLO blobs
        and hitting the XLA persistent compilation cache. Any
        fingerprint mismatch (weights, jaxlib, backend, shapes) falls
        through to live compile.
    model_version : opaque version string folded into the cache
        fingerprint (the fleet router's swap path sets it)
    """

    def __init__(self, model, *, batch_limit: Optional[int] = None,
                 queue_limit: int = 128, timeout_ms: float = 5.0,
                 depth: int = 1, pipelined: bool = True,
                 replicas: Union[int, str] = 1,
                 min_bucket: int = 1,
                 feature_shape: Optional[Tuple[int, ...]] = None,
                 dtype: Any = np.float32, bf16: bool = False,
                 precision: Any = None,
                 warmup: Optional[bool] = None,
                 aot_cache_dir: Optional[str] = None,
                 model_version: Optional[str] = None,
                 tuned_config=None,
                 tracer=None, registry=None, watchdog=None,
                 session_id: str = "serve"):
        import jax
        # explicit batch_limit > TunedConfig (this engine's, else the
        # process-wide one) > the committed default of 32 — the autotune
        # resolution ladder; an engine that never sees a tuned config
        # behaves exactly as before
        from deeplearning4j_tpu.optimize.autotune import resolve_tuned
        batch_limit = int(resolve_tuned(batch_limit, tuned_config,
                                        "serving.batch_limit"))
        self.tuned_config = tuned_config
        if batch_limit < 1:
            raise ValueError("batch_limit must be >= 1")
        if not 1 <= min_bucket <= batch_limit:
            raise ValueError("need 1 <= min_bucket <= batch_limit")
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self.model = model
        self.batch_limit = int(batch_limit)
        self.timeout_ms = float(timeout_ms)  # host-sync-ok: Python config scalar, not a device value
        self.depth = int(depth)
        self.pipelined = bool(pipelined)
        self.session_id = session_id
        self.dtype = np.dtype(dtype)
        self.feature_shape = (None if feature_shape is None
                              else tuple(feature_shape))
        from deeplearning4j_tpu.parallel.quant import PrecisionPolicy
        if precision is None:
            if bf16:
                import warnings
                warnings.warn(
                    "ServingEngine(bf16=True) is deprecated; pass "
                    "precision=PrecisionPolicy.bf16() instead",
                    DeprecationWarning, stacklevel=2)
                precision = PrecisionPolicy.bf16()
            else:
                precision = PrecisionPolicy.f32()
        else:
            if bf16:
                raise ValueError(
                    "pass either precision= or the deprecated bf16= "
                    "flag, not both")
            if isinstance(precision, str):
                precision = PrecisionPolicy(mode=precision)
        self.precision = precision
        self._ptag = precision.tag
        self.bf16 = precision.mode == "bf16"   # back-compat attribute
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.registry = registry if registry is not None \
            else default_registry()
        self.watchdog = watchdog if watchdog is not None else \
            RecompileWatchdog(self.registry, session_id=session_id)
        self.latency = LatencyRing()

        devs = jax.devices()
        n = len(devs) if replicas == "auto" else int(replicas)
        if not 1 <= n <= len(devs):
            raise ValueError(f"replicas={replicas!r} but {len(devs)} "
                             "devices are visible")
        self.devices = devs[:n]
        self.n_replicas = n

        # bounded pow2 ladder: min_bucket..batch_limit (limit included
        # even when it is not itself a power of two)
        ladder, b = [], 1 << (min_bucket - 1).bit_length()
        while b < self.batch_limit:
            ladder.append(b)
            b <<= 1
        ladder.append(self.batch_limit)
        self.ladder = ladder

        # ---- metrics -----------------------------------------------------
        reg = self.registry
        self._c_requests = reg.counter(
            "dl4j_serving_requests_total",
            "inference requests accepted by the serving engine")
        self._c_batches = reg.counter(
            "dl4j_serving_batches_total",
            "device batches dispatched by the serving engine")
        self._c_compiles = reg.counter(
            "dl4j_serving_compiles_total",
            "bucket executables compiled, by phase (warmup|live); a "
            "nonzero live count means a request paid a compile")
        self._g_inflight = reg.gauge(
            "dl4j_serving_inflight",
            "requests accepted but not yet answered")
        self._g_queue = reg.gauge(
            "dl4j_serving_queue_depth",
            "request chunks waiting for the dispatcher")
        self._g_occupancy = reg.gauge(
            "dl4j_serving_batch_occupancy",
            "real examples / bucket size of the last dispatched batch")
        self._g_latency = reg.gauge(
            "dl4j_serving_latency_ms",
            "streaming request latency quantiles over the last 4096 "
            "requests")
        self._c_replica_disp = reg.counter(
            "dl4j_serving_replica_dispatches_total",
            "batches dispatched per replica ('mesh' = sharded full "
            "buckets across all replicas)")
        self._c_replica_busy = reg.counter(
            "dl4j_serving_replica_busy_ms",
            "cumulative ms a replica spent computing dispatched batches")
        self._g_precision = reg.gauge(
            "dl4j_serving_precision",
            "1 for the engine's active precision label (f32|bf16|int8)")
        self._g_quant_err = reg.gauge(
            "dl4j_quant_layer_error",
            "per-layer relative L2 quantization error observed on the "
            "calibration probe batch (int8 engines only; layers over "
            "the policy budget fell back to f32)")
        self._c_deadline_shed = reg.counter(
            "dl4j_serving_deadline_shed_total",
            "requests shed because their deadline expired before "
            "device dispatch; stage=ingress|batch")
        self._c_requests.inc(0.0, session=session_id, precision=self._ptag)
        self._c_batches.inc(0.0, session=session_id, precision=self._ptag)
        self._c_compiles.inc(0.0, session=session_id, precision=self._ptag, phase="live")
        self._g_inflight.set(0.0, session=session_id, precision=self._ptag)
        self._g_precision.set(1.0, session=session_id,
                              precision=self._ptag)
        # $/req proxy accumulators (benchmarks/serving.py --precision-ab)
        self.dispatch_count = 0
        self.device_ms_total = 0.0

        # ---- committed inference params ----------------------------------
        # Duck-typed models exposing only .output() (pre-engine callers,
        # test doubles) skip the committed-params/AOT machinery and run
        # the legacy direct call under the same batching discipline.
        self._committed: Dict[Union[int, str], Any] = {}
        self._batch_sharding = None
        self._jit = None
        self.quantized = None        # QuantizedModel for int8 engines
        self._calib_hash: Optional[str] = None
        if hasattr(model, "build_inference_fn"):
            if model.train_state is None:
                model.init()
            params = model.train_state.params
            mstate = model.train_state.model_state
            if self.precision.mode == "int8":
                from deeplearning4j_tpu.parallel.quant import (
                    quantize_model)
                qm = quantize_model(model, self.precision,
                                    registry=self.registry,
                                    tracer=self.tracer)
                self.quantized = qm
                self._calib_hash = qm.calibration_hash()
                params = qm.params
                fwd = qm.build_inference_fn()
                for lname, rep in qm.report.items():
                    self._g_quant_err.set(
                        rep["error"], session=session_id, layer=lname,
                        quantized=str(rep["quantized"]).lower())
            else:
                if self.bf16:
                    import jax.numpy as jnp
                    params = jax.tree_util.tree_map(
                        lambda a: a.astype(jnp.bfloat16)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a,
                        params)
                fwd = model.build_inference_fn()
            self._jit = jax.jit(lambda p, s, x: fwd(p, s, x, None))
            # one committed (params, model_state) copy per replica; plus
            # a mesh-replicated copy backing the sharded full-bucket path
            for r, dev in enumerate(self.devices):
                self._committed[r] = jax.device_put((params, mstate),
                                                    dev)
            if self.n_replicas > 1:
                from deeplearning4j_tpu.parallel.mesh import (
                    DATA_AXIS, batch_sharding, create_mesh, replicated)
                mesh = create_mesh({DATA_AXIS: self.n_replicas},
                                   self.devices)
                self._committed[MESH] = jax.device_put(
                    (params, mstate), replicated(mesh))
                self._batch_sharding = batch_sharding(mesh)
        elif self.n_replicas > 1 or self.precision.mode != "f32":
            raise ValueError(
                f"replicas > 1 / precision={self.precision.mode!r} "
                "need a model exposing build_inference_fn (committed "
                "per-replica params); "
                f"{type(model).__name__} only has .output")

        # ---- persisted AOT executable cache ------------------------------
        self.aot_cache = None
        self.model_version = model_version
        self._loaded_exports: Dict[int, Any] = {}
        self._cache_fp = None
        self._c_aot = reg.counter(
            "dl4j_serving_aot_cache_total",
            "persisted AOT executable cache events: hit = bucket "
            "loaded from a StableHLO blob, save = bucket persisted "
            "after warmup")
        if aot_cache_dir is not None and self._jit is not None \
                and self.feature_shape is not None:
            from deeplearning4j_tpu.parallel.aot_cache import (
                AOTExecutableCache, fingerprint)
            self.aot_cache = AOTExecutableCache(aot_cache_dir)
            params0, mstate0 = self._committed[0]
            self._cache_fp = fingerprint(
                params0, mstate0, feature_shape=self.feature_shape,
                dtype=self.dtype, ladder=self.ladder,
                precision=self._ptag, calibration=self._calib_hash,
                model_version=model_version)
            self._loaded_exports = self.aot_cache.try_load(self._cache_fp)
            if (self.aot_cache.state == "mismatch"
                    and self.precision.mode == "int8"):
                # a rejected quant cache is worth a breadcrumb: the
                # divergence reason (stale calibration? precision?)
                # rides into any later crash dump's context.json
                from deeplearning4j_tpu.observe.flight_recorder import (
                    default_flight_recorder)
                rec = default_flight_recorder()
                if rec is not None:
                    rec.note(f"aot_cache_rejected_{session_id}", {
                        "dir": str(aot_cache_dir),
                        "precision": self._ptag,
                        "calibration": self._calib_hash,
                        "reason": self.aot_cache.reason,
                    })

        # ---- dispatch machinery ------------------------------------------
        # executable table keyed (bucket, target, precision): precision
        # is per-engine today, but first-class in the key so quant and
        # f32 executables of co-resident engines can never collide
        self._exe: Dict[Tuple[int, Union[int, str], str], Any] = {}
        self._exe_lock = threading.Lock()
        self._chaos_dispatch = chaos_site("serve.dispatch")
        self._warmed = False
        self._post_warmup_compiles = 0
        self.param_swaps = 0
        self._rr = 0                       # round-robin replica cursor
        self._inflight_count = 0
        self._count_lock = threading.Lock()
        self._queue: "queue.Queue[_Request]" = queue.Queue(
            maxsize=queue_limit)
        # aggregation overflow; shared between the dispatcher
        # (_form_batch) and caller threads (_drain_queue via a shutdown
        # race, stats) — every touch goes through _carry_lock or the
        # parked request can be dropped or double-failed
        self._carry: Optional[_Request] = None
        self._carry_lock = threading.Lock()
        self._completions: "queue.Queue[Optional[_InFlight]]" = \
            queue.Queue(maxsize=self.depth)
        self._shutdown = threading.Event()
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, daemon=True,
            name=f"serving-dispatch-{session_id}")
        self._completer: Optional[threading.Thread] = None
        if self.pipelined:
            self._completer = threading.Thread(
                target=self._complete_loop, daemon=True,
                name=f"serving-complete-{session_id}")

        do_warmup = (self.feature_shape is not None if warmup is None
                     else bool(warmup))
        self.warmup_seconds = 0.0
        self.cache_save_seconds = 0.0
        if do_warmup:
            if self.feature_shape is None:
                raise ValueError("warmup needs feature_shape (and dtype)")
            t0 = time.perf_counter()
            self._warmup_sweep()
            self.warmup_seconds = time.perf_counter() - t0
            if (self.aot_cache is not None
                    and self.aot_cache.state in ("cold", "mismatch")):
                self.save_aot_cache()
        self._warmed = True
        self._dispatcher.start()
        if self._completer is not None:
            self._completer.start()

    # ---- bucket ladder ---------------------------------------------------
    def bucket_of(self, n: int) -> int:
        """Smallest ladder bucket >= n (n must be <= batch_limit)."""
        for b in self.ladder:
            if b >= n:
                return b
        raise ValueError(f"batch of {n} exceeds batch_limit "
                         f"{self.batch_limit}")

    def _target_for(self, bucket: int) -> Union[int, str]:
        """Full buckets shard across the mesh; everything else
        round-robins whole replicas."""
        if (bucket == self.batch_limit and self.n_replicas > 1
                and bucket % self.n_replicas == 0):
            return MESH
        t = self._rr % self.n_replicas
        self._rr += 1
        return t

    # ---- executables -----------------------------------------------------
    def _place(self, x: np.ndarray, where: Union[int, str]):
        import jax
        if where == MESH:
            return jax.device_put(x, self._batch_sharding)
        return jax.device_put(x, self.devices[where])

    def _get_exe(self, bucket: int, where: Union[int, str]):
        key = (bucket, where, self._ptag)
        exe = self._exe.get(key)
        if exe is not None:
            return exe
        with self._exe_lock:
            exe = self._exe.get(key)
            if exe is not None:
                return exe
            import jax
            params, mstate = self._committed[where]
            x = self._place(np.zeros((bucket,) + self.feature_shape,
                                     self.dtype), where)
            exp = (self._loaded_exports.get(bucket)
                   if where != MESH else None)
            # a compile error raises here, at warmup, not on the first
            # live request: a blob that passed the fingerprint and
            # checksum checks and still will not compile is a fault, not
            # a cache miss
            if exp is not None:
                # persisted-cache path: compile the deserialized
                # StableHLO wrapper (no model re-trace; the XLA compile
                # itself is a persistent-cache disk hit, primed at save)
                exe = jax.jit(exp.call).lower(params, mstate, x).compile()
                self.aot_cache.hits += 1
                self._c_aot.inc(1.0, session=self.session_id,
                                precision=self._ptag, event="hit")
            else:
                exe = self._jit.lower(params, mstate, x).compile()
            self._exe[key] = exe
            phase = "warmup" if not self._warmed else "live"
            if self._warmed:
                self._post_warmup_compiles += 1
            self._c_compiles.inc(1.0, session=self.session_id, precision=self._ptag,
                                 phase=phase)
            self.tracer.instant("serve_compile", cat="serve",
                                bucket=bucket, where=str(where),
                                phase=phase)
            return exe

    def _warmup_sweep(self):
        """Compile the whole ladder for every dispatch target the live
        traffic can hit, so no request ever pays a compile."""
        t0 = time.perf_counter()
        for bucket in self.ladder:
            targets: List[Union[int, str]]
            if (bucket == self.batch_limit and self.n_replicas > 1
                    and bucket % self.n_replicas == 0):
                targets = [MESH]
            else:
                targets = list(range(self.n_replicas))
            for where in targets:
                x = np.zeros((bucket,) + self.feature_shape, self.dtype)
                out = self._run(x, bucket, where)
                # block so compile cost lands here, not on a request
                if hasattr(out, "block_until_ready"):
                    out.block_until_ready()  # host-sync-ok: warmup sweep is pre-traffic by design
        self.tracer.add_span("serve_warmup", t0, time.perf_counter(),
                             cat="serve", buckets=len(self.ladder),
                             replicas=self.n_replicas)

    def _run(self, x: np.ndarray, bucket: int, where: Union[int, str]):
        """Issue the compiled forward for one padded batch; returns the
        device-resident (un-fetched) result."""
        if x.dtype != self.dtype:
            x = x.astype(self.dtype)
        self.watchdog.observe(f"serve_fwd_{self._ptag}_b{bucket}", x)
        if self._jit is None:        # legacy duck-typed model
            return self.model.output(x)
        exe = self._get_exe(bucket, where)
        params, mstate = self._committed[where]
        return exe(params, mstate, self._place(x, where))

    # ---- public API ------------------------------------------------------
    def submit(self, features,
               deadline: Optional[Deadline] = None) -> Future:
        """Enqueue a request; the Future resolves to the (N, ...) host
        output. Oversized requests split across dispatches and
        reassemble transparently. An expired ``deadline`` sheds
        synchronously (DeadlineExceeded, never enqueued); one that
        expires while queued sheds at batch forming — either way the
        request never reaches the device."""
        x = np.asarray(features)  # host-sync-ok: serving ingress stages request features on host
        if x.ndim == 0 or x.shape[0] == 0:
            raise ValueError(
                "features must be a non-empty batch (got shape "
                f"{x.shape}); a single example is shape (1, ...)")
        if self.feature_shape is None:
            # first request fixes the wire contract
            self.feature_shape = x.shape[1:]
            if self.dtype is None:
                self.dtype = x.dtype
        elif x.shape[1:] != self.feature_shape:
            raise ValueError(
                f"request feature shape {x.shape[1:]} does not match "
                f"the engine's {self.feature_shape}")
        if x.dtype != self.dtype:
            x = x.astype(self.dtype)
        if self._shutdown.is_set():
            raise RuntimeError("ServingEngine is shut down")
        if deadline is not None and deadline.expired:
            self._c_deadline_shed.inc(1.0, session=self.session_id,
                                      precision=self._ptag,
                                      stage="ingress")
            raise DeadlineExceeded(
                "serving: deadline expired at ingress")
        chunks = [x[i:i + self.batch_limit]
                  for i in range(0, x.shape[0], self.batch_limit)]
        self._c_requests.inc(1.0, session=self.session_id, precision=self._ptag)
        with self._count_lock:
            self._inflight_count += 1  # graftlint: disable=release-discipline: released by the _track/_join_futures done-callbacks (cross-method by design); the error edge below releases inline
            self._g_inflight.set(self._inflight_count,
                                 session=self.session_id, precision=self._ptag)
        try:
            futures = [self._enqueue(c, deadline) for c in chunks]
        except BaseException:
            # _enqueue can raise on the shutdown race; without this
            # release the count never comes down and least-loaded
            # routing starves the engine forever
            with self._count_lock:
                self._inflight_count -= 1
                self._g_inflight.set(self._inflight_count,
                                     session=self.session_id,
                                     precision=self._ptag)
            raise
        if len(futures) == 1:
            self._track(futures[0])
            return futures[0]
        return self._join_futures(futures)

    def output(self, features,
               deadline: Optional[Deadline] = None) -> np.ndarray:
        """Blocking inference (reference: ParallelInference.output:113)."""
        return self.submit(features, deadline=deadline).result()

    def _enqueue(self, chunk: np.ndarray,
                 deadline: Optional[Deadline] = None) -> Future:
        f: Future = Future()
        req = _Request(chunk, f, time.perf_counter(), deadline)
        while True:
            if self._shutdown.is_set():
                raise RuntimeError("ServingEngine is shut down")
            try:
                # bounded wait so a full queue + dead worker can't block
                # the caller forever
                self._queue.put(req, timeout=0.1)
                break
            except queue.Full:
                continue
        self._g_queue.set(self._queue.qsize(), session=self.session_id, precision=self._ptag)
        if self._shutdown.is_set():
            # raced with shutdown(): the dispatcher may never pop this
            self._drain_queue()
        return f

    def _track(self, f: Future):
        def done(_):
            with self._count_lock:
                self._inflight_count -= 1
                self._g_inflight.set(self._inflight_count,
                                     session=self.session_id, precision=self._ptag)
        f.add_done_callback(done)

    def _join_futures(self, parts: List[Future]) -> Future:
        """One Future over a split request: concatenated result in chunk
        order, or the first chunk failure."""
        outer: Future = Future()
        self._track(outer)
        remaining = [len(parts)]
        lock = threading.Lock()

        def on_done(_f):
            with lock:
                remaining[0] -= 1
                last = remaining[0] == 0
            if not last or outer.done():
                return
            try:
                outer.set_result(
                    np.concatenate([p.result() for p in parts], axis=0))
            except Exception as e:
                outer.set_exception(e)
        for p in parts:
            p.add_done_callback(on_done)
        return outer

    @property
    def inflight(self) -> int:
        """Requests accepted but not yet answered (the fleet router's
        least-loaded dispatch key)."""
        return self._inflight_count

    def _peek_carry(self) -> Optional[_Request]:
        with self._carry_lock:
            return self._carry

    @property
    def params_resident_bytes(self) -> int:
        """Bytes of ONE committed params copy (int8 engines ~1/4 of
        f32) — the params term of the $/req proxy."""
        if not self._committed:
            return 0
        from deeplearning4j_tpu.parallel.quant import params_nbytes
        return params_nbytes(self._committed[0][0])

    def stats(self) -> Dict[str, Any]:
        """Point-in-time snapshot for the CLI / UI module."""
        q = self.latency.quantiles()
        out = {
            "session": self.session_id,
            "replicas": self.n_replicas,
            "ladder": list(self.ladder),
            "pipelined": self.pipelined,
            "precision": self._ptag,
            "params_resident_bytes": self.params_resident_bytes,
            "batches": self.dispatch_count,
            "device_ms_total": self.device_ms_total,
            "requests": self.latency.count,
            "inflight": self._inflight_count,
            # a carried-over request parked in self._carry is waiting
            # for the dispatcher exactly like a queued one — count it
            "queue_depth": self._queue.qsize()
            + (1 if self._peek_carry() is not None else 0),
            "recompiles_after_warmup": self._post_warmup_compiles,
            "warmup_s": self.warmup_seconds,
            "latency_ms": {f"p{int(k * 100)}": v * 1e3
                           for k, v in q.items()},
        }
        if self.aot_cache is not None:
            out["aot_cache"] = self.aot_cache.stats()
        if self.quantized is not None:
            out["quant"] = {
                "calibration": self._calib_hash,
                "error_budget": self.precision.error_budget,
                "fallback": list(self.quantized.fallback),
                "layers": {n: r["error"]
                           for n, r in self.quantized.report.items()},
            }
        return out

    def save_aot_cache(self) -> int:
        """Export + persist the warmed executable table (called
        automatically after the warmup sweep when the cache was cold or
        stale; callable explicitly after e.g. a weight update). Returns
        the number of buckets saved."""
        if (self.aot_cache is None or self._jit is None
                or self.feature_shape is None):
            return 0
        t0 = time.perf_counter()
        example = np.zeros((1,) + self.feature_shape, self.dtype)
        n = self.aot_cache.save(self._jit, self._committed[0],
                                self._cache_fp, self.ladder, example)
        self.cache_save_seconds = time.perf_counter() - t0
        if n:
            self._c_aot.inc(float(n),  # host-sync-ok: python int bucket count, not a device value
                            session=self.session_id, precision=self._ptag,
                            event="save")
        return n

    @property
    def recompiles_after_warmup(self) -> int:
        return self._post_warmup_compiles

    # ---- param-only hot swap ---------------------------------------------
    def committed_host(self) -> Tuple[Any, Any]:
        """Host copies of the committed ``(params, model_state)`` for
        replica 0 — the rollback standby snapshot. ``np.array`` copies,
        never views: on the CPU backend ``device_get`` can alias the
        live buffers, and a standby that shares storage with params
        about to be overwritten is no standby at all."""
        if not self._committed:
            raise ValueError(
                "legacy .output-only engines have no committed params")
        import jax
        return jax.tree_util.tree_map(
            lambda a: np.array(a, copy=True),
            jax.device_get(self._committed[0]))

    def swap_params(self, params, model_state=None, *,
                    version: Optional[str] = None) -> None:
        """Atomically replace the committed inference params without
        touching the executable table.

        Params are **traced arguments** of every bucket executable (not
        baked constants), so as long as the new tree matches the old
        one structurally — same treedef, same leaf shapes/dtypes — the
        warm AOT executables serve the new weights with **zero
        recompiles**. Structure is validated up front and a mismatch
        raises before anything is committed; the swap itself is one
        dict-reference assignment, so a dispatch racing the swap sees
        either the old committed set or the new one, never a mix.

        int8 engines refuse: quantized params bake calibration scales,
        so new weights need requantization (build a new engine — the
        fleet's warm-first ``swap`` path).
        """
        import jax
        if self._jit is None:
            raise ValueError(
                "legacy .output-only model: no committed params to swap")
        if self.precision.mode == "int8":
            raise ValueError(
                "int8 engines cannot hot-swap params (weights bake "
                "calibration scales); build a new engine and use the "
                "fleet swap path")
        old_params, old_mstate = self._committed[0]
        if model_state is None:
            model_state = old_mstate
        if self.bf16:
            import jax.numpy as jnp
            params = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16)
                if jnp.issubdtype(np.asarray(a).dtype,  # host-sync-ok: incoming host candidate, dtype probe only
                                  np.floating)
                else a, params)
        old_leaves, old_def = jax.tree_util.tree_flatten(
            (old_params, old_mstate))
        new_leaves, new_def = jax.tree_util.tree_flatten(
            (params, model_state))
        if new_def != old_def:
            raise ValueError(
                "swap_params: tree structure mismatch vs committed "
                f"params ({new_def} != {old_def}); a structural change "
                "invalidates the warm executables — use the fleet's "
                "full swap instead")
        for i, (o, nl) in enumerate(zip(old_leaves, new_leaves)):
            os_, ns = np.shape(o), np.shape(nl)
            od = o.dtype if hasattr(o, "dtype") \
                else np.asarray(o).dtype  # host-sync-ok: plain-python leaf, structural check
            nd = nl.dtype if hasattr(nl, "dtype") \
                else np.asarray(nl).dtype  # host-sync-ok: plain-python leaf, structural check
            if os_ != ns or od != nd:
                raise ValueError(
                    f"swap_params: leaf {i} is {ns}/{nd}, committed "
                    f"expects {os_}/{od}; shape/dtype changes "
                    "invalidate the warm executables")
        new_committed: Dict[Union[int, str], Any] = {}
        for r, dev in enumerate(self.devices):
            new_committed[r] = jax.device_put((params, model_state),
                                              dev)
        if MESH in self._committed:
            # reuse the live replicated sharding rather than rebuilding
            # the mesh — same placement, no new compile keys
            shd = jax.tree_util.tree_leaves(
                self._committed[MESH])[0].sharding
            new_committed[MESH] = jax.device_put(
                (params, model_state), shd)
        # single reference assignment = the atomic commit point
        self._committed = new_committed
        if version is not None:
            self.model_version = version
        self.param_swaps += 1

    def assert_warm(self):
        """Raise when any live request paid a compile after the warmup
        sweep — the zero-recompile serving contract."""
        if self._post_warmup_compiles:
            raise AssertionError(
                f"{self._post_warmup_compiles} bucket executables were "
                "compiled by live traffic after warmup; widen the warmup"
                " sweep (feature_shape/min_bucket/batch_limit)")
        if self.watchdog.count() > 0:
            raise AssertionError(
                "RecompileWatchdog saw new dispatch signatures after "
                f"first compile: {self.watchdog.events}")

    # ---- dispatcher ------------------------------------------------------
    def _form_batch(self) -> Optional[List[_Request]]:
        with self._carry_lock:
            first, self._carry = self._carry, None
        if first is None:
            try:
                first = self._queue.get(timeout=0.05)
            except queue.Empty:
                return None
        batch = [first]
        total = first.x.shape[0]
        deadline = time.monotonic() + self.timeout_ms / 1000.0
        while total < self.batch_limit:
            if self.pipelined:
                # backpressure aggregation: only wait for stragglers
                # while the completion pipe is full (device busy) —
                # never idle a free device on the timer
                try:
                    item = self._queue.get_nowait()
                except queue.Empty:
                    rem = deadline - time.monotonic()
                    if rem <= 0 or not self._completions.full():
                        break
                    try:
                        item = self._queue.get(timeout=min(rem, 0.001))
                    except queue.Empty:
                        continue
            else:
                # the seed's fixed window: one absolute aggregation
                # deadline per batch
                rem = deadline - time.monotonic()
                if rem <= 0:
                    break
                try:
                    item = self._queue.get(timeout=rem)
                except queue.Empty:
                    break
            if total + item.x.shape[0] > self.batch_limit:
                # doesn't fit: hold it for the next batch (the seed
                # padded past the limit instead — minting an executable
                # per overflow size)
                with self._carry_lock:
                    self._carry = item
                break
            batch.append(item)
            total += item.x.shape[0]
        return batch

    def _shed_expired(self,
                      batch: List[_Request]) -> List[_Request]:
        """Drop requests whose deadline expired while they queued —
        the last gate before the device; the waiter gets
        DeadlineExceeded instead of a stale answer."""
        live = []
        for req in batch:
            if req.deadline is not None and req.deadline.expired:
                self._c_deadline_shed.inc(
                    1.0, session=self.session_id,
                    precision=self._ptag, stage="batch")
                if not req.future.done():
                    req.future.set_exception(DeadlineExceeded(
                        "serving: deadline expired while queued"))
            else:
                live.append(req)
        return live

    def _dispatch_loop(self):
        while not self._shutdown.is_set():
            t_form0 = time.perf_counter()
            batch = self._form_batch()
            if batch:
                batch = self._shed_expired(batch)
            if not batch:
                continue
            self._g_queue.set(self._queue.qsize(),
                              session=self.session_id, precision=self._ptag)
            try:
                inflight = self._dispatch(batch, t_form0)
            except Exception as e:
                # a malformed batch must fail its waiters, not kill the
                # dispatcher (they would hang forever)
                for req in batch:
                    if not req.future.done():
                        req.future.set_exception(e)
                continue
            if not self.pipelined:
                self._complete(inflight)
                continue
            while True:
                try:
                    self._completions.put(inflight, timeout=0.1)
                    break
                except queue.Full:
                    if (self._completer is None
                            or not self._completer.is_alive()):
                        err = RuntimeError(
                            "serving completion thread died")
                        for req in inflight.requests:
                            if not req.future.done():
                                req.future.set_exception(err)
                        break

    def _dispatch(self, batch: List[_Request],
                  t_form0: float) -> _InFlight:
        tracer = self.tracer
        n = sum(req.x.shape[0] for req in batch)
        bucket = self.bucket_of(n)
        # write requests straight into one bucket-sized staging buffer
        # (a fresh one per dispatch: the CPU backend zero-copy adopts
        # numpy buffers, so reuse would corrupt in-flight batches)
        x = np.empty((bucket,) + batch[0].x.shape[1:], self.dtype)
        ofs = 0
        for req in batch:
            k = req.x.shape[0]
            x[ofs:ofs + k] = req.x
            ofs += k
        if bucket > n:
            # duplicate the last row (finite activations) — padded rows
            # are sliced off before waiters see the result
            x[n:] = x[n - 1]
        t_formed = time.perf_counter()
        for req in batch:
            tracer.add_span("queue_wait", req.t_enqueue, t_form0,
                            cat="serve")
        tracer.add_span("batch_form", t_form0, t_formed, cat="serve",
                        n=n, bucket=bucket)
        where = self._target_for(bucket)
        if self._chaos_dispatch is not None:
            self._chaos_dispatch.fail(arg=str(where))
        out = self._run(x, bucket, where)
        t_dispatched = time.perf_counter()
        tracer.add_span("dispatch", t_formed, t_dispatched, cat="serve",
                        where=str(where))
        self._c_batches.inc(1.0, session=self.session_id, precision=self._ptag)
        self.dispatch_count += 1
        self._c_replica_disp.inc(1.0, session=self.session_id, precision=self._ptag,
                                 replica=str(where))
        self._g_occupancy.set(n / bucket, session=self.session_id, precision=self._ptag)
        return _InFlight(out, batch, n, bucket, where, t_dispatched)

    # ---- completion ------------------------------------------------------
    def _complete_loop(self):
        while True:
            item = self._completions.get()
            if item is None:
                return
            self._complete(item)

    def _complete(self, inflight: _InFlight):
        tracer = self.tracer
        try:
            if hasattr(inflight.out, "block_until_ready"):
                inflight.out.block_until_ready()  # host-sync-ok: completion thread absorbs the device wait off the dispatch path
            t_ready = time.perf_counter()
            host = np.asarray(inflight.out)  # host-sync-ok: completion-thread fetch is the one place results come to host
            t_fetched = time.perf_counter()
            tracer.add_span("device", inflight.t_dispatched, t_ready,
                            cat="serve", where=str(inflight.where))
            tracer.add_span("fetch", t_ready, t_fetched, cat="serve",
                            bytes=host.nbytes)
            self._c_replica_busy.inc(
                (t_ready - inflight.t_dispatched) * 1e3,
                session=self.session_id, precision=self._ptag, replica=str(inflight.where))
            self.device_ms_total += (t_ready
                                     - inflight.t_dispatched) * 1e3
            ofs = 0
            now = time.perf_counter()
            for req in inflight.requests:
                k = req.x.shape[0]
                if not req.future.done():
                    req.future.set_result(host[ofs:ofs + k])
                ofs += k
                self.latency.record(now - req.t_enqueue)
            self._publish_latency()
        except Exception as e:    # propagate to every waiter
            for req in inflight.requests:
                if not req.future.done():
                    req.future.set_exception(e)

    def _publish_latency(self):
        q = self.latency.quantiles()
        for qq, v in q.items():
            self._g_latency.set(v * 1e3, session=self.session_id, precision=self._ptag,
                                quantile=f"p{int(qq * 100)}")

    # ---- lifecycle -------------------------------------------------------
    def shutdown(self):
        if self._shutdown.is_set():
            return
        self._shutdown.set()
        self._dispatcher.join(timeout=5)
        if self._completer is not None:
            # sentinel after the dispatcher stops feeding; the completer
            # drains in-flight batches first (their results are valid)
            while self._completer.is_alive():
                try:
                    self._completions.put(None, timeout=0.1)
                    break
                except queue.Full:
                    continue
            self._completer.join(timeout=5)
        self._drain_queue()

    def _drain_queue(self):
        """Fail any still-queued request (post-shutdown)."""
        with self._carry_lock:
            carried, self._carry = self._carry, None
        if carried is not None and not carried.future.done():
            carried.future.set_exception(
                RuntimeError("ServingEngine shut down"))
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if not req.future.done():
                req.future.set_exception(
                    RuntimeError("ServingEngine shut down"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
