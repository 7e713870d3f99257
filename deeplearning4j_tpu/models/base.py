"""Base model: shared fit/evaluate machinery for MultiLayerNetwork and
ComputationGraph.

Analog of the reference's ``Model``/``NeuralNetwork`` contracts
(deeplearning4j-nn/.../nn/api/Model.java) and the shared parts of the fit
loop (MultiLayerNetwork.fit at nn/multilayer/MultiLayerNetwork.java:1268):
iterate minibatches, record ETL time, run the optimizer step, fire
listeners. Here the optimizer step is one donated jitted function
(optimize/solver.py) and 'workspaces' are XLA's memory plan.
"""

from __future__ import annotations

import time
from collections import deque
from contextlib import contextmanager
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, DataSetIterator
from deeplearning4j_tpu.evaluation.evaluation import Evaluation, RegressionEvaluation
from deeplearning4j_tpu.observe.tracer import get_tracer
from deeplearning4j_tpu.optimize.listeners import TrainingListener
from deeplearning4j_tpu.optimize.solver import (
    TrainState,
    make_constrain_fn,
    make_scan_train_step,
    make_train_step,
)


def compute_cast(x, dt: str):
    """Cast an activation to the configured compute dtype (bf16 policy)."""
    if dt == "bfloat16" and jnp.issubdtype(x.dtype, jnp.floating):
        return x.astype(jnp.bfloat16)
    return x


def cast_params(lp, dt: str):
    """Cast a layer's float params to the compute dtype (master copies
    stay f32 in the optimizer; this is the per-step working copy)."""
    if dt != "bfloat16":
        return lp
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, lp)



class BaseModel:
    def __init__(self):
        self.train_state: Optional[TrainState] = None
        self.listeners: List[TrainingListener] = []
        self._train_step = None
        self._scan_step = None
        self._tbptt_step = None
        self._rng = None
        self.epoch_count = 0
        self._last_loss = None
        # observability (observe/): in-step telemetry collector, span
        # tracer, recompile watchdog. All optional; the defaults cost one
        # branch per step.
        self._telemetry = None
        self.tracer = None
        self.recompile_watchdog = None
        # flight recorder: None means "use the process-wide default",
        # which is armed unless DL4J_CRASH_DUMPS=0 (the reference's
        # CrashReportingUtil is likewise on by default)
        self._flight_recorder = None
        # host-side mirror of train_state.iteration: reading the device
        # scalar every step (int(ts.iteration)) is itself a per-step
        # device sync; the mirror is re-adopted from the device once per
        # fit() call and advanced locally afterwards
        self._host_iteration: Optional[int] = None
        # the train step whose named scopes were read (observe/scopes.py),
        # their table, and the fit() call that last handed it to a tracer
        self._scoped_step = None
        self._step_scopes = None
        self._step_kernel_calls = None
        self._scopes_traced_in = None
        self._fit_calls = 0
        # in a traced fit() call only (_enter_fit_call): when it was
        # entered, and the dispatched steps' losses with the steps each
        # stands for, oldest first (_in_flight)
        self._fit_entered = None
        self._sent = None

    # ---- to be provided by subclasses -----------------------------------
    def init(self, seed: Optional[int] = None):
        raise NotImplementedError

    def _loss(self, params, model_state, features, labels, fmask, lmask,
              rng, iteration, carries: Optional[dict] = None):
        """(loss, new model state): the model's whole training loss."""
        raise NotImplementedError

    def _constraint_layers(self):
        """The model's layers, in the order of ``layer_names``."""
        raise NotImplementedError

    def output(self, features, train: bool = False):
        raise NotImplementedError

    @property
    def conf_global(self):
        raise NotImplementedError

    # ---- params ---------------------------------------------------------
    @property
    def params(self):
        return self.train_state.params

    @property
    def model_state(self):
        return self.train_state.model_state

    def num_params(self) -> int:
        leaves = jax.tree_util.tree_leaves(self.train_state.params)
        return int(sum(np.prod(l.shape) for l in leaves))

    def set_params(self, params):
        # numpy leaves are copied onto the device, never zero-copy
        # aliased: the donated train step must own every buffer it is
        # handed, and CPU asarray/device_put alias aligned host arrays
        params = jax.tree_util.tree_map(
            lambda a: jnp.array(a, copy=True)
            if isinstance(a, np.ndarray) else a, params)
        self.train_state = self.train_state._replace(params=params)

    def set_listeners(self, *listeners: TrainingListener):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners: TrainingListener):
        self.listeners.extend(listeners)
        return self

    @property
    def iteration_count(self) -> int:
        return int(self.train_state.iteration)

    # ---- observability ---------------------------------------------------
    @property
    def telemetry(self):
        """The attached TelemetryCollector, or None."""
        return self._telemetry

    def set_telemetry(self, collector):
        """Attach an ``observe.TelemetryCollector``: the metric spec is
        compiled into the next train step built, the ring buffer rides in
        the TrainState, and the collector flushes it every N steps in one
        device fetch. Pass None to detach."""
        if collector is not None:
            collector.spec_for(self)
        self._telemetry = collector
        # the spec is baked into the jitted steps — force rebuilds
        self._train_step = None
        self._scan_step = None
        self._tbptt_step = None
        return self

    def set_tracer(self, tracer):
        """Attach an ``observe.SpanTracer`` recording etl / transfer /
        dispatch / flush spans around the fit loop."""
        self.tracer = tracer
        return self

    def set_recompile_watchdog(self, watchdog):
        self.recompile_watchdog = watchdog
        return self

    def set_flight_recorder(self, recorder):
        """Attach an ``observe.FlightRecorder`` (post-mortem dumps on
        NaN/OOM/crash). Without one the process-wide default recorder is
        used; attach a recorder with ``enabled=False`` to opt this model
        out without touching the environment."""
        self._flight_recorder = recorder
        return self

    def _recorder(self):
        if self._flight_recorder is not None:
            return self._flight_recorder
        from deeplearning4j_tpu.observe.flight_recorder import (
            default_flight_recorder)
        return default_flight_recorder()

    def _telemetry_spec(self):
        return (None if self._telemetry is None
                else self._telemetry.spec_for(self))

    def _trace_step_scopes(self, tracer, step, *args):
        """Under an enabled tracer, for models whose layers declare named
        scopes: which scope each operation of the compiled step belongs to
        (observe/scopes.py; computed once per built step), handed to the
        tracer as a zero-length ``step_scopes`` span once per ``fit()``
        call, and with it the gauge of the step's Pallas kernel launches
        under each declared scope."""
        from deeplearning4j_tpu.observe import scopes
        if self._scoped_step is not step:
            self._scoped_step = step
            declared = dict.fromkeys(
                s for layer in self._constraint_layers()
                for s in getattr(layer, "named_scopes", ()))
            self._step_scopes = self._step_kernel_calls = None
            if declared:
                text = step.lower(*args).compile().as_text()
                self._step_scopes = scopes.scopes_in_hlo(text)
                self._step_kernel_calls = scopes.kernel_calls(
                    scopes.kernels_in_hlo(text), declared)
            self._scopes_traced_in = None
        if self._step_scopes and self._scopes_traced_in != self._fit_calls:
            self._scopes_traced_in = self._fit_calls
            now = time.perf_counter()
            tracer.add_span("step_scopes", now, now, cat="step",
                            table=self._step_scopes)
            scopes.publish_kernel_calls(self._step_kernel_calls)

    def _publish_routing_gauges(self):
        """Expert layers leave their step's routing counters in the model
        state (``moe_routing``), and a head with more than one loss term
        its terms (``lm_loss_terms``); publish the last step's as gauges.
        One small fetch, at a telemetry flush and at the end of a ``fit()``
        call, never between steps; it waits for every step in flight
        (span ``blocked``, ``on="routing"``)."""
        state = self.train_state.model_state
        rows = {key: {name: s[key] for name, s in state.items()
                      if isinstance(s, dict) and key in s}
                for key in ("moe_routing", "lm_loss_terms")}
        if any(rows.values()):
            from deeplearning4j_tpu.observe.telemetry import (
                publish_loss_terms, publish_routing)
            with self._blocked("routing"):
                rows = jax.device_get(rows)  # host-sync-ok: once per fit() call / telemetry flush
            publish_routing(rows["moe_routing"])
            publish_loss_terms(rows["lm_loss_terms"])

    def _enter_fit_call(self):
        """Start of a ``fit()`` / ``ParallelWrapper.fit()`` call: the
        device's iteration count is adopted again at the call's first
        ``_post_step`` (external code may have swapped ``train_state``
        since the last call: checkpoint load, transfer learning). Returns
        the model's tracer; under an enabled one the entry is timed (for
        ``blocked``'s ``since_call_ms``) and the dispatched steps are
        kept count of (``_in_flight``), from one call into the next."""
        self._host_iteration = None
        self._fit_calls += 1
        tracer = get_tracer(self)
        if not tracer.enabled:
            self._fit_entered = self._sent = None
        else:
            self._fit_entered = time.perf_counter()
            if self._sent is None:
                self._sent = deque()
        return tracer

    def _in_flight(self, unnoted: int = 0):
        """In a traced ``fit()`` call, how far the loop runs ahead of the
        device: the optimizer steps dispatched whose loss is not ready,
        plus ``unnoted`` dispatched since the last ``_post_step``. One
        deque of (loss, steps), which ``_post_step`` appends to; the ready
        ones are popped from the left with ``is_ready()``, which does not
        block. Scalars only: it keeps no batch and no train state alive.
        None in an untraced call, which has no deque."""
        sent = self._sent
        if sent is None:
            return None
        while sent and sent[0][0].is_ready():
            sent.popleft()
        return unnoted + sum(steps for _, steps in sent)

    @contextmanager
    def _blocked(self, on: str, unnoted: int = 0):
        """Round a read on which the loop's thread waits for the device
        outside a ``dispatch`` span: in a traced ``fit()`` call the span
        ``blocked`` (cat ``step``) with what it waits ``on`` and the steps
        ``in_flight`` as the wait begins. The read waits for all of them,
        so the count starts from nothing afterwards. ``on="iteration"``
        also says how long the call had run by then."""
        if self._sent is None:
            yield
            return
        args = {"on": on, "in_flight": self._in_flight(unnoted)}
        if on == "iteration":
            args["since_call_ms"] = (time.perf_counter()
                                     - self._fit_entered) * 1e3
        try:
            with get_tracer(self).span("blocked", cat="step", **args):
                yield
        finally:
            self._sent.clear()

    def _advance_iteration(self, steps: int = 1) -> int:
        """Host-tracked iteration count after a dispatched step. The
        first read of a ``fit()`` call takes the device's scalar, which
        is **a full wait for the device**: for the step just dispatched
        and every one before it (span ``blocked``, ``on="iteration"``).
        After it the mirror advances on the host, so steady-state
        listener dispatch costs no device→host round trip."""
        if self._host_iteration is None:
            with self._blocked("iteration"):
                self._host_iteration = int(self.train_state.iteration)
        else:
            self._host_iteration += steps
        return self._host_iteration

    def _post_step(self, steps: int = 1, loss=None) -> int:
        """Shared per-dispatch epilogue: note the dispatched ``steps`` by
        their ``loss`` (in a traced call, for ``_in_flight``),
        advance the iteration mirror (the first one of a ``fit()`` call
        waits for the device, see ``_advance_iteration``), give the
        telemetry collector its flush opportunity, and let the flight
        recorder scan whatever that flush decoded (the recorder reads
        host-side history only — no device interaction)."""
        if self._sent is not None and loss is not None:
            self._sent.append((loss, steps))
        it = self._advance_iteration(steps)
        tel = self._telemetry
        if tel is not None:
            flushed = tel.will_flush(steps)
            if flushed:
                with get_tracer(self).span("telemetry_flush",
                                           cat="telemetry"):
                    tel.on_step(self.train_state, steps)
            else:
                tel.on_step(self.train_state, steps)
            if flushed:
                self._publish_routing_gauges()
                rec = self._recorder()
                if rec is not None:
                    rec.poll(self)
        return it

    # ---- the dispatch protocol of every fit path -------------------------
    def _send_step(self, step, name, batch, *, lead=(), after=(), seq=None,
                   k=None, unnoted=0):
        """Dispatch one training step: split the step key off ``_rng``,
        make sure the telemetry ring rides in the train state, show the
        recompile watchdog the ``batch`` arrays under the step's ``name``,
        hand an enabled tracer the step's named scopes, and call
        ``step(train_state, *lead, *batch, key, *after)`` inside the
        ``dispatch`` span (cat ``step``) with the steps ``in_flight``
        (``unnoted`` more since the last ``_record_step``) and, for a fed
        item, its ``seq`` and ``k``. Returns what the step returns.
        ``fit()``'s bodies and ``ParallelWrapper``'s all dispatch here."""
        self._rng, key = jax.random.split(self._rng)
        if self._telemetry is not None:
            self.train_state = self._telemetry.ensure_buffer(self.train_state)
        if self.recompile_watchdog is not None:
            self.recompile_watchdog.observe(name, *batch)
        args = (self.train_state, *lead, *batch, key, *after)
        tracer = get_tracer(self)
        if tracer.enabled:
            self._trace_step_scopes(tracer, step, *args)
        span = {"in_flight": self._in_flight(unnoted)}
        if seq is not None:
            span["seq"] = seq
        if k is not None:
            span["k"] = k
        with tracer.span("dispatch", cat="step", **span):
            return step(*args)

    def _record_step(self, steps: int, loss, wait_ms: float,
                     n_examples: int):
        """After ``_send_step``: ``_post_step`` for the ``steps`` the
        dispatch ran, ``iteration_done`` on every listener with the wait
        for the batch and its real example count, and the last loss. A
        scanned step returns its K inner losses; listeners see the last."""
        it = self._post_step(steps, loss)
        if np.ndim(loss):
            loss = loss[-1]
        for lst in self.listeners:
            lst.iteration_done(self, it, self.epoch_count, loss, wait_ms,
                               n_examples)
        self._last_loss = loss

    def _epochs(self, epochs: int, source):
        """The epoch loop of every fit: yields once a pass, between the
        listeners' epoch start and end; after each pass the source is
        reset (a ``DataSetIterator`` reshuffles) and the epoch counted."""
        for _ in range(epochs):
            for lst in self.listeners:
                lst.on_epoch_start(self, self.epoch_count)
            yield
            if isinstance(source, DataSetIterator):
                source.reset()
            for lst in self.listeners:
                lst.on_epoch_end(self, self.epoch_count)
            self.epoch_count += 1

    def _end_fit_call(self):
        """End of a ``fit()`` / ``ParallelWrapper.fit()`` call: publish
        the routing gauges, flush the telemetry rows still on the device
        (fewer than a flush interval) and give the recorder a last look."""
        self._publish_routing_gauges()
        if self._telemetry is not None:
            with get_tracer(self).span("telemetry_flush", cat="telemetry"):
                self._telemetry.flush(self.train_state)
            rec = self._recorder()
            if rec is not None:
                rec.poll(self)

    # ---- train steps ----------------------------------------------------
    def _loss_fn(self):
        def loss_fn(params, model_state, features, labels, fmask, lmask, rng,
                    iteration):
            return self._loss(params, model_state, features, labels, fmask,
                              lmask, rng, iteration)
        return loss_fn

    def _build_train_step(self):
        return make_train_step(
            self._loss_fn(), self._tx,
            constrain_fn=make_constrain_fn(
                [l for l in self._constraint_layers()]),
            telemetry=self._telemetry_spec())

    def _build_scan_train_step(self):
        """K fused optimizer steps per dispatch (fit(k_steps=K)); same
        loss/constraint/telemetry spec as the per-batch step, scanned
        over a leading K dim. No bf16 shadow here: the regularization
        term reads master params, and the fed path promises a bitwise
        match with the per-batch trajectory."""
        return make_scan_train_step(
            self._loss_fn(), self._tx,
            constrain_fn=make_constrain_fn(
                [l for l in self._constraint_layers()]),
            telemetry=self._telemetry_spec())

    # ---- truncated BPTT (reference: doTruncatedBPTT, SURVEY §5.7) --------
    def _recurrent_carry_nodes(self):
        """(layer name, stateful core layer, is_lstm) for every layer whose
        hidden state crosses TBPTT chunks / rnn_time_step calls —
        including cores wrapped in LastTimeStep / MaskZeroLayer (the
        wrappers delegate state + initial_state)."""
        from deeplearning4j_tpu.nn.layers.recurrent import (
            LSTM, SimpleRnn, unwrap_recurrent)
        out = []
        for name, layer in zip(self.layer_names, self._constraint_layers()):
            core = unwrap_recurrent(layer)
            if isinstance(core, (LSTM, SimpleRnn)):
                out.append((name, core, isinstance(core, LSTM)))
        return out

    def _zero_carries(self, batch_size: int):
        dt = (jnp.bfloat16 if self.conf_global.compute_dtype == "bfloat16"
              else jnp.float32)
        out = {}
        for name, core, is_lstm in self._recurrent_carry_nodes():
            h = jnp.zeros((batch_size, core.n_out), dt)
            out[name] = (h, h) if is_lstm else h
        return out

    def _tbptt_ready(self):
        """Before a TBPTT batch: warn about a bidirectional layer (its
        backward half sees one chunk at a time), build the chunk step."""
        from deeplearning4j_tpu.nn.layers.recurrent import (
            first_bidirectional_name, warn_tbptt_bidirectional)
        bidi = first_bidirectional_name(
            zip(self.layer_names, self._constraint_layers()))
        if bidi is not None:
            warn_tbptt_bidirectional(bidi)
        if self._tbptt_step is None:
            self._tbptt_step = self._build_tbptt_step()

    def _build_tbptt_step(self):
        import optax
        constrain_fn = make_constrain_fn(list(self._constraint_layers()))
        carry_nodes = self._recurrent_carry_nodes()
        telemetry = self._telemetry_spec()

        def step(ts, features, labels, fmask, lmask, rng, carries):
            def lf(params):
                return self._loss(params, ts.model_state, features, labels,
                                  fmask, lmask, rng, ts.iteration,
                                  carries=carries)
            (loss, new_ms), grads = jax.value_and_grad(
                lf, has_aux=True)(ts.params)
            updates, new_opt = self._tx.update(grads, ts.opt_state, ts.params)
            new_params = optax.apply_updates(ts.params, updates)
            if constrain_fn is not None:
                new_params = constrain_fn(new_params)
            buf = ts.telemetry
            if telemetry is not None:
                buf = telemetry.record(buf, loss=loss, grads=grads,
                                       params=new_params,
                                       prev_params=ts.params,
                                       iteration=ts.iteration)
            # carries cross the chunk boundary with gradients cut — this IS
            # the truncation (reference: tbpttBackLength; here back==fwd)
            new_carries = {}
            for name, _core, is_lstm in carry_nodes:
                s = new_ms[name]
                c = ((s["last_h"], s["last_c"]) if is_lstm else s["last_h"])
                new_carries[name] = jax.lax.stop_gradient(c)
            return (TrainState(new_params, new_ms, new_opt,
                               ts.iteration + 1, buf), loss, new_carries)

        return jax.jit(step, donate_argnums=(0,))

    # ---- fit loop -------------------------------------------------------
    def fit(self, data, epochs: int = 1, k_steps: Optional[int] = None,
            prefetch: Optional[int] = None,
            byte_budget: Optional[int] = None):
        """fit(DataSet) / fit(DataSetIterator[, epochs]) — the reference's
        MultiLayerNetwork.fit(DataSetIterator) hot loop.

        Iterator fits run through the DeviceFeeder input pipeline
        (datasets/feeder.py): the next ``prefetch`` batches (default 2)
        are staged onto the device while the current step computes, and
        plain iterators are auto-wrapped in an AsyncDataSetIterator so
        host-side batch production overlaps too (the reference wraps at
        MultiLayerNetwork.java:1273). Wrap the iterator in
        AsyncShieldDataSetIterator (``async_supported = False``) or pass
        ``prefetch=0`` to opt out and get the strictly synchronous loop.

        ``k_steps > 1`` additionally fuses K prefetched batches into ONE
        device dispatch via the scanned train step — per-dispatch
        overhead is paid once per K optimizer steps. Ragged batches are
        padded to the bucket size with a zero labels mask (bitwise-
        neutral for the masked loss), so the whole epoch — partial final
        batch included — runs on one compiled signature. Iteration
        counts advance by K and telemetry still records one row per
        inner step; listeners fire once per dispatch with the last inner
        loss.

        Any exception escaping the loop (including XLA OOM) first passes
        through the flight recorder, which writes a post-mortem dump and
        re-raises — the CrashReportingUtil contract: the crash still
        surfaces, but the evidence survives."""
        try:
            return self._fit_inner(data, epochs, k_steps=k_steps,
                                   prefetch=prefetch,
                                   byte_budget=byte_budget)
        except Exception as e:
            rec = self._recorder()
            if rec is not None:
                rec.record_crash(self, exc=e)
            raise

    def _feed_supported(self) -> bool:
        """TBPTT slices batches along time on the host, so those configs
        take the unfed path; everything else can be staged ahead."""
        return getattr(getattr(self, "conf", None), "backprop_type",
                       None) != "tbptt"

    def _staged_step_args(self, features, labels, fmask, lmask):
        """Adapt device-staged arrays to this model's step signature
        (ComputationGraph wraps singles into input/output tuples)."""
        return features, labels, fmask, lmask

    def _fit_inner(self, data, epochs: int = 1,
                   k_steps: Optional[int] = None,
                   prefetch: Optional[int] = None,
                   byte_budget: Optional[int] = None):
        tracer = self._enter_fit_call()
        if self.train_state is None:
            self.init()
        else:
            # scope-panic analog (utils/sanitizers.py): a donated/stale
            # TrainState must fail HERE with a clear message, not at the
            # next dispatch deep inside jit
            from deeplearning4j_tpu.utils.sanitizers import (
                check_not_donated)
            check_not_donated(self.train_state.params,
                              what="fit() train state")
        if self._train_step is None:
            self._train_step = self._build_train_step()
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        if isinstance(data, MultiDataSet):
            from deeplearning4j_tpu.models.computation_graph import (
                ComputationGraph)
            if not isinstance(self, ComputationGraph):
                raise TypeError(
                    "MultiDataSet requires a ComputationGraph; wrap "
                    "single-input data in a DataSet for "
                    "MultiLayerNetwork")
        if isinstance(data, (DataSet, MultiDataSet)):
            # single-batch fit: _post_step already flushed if an interval
            # completed; flushing unconditionally here would turn the
            # common fit-per-batch driver loop into one fetch per step
            self._fit_batch(data)
            return self
        iterator = data
        # k_steps/prefetch left at None pick up the machine-measured
        # TunedConfig when one is installed (serve/train started with
        # --tuned-config), else the committed defaults — explicit
        # arguments always win
        from deeplearning4j_tpu.optimize.autotune import tuned_value
        k_tuned = False
        if k_steps is None:
            k_steps = tuned_value("fit.k_steps")
            k_tuned = k_steps is not None
        k = 1 if k_steps is None else int(k_steps)
        if k < 1:
            raise ValueError("k_steps must be >= 1")
        from deeplearning4j_tpu.datasets.feeder import (
            DEFAULT_DEPTH, DeviceFeeder)
        from deeplearning4j_tpu.datasets.iterators import (
            AsyncDataSetIterator)
        if prefetch is None:
            prefetch = tuned_value("feeder.depth")
        depth = DEFAULT_DEPTH if prefetch is None else int(prefetch)
        feed = (depth > 0 and self._feed_supported()
                and getattr(iterator, "async_supported", True))
        if k > 1 and not feed:
            if k_tuned:
                # a machine-tuned k must never break a fit the feeder
                # can't serve (shielded iterator, TBPTT, prefetch=0) —
                # implicit tuning degrades, only explicit asks raise
                k = 1
            else:
                raise ValueError(
                    "k_steps > 1 needs the device feeder: prefetch must "
                    "be >= 1, the iterator async-capable (no "
                    "AsyncShield), and the model not configured for "
                    "TBPTT")
        source = iterator
        if (feed and isinstance(iterator, DataSetIterator)
                and not isinstance(iterator, AsyncDataSetIterator)):
            # the reference's contract: fit() itself provides the
            # prefetch thread unless the iterator opted out (shield) or
            # already is one
            source = AsyncDataSetIterator(iterator)
        if isinstance(source, AsyncDataSetIterator):
            source.tracer = tracer      # its worker's ``produce`` spans
        feeder = (DeviceFeeder(source, depth=depth, byte_budget=byte_budget,
                               k_steps=k, tracer=tracer)
                  if feed else None)
        try:
            for _ in self._epochs(epochs, source):
                if feeder is not None:
                    for item in feeder:
                        if item.k == 0:
                            # a foreign object (e.g. MultiDataSet) the
                            # feeder passed through: the unfed path
                            self._fit_batch(item.raw,
                                            etl_ms=item.queue_wait_ms)
                        else:
                            self._fit_item(item)
                else:
                    it_start = time.perf_counter()
                    for batch in iterator:
                        now = time.perf_counter()
                        etl_ms = (now - it_start) * 1000.0
                        tracer.add_span("etl", it_start, now, cat="data")
                        self._fit_batch(batch, etl_ms=etl_ms)
                        it_start = time.perf_counter()
        finally:
            if feeder is not None:
                # a traced fit's last ``resident`` spans; no-op untraced
                feeder.close()
        self._end_fit_call()
        return self

    def _host_step_args(self, batch):
        """A host batch moved to the device in this model's step
        signature."""
        return self._staged_step_args(
            jnp.asarray(batch.features), jnp.asarray(batch.labels),
            None if batch.features_mask is None
            else jnp.asarray(batch.features_mask),
            None if batch.labels_mask is None
            else jnp.asarray(batch.labels_mask))

    def _fit_batch(self, batch: DataSet, etl_ms: float = 0.0):
        with get_tracer(self).span("host_to_device", cat="data"):
            args = self._host_step_args(batch)
        self.train_state, loss = self._send_step(self._train_step,
                                                 "train_step", args)
        self._record_step(1, loss, etl_ms, batch.num_examples())

    def _fit_item(self, item):
        """A batch the DeviceFeeder staged (``k == 1``) is one step, the
        step ``_fit_batch`` takes (the K=1 fed trajectory is bitwise the
        unfed one); K stacked batches are ONE scanned dispatch of K
        optimizer steps, whose telemetry records a row per inner step.
        Listeners fire once with the item's real example count."""
        args = self._staged_step_args(item.features, item.labels,
                                      item.features_mask, item.labels_mask)
        if item.k == 1:
            self.train_state, loss = self._send_step(
                self._train_step, "train_step", args, seq=item.seq)
        else:
            if self._scan_step is None:
                self._scan_step = self._build_scan_train_step()
            self.train_state, loss = self._send_step(
                self._scan_step, "scan_train_step", args, seq=item.seq,
                k=item.k)
        self._record_step(item.k, loss, item.queue_wait_ms, item.n_examples)

    def score(self, dataset: Optional[DataSet] = None) -> float:
        """Loss on a dataset (reference: MultiLayerNetwork.score(DataSet)),
        or the last training loss when called without arguments."""
        if dataset is None:
            if self._last_loss is None:
                raise RuntimeError("no score yet: call fit() first or pass a"
                                   " DataSet to score(dataset)")
            return float(self._last_loss)  # host-sync-ok: score() API returns a Python float
        return float(self.compute_loss(dataset))  # host-sync-ok: eval-path loss read, not the train loop

    def compute_loss(self, dataset: DataSet):
        raise NotImplementedError

    def _output_for_eval(self, batch: DataSet):
        """Inference with the batch's features mask threaded through (both
        model classes accept mask=; CG uses it as the default input mask)."""
        return self.output(batch.features, mask=batch.features_mask)

    # ---- evaluation -----------------------------------------------------
    def evaluate(self, iterator, evaluation: Optional[Evaluation] = None
                 ) -> Evaluation:
        e = evaluation or Evaluation()
        single = isinstance(iterator, DataSet)
        batches = [iterator] if single else iterator
        for batch in batches:
            preds = self._output_for_eval(batch)
            e.eval(batch.labels, np.asarray(preds),  # host-sync-ok: evaluation consumes host arrays
                   mask=batch.labels_mask if batch.labels_mask is not None
                   else batch.features_mask)
        if not single and isinstance(iterator, DataSetIterator):
            iterator.reset()
        return e

    def evaluate_regression(self, iterator) -> RegressionEvaluation:
        e = RegressionEvaluation()
        single = isinstance(iterator, DataSet)
        batches = [iterator] if single else iterator
        for batch in batches:
            preds = self._output_for_eval(batch)
            e.eval(batch.labels, np.asarray(preds), mask=batch.labels_mask)  # host-sync-ok: evaluation consumes host arrays
        if not single and isinstance(iterator, DataSetIterator):
            iterator.reset()
        return e
