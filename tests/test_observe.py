"""observe/ subsystem tests: ring-buffer telemetry, one-fetch flush,
scan/unscan equivalence, tracer export, recompile watchdog, Prometheus
endpoint, host-sync lint."""

import json
import re
import subprocess
import sys
import urllib.request
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.observe import (
    MetricsRegistry,
    RecompileWatchdog,
    SpanTracer,
    TelemetryCollector,
    TelemetrySpec,
)
from deeplearning4j_tpu.observe.telemetry import has_buffer
from deeplearning4j_tpu.optimize.solver import (
    TrainState,
    make_scan_train_step,
    make_train_step,
)

REPO = Path(__file__).resolve().parent.parent


def _tiny_model(seed=1):
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer
    from deeplearning4j_tpu.nn.layers.output import OutputLayer
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.ops.losses import LossFunction
    from deeplearning4j_tpu.optimize.updaters import Adam
    conf = (NeuralNetConfiguration.Builder().seed(seed).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=8))
            .layer(OutputLayer(n_out=3, loss=LossFunction.MCXENT))
            .set_input_type(InputType.feed_forward(5)).build())
    return MultiLayerNetwork(conf).init()


def _batches(n, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.normal(size=(batch, 5)).astype(np.float32)
        y = np.zeros((batch, 3), np.float32)
        y[np.arange(batch), rng.integers(0, 3, batch)] = 1.0
        out.append(DataSet(x, y))
    return out


class _ListIter:
    def __init__(self, batches):
        self.batches = batches

    def __iter__(self):
        return iter(self.batches)

    def reset(self):
        pass


class TestTelemetrySpec:
    def test_metric_catalog(self):
        spec = TelemetrySpec(("a", "b"), capacity=8)
        assert spec.metric_names == ("loss", "grad_norm",
                                     "nonfinite_count",
                                     "update_ratio/a", "update_ratio/b")
        buf = spec.init()
        assert buf.rows.shape == (8, 5)
        assert int(buf.count) == 0

    def test_ring_wraparound_drops_oldest(self):
        # 10 rows through a 4-slot ring: flush sees the newest 4, reports
        # the 6 overwritten ones as dropped
        tel = TelemetryCollector(flush_interval=4, capacity=4,
                                 per_layer=False,
                                 registry=MetricsRegistry())
        spec = tel.spec_for(SimpleNamespace(layer_names=()))
        buf = spec.init()
        g = {"w": jnp.ones((2,), jnp.float32)}
        for i in range(10):
            buf = spec.record(buf, loss=jnp.float32(i), grads=g,
                              params=g, prev_params=g,
                              iteration=jnp.int32(i))
        ts = TrainState({}, {}, {}, jnp.int32(10), buf)
        records = tel.flush(ts)
        assert [r["loss"] for r in records] == [6.0, 7.0, 8.0, 9.0]
        assert [r["iteration"] for r in records] == [7, 8, 9, 10]
        assert tel.dropped_rows == 6
        assert tel.registry.counter(
            "dl4j_telemetry_dropped_rows_total").get(
            session="train") == 6.0

    def test_nonfinite_counted(self):
        spec = TelemetrySpec((), capacity=2)
        buf = spec.init()
        g = {"w": jnp.array([1.0, jnp.nan, jnp.inf], jnp.float32)}
        buf = spec.record(buf, loss=jnp.float32(0.5), grads=g,
                          params=g, prev_params=g,
                          iteration=jnp.int32(0))
        row = np.asarray(buf.rows[0])
        assert row[2] == 2.0          # nan + inf in grads, finite loss


class TestOneFetchFlush:
    def test_single_device_fetch_per_interval(self, monkeypatch):
        """The acceptance property: N=4 steps per flush -> the whole fit
        performs exactly ceil(12/4)+1 tail = 4 host transfers, counted at
        jax.device_get itself."""
        fetches = []
        real = jax.device_get

        def counting(x):
            fetches.append(type(x).__name__)
            return real(x)

        m = _tiny_model()
        tel = TelemetryCollector(flush_interval=4,
                                 registry=MetricsRegistry())
        m.set_telemetry(tel)
        monkeypatch.setattr(jax, "device_get", counting)
        m.fit(_ListIter(_batches(12)), epochs=1)
        monkeypatch.setattr(jax, "device_get", real)
        assert tel.fetch_count == 4       # steps 4, 8, 12 + tail flush
        assert len(fetches) == 4
        assert len(tel.history) == 12
        # rows decode in iteration order with no gaps
        assert [r["iteration"] for r in tel.history] == list(range(1, 13))

    def test_listener_values_come_from_flush(self):
        from deeplearning4j_tpu.optimize.listeners import (
            ScoreIterationListener)
        m = _tiny_model()
        tel = TelemetryCollector(flush_interval=4,
                                 registry=MetricsRegistry())
        m.set_telemetry(tel)
        lst = ScoreIterationListener(frequency=1)
        m.set_listeners(lst)
        m.fit(_ListIter(_batches(6)), epochs=1)
        # iterations 1-3 ran before the first flush: no score, no sync;
        # from 4 on the flushed value is visible
        assert len(lst.scores) == 3
        assert all(np.isfinite(s) for s in lst.scores)
        assert lst.scores[-1] == tel.history[3]["loss"]

    def test_buffer_attaches_once(self):
        m = _tiny_model()
        tel = TelemetryCollector(flush_interval=4,
                                 registry=MetricsRegistry())
        m.set_telemetry(tel)
        m.fit(_batches(1)[0])
        assert has_buffer(m.train_state.telemetry)
        ts = m.train_state
        assert tel.ensure_buffer(ts) is ts

    def test_capacity_below_interval_rejected(self):
        with pytest.raises(ValueError):
            TelemetryCollector(flush_interval=64, capacity=8)

    def test_collector_rejects_different_layers(self):
        tel = TelemetryCollector(registry=MetricsRegistry())
        tel.spec_for(SimpleNamespace(layer_names=("a",)))
        with pytest.raises(ValueError):
            tel.spec_for(SimpleNamespace(layer_names=("b",)))


class TestScanEquivalence:
    def test_scanned_and_unscanned_buffers_match(self):
        """make_scan_train_step must record the identical telemetry rows
        as k dispatches of make_train_step."""
        k = 6
        params = {"lin": {"w": jnp.arange(3, dtype=jnp.float32) / 3.0}}
        tx = optax.sgd(0.1)

        def loss_fn(p, ms, x, y, fm, lm, rng, it):
            pred = jnp.sum(p["lin"]["w"] * x, axis=-1)
            return jnp.mean((pred - y) ** 2), ms

        spec = TelemetrySpec(("lin",), capacity=16)
        rng = np.random.default_rng(3)
        xs = jnp.asarray(rng.normal(size=(k, 4, 3)).astype(np.float32))
        ys = jnp.asarray(rng.normal(size=(k, 4)).astype(np.float32))

        def init_state():
            return TrainState(params, {}, tx.init(params),
                              jnp.zeros((), jnp.int32), spec.init())

        step = make_train_step(loss_fn, tx, donate=False, telemetry=spec)
        ts_a = init_state()
        key = jax.random.PRNGKey(0)
        for i in range(k):
            ts_a, _ = step(ts_a, xs[i], ys[i], None, None, key)

        steps = make_scan_train_step(loss_fn, tx, donate=False,
                                     telemetry=spec)
        ts_b, _ = steps(init_state(), xs, ys, None, None, key)

        np.testing.assert_allclose(np.asarray(ts_a.telemetry.rows),
                                   np.asarray(ts_b.telemetry.rows),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(ts_a.telemetry.iters),
                                      np.asarray(ts_b.telemetry.iters))
        assert int(ts_a.telemetry.count) == int(ts_b.telemetry.count) == k

    def test_trainstate_default_slot_backcompat(self):
        # 4-positional construction (all pre-observe call sites) still
        # works and carries the empty sentinel
        ts = TrainState({}, {}, {}, jnp.int32(0))
        assert ts.telemetry == ()
        assert not has_buffer(ts.telemetry)


class TestTracer:
    def test_chrome_trace_export(self, tmp_path):
        import time
        tr = SpanTracer()
        with tr.span("dispatch", cat="step", k=3):
            pass
        start = time.perf_counter()
        tr.add_span("etl", start, time.perf_counter(), cat="data")
        tr.instant("recompile")
        doc = tr.to_chrome_trace()
        assert doc["displayTimeUnit"] == "ms"
        names = [e["name"] for e in doc["traceEvents"]]
        assert names == ["dispatch", "etl", "recompile"]
        for ev in doc["traceEvents"]:
            assert ev["ph"] in ("X", "i")
            assert ev["ts"] >= 0
        path = tr.save(str(tmp_path / "trace.json"))
        loaded = json.loads(Path(path).read_text())
        assert len(loaded["traceEvents"]) == 3

    def test_export_names_its_zero(self):
        """``ts`` counts from ``t0`` on ``perf_counter``; the export says
        so, which is what lays a saved trace beside a profile."""
        import time
        before = time.perf_counter()
        tr = SpanTracer()
        assert before <= tr.t0 <= time.perf_counter()
        start = time.perf_counter()
        tr.add_span("etl", start, start + 0.5, cat="data")
        doc = tr.to_chrome_trace()
        assert doc["otherData"]["t0_perf_counter_s"] == tr.t0
        (ev,) = doc["traceEvents"]
        assert tr.t0 + ev["ts"] / 1e6 == pytest.approx(start, abs=1e-6)

    def test_profiler_annotations_are_gone(self):
        with pytest.raises(TypeError):
            SpanTracer(**{"use_jax" + "_profiler": True})

    def test_disabled_tracer_records_nothing(self):
        tr = SpanTracer(enabled=False)
        with tr.span("x"):
            pass
        assert tr.events == []

    def test_fit_emits_phase_spans(self):
        m = _tiny_model()
        tr = SpanTracer()
        m.set_tracer(tr)
        m.fit(_ListIter(_batches(2)), epochs=1)
        cats = {e["name"] for e in tr.events}
        assert {"etl", "host_to_device", "dispatch"} <= cats


def _loop_spans(tr):
    """(start, end, args) of the fit loop's ``blocked`` and ``dispatch``
    spans (cat ``step``), each list in the order recorded."""
    out = {"blocked": [], "dispatch": []}
    for e in tr.events:
        if e["name"] in out and e["cat"] == "step":
            out[e["name"]].append((e["tid"], e["ts"], e["ts"] + e["dur"],
                                   e.get("args", {})))
    return out


def _assert_disjoint(spans):
    """No ``blocked`` span overlaps a ``dispatch`` span of its thread."""
    for tid, b0, b1, _ in spans["blocked"]:
        for dtid, d0, d1, _ in spans["dispatch"]:
            assert dtid != tid or b1 <= d0 or d1 <= b0


class _Losses:
    """Listener that keeps every step's loss, on the device."""

    def __init__(self):
        self.losses = []

    def __getattr__(self, name):        # every other hook: nothing
        return lambda *a, **k: None

    def iteration_done(self, model, iteration, epoch, loss, etl_ms, n):
        self.losses.append(loss)


class TestLoopWaits:
    """Where the fit loop waits for the device (``blocked``) and how far
    ahead of it it runs (``in_flight``), recorded under a tracer only."""

    @pytest.mark.parametrize("fit_args", [
        {}, {"prefetch": 0}, {"k_steps": 2}], ids=["fed", "unfed", "k2"])
    def test_one_iteration_wait_a_call_and_in_flight_on_every_dispatch(
            self, fit_args):
        m = _tiny_model()
        tr = SpanTracer()
        m.set_tracer(tr)
        for _ in range(2):
            m.fit(_ListIter(_batches(4)), epochs=2, **fit_args)
        spans = _loop_spans(tr)
        waits = [a for *_, a in spans["blocked"]]
        assert [a["on"] for a in waits] == ["iteration"] * 2
        assert all(a["since_call_ms"] >= 0 for a in waits)
        k = fit_args.get("k_steps", 1)
        assert len(spans["dispatch"]) == 2 * 2 * 4 // k
        for *_, a in spans["dispatch"]:
            assert type(a["in_flight"]) is int and a["in_flight"] >= 0
        assert all(a["in_flight"] % k == 0
                   for *_, a in spans["dispatch"] + spans["blocked"])
        _assert_disjoint(spans)

    def test_in_flight_counts_the_steps_whose_loss_is_not_ready(self):
        class Loss:
            def __init__(self, ready):
                self.ready = ready

            def is_ready(self):
                return self.ready

        m = _tiny_model()
        tr = SpanTracer()
        m.set_tracer(tr)
        m._enter_fit_call()
        m._host_iteration = 0           # the call's first wait is behind it
        first, group, last = Loss(False), Loss(False), Loss(False)
        assert m._in_flight() == 0
        m._post_step(1, first)
        m._post_step(4, group)          # a k_steps group counts k
        assert m._in_flight() == 5 and m._in_flight(2) == 7
        group.ready = True              # steps complete in order: the
        assert m._in_flight() == 5      # oldest is still out
        first.ready = True
        assert m._in_flight() == 0 and not m._sent
        m._post_step(1, last)
        with m._blocked("collective", 3):
            pass
        (ev,) = [e for e in tr.events if e["name"] == "blocked"]
        assert ev["args"] == {"on": "collective", "in_flight": 4}
        assert not m._sent              # the read waited for all of them

    def test_single_batch_fit_records_them_too(self):
        m = _tiny_model()
        tr = SpanTracer()
        m.set_tracer(tr)
        m.fit(_batches(1)[0])
        spans = _loop_spans(tr)
        assert [a["on"] for *_, a in spans["blocked"]] == ["iteration"]
        assert [a["in_flight"] for *_, a in spans["dispatch"]] == [0]

    def test_an_expert_models_call_ends_in_one_routing_wait(self):
        from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator
        from deeplearning4j_tpu.nn.layers.decoder import next_token_labels
        from deeplearning4j_tpu.zoo.models import Qwen3Next
        m = Qwen3Next(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            full_attention_interval=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=8, num_experts=16,
            held_experts=tuple(range(8)), num_experts_per_tok=2,
            moe_intermediate_size=16, shared_expert_intermediate_size=16,
            seq_len=48, chunk_size=16, compute_dtype="float32").init()
        ids = np.random.default_rng(0).integers(0, 64, (4, 48))
        it = ArrayDataSetIterator(DataSet(ids, next_token_labels(ids)), 2)
        tr = SpanTracer()
        m.set_tracer(tr)
        m.fit(it, epochs=2)
        spans = _loop_spans(tr)
        assert [a["on"] for *_, a in spans["blocked"]] == [
            "iteration", "routing"]
        assert "since_call_ms" not in spans["blocked"][1][3]
        assert spans["blocked"][1][1] >= max(
            end for _, _, end, _ in spans["dispatch"])
        _assert_disjoint(spans)

    def test_traced_and_untraced_losses_are_equal_bit_for_bit(self):
        got = []
        for tracer in (SpanTracer(), None):
            m = _tiny_model()
            tap = _Losses()
            m.set_listeners(tap)
            m.set_tracer(tracer)
            m.fit(_ListIter(_batches(4)), epochs=2)
            got.append(np.asarray(jax.device_get(tap.losses)))
        assert got[0].shape == (8,) and np.array_equal(got[0], got[1])

    def test_untraced_fit_keeps_no_deque_and_polls_nothing(self, monkeypatch):
        polled = []
        array_type = type(jnp.zeros(()))
        real = array_type.is_ready
        monkeypatch.setattr(array_type, "is_ready", lambda self: (
            polled.append(1), real(self))[1])
        m = _tiny_model()
        assert jnp.zeros(()).is_ready() in (True, False) and polled
        del polled[:]
        m.fit(_ListIter(_batches(3)), epochs=2)
        m.fit(_batches(1)[0])
        assert m._sent is None and m._fit_entered is None
        assert not polled
        # and a tracer taken off again leaves nothing behind
        m.set_tracer(SpanTracer())
        m.fit(_ListIter(_batches(3)), epochs=1)
        assert m._sent is not None and polled
        m.set_tracer(None)
        del polled[:]
        m.fit(_ListIter(_batches(3)), epochs=1)
        assert m._sent is None and not polled


class TestRecompileWatchdog:
    def test_new_signature_detected(self):
        reg = MetricsRegistry()
        wd = RecompileWatchdog(registry=reg)
        a = jnp.zeros((4, 5))
        assert wd.observe("train_step", a, None)        # first compile
        assert not wd.observe("train_step", a, None)    # same signature
        assert wd.count("train_step") == 0
        # batch-size drift = new signature = recompile
        assert wd.observe("train_step", jnp.zeros((7, 5)), None)
        # dtype drift too
        assert wd.observe("train_step", a.astype(jnp.bfloat16), None)
        # optional mask appearing flips the compiled branch
        assert wd.observe("train_step", a, jnp.ones((4,)))
        assert wd.count("train_step") == 3
        assert reg.counter("dl4j_recompiles_total").get(
            session="train") == 3.0

    def test_per_step_key_isolation(self):
        wd = RecompileWatchdog(registry=MetricsRegistry())
        wd.observe("train_step", jnp.zeros((2, 2)))
        wd.observe("tbptt_step", jnp.zeros((2, 2)))
        assert wd.count() == 0          # each key's first compile is free


_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="
    r"\"[^\"]*\"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})? "
    r"(-?[0-9.e+-]+|NaN|[+-]Inf)$")


class TestMetricsEndpoint:
    def test_registry_render_format(self):
        reg = MetricsRegistry()
        reg.gauge("g", "a gauge").set(1.5, session="s")
        reg.counter("c", "a counter").inc(2.0)
        txt = reg.render()
        assert "# TYPE g gauge" in txt
        assert "# TYPE c counter" in txt
        assert 'g{session="s"} 1.5' in txt
        for line in txt.splitlines():
            if line and not line.startswith("#"):
                assert _PROM_LINE.match(line), line

    def test_registry_kind_conflict(self):
        reg = MetricsRegistry()
        reg.gauge("m")
        with pytest.raises(TypeError):
            reg.counter("m")

    def test_metrics_and_healthz_endpoints(self):
        """curl localhost:<port>/metrics returns valid Prometheus text
        with the loss / grad-norm / steps-per-sec / recompile series."""
        from deeplearning4j_tpu.observe import default_registry
        from deeplearning4j_tpu.ui import InMemoryStatsStorage, UIServer

        m = _tiny_model()
        tel = TelemetryCollector(flush_interval=2,
                                 registry=default_registry())
        m.set_telemetry(tel)
        m.set_recompile_watchdog(RecompileWatchdog())
        m.fit(_ListIter(_batches(4)), epochs=1)

        srv = UIServer(port=0).attach(InMemoryStatsStorage()).start()
        try:
            with urllib.request.urlopen(f"{srv.url}/metrics") as r:
                ctype = r.headers["Content-Type"]
                body = r.read().decode()
            assert ctype.startswith("text/plain")
            assert "version=0.0.4" in ctype
            for series in ("dl4j_loss{", "dl4j_grad_norm{",
                           "dl4j_steps_per_second{",
                           "dl4j_recompiles_total{",
                           "dl4j_telemetry_flushes_total{"):
                assert series in body, f"missing {series} in /metrics"
            for line in body.splitlines():
                if line and not line.startswith("#"):
                    assert _PROM_LINE.match(line), line
            with urllib.request.urlopen(f"{srv.url}/healthz") as r:
                health = json.loads(r.read())
            assert health["status"] == "ok"
        finally:
            srv.stop()


class TestHostSyncChecker:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, str(REPO / "tools" / "check_host_sync.py"),
             *args], capture_output=True, text=True)

    def test_hot_paths_clean(self):
        r = self._run()
        assert r.returncode == 0, r.stdout + r.stderr

    def test_flags_unallowed_sync(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("x = float(loss)\ny = arr.item()\n"
                       "z = np.asarray(dev)\nok = jnp.asarray(dev)\n")
        r = self._run("--paths", str(bad))
        assert r.returncode == 1
        assert "bad.py:1" in r.stderr
        assert "bad.py:2" in r.stderr
        assert "bad.py:3" in r.stderr
        assert "bad.py:4" not in r.stderr   # jnp.asarray is device-side

    def test_pragma_allowlists(self, tmp_path):
        ok = tmp_path / "ok.py"
        ok.write_text(
            "x = float(dh) ** 0.5  # host-sync-ok: static shape\n")
        r = self._run("--paths", str(ok))
        assert r.returncode == 0, r.stderr
