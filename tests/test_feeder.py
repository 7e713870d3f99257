"""Input-pipeline tests: DeviceFeeder prefetch, K-step fused dispatch,
ragged-batch normalization, AsyncDataSetIterator lifecycle, and the
fit() integration contract (bitwise trajectories, zero recompiles, no
new per-step device fetches)."""

import threading
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import (
    ArrayDataSetIterator,
    DataSet,
    DataSetIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.datasets.feeder import (
    DeviceFeeder,
    ensure_labels_mask,
    ones_labels_mask,
    pad_to_bucket,
)
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator,
    AsyncShieldDataSetIterator,
)
from deeplearning4j_tpu.observe import (
    MetricsRegistry,
    RecompileWatchdog,
    SpanTracer,
    TelemetryCollector,
)


# ---- shared fixtures ----------------------------------------------------

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


def _batches(n, batch=16, seed=0, tail=None):
    """n full batches, optionally followed by one ragged tail batch."""
    rng = np.random.default_rng(seed)
    sizes = [batch] * n + ([tail] if tail else [])
    out = []
    for b in sizes:
        x = rng.normal(size=(b, 5)).astype(np.float32)
        y = np.zeros((b, 3), np.float32)
        y[np.arange(b), rng.integers(0, 3, b)] = 1.0
        out.append(DataSet(x, y))
    return out


def _params(m):
    return jax.device_get(m.train_state.params)


def _assert_params_equal(pa, pb):
    la = jax.tree_util.tree_leaves(pa)
    lb = jax.tree_util.tree_leaves(pb)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _watcher_threads():
    return {t for t in threading.enumerate()
            if t.name == "dl4j-feed-resident"}


class _Recording(ListDataSetIterator):
    """ListDataSetIterator that counts reset() calls."""

    def __init__(self, batches):
        super().__init__(batches)
        self.resets = 0

    def reset(self):
        self.resets += 1


# ---- ragged-batch normalization -----------------------------------------

class TestRaggedNormalization:
    def test_pad_to_bucket_shapes_and_mask(self):
        (b,) = _batches(0, tail=5)
        p = pad_to_bucket(b, 16)
        assert p.features.shape == (16, 5)
        assert p.labels.shape == (16, 3)
        assert p.labels_mask.shape == (16,)
        np.testing.assert_array_equal(p.labels_mask[:5], np.ones(5))
        np.testing.assert_array_equal(p.labels_mask[5:], np.zeros(11))
        # padding duplicates the last real row (finite activations)
        np.testing.assert_array_equal(p.features[5:],
                                      np.repeat(b.features[-1:], 11, 0))

    def test_pad_noop_on_full_batch_keeps_mask_ones(self):
        (b,) = _batches(1)
        p = pad_to_bucket(b, 16)
        assert p.features is b.features
        np.testing.assert_array_equal(p.labels_mask, np.ones(16))

    def test_oversized_batch_rejected(self):
        (b,) = _batches(1)
        with pytest.raises(ValueError):
            pad_to_bucket(b, 8)

    def test_ones_mask_is_masked_mean_identity(self):
        """sum(per * ones)/sum(ones) == mean(per) bitwise — the property
        the whole normalization scheme leans on."""
        from deeplearning4j_tpu.ops.losses import _masked_mean
        import jax.numpy as jnp
        per = jnp.asarray(
            np.random.default_rng(7).normal(size=(16,)).astype(np.float32))
        ones = jnp.ones((16,), jnp.float32)
        assert jax.jit(_masked_mean)(per, ones) == jax.jit(
            lambda p: _masked_mean(p, None))(per)

    def test_padded_loss_matches_unpadded(self):
        """Masked loss of the padded tail equals the raw tail's loss.
        The compiled programs differ (different shapes), so this is a
        tight-tolerance check; the bitwise guarantees live at the
        trajectory level (TestFitIntegration)."""
        m = _tiny_model()
        (tail,) = _batches(0, tail=5)
        raw = float(m.compute_loss(tail))
        padded = float(m.compute_loss(pad_to_bucket(tail, 16)))
        assert raw == pytest.approx(padded, rel=1e-6)

    def test_ensure_labels_mask_sequence_uses_features_mask(self):
        x = np.zeros((2, 4, 5), np.float32)
        y = np.zeros((2, 4, 3), np.float32)
        fm = np.asarray([[1, 1, 0, 0], [1, 1, 1, 0]], np.float32)
        b = ensure_labels_mask(DataSet(x, y, fm, None))
        np.testing.assert_array_equal(b.labels_mask, fm)
        assert ones_labels_mask(DataSet(x, y)).shape == (2, 4)


# ---- DeviceFeeder mechanics ---------------------------------------------

class TestDeviceFeeder:
    def test_ordering_and_exactness(self):
        batches = _batches(4, tail=5)
        feeder = DeviceFeeder(ListDataSetIterator(batches),
                              registry=MetricsRegistry())
        items = list(feeder)
        assert [it.k for it in items] == [1] * 5
        assert [it.n_examples for it in items] == [16, 16, 16, 16, 5]
        for it, b in zip(items, batches):
            np.testing.assert_array_equal(np.asarray(it.features),
                                          b.features)
            np.testing.assert_array_equal(np.asarray(it.labels), b.labels)

    def test_depth_bounded_under_slow_consumer(self):
        """A stalled consumer must not let the feeder stage the whole
        epoch: staged depth stays <= depth (the byte/HBM bound)."""
        feeder = DeviceFeeder(ListDataSetIterator(_batches(10)),
                              depth=2, registry=MetricsRegistry())
        it = iter(feeder)
        next(it)
        time.sleep(0.02)      # consumer stalls; feeder must not run ahead
        for _ in it:
            pass
        assert 1 <= feeder.max_depth_seen <= 2

    def test_byte_budget_limits_depth(self):
        batches = _batches(6)
        per_batch = batches[0].features.nbytes + batches[0].labels.nbytes
        feeder = DeviceFeeder(ListDataSetIterator(batches), depth=4,
                              byte_budget=per_batch,  # room for ~1 batch
                              registry=MetricsRegistry())
        assert len(list(feeder)) == 6
        assert feeder.max_depth_seen <= 2   # 1 staged + 1 in-flight refill

    def test_k_groups_and_split_tail(self):
        """7 batches at K=3 -> two stacked groups + one padded single
        (no dummy optimizer steps for the tail)."""
        feeder = DeviceFeeder(ListDataSetIterator(_batches(6, tail=5)),
                              k_steps=3, registry=MetricsRegistry())
        items = list(feeder)
        assert [it.k for it in items] == [3, 3, 1]
        assert [it.n_examples for it in items] == [48, 48, 5]
        assert items[0].features.shape == (3, 16, 5)
        assert items[0].labels_mask.shape == (3, 16)
        # tail single arrives at the bucket shape with a zeroed pad mask
        assert items[2].features.shape == (16, 5)
        np.testing.assert_array_equal(np.asarray(items[2].labels_mask[5:]),
                                      np.zeros(11))

    def test_group_remainder_pad_repeats_tail(self):
        """'pad' remainder (the AVERAGING-round contract): the short tail
        group is filled by repeating its last batch, repeats counted."""
        feeder = DeviceFeeder(ListDataSetIterator(_batches(4)),
                              k_steps=3, group_remainder="pad",
                              pad_ragged=False,
                              registry=MetricsRegistry())
        items = list(feeder)
        assert [it.k for it in items] == [3, 3]
        # repeats are COUNTED (the round is the unit — matches the old
        # _run_averaging_round accounting)
        assert items[1].n_examples == 48
        np.testing.assert_array_equal(np.asarray(items[1].features[1]),
                                      np.asarray(items[1].features[2]))

    def test_group_prepare_runs_at_k1(self):
        """A group_prepare hook defines the staged LAYOUT (the parallel
        wrapper's stacked (K, B, ...) AVERAGING rounds), so it must run
        even when averaging_frequency == 1 — regression for the raw
        (B, ...) array reaching the stacked-round sharding."""
        calls = []

        def gp(batches):
            calls.append(len(batches))
            return (np.stack([b.features for b in batches]),
                    np.stack([b.labels for b in batches]), None, None)

        feeder = DeviceFeeder(ListDataSetIterator(_batches(3)),
                              k_steps=1, pad_ragged=False,
                              group_prepare=gp, group_remainder="pad",
                              registry=MetricsRegistry())
        items = list(feeder)
        assert calls == [1, 1, 1]
        assert [it.k for it in items] == [1, 1, 1]
        assert items[0].features.shape == (1, 16, 5)

    def test_foreign_objects_pass_through(self):
        marker = object()
        feeder = DeviceFeeder([marker], registry=MetricsRegistry())
        (item,) = list(feeder)
        assert item.k == 0 and item.raw is marker

    def test_gauges_registered_and_set(self):
        reg = MetricsRegistry()
        feeder = DeviceFeeder(ListDataSetIterator(_batches(3)),
                              registry=reg, session_id="t")
        list(feeder)
        assert reg.gauge("dl4j_feed_depth").get(session="t") >= 1.0
        assert reg.gauge("dl4j_etl_stall_ms").get(session="t") >= 0.0

    def test_tracer_spans_emitted(self):
        tracer = SpanTracer()
        feeder = DeviceFeeder(ListDataSetIterator(_batches(3)),
                              tracer=tracer, registry=MetricsRegistry())
        list(feeder)
        names = {e["name"] for e in tracer._events}
        assert {"etl", "host_to_device", "feed_stall"} <= names
        wire = [e for e in tracer._events if e["name"] == "host_to_device"]
        assert [e["args"]["seq"] for e in wire] == [0, 1, 2]
        assert all(e["args"]["bytes"] > 0 for e in wire)

    def test_resident_span_per_staged_item(self):
        """Every staged item: one ``resident`` span, from the reading its
        ``host_to_device`` span starts at, under the same ``seq``; the
        count runs on over the feeder's passes."""
        before = _watcher_threads()
        tracer = SpanTracer()
        feeder = DeviceFeeder(ListDataSetIterator(_batches(5)),
                              tracer=tracer, registry=MetricsRegistry())
        seqs = [item.seq for _ in range(2) for item in feeder]
        assert len(_watcher_threads() - before) == 1
        feeder.close()
        assert seqs == list(range(10))
        wire = {e["args"]["seq"]: e for e in tracer.events
                if e["name"] == "host_to_device"}
        resident = [e for e in tracer.events if e["name"] == "resident"]
        assert [e["args"]["seq"] for e in resident] == seqs
        for e in resident:
            assert e["cat"] == "data" and e["args"]["k"] == 1
            assert e["ts"] == wire[e["args"]["seq"]]["ts"]
            assert e["dur"] >= wire[e["args"]["seq"]]["dur"]
            assert e["args"]["bytes"] == wire[e["args"]["seq"]]["args"]["bytes"]
            assert e["tid"] != threading.get_ident()
        assert _watcher_threads() <= before

    def test_dropped_feeder_ends_its_watcher(self):
        before = _watcher_threads()
        feeder = DeviceFeeder(ListDataSetIterator(_batches(2)),
                              tracer=SpanTracer(), registry=MetricsRegistry())
        list(feeder)
        (watcher,) = _watcher_threads() - before
        del feeder
        watcher.join(timeout=5.0)
        assert not watcher.is_alive()

    def test_no_watcher_without_tracer(self):
        before = set(threading.enumerate())
        feeder = DeviceFeeder(ListDataSetIterator(_batches(3)),
                              registry=MetricsRegistry())
        it = iter(feeder)
        first = next(it)
        assert set(threading.enumerate()) == before
        assert [first.seq] + [item.seq for item in it] == [0, 1, 2]
        feeder.close()                  # nothing to end: a no-op
        marker = object()
        assert [i.seq for i in DeviceFeeder(
            [marker], registry=MetricsRegistry())] == [-1]

    @pytest.mark.parametrize("path", ["single", "k_steps", "group_prepare"])
    def test_put_receives_what_the_source_produced(self, path, monkeypatch):
        """The feeder makes no host copy of its own: ``put`` is handed the
        source's arrays themselves, or the ones a K-group was stacked
        into."""
        batches = _batches(4)
        made = []                       # what the host side produced

        def stack(arrays):
            made.append(real_stack(arrays))
            return made[-1]

        def group_prepare(group):
            made.extend([np.concatenate([b.features for b in group]),
                         np.concatenate([b.labels for b in group])])
            return (made[-2], made[-1], None, None)

        kwargs = {"single": {},
                  "k_steps": {"k_steps": 2, "pad_ragged": False},
                  "group_prepare": {"k_steps": 2, "pad_ragged": False,
                                    "group_prepare": group_prepare}}[path]
        if path == "single":
            made = [a for b in batches for a in (b.features, b.labels)]
        elif path == "k_steps":
            real_stack = np.stack
            monkeypatch.setattr(np, "stack", stack)
        put = []
        feeder = DeviceFeeder(
            ListDataSetIterator(batches), registry=MetricsRegistry(),
            put=lambda a: put.append(a) or jax.device_put(a), **kwargs)
        items = list(feeder)
        assert len(items) == (4 if path == "single" else 2)
        # features and labels; the stacked groups carry a labels mask too
        assert len(put) == len(made) == (
            3 if path == "k_steps" else 2) * len(items)
        assert all(got is want for got, want in zip(put, made))

    @pytest.mark.parametrize("consumer_ms", [0, 8], ids=["fast", "slow"])
    def test_reused_buffers_under_a_put_that_copies_late(self, consumer_ms):
        """``ArrayDataSetIterator(shuffle=True)`` -> ``AsyncDataSetIterator``
        -> ``DeviceFeeder`` with a ``put`` that reads its array late, on a
        thread, as an accelerator's runtime does, keeping the array it was
        given until then: three epochs deliver the batches of
        ``DataSet.shuffle`` bit for bit."""
        from concurrent.futures import ThreadPoolExecutor
        data = DataSet.merge(_batches(12, batch=8))
        seed = 3
        source = AsyncDataSetIterator(
            ArrayDataSetIterator(data, 8, shuffle=True, seed=seed),
            queue_size=4)
        reused = []

        def late_copy(a):
            time.sleep(0.004)
            return a.copy()

        with ThreadPoolExecutor(max_workers=3) as runtime:
            feeder = DeviceFeeder(source, registry=MetricsRegistry(),
                                  put=lambda a: runtime.submit(late_copy, a))
            for epoch in range(3):
                whole = data.shuffle(seed + epoch)
                n = 0
                for n, item in enumerate(feeder, 1):
                    time.sleep(consumer_ms / 1e3)
                    lo = 8 * (n - 1)
                    np.testing.assert_array_equal(
                        item.features.result(), whole.features[lo:lo + 8])
                    np.testing.assert_array_equal(
                        item.labels.result(), whole.labels[lo:lo + 8])
                assert n == 12
                source.reset()
        pools = source.base._pools[:2]
        assert all(2 <= len(pool) <= source.base._POOL_BUFFERS
                   for pool in pools)

    def test_rejects_bad_config(self):
        src = ListDataSetIterator(_batches(1))
        with pytest.raises(ValueError):
            DeviceFeeder(src, depth=0, registry=MetricsRegistry())
        with pytest.raises(ValueError):
            DeviceFeeder(src, k_steps=0, registry=MetricsRegistry())
        with pytest.raises(ValueError):
            DeviceFeeder(src, group_remainder="drop",
                         registry=MetricsRegistry())


# ---- AsyncDataSetIterator lifecycle -------------------------------------

class TestAsyncIterator:
    def test_exactness_and_order(self):
        batches = _batches(8, tail=3)
        got = list(AsyncDataSetIterator(ListDataSetIterator(batches)))
        assert len(got) == 9
        for a, b in zip(got, batches):
            np.testing.assert_array_equal(a.features, b.features)

    def test_reset_joins_worker_before_base_reset(self):
        """The race this PR fixes: reset() during an active pass must
        stop + drain + JOIN the worker before touching the base, so no
        stale batch from the old pass leaks into the new one."""
        base = _Recording(_batches(50, batch=4))
        it = AsyncDataSetIterator(base, queue_size=2)
        gen = iter(it)
        next(gen)                       # worker running, queue full
        worker = it._worker
        assert worker is not None and worker.is_alive()
        it.reset()
        assert not worker.is_alive()    # joined, not abandoned
        assert base.resets == 1
        assert it._worker is None
        fresh = list(it)
        assert len(fresh) == 50
        np.testing.assert_array_equal(fresh[0].features,
                                      base._batches[0].features)

    def test_abandoned_pass_reaps_worker(self):
        it = AsyncDataSetIterator(ListDataSetIterator(
            _batches(50, batch=4)), queue_size=2)
        gen = iter(it)
        next(gen)
        gen.close()                     # consumer breaks out early
        assert it._worker is None
        deadline = time.time() + 2.0
        while threading.active_count() > 0 and time.time() < deadline:
            if all(not t.name.startswith("Thread-") or not t.is_alive()
                   for t in threading.enumerate()
                   if t is not threading.main_thread()):
                break
            time.sleep(0.01)

    def test_worker_error_propagates(self):
        class Boom(DataSetIterator):
            def __iter__(self):
                yield _batches(1)[0]
                raise RuntimeError("bad shard")

        with pytest.raises(RuntimeError, match="bad shard"):
            list(AsyncDataSetIterator(Boom()))

    def test_two_sequential_passes(self):
        it = AsyncDataSetIterator(ListDataSetIterator(_batches(5)))
        assert len(list(it)) == 5
        assert len(list(it)) == 5


# ---- fit() integration ---------------------------------------------------

class TestFitIntegration:
    def test_fed_k1_bitwise_equals_unfed(self):
        """The headline acceptance: the fed path (prefetch + staged
        dispatch) replays the exact unfed trajectory bit for bit,
        ragged final batch included."""
        batches = _batches(6, tail=5)
        m_fed = _tiny_model()
        m_ref = _tiny_model()
        m_fed.fit(ListDataSetIterator(batches), epochs=2)
        m_ref.fit(ListDataSetIterator(batches), epochs=2, prefetch=0)
        _assert_params_equal(_params(m_fed), _params(m_ref))
        assert float(m_fed.score()) == float(m_ref.score())

    def test_shuffling_iterator_equals_slices_of_the_shuffled_set(self):
        """``fit(ArrayDataSetIterator(shuffle=True), epochs=2)`` (prefetch
        thread, per-batch gather, feeder) sees the losses of the same
        model fed the slices of ``data.shuffle(seed + epoch)`` from a
        list, epoch by epoch, ragged tail included."""
        from deeplearning4j_tpu.optimize.listeners import (
            CollectScoresIterationListener)
        data = DataSet.merge(_batches(4, batch=8, tail=5))
        seed = 5
        fed, fed_scores = _tiny_model(), CollectScoresIterationListener()
        fed.set_listeners(fed_scores)
        fed.fit(ArrayDataSetIterator(data, 8, shuffle=True, seed=seed),
                epochs=2)
        ref, ref_scores = _tiny_model(), CollectScoresIterationListener()
        ref.set_listeners(ref_scores)
        for epoch in range(2):
            whole = data.shuffle(seed + epoch)
            ref.fit(ListDataSetIterator(
                [DataSet(whole.features[lo:lo + 8], whole.labels[lo:lo + 8])
                 for lo in range(0, 37, 8)]), epochs=1)
        assert len(fed_scores.scores) == 10
        assert fed_scores.scores == ref_scores.scores

    @pytest.mark.parametrize("k_steps, untraced", [
        (1, {"prefetch": 0}), (3, {"k_steps": 3})])
    def test_traced_fit_spans_complete_and_bitwise(self, k_steps, untraced):
        """With the tracer on: the fed fit still agrees bit for bit with
        the unfed one (the scanned one with its untraced self), every
        dispatch has its batch's ``resident`` span in the tracer when
        ``fit()`` returns, and the watcher is gone."""
        batches = _batches(6, tail=5)
        m_fed, m_ref = _tiny_model(), _tiny_model()
        tracer = SpanTracer()
        m_fed.set_tracer(tracer)
        before = _watcher_threads()
        m_fed.fit(ListDataSetIterator(batches), epochs=2, k_steps=k_steps)
        events = tracer.events          # read at once: nothing comes later
        assert _watcher_threads() <= before
        m_ref.fit(ListDataSetIterator(batches), epochs=2, **untraced)
        _assert_params_equal(_params(m_fed), _params(m_ref))
        by_name = {n: [e["args"]["seq"] for e in events if e["name"] == n]
                   for n in ("host_to_device", "resident", "dispatch")}
        assert by_name["dispatch"] == sorted(by_name["dispatch"])
        assert len(by_name["dispatch"]) == (14 if k_steps == 1 else 6)
        assert (by_name["resident"] == by_name["host_to_device"]
                == by_name["dispatch"])

    def test_fit_hands_its_tracer_to_the_iterator_it_was_given(self):
        tracer = SpanTracer()
        model = _tiny_model()
        model.set_tracer(tracer)
        handed = AsyncDataSetIterator(ListDataSetIterator(_batches(3)))
        model.fit(handed, epochs=2)
        assert handed.tracer is tracer
        assert [e["args"]["index"] for e in tracer.events
                if e["name"] == "produce"] == [0, 1, 2] * 2

    def test_untraced_fit_starts_no_watcher(self, monkeypatch):
        from deeplearning4j_tpu.datasets import feeder as feeder_mod
        made = []
        monkeypatch.setattr(
            feeder_mod, "_ResidentWatcher",
            lambda tracer: made.append(tracer) or pytest.fail("watcher"))
        _tiny_model().fit(ListDataSetIterator(_batches(3)), epochs=2)
        assert made == []

    def test_failed_traced_fit_leaves_no_watcher(self):
        from deeplearning4j_tpu.optimize.listeners import TrainingListener

        class Boom(TrainingListener):
            def iteration_done(self, *a, **k):
                raise RuntimeError("listener")

        m = _tiny_model()
        m.set_tracer(SpanTracer())
        m.set_listeners(Boom())
        before = _watcher_threads()
        with pytest.raises(RuntimeError, match="listener"):
            m.fit(ListDataSetIterator(_batches(3)), epochs=1)
        assert _watcher_threads() <= before

    def test_fused_ksteps_bitwise_equals_per_batch(self):
        """fit(k_steps=3) over the raw ragged stream must replay the
        per-batch trajectory over the bucket-normalized stream bitwise
        (the normalization itself is loss-neutral, see
        TestRaggedNormalization; XLA compiles masked and mask-free
        programs differently, so the bitwise comparison normalizes
        both sides)."""
        batches = _batches(6, tail=5)
        normalized = [pad_to_bucket(b, 16) for b in batches]
        m_fused = _tiny_model()
        m_ref = _tiny_model()
        m_fused.fit(ListDataSetIterator(batches), epochs=2, k_steps=3)
        m_ref.fit(ListDataSetIterator(normalized), epochs=2, prefetch=0)
        _assert_params_equal(_params(m_fused), _params(m_ref))

    def test_fused_listener_semantics(self):
        """Iteration advances by K per dispatch; listeners see the
        group's REAL example count (48 for full groups, 5 for the
        ragged tail dispatched as a bucket-shaped single)."""
        from deeplearning4j_tpu.optimize.listeners import (
            ScoreIterationListener)

        class Spy(ScoreIterationListener):
            rows = []

            def iteration_done(self, model, iteration, epoch, loss,
                               etl_ms, n_examples):
                self.rows.append((iteration, n_examples))

        m = _tiny_model()
        spy = Spy(frequency=1)
        m.set_listeners(spy)
        m.fit(ListDataSetIterator(_batches(6, tail=5)), k_steps=3)
        assert spy.rows == [(3, 48), (6, 48), (7, 5)]

    def test_zero_recompiles_across_ragged_epochs(self):
        """The watchdog acceptance: two epochs with a partial final
        batch at k_steps=3 compile exactly one signature per step key —
        zero recompiles (the ragged tail used to cost one per epoch)."""
        wd = RecompileWatchdog(registry=MetricsRegistry())
        m = _tiny_model()
        m.set_recompile_watchdog(wd)
        m.fit(ListDataSetIterator(_batches(6, tail=5)), epochs=2,
              k_steps=3)
        assert wd.count() == 0

    def test_zero_recompiles_k1_padded(self):
        """Same property on the K=1 fed path when bucket padding is on
        explicitly (pad_ragged defaults off at K=1, where the tail's
        own signature is the first and only one for its shape... so
        instead: unpadded K=1 costs exactly the one tail signature)."""
        wd = RecompileWatchdog(registry=MetricsRegistry())
        m = _tiny_model()
        m.set_recompile_watchdog(wd)
        m.fit(ListDataSetIterator(_batches(6, tail=5)), epochs=2)
        # full-batch sig is free; the ragged tail adds ONE signature
        # total (not one per epoch)
        assert wd.count("train_step") == 1

    def test_shield_opts_out_of_feeder(self, monkeypatch):
        import deeplearning4j_tpu.datasets.feeder as feeder_mod
        built = []
        real = feeder_mod.DeviceFeeder

        def spy(*a, **k):
            built.append(1)
            return real(*a, **k)

        monkeypatch.setattr(feeder_mod, "DeviceFeeder", spy)
        batches = _batches(3)
        m = _tiny_model()
        m.fit(AsyncShieldDataSetIterator(ListDataSetIterator(batches)))
        assert not built                  # shield -> strictly sync loop
        m.fit(ListDataSetIterator(batches))
        assert built                      # plain iterator -> fed

    def test_ksteps_require_feeder(self):
        m = _tiny_model()
        shield = AsyncShieldDataSetIterator(
            ListDataSetIterator(_batches(3)))
        with pytest.raises(ValueError):
            m.fit(shield, k_steps=2)
        with pytest.raises(ValueError):
            m.fit(ListDataSetIterator(_batches(3)), k_steps=2, prefetch=0)

    def test_source_reset_per_epoch(self):
        base = _Recording(_batches(3))
        m = _tiny_model()
        m.fit(base, epochs=3)
        assert base.resets == 3

    def test_no_new_per_step_device_fetch(self, monkeypatch):
        """The one-fetch telemetry contract survives the fed + fused
        path: 12 inner steps at flush_interval=4 -> exactly 4 host
        transfers (3 interval flushes + the tail flush) — the same
        count the unfed loop performs (test_observe), so the feeder
        and the scan dispatch added NO new per-step fetch."""
        fetches = []
        real = jax.device_get

        def counting(x):
            fetches.append(x)
            return real(x)

        m = _tiny_model()
        tel = TelemetryCollector(flush_interval=4,
                                 registry=MetricsRegistry())
        m.set_telemetry(tel)
        monkeypatch.setattr(jax, "device_get", counting)
        m.fit(ListDataSetIterator(_batches(12)), k_steps=4)
        monkeypatch.setattr(jax, "device_get", real)
        assert tel.fetch_count == 4
        assert len(fetches) == 4
        assert [r["iteration"] for r in tel.history] == list(range(1, 13))
