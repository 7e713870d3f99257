"""``ParallelWrapper`` under a tracer, on the virtual CPU mesh: a
``dispatch`` span (cat ``step``) around every call of the step, keyed by
the batch's ``seq`` like its ``host_to_device`` and ``resident`` spans,
and the worker's ``produce`` spans; and the loop's own waits for the
devices (``blocked``) with the steps in flight."""

import threading

import pytest

from deeplearning4j_tpu.datasets.fetchers import IrisDataSetIterator
from deeplearning4j_tpu.datasets.iterators import AsyncShieldDataSetIterator
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer
from deeplearning4j_tpu.nn.layers.output import OutputLayer
from deeplearning4j_tpu.observe import SpanTracer
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper, TrainingMode


def _fit(mode, iterator, epochs=2, calls=1, **build):
    conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=16)).layer(OutputLayer(n_out=3))
            .set_input_type(InputType.feed_forward(4)).build())
    model = MultiLayerNetwork(conf).init()
    tracer = SpanTracer()
    model.set_tracer(tracer)
    builder = ParallelWrapper.builder(model).training_mode(mode).workers(2)
    for name, value in build.items():
        builder = getattr(builder, name)(value)
    before = set(threading.enumerate())
    wrapper = builder.build()
    for _ in range(calls):
        wrapper.fit(iterator, epochs=epochs)
    assert set(threading.enumerate()) <= before     # watcher and worker gone
    return {name: [e for e in tracer.events if e["name"] == name]
            for name in ("dispatch", "host_to_device", "resident", "produce",
                         "blocked")}


def test_sync_fit_one_dispatch_span_per_step():
    # 150 rows in batches of 32: five steps an epoch
    spans = _fit(TrainingMode.SHARED_GRADIENTS, IrisDataSetIterator(32))
    assert len(spans["dispatch"]) == 10
    assert all(e["cat"] == "step" for e in spans["dispatch"])
    seqs = [e["args"]["seq"] for e in spans["dispatch"]]
    assert seqs == list(range(10))
    assert [e["args"]["seq"] for e in spans["resident"]] == seqs
    assert [e["args"]["seq"] for e in spans["host_to_device"]] == seqs
    assert ([e["args"]["index"] for e in spans["produce"]]
            == list(range(5)) * 2)


def test_unfed_sync_fit_has_dispatch_spans_without_seq():
    spans = _fit(TrainingMode.SHARED_GRADIENTS,
                 AsyncShieldDataSetIterator(IrisDataSetIterator(32)))
    assert len(spans["dispatch"]) == 10
    assert all("seq" not in e.get("args", {}) for e in spans["dispatch"])
    assert spans["resident"] == [] and spans["produce"] == []


def test_averaging_fit_one_dispatch_span_per_round():
    # five batches an epoch in rounds of two: three rounds, the last padded
    spans = _fit(TrainingMode.AVERAGING, IrisDataSetIterator(32),
                 averaging_frequency=2)
    assert [e["args"]["seq"] for e in spans["dispatch"]] == list(range(6))
    assert all(e["args"]["k"] == 2 for e in spans["dispatch"])
    assert len(spans["resident"]) == 6


def _assert_blocked_outside_dispatch(spans):
    for b in spans["blocked"]:
        assert b["cat"] == "step"
        for d in spans["dispatch"]:
            assert (d["tid"] != b["tid"] or b["ts"] + b["dur"] <= d["ts"]
                    or d["ts"] + d["dur"] <= b["ts"])


@pytest.mark.parametrize("mode, build, k", [
    (TrainingMode.SHARED_GRADIENTS, {}, 1),
    (TrainingMode.AVERAGING, {"averaging_frequency": 2}, 2)],
    ids=["sync", "averaging"])
def test_one_iteration_wait_a_call_and_in_flight_on_every_dispatch(
        mode, build, k):
    spans = _fit(mode, IrisDataSetIterator(32), calls=2, **build)
    waits = [e["args"] for e in spans["blocked"]]
    assert [a["on"] for a in waits] == ["iteration"] * 2
    assert all(a["since_call_ms"] >= 0 for a in waits)
    assert len(spans["dispatch"]) == (20 if k == 1 else 12)
    for e in spans["dispatch"] + spans["blocked"]:
        n = e["args"]["in_flight"]
        assert type(n) is int and n >= 0 and n % k == 0
    _assert_blocked_outside_dispatch(spans)


def test_unfed_sync_fit_counts_the_steps_in_flight_too():
    spans = _fit(TrainingMode.SHARED_GRADIENTS,
                 AsyncShieldDataSetIterator(IrisDataSetIterator(32)))
    assert [e["args"]["on"] for e in spans["blocked"]] == ["iteration"]
    assert all(type(e["args"]["in_flight"]) is int
               for e in spans["dispatch"])


def test_under_a_watchdog_every_step_is_a_collective_wait(tmp_path):
    from deeplearning4j_tpu.parallel.cluster import CollectiveWatchdog
    wd = CollectiveWatchdog(str(tmp_path), rank=0, n_ranks=1)  # not started
    spans = _fit(TrainingMode.SHARED_GRADIENTS, IrisDataSetIterator(32),
                 watchdog=wd)
    on = [e["args"]["on"] for e in spans["blocked"]]
    assert on == ["collective", "iteration"] + ["collective"] * 9
    # the wait begins with the step just dispatched in flight, and the
    # read of the iteration after it finds nothing to wait for
    assert all(e["args"]["in_flight"] >= 1 for e in spans["blocked"]
               if e["args"]["on"] == "collective")
    assert spans["blocked"][1]["args"]["in_flight"] == 0
    assert all(e["args"]["in_flight"] == 0 for e in spans["dispatch"])
    _assert_blocked_outside_dispatch(spans)
