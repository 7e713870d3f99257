"""``ParallelWrapper`` under a tracer, on the virtual CPU mesh: a
``dispatch`` span (cat ``step``) around every call of the step, keyed by
the batch's ``seq`` like its ``host_to_device`` and ``resident`` spans,
and the worker's ``produce`` spans."""

import threading

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


def _fit(mode, iterator, epochs=2, **build):
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
    builder.build().fit(iterator, epochs=epochs)
    assert set(threading.enumerate()) <= before     # watcher and worker gone
    return {name: [e for e in tracer.events if e["name"] == name]
            for name in ("dispatch", "host_to_device", "resident", "produce")}


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
