"""Every fit path dispatches through ``BaseModel._send_step`` and records
through ``_record_step``: ``fit()``'s unfed, fed and grouped bodies, both
models' TBPTT chunk loops and every ``ParallelWrapper`` mode, fed or not.
Under an enabled tracer each dispatch is one ``dispatch`` span (cat
``step``) with ``in_flight``, ``seq`` where the feeder staged the batch
and ``k`` where it is a group; the host iteration advances by the steps
dispatched, listeners hear once per record with the real example count,
and the last loss is kept."""

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import (DataSet, ListDataSetIterator,
                                                 MultiDataSet)
from deeplearning4j_tpu.datasets.fetchers import IrisDataSetIterator
from deeplearning4j_tpu.datasets.iterators import AsyncShieldDataSetIterator
from deeplearning4j_tpu.models.computation_graph import ComputationGraph
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer
from deeplearning4j_tpu.nn.layers.output import OutputLayer, RnnOutputLayer
from deeplearning4j_tpu.nn.layers.recurrent import LSTM
from deeplearning4j_tpu.observe import SpanTracer
from deeplearning4j_tpu.optimize.listeners import TrainingListener
from deeplearning4j_tpu.optimize.updaters import Sgd
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper, TrainingMode

IRIS = [32, 32, 32, 32, 22]     # IrisDataSetIterator(32): 150 rows
T, K_TBPTT = 10, 4              # three chunks a sequence batch, one ragged


class _Heard(TrainingListener):
    def __init__(self):
        self.calls = []

    def iteration_done(self, model, iteration, epoch, loss, etl_ms,
                       batch_size):
        self.calls.append((iteration, loss, batch_size))


def _dense_mln():
    conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=16)).layer(OutputLayer(n_out=3))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def _dense_cg():
    g = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1))
         .graph_builder().add_inputs("in")
         .set_input_types(InputType.feed_forward(4)))
    g.add_layer("h", DenseLayer(n_out=16), "in")
    g.add_layer("out", OutputLayer(n_out=3), "h")
    g.set_outputs("out")
    return ComputationGraph(g.build()).init()


def _tbptt_mln():
    conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1)).list()
            .layer(LSTM(n_out=5)).layer(RnnOutputLayer(n_out=2))
            .backprop_type("tbptt").tbptt_fwd_length(K_TBPTT)
            .set_input_type(InputType.recurrent(3)).build())
    return MultiLayerNetwork(conf).init()


def _tbptt_cg():
    g = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.1))
         .graph_builder().add_inputs("in")
         .set_input_types(InputType.recurrent(3)))
    g.add_layer("lstm", LSTM(n_out=5), "in")
    g.add_layer("out", RnnOutputLayer(n_out=2), "lstm")
    g.set_outputs("out")
    g.backprop_type("tbptt").tbptt_fwd_length(K_TBPTT)
    return ComputationGraph(g.build()).init()


def _sequences(rows=(6, 4)):
    rng = np.random.default_rng(7)
    out = []
    for n in rows:
        y = np.zeros((n, T, 2), np.float32)
        y[np.arange(n)[:, None], np.arange(T), rng.integers(0, 2, (n, T))] = 1
        out.append(DataSet(rng.normal(size=(n, T, 3)).astype(np.float32), y))
    return out


def _iris_multi():
    return [MultiDataSet([b.features], [b.labels])
            for b in IrisDataSetIterator(32)]


def _wrapped(mode, shielded):
    def fit(model):
        builder = (ParallelWrapper.builder(model).training_mode(mode)
                   .workers(2).averaging_frequency(2))
        it = IrisDataSetIterator(32)
        builder.build().fit(AsyncShieldDataSetIterator(it) if shielded
                            else it)
    return fit


def _round_ns():
    # rounds of two batches; the last is padded with its own batch again,
    # and the repeat is counted (the feeder's "pad" remainder contract)
    return [64, 64, 44]


SHARED, AVG, ASYNC = (TrainingMode.SHARED_GRADIENTS, TrainingMode.AVERAGING,
                      TrainingMode.ASYNC_ELASTIC)

# id: (model, fit, seq?, span k's, steps per span, examples per record)
CASES = {
    "mln-unfed": (_dense_mln, lambda m: m.fit(IrisDataSetIterator(32),
                                              prefetch=0),
                  False, [None] * 5, [1] * 5, IRIS),
    "mln-fed-k1": (_dense_mln, lambda m: m.fit(IrisDataSetIterator(32)),
                   True, [None] * 5, [1] * 5, IRIS),
    "mln-fed-k2": (_dense_mln, lambda m: m.fit(IrisDataSetIterator(32),
                                               k_steps=2),
                   True, [2, 2, None], [2, 2, 1], [64, 64, 22]),
    "mln-tbptt": (_tbptt_mln,
                  lambda m: m.fit(ListDataSetIterator(_sequences())),
                  False, [None] * 6, [1] * 6, [6, 4]),
    "cg-unfed": (_dense_cg, lambda m: m.fit(_iris_multi()),
                 False, [None] * 5, [1] * 5, IRIS),
    "cg-tbptt": (_tbptt_cg,
                 lambda m: m.fit(ListDataSetIterator(_sequences())),
                 False, [None] * 6, [1] * 6, [6, 4]),
    "sync-fed": (_dense_mln, _wrapped(SHARED, False),
                 True, [None] * 5, [1] * 5, IRIS),
    "sync-unfed": (_dense_mln, _wrapped(SHARED, True),
                   False, [None] * 5, [1] * 5, IRIS),
    "averaging-fed": (_dense_mln, _wrapped(AVG, False),
                      True, [2] * 3, [2] * 3, _round_ns()),
    "averaging-unfed": (_dense_mln, _wrapped(AVG, True),
                        False, [2] * 3, [2] * 3, _round_ns()),
    "async-fed": (_dense_mln, _wrapped(ASYNC, False),
                  True, [2] * 3, [2] * 3, _round_ns()),
    "async-unfed": (_dense_mln, _wrapped(ASYNC, True),
                    False, [2] * 3, [2] * 3, _round_ns()),
}


@pytest.mark.parametrize("case", list(CASES))
def test_every_fit_path_dispatches_through_the_one_protocol(case):
    make, fit, fed, ks, steps, ns = CASES[case]
    model = make()
    tracer, heard = SpanTracer(), _Heard()
    model.set_tracer(tracer)
    model.set_listeners(heard)
    fit(model)

    spans = [e for e in tracer.events if e["name"] == "dispatch"]
    assert len(spans) == len(ks)
    for span, k in zip(spans, ks):
        args = span["args"]
        assert span["cat"] == "step"
        assert type(args["in_flight"]) is int and args["in_flight"] >= 0
        assert ("seq" in args) == fed
        assert args.get("k") == k
    if fed:
        assert [s["args"]["seq"] for s in spans] == list(range(len(spans)))

    total = sum(steps)
    assert model._host_iteration == int(model.train_state.iteration) == total
    # one record a dispatch; a TBPTT batch is one record of its chunks
    assert [n for _, _, n in heard.calls] == ns
    assert heard.calls[-1][0] == total
    assert model._last_loss is heard.calls[-1][1]
    assert np.ndim(model._last_loss) == 0


class _CountedFeatures:
    """Host features that count how often they are converted."""

    def __init__(self, a):
        self.a, self.conversions = a, 0
        self.ndim, self.shape, self.dtype = a.ndim, a.shape, a.dtype

    def __array__(self, dtype=None, copy=None):
        self.conversions += 1
        return self.a if dtype is None else self.a.astype(dtype)


def test_unfed_mln_batch_is_converted_once():
    """Deciding between the TBPTT and the plain path reads the features'
    rank; only the plain path's staging converts them."""
    model = _dense_mln()
    batch = next(iter(IrisDataSetIterator(32)))
    features = _CountedFeatures(np.asarray(batch.features))
    model.fit(DataSet(features, batch.labels))
    assert features.conversions == 1
    assert int(model.train_state.iteration) == 1
