"""``AsyncDataSetIterator``'s ``produce`` spans: one around each ``next()``
of the base, on the worker's thread, ``index`` from 0 in each pass."""

import threading
import time

import numpy as np

from deeplearning4j_tpu.datasets.dataset import (
    ArrayDataSetIterator,
    DataSet,
    DataSetIterator,
    ListDataSetIterator,
)
from deeplearning4j_tpu.datasets.iterators import AsyncDataSetIterator
from deeplearning4j_tpu.observe import SpanTracer
from deeplearning4j_tpu.observe.tracer import NULL_TRACER


def _batches(n, rows=4):
    rng = np.random.default_rng(0)
    return [DataSet(rng.normal(size=(rows, 5)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, rows)])
            for _ in range(n)]


def _produce(tracer):
    return [e for e in tracer.events if e["name"] == "produce"]


def test_produce_spans_count_batches_and_restart_each_pass():
    tracer = SpanTracer()
    it = AsyncDataSetIterator(ListDataSetIterator(_batches(5)),
                              tracer=tracer)
    assert len(list(it)) == 5
    it.reset()
    assert len(list(it)) == 5
    spans = _produce(tracer)
    assert [e["args"]["index"] for e in spans] == list(range(5)) * 2
    assert all(e["cat"] == "data" for e in spans)
    # both passes' workers, and neither is the consumer's thread
    tids = {e["tid"] for e in spans}
    assert len(tids) <= 2 and threading.get_ident() not in tids


def test_pass_start_lands_in_the_first_span():
    """Whatever the base does when a pass starts (here: a slow start, as a
    reader that opens its files is) is inside the ``index=0`` span."""
    class SlowStart(DataSetIterator):
        def __iter__(self):
            time.sleep(0.05)
            yield from _batches(3)

    tracer = SpanTracer()
    list(AsyncDataSetIterator(SlowStart(), tracer=tracer))
    first, *rest = _produce(tracer)
    assert first["args"]["index"] == 0 and first["dur"] >= 50_000
    assert all(e["dur"] < 50_000 for e in rest)


def test_default_tracer_is_off_and_batches_are_the_same():
    data = DataSet(np.arange(40, dtype=np.float32).reshape(20, 2),
                   np.eye(2, dtype=np.float32)[np.arange(20) % 2])
    plain = AsyncDataSetIterator(ArrayDataSetIterator(data, 4, shuffle=True,
                                                      seed=3))
    traced = AsyncDataSetIterator(ArrayDataSetIterator(data, 4, shuffle=True,
                                                       seed=3),
                                  tracer=SpanTracer())
    assert plain.tracer is NULL_TRACER
    for a, b in zip(plain, traced, strict=True):
        np.testing.assert_array_equal(a.features, b.features)
    assert len(_produce(traced.tracer)) == 5


def test_abandoned_traced_pass_leaves_no_thread():
    tracer = SpanTracer()
    it = AsyncDataSetIterator(ListDataSetIterator(_batches(50)),
                              queue_size=2, tracer=tracer)
    before = set(threading.enumerate())
    for i, _ in enumerate(it):
        if i == 1:
            break                       # the consumer walks away
    assert it._worker is None
    assert set(threading.enumerate()) <= before
    assert 2 <= len(_produce(tracer)) < 50
