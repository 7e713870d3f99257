"""The reader of ``produce_reuse_share`` on spans made by hand."""

import types

from pytest import approx

from yardstick import cells
from yardstick.spans import Spans

T0 = 1000.0                       # the tracer's zero on perf_counter
WINDOW_S = 4.0


def produce(start_s, **args):
    return {"name": "produce", "cat": "data", "ph": "X", "ts": start_s * 1e6,
            "dur": 5e3, "args": args}


def read(events):
    cell = cells.resolve_cell("resnet50-tiny64.fit")
    obs = types.SimpleNamespace(
        cell=cell, spans=Spans(events, T0, (T0, T0 + WINDOW_S)))
    return cells.load_reader(cell, "produce_reuse_share").read(obs)


def test_share_of_the_spans_that_say():
    events = ([produce(0.1 * i, index=i, reused=i >= 2) for i in range(8)]
              + [produce(1.0, index=8),                     # says nothing
                 produce(5.0, index=0, reused=False),       # after the window
                 {"name": "produce", "cat": "serve", "ph": "X", "ts": 2e6,
                  "dur": 1.0, "args": {"reused": False}}])  # another layer's
    assert read(events) == approx(100.0 * 6 / 8)


def test_nothing_to_read_is_none():
    """A program whose spans carry no ``reused`` (the parent commit; an
    iterator that hands out views), or that has no ``produce`` span."""
    assert read([produce(0.1, index=0), produce(0.2, index=1)]) is None
    assert read([]) is None
