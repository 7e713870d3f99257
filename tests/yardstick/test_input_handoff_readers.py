"""The three readers of the input hand-off (``input_wait_share``,
``h2d_mbytes_per_s``, ``pass_start_produce_ms``) on spans made by hand,
against values worked out from them."""

import types

import pytest
from pytest import approx

from yardstick import cells
from yardstick.spans import Spans

T0 = 1000.0                       # the tracer's zero on perf_counter
WINDOW_S = 4.0
MB = 1_000_000


def span(name, cat, start_s, dur_ms, **args):
    ev = {"name": name, "cat": cat, "ph": "X", "ts": start_s * 1e6,
          "dur": dur_ms * 1e3}
    if args:
        ev["args"] = args
    return ev


def resident(seq, start_s, end_s, nbytes=50 * MB):
    return span("resident", "data", start_s, (end_s - start_s) * 1e3,
                seq=seq, bytes=nbytes, k=1)


def dispatch(start_s, end_s, **args):
    return span("dispatch", "step", start_s, (end_s - start_s) * 1e3, **args)


def read(metric, events):
    cell = cells.resolve_cell("resnet50-tiny64.fit")
    obs = types.SimpleNamespace(
        cell=cell, spans=Spans(events, T0, (T0, T0 + WINDOW_S)))
    return cells.load_reader(cell, metric).read(obs)


# three batches on their way at once (union 0.1-1.5 s), then one alone
# (2.0-2.5 s): 1.9 s with a batch in flight
HANDOFF = [
    resident(0, 0.1, 1.0), resident(1, 0.2, 1.2), resident(2, 0.3, 1.5),
    resident(3, 2.0, 2.5),
    # seq 0 and 1 are dispatched before they arrive and wait 0.5-1.0 and
    # 0.6-1.2: together 0.5-1.2; seq 2 is resident (1.5) before its step
    # goes out (1.6-1.7) and waits for nothing; seq 3 waits 2.1-2.5
    dispatch(0.4, 0.5, seq=0), dispatch(0.5, 0.6, seq=1),
    dispatch(1.6, 1.7, seq=2), dispatch(2.05, 2.1, seq=3),
    # the unfed path's step carries no seq; a seq that no batch has
    dispatch(3.0, 3.1), dispatch(3.2, 3.3, seq=9),
    # after the window
    resident(4, 5.0, 6.0), dispatch(5.0, 5.5, seq=4),
]


def test_input_wait_share_is_the_union_of_the_waits():
    assert read("input_wait_share", HANDOFF) == approx(
        100.0 * (0.7 + 0.4) / WINDOW_S)


def test_input_wait_share_is_zero_when_batches_arrive_first():
    events = [resident(0, 0.1, 0.3), dispatch(0.5, 0.6, seq=0),
              resident(1, 0.4, 0.6), dispatch(0.7, 0.8, seq=1)]
    assert read("input_wait_share", events) == 0.0


def test_input_wait_share_takes_the_newest_batch_of_a_seq():
    """Each ``fit()`` call's feeder counts from 0 again: the step of the
    second call waits for the second call's batch, 2.2-2.6."""
    events = [resident(0, 0.1, 0.2), dispatch(0.3, 0.4, seq=0),
              resident(0, 2.0, 2.6), dispatch(2.1, 2.2, seq=0)]
    assert read("input_wait_share", events) == approx(100.0 * 0.4 / WINDOW_S)


def test_input_wait_share_ends_with_the_window():
    events = [resident(0, 3.0, 4.5), dispatch(3.4, 3.5, seq=0)]
    assert read("input_wait_share", events) == approx(100.0 * 0.5 / WINDOW_S)


def test_h2d_mbytes_per_s_is_bytes_over_time_in_flight():
    assert read("h2d_mbytes_per_s", HANDOFF) == approx(4 * 50 / 1.9)


def test_pass_start_produce_ms_is_the_median_first_span(capsys):
    events = ([span("produce", "data", 0.0, 1400.0, index=0),
               span("produce", "data", 2.0, 1500.0, index=0),
               span("produce", "data", 3.9, 1900.0, index=0),
               span("produce", "data", 9.0, 5000.0, index=0)]  # too late
              + [span("produce", "data", 1.5 + 0.01 * i, d, index=i + 1)
                 for i, d in enumerate([2.0, 4.0, 3.0])])
    assert read("pass_start_produce_ms", events) == approx(1500.0)
    assert "other 3 produce spans 3.000 ms" in capsys.readouterr().err


@pytest.mark.parametrize("metric", ["input_wait_share", "h2d_mbytes_per_s",
                                    "pass_start_produce_ms"])
def test_nothing_to_read_is_none(metric):
    """A program without the spans (the parent commit; the unfed path)."""
    events = [dispatch(0.1, 0.2), span("host_to_device", "data", 0.0, 10.0),
              span("feed_stall", "data", 0.0, 300.0)]
    assert read(metric, events) is None
