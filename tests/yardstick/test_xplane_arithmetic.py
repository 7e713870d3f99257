"""The interval arithmetic under the xplane reduction, on intervals small
enough to work out by hand."""

import numpy as np
from pytest import approx

from yardstick import xplane
from yardstick.xplane import DeviceTrace, Line


def line(*events):
    """events: (name, start, end)"""
    return Line([e[0] for e in events],
                np.array([e[1] for e in events], float),
                np.array([e[2] for e in events], float))


def test_merge_joins_touching_and_nested_intervals():
    s, e = xplane.merge(np.array([5.0, 0.0, 1.0, 9.0, 6.0]),
                        np.array([8.0, 2.0, 3.0, 10.0, 7.0]))
    assert s.tolist() == [0.0, 5.0, 9.0] and e.tolist() == [3.0, 8.0, 10.0]
    assert xplane.merge(np.zeros(0), np.zeros(0))[0].size == 0


def test_covered_measures_each_query_against_the_union():
    start, end = np.array([0.0, 5.0, 9.0]), np.array([3.0, 8.0, 10.0])
    a = np.array([-1.0, 1.0, 2.0, 3.0, 0.0, 8.5])
    b = np.array([0.0, 2.0, 6.0, 5.0, 10.0, 20.0])
    assert xplane.covered(start, end, a, b).tolist() == [
        0.0, 1.0, 2.0, 0.0, 7.0, 1.0]


def test_gaps_are_the_complement_inside_the_window():
    a, b = xplane.gaps(line(("x", 2, 4), ("y", 3, 6), ("z", 8, 9)), 0, 10)
    assert list(zip(a, b)) == [(0, 2), (6, 8), (9, 10)]
    a, b = xplane.gaps(line(("x", 0, 10)), 0, 10)
    assert len(a) == 0


def test_clip_cuts_events_to_the_window_and_drops_the_rest():
    got = line(("before", 0, 1), ("across", 1, 4), ("in", 5, 6),
               ("after", 11, 12)).clip(2, 10)
    assert got.names == ["across", "in"]
    assert got.start.tolist() == [2, 5] and got.end.tolist() == [4, 6]


def _trace(ops, modules=(), lo=0.0, hi=100.0):
    return DeviceTrace(ops=[line(*o) for o in ops],
                       modules=[line(*m) for m in modules] or [line()],
                       lo=lo, hi=hi, perf_at_lo=50.0)


def test_busy_is_the_union_averaged_over_chips_and_nesting_counts_once():
    t = _trace([[("while.1", 0, 40), ("fusion.1", 0, 10), ("fusion.2", 10, 30),
                 ("copy.3", 60, 70)],
                [("fusion.1", 0, 20)]], hi=100.0)
    # chip 0: [0,40) u [60,70) = 50 ns; chip 1: 20 ns
    assert xplane.busy_s(t) == approx(35e-9)
    # the container is not an operation of its own; per-chip average
    top = xplane.top_ops(t)
    assert [n for n, _ in top] == ["fusion.1", "fusion.2", "copy.3"]
    assert [s for _, s in top] == approx([15e-9, 10e-9, 5e-9])


def test_main_module_and_its_whole_runs():
    mods = [("jit_step(1)", -5, 8), ("jit_step(1)", 10, 18),
            ("jit_small(2)", 18, 19), ("jit_step(1)", 22, 30),
            ("jit_step(1)", 95, 105)]
    t = _trace([[("fusion.1", 0, 1)]], [mods])
    t.modules = [t.modules[0].clip(t.lo, t.hi)]
    assert xplane.main_module(t) == "jit_step(1)"
    runs = xplane.module_runs(t, "jit_step(1)")
    # the runs cut by the window's edges are not whole
    assert list(zip(runs.start, runs.end)) == [(10, 18), (22, 30)]
    assert len(xplane.module_runs(t).start) == 3


def test_exposed_collective_time_is_what_no_other_operation_covers():
    t = _trace([[("all-reduce.1", 0, 10), ("fusion.1", 4, 6),
                 ("all-reduce.2", 20, 30), ("fusion.2", 25, 40),
                 ("while.3", 0, 40)]])
    coll = xplane.collectives(xplane.leaf_ops(t.ops[0]))
    assert coll.names == ["all-reduce.1", "all-reduce.2"]
    # 20 ns of collectives, of which [4,6) and [25,30) are hidden
    assert xplane.exposed_collective_s(t) == approx(13e-9)


def test_idle_goes_to_the_host_span_that_covers_most_of_each_gap():
    t = _trace([[("fusion.1", 10, 20), ("fusion.2", 50, 60),
                 ("fusion.3", 90, 100)]])
    # gaps: [0,10) [20,50) [60,90)
    host = {"data.feed_stall": (np.array([22.0]), np.array([48.0])),
            "data.etl": (np.array([23.0, 61.0]), np.array([30.0, 70.0])),
            "step.dispatch": (np.array([62.0]), np.array([88.0]))}
    got = dict(xplane.idle_by_host_span(t, host))
    # [20,50): feed_stall covers 26 of 30, its inner etl 7; [60,90):
    # dispatch covers 26, etl 9; [0,10): nothing covers half
    assert got == approx({"data.feed_stall": 30e-9, "step.dispatch": 30e-9,
                          "no_span": 10e-9})
    # a gap no span of the program covers goes to the runtime's own event
    t.runtime = {"XlaLinearize": (np.array([1.0, 21.0]), np.array([9.0, 49.0]))}
    got = dict(xplane.idle_by_host_span(t, host))
    assert got == approx({"data.feed_stall": 30e-9, "step.dispatch": 30e-9,
                          "runtime.XlaLinearize": 10e-9})
    assert t.to_trace_ns(50.0) == 0.0 and t.to_trace_ns(50.5) == 0.5e9
