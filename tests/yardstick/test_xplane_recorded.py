"""The reduction on two traces recorded on the chip by this benchmark's
first traced runs (PR 22) and cut by ``yardstick/testdata/trim_xplane.py``.

``resnet50_fit_3steps``: three optimizer steps of ``resnet50-tiny64.fit``
(batch 1280) on one v5e chip, 1.614 s: one step, 1.46 s in which the chip
waits for the host to transpose the next batches, then two steps back to
back. ``resnet50_dp4_1step_2chips``: one step of ``.fit-dp4`` on the first
two of four chips, 45.8 ms. The expected numbers were read off the traces
with a separate, slower sweep (below) and a text dump of the events."""


import numpy as np
import pytest
from pytest import approx

from yardstick import xplane
from yardstick.cells import ROOT

DATA = ROOT / "yardstick" / "testdata"


def sweep_union_ns(line):
    """Union length by sorting the edges and counting depth."""
    edges = sorted([(s, 0, 1) for s in line.start]      # starts first at a tie
                   + [(e, 1, -1) for e in line.end])
    depth, since, total = 0, 0.0, 0.0
    for t, _, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            total += t - since
    return total


@pytest.fixture(scope="module")
def fit():
    return xplane.load(DATA / "resnet50_fit_3steps.xplane.pb", 1)


@pytest.fixture(scope="module")
def dp4():
    return xplane.load(DATA / "resnet50_dp4_1step_2chips.xplane.pb", 2)


def test_window_and_clock_come_from_the_two_annotations(fit):
    assert fit.window_s == approx(1.613935876)
    assert fit.perf_at_lo == approx(287.321117931)
    # a span the program timed 0.5 s after the window opened
    assert fit.to_trace_ns(287.821117931) - fit.lo == approx(0.5e9)
    with pytest.raises(ValueError, match="the cell used 4"):
        xplane.load(DATA / "resnet50_fit_3steps.xplane.pb", 4)


def test_idle_share_of_the_recorded_window(fit):
    busy = xplane.busy_s(fit)
    assert busy == approx(0.108779925)
    assert busy == approx(sweep_union_ns(fit.ops[0]) / 1e9)
    # three steps of 36 ms in 1.614 s: the chip is idle 93% of the time
    assert 100 * (1 - busy / fit.window_s) == approx(93.26, abs=0.01)


def test_step_time_and_the_gaps_between_steps(fit):
    assert xplane.main_module(fit) == "jit_step"
    runs = xplane.module_runs(fit, "jit_step")
    assert (runs.end - runs.start) / 1e6 == approx(
        [35.921769, 36.182943, 35.919788])
    assert (runs.start - fit.lo) / 1e6 == approx(
        [5.0, 1540.81445, 1577.01609])
    gaps_ms = (runs.start[1:] - runs.end[:-1]) / 1e6
    assert gaps_ms == approx([1499.89268, 0.018695], rel=1e-4)
    # beside the step, each iteration runs the two small programs of
    # jax.random.split
    assert set(fit.modules[0].names) == {"jit_step", "jit__threefry_split",
                                         "jit__unstack"}


def test_top_operations_are_kinds_with_their_result_shape(fit):
    top = xplane.top_ops(fit)
    assert len(top) == 10
    assert top[0] == ["fusion f32[64]", approx(0.008127207)]
    assert top[1] == ["multiply_reduce_fusion bf16[256]", approx(0.00665928)]
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    # every operation is a leaf here (no loop in the step), so the kinds'
    # seconds add up to the operations' own
    leaves = xplane.leaf_ops(fit.ops[0])
    assert len(leaves.names) == len(fit.ops[0].names) == 13245
    assert len(xplane.collectives(leaves).names) == 0


def test_the_long_gap_goes_to_what_the_host_was_doing(fit):
    a, b = xplane.gaps(fit.ops[0], fit.lo, fit.hi)
    assert np.max(b - a) / 1e6 == approx(1458.972537)
    # no span of the program: the runtime's threads were transposing
    by_runtime = dict(xplane.idle_by_host_span(fit, {}))
    assert by_runtime["runtime.Transpose"] == approx(1.503083, rel=1e-4)
    assert sum(by_runtime.values()) == approx(fit.window_s
                                              - xplane.busy_s(fit))
    # a program span that covers it wins over the runtime's event
    stall = {"data.feed_stall": (np.array([fit.lo + 45e6]),
                                 np.array([fit.lo + 1530e6]))}
    by_span = dict(xplane.idle_by_host_span(fit, stall))
    # the long gap and the shorter ones around the two small programs
    # that run between the first step and the second
    assert by_span["data.feed_stall"] == approx(1.499897, rel=1e-5)
    assert "runtime.Transpose" in by_span      # the other gaps' still


def test_collectives_on_two_of_four_chips(dp4):
    assert dp4.window_s == approx(0.045780493)
    assert [len(line.names) for line in dp4.ops] == [4767, 4793]
    per_chip = [xplane.collectives(xplane.leaf_ops(line)) for line in dp4.ops]
    assert [len(c.names) for c in per_chip] == [99, 101]
    assert all(n.startswith("all-reduce") for c in per_chip for n in c.names)
    assert [float(np.sum(c.end - c.start)) / 1e6 for c in per_chip] == approx(
        [1.221995, 4.113049])
    assert xplane.exposed_collective_s(dp4) == approx(0.002667522)
    assert xplane.busy_s(dp4) == approx(0.039532203)
    assert xplane.busy_s(dp4) == approx(
        np.mean([sweep_union_ns(line) for line in dp4.ops]) / 1e9)
    runs = xplane.module_runs(dp4, "jit_step")
    assert (runs.end - runs.start) / 1e6 == approx([39.780493])
    assert dict(xplane.top_ops(dp4))["all-reduce f32[64]"] > 0


def test_a_trace_without_the_annotations_is_refused(tmp_path):
    empty = tmp_path / "plugins" / "profile" / "x"
    empty.mkdir(parents=True)
    with pytest.raises(FileNotFoundError):
        xplane.newest_xplane(tmp_path)
    from jax.profiler import ProfileData
    (empty / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(
            'planes { name: "/host:CPU" }'))
    assert xplane.newest_xplane(tmp_path).name == "t.xplane.pb"
    with pytest.raises(ValueError, match="annotations"):
        xplane.load(xplane.newest_xplane(tmp_path), 1)


@pytest.mark.parametrize("text, short, kind", [
    ("%fusion.398 = (bf16[128,128,3072]{2,1,0:T(8,128)(2,1)S(1)}, bf16[1]) "
     "fusion(bf16[3072] %copy-done.591), kind=kOutput",
     "fusion.398", "fusion bf16[128,128,3072]"),
    ("%all-reduce.12 = f32[64,256]{1,0} all-reduce(f32[64,256] %x)",
     "all-reduce.12", "all-reduce f32[64,256]"),
    ("%while.3 = (s32[]) while(%tuple)", "while.3", "while s32[]"),
    ("jit_step(12693294662087768080)", "jit_step", "jit_step"),
    ("XlaLinearize", "XlaLinearize", "XlaLinearize"),
])
def test_names_are_cut_from_hlo_text(text, short, kind):
    assert xplane.short_name(text) == short
    assert xplane.kind_of(text) == kind
