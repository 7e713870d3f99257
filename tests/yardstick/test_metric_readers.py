"""Every per-layer metric's reader, given the recorded traces and spans
made by hand, against values worked out from them."""

import dataclasses

import numpy as np
import pytest
from pytest import approx

from yardstick import cells, xplane
from yardstick.cells import ROOT
from yardstick.compiles import Compiles
from yardstick.observed import Observed
from yardstick.spans import Spans

DATA = ROOT / "yardstick" / "testdata"
T0 = 1000.0                       # the tracer's zero on perf_counter


def span(name, cat, start_s, dur_ms, **args):
    ev = {"name": name, "cat": cat, "ph": "X", "ts": start_s * 1e6,
          "dur": dur_ms * 1e3}
    if args:
        ev["args"] = args
    return ev


EVENTS = (
    [span("dispatch", "step", 0.1 * i, d) for i, d in
     enumerate([2.0, 3.0, 40.0])]
    + [span("feed_stall", "data", 0.05, 300.0),
       span("feed_stall", "data", 0.9, 100.0),
       span("host_to_device", "data", 0.2, 10.0, bytes=1),
       span("host_to_device", "data", 0.3, 14.0, bytes=1),
       span("etl", "data", 0.05, 1.0)]
    + [span("queue_wait", "serve", 0.1, w) for w in (1.0, 5.0, 2.0)]
    + [span("batch_form", "serve", 0.1, 0.2, n=3, bucket=4),
       span("batch_form", "serve", 0.2, 0.4, n=8, bucket=8),
       span("dispatch", "serve", 0.1, 0.5), span("dispatch", "serve", 0.2, 0.7),
       span("fetch", "serve", 0.1, 0.1), span("fetch", "serve", 0.2, 0.3)]
    + [span("dispatch", "step", 5.0, 999.0),          # after the window
       {"name": "serve_compile", "cat": "serve", "ph": "i", "ts": 1.0}])


def observed(cell_name, trace_file, chips, **facts):
    cell = cells.resolve_cell(cell_name)
    compiles = Compiles.__new__(Compiles)
    compiles.seconds, compiles.cache_hits, compiles.in_window = 4.5, 4, 0
    return Observed(
        cell=cell,
        spans=Spans(EVENTS, T0, (T0, T0 + 2.0)),
        device=xplane.load(DATA / trace_file, chips), compiles=compiles,
        device_kind="TPU v5 lite", memory_peak_bytes=4_875_736_576,
        facts=facts)


def read(obs, metric):
    return cells.load_reader(obs.cell, metric).read(obs)


@pytest.fixture(scope="module")
def fit():
    return observed("resnet50-tiny64.fit", "resnet50_fit_3steps.xplane.pb", 1,
                    steps=3, etl_stall_ms=400.0,
                    flops_per_step_per_chip=1280 * 1891074048)


@pytest.fixture(scope="module")
def dp4():
    return observed("resnet50-tiny64.fit-dp4",
                    "resnet50_dp4_1step_2chips.xplane.pb", 2, steps=1,
                    flops_per_step_per_chip=1280 * 1891074048)


@pytest.mark.parametrize("metric, want", [
    ("host_dispatch_ms", 3.0),            # median of 2, 3, 40; 999 is outside
    ("h2d_ms", 12.0),
    ("feed_stall_share", 20.0),           # 0.4 s of a 2 s window
    ("device_step_ms", 35.921769),
    ("dispatch_gap_ms", (1499.89268 + 0.018695) / 2),
    # 1280 x 1.891 GFLOP = 2.42 TFLOP at 197 TFLOP/s is 12.29 ms of 35.92
    ("train_step_roofline", 100 * 12.2871 / 35.921769),
    ("device_idle_share", 93.26),
    ("device_peak_bytes", 4_875_736_576),
    ("compile_s", 4.5), ("cache_hits", 4), ("compiles_in_window", 0),
    ("queue_wait_p50_ms", 2.0),
    ("batch_rows_mean", 5.5),
    ("serve_host_ms", 0.3 + 0.6 + 0.2),
])
def test_readers_on_one_chip(fit, metric, want):
    assert read(fit, metric) == approx(want, rel=1e-3)


def test_collective_readers_need_more_than_one_chip(fit, dp4):
    assert read(fit, "collective_ms_per_step") is None
    assert read(fit, "collective_exposed_share") is None
    # 1.222 ms on one chip and 4.113 ms on the other, one step
    assert read(dp4, "collective_ms_per_step") == approx(
        (1.221995 + 4.113049) / 2)
    assert read(dp4, "collective_exposed_share") == approx(
        100 * 0.002667522 / 0.045780493)
    assert read(dp4, "device_step_ms") == approx(39.780493)


def test_readers_that_find_nothing_return_nothing(fit):
    bare = dataclasses.replace(fit, spans=Spans([], T0, (T0, T0 + 2.0)),
                               facts={})
    for metric in ("host_dispatch_ms", "h2d_ms", "queue_wait_p50_ms",
                   "batch_rows_mean", "serve_host_ms", "generator_lag_p99_ms",
                   "train_step_roofline"):
        assert read(bare, metric) is None, metric
    assert read(bare, "feed_stall_share") == 0.0     # a fit that is not fed


def test_feed_stall_says_when_spans_and_gauge_disagree(fit, capsys):
    off = dataclasses.replace(fit, facts={**fit.facts, "etl_stall_ms": 900.0})
    assert read(off, "feed_stall_share") == approx(20.0)
    assert "dl4j_etl_stall_ms says 0.900" in capsys.readouterr().err


def test_generator_lag_reads_the_drivers_lags(fit):
    lags = np.concatenate([np.full(99, 0.1), [7.0]])
    with_lags = dataclasses.replace(fit, facts={"lags_ms": lags})
    assert read(with_lags, "generator_lag_p99_ms") == approx(
        np.percentile(lags, 99))


def test_roofline_refuses_a_chip_with_no_published_peaks(fit):
    other = dataclasses.replace(fit, device_kind="TPU v9 imaginary")
    with pytest.raises(KeyError, match="no published peaks"):
        read(other, "train_step_roofline")


def test_breakdown_puts_program_spans_on_the_profilers_clock(fit):
    # a stall span laid over the recorded trace's long gap
    lo = fit.device.perf_at_lo
    spans = Spans([span("feed_stall", "data", lo - T0 + 0.045, 1485.0)], T0,
                  (lo, lo + 2.0))
    b = dataclasses.replace(fit, spans=spans).breakdown()
    assert b["idle_gaps"][0] == ["data.feed_stall",
                                 approx(1.499897, rel=1e-5)]
    assert b["device_ops"][0][0] == "fusion f32[64]"
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
