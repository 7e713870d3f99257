"""The three readers of the fit loop's own waits (``loop_blocked_share``,
``fit_call_lead_in_ms``, ``steps_in_flight_p50``) on spans made by hand,
against values worked out from them."""

import types

import pytest
from pytest import approx

from yardstick import cells
from yardstick.spans import Spans

T0 = 1000.0                       # the tracer's zero on perf_counter
WINDOW_S = 4.0
READERS = ("loop_blocked_share", "fit_call_lead_in_ms",
           "steps_in_flight_p50")


def span(name, start_s, end_s, **args):
    ev = {"name": name, "cat": "step", "ph": "X", "ts": start_s * 1e6,
          "dur": (end_s - start_s) * 1e6}
    if args:
        ev["args"] = args
    return ev


def blocked(start_s, end_s, on="iteration", in_flight=1, **args):
    return span("blocked", start_s, end_s, on=on, in_flight=in_flight, **args)


def dispatch(start_s, in_flight=None, **args):
    if in_flight is not None:
        args["in_flight"] = in_flight
    return span("dispatch", start_s, start_s + 0.003, **args)


def read(metric, events, cell="resnet50-tiny64.fit"):
    cell = cells.resolve_cell(cell)
    obs = types.SimpleNamespace(
        cell=cell, spans=Spans(events, T0, (T0, T0 + WINDOW_S)))
    return cells.load_reader(cell, metric).read(obs)


# the parent's program: the hand-off's spans and dispatches without a count
BEFORE_PR_35 = [dispatch(0.1, seq=0), dispatch(0.2, seq=1),
                span("step_scopes", 0.1, 0.1, table={}),
                {"name": "blocked", "cat": "serve", "ph": "X",
                 "ts": 0.5e6, "dur": 1e5}]      # another layer's span


@pytest.mark.parametrize("metric", READERS)
@pytest.mark.parametrize("events", [[], BEFORE_PR_35],
                         ids=["no_spans", "the_parents_spans"])
def test_none_where_the_program_records_nothing_to_read(metric, events):
    assert read(metric, events) is None


@pytest.mark.parametrize("cell", [c["name"] for c in
                                  cells.load_manifest()["workloads"]])
def test_every_cell_is_to_report_all_three(cell):
    reported = {m["name"]: m for m in cells.resolve_cell(cell).per_layer}
    for name in READERS:
        assert reported[name]["layer"] == "step"
        assert reported[name]["source"] == "program_span"
        assert "workloads" not in reported[name]


@pytest.mark.parametrize("events, seconds", [
    # two reads that touch (0.5-0.9, 0.9-1.0) and one inside another
    # (2.0-2.6 holds 2.1-2.2): a union, 0.5 + 0.6, where a sum says 1.2
    ([blocked(0.5, 0.9), blocked(0.9, 1.0, on="routing"),
      blocked(2.0, 2.6, on="collective"), blocked(2.1, 2.2)], 1.1),
    # one that begins before the window and one after it are left out
    ([blocked(-0.5, 0.3), blocked(1.0, 1.25), blocked(4.5, 5.0)], 0.25),
    # one that is still waiting as the window ends is cut there
    ([blocked(3.5, 6.0, on="routing")], 0.5),
    # a dispatch is no wait of this kind
    ([blocked(1.0, 1.1), dispatch(2.0, 3)], 0.1),
], ids=["union", "begins_in_the_window", "cut_at_the_end", "blocked_only"])
def test_loop_blocked_share_is_the_union_over_the_window(events, seconds):
    assert read("loop_blocked_share", events) == approx(
        100.0 * seconds / WINDOW_S)


def test_loop_blocked_share_says_what_the_loop_waited_on(capsys):
    read("loop_blocked_share", [blocked(0.5, 0.75), blocked(1.0, 1.25),
                                blocked(2.0, 3.0, on="routing")])
    said = capsys.readouterr().err
    assert "on=iteration 0.500 s" in said and "on=routing 1.000 s" in said


@pytest.mark.parametrize("events, ms", [
    # lead-ins of 30 + 100, 10 + 40 and 500 + 1300 ms: the median call
    ([blocked(0.5, 0.6, since_call_ms=30.0),
      blocked(1.0, 1.04, since_call_ms=10.0),
      blocked(2.0, 3.3, since_call_ms=500.0)], 130.0),
    # the other reads of the loop are no call's lead-in
    ([blocked(0.5, 0.6, since_call_ms=20.0),
      blocked(1.0, 3.0, on="routing"),
      blocked(3.1, 3.2, on="collective")], 120.0),
    # a call whose first read began before the window is left out
    ([blocked(-0.2, 0.4, since_call_ms=5.0),
      blocked(1.0, 1.2, since_call_ms=50.0)], 250.0),
], ids=["median", "iteration_only", "begins_in_the_window"])
def test_fit_call_lead_in_is_the_median_call(events, ms):
    assert read("fit_call_lead_in_ms", events) == approx(ms)


def test_fit_call_lead_in_is_none_without_a_call_in_the_window():
    assert read("fit_call_lead_in_ms",
                [blocked(1.0, 3.0, on="routing"), dispatch(0.5, 2)]) is None


@pytest.mark.parametrize("events, steps", [
    ([dispatch(0.1, 0), dispatch(0.2, 1), dispatch(0.3, 2), dispatch(0.4, 9),
      dispatch(0.5, 10)], 2.0),
    ([dispatch(0.1, 4, k=4, seq=0), dispatch(0.2, 8, k=4, seq=1)], 6.0),
    # dispatches outside the window, one without a count, a wait's count
    ([dispatch(-0.1, 30), dispatch(0.1, 3), dispatch(0.2), dispatch(4.2, 30),
      blocked(1.0, 1.1, in_flight=20)], 3.0),
    ([dispatch(0.1, 0), dispatch(0.2, 0), dispatch(0.3, 1)], 0.0),
], ids=["median", "even_count", "dispatches_in_the_window", "zero"])
def test_steps_in_flight_is_the_median_dispatch(events, steps):
    assert read("steps_in_flight_p50", events) == approx(steps)


def test_through_the_wrappers_spans_in_the_four_chip_cell():
    events = [blocked(0.1, 1.4, since_call_ms=40.0, in_flight=11),
              dispatch(1.5, 0, seq=1), dispatch(1.6, 1, seq=2)]
    cell = "resnet50-tiny64.fit-dp4"
    assert read("fit_call_lead_in_ms", events, cell) == approx(1340.0)
    assert read("steps_in_flight_p50", events, cell) == approx(0.5)
    assert read("loop_blocked_share", events, cell) == approx(32.5)


@pytest.mark.parametrize("cell", ["dummy-mlp.fit", "dummy-mlp.fit-dp4"])
def test_a_traced_run_through_the_driver_prints_all_three(
        dummy_root, monkeypatch, capsys, cell):
    """``fit()`` and ``ParallelWrapper.fit()`` under the driver's tracer, on
    the CPU: the program's spans reach the readers as the harness hands
    them over (the device's side is a recorded trace)."""
    import json

    import jax

    from yardstick import device, run, xplane
    from yardstick.tracing import Window
    recorded = cells.ROOT / "yardstick/testdata/resnet50_fit_3steps.xplane.pb"
    monkeypatch.setattr(device, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(device, "peak_bytes", lambda dev: 5_000_000_000)
    monkeypatch.setattr(Window, "device_trace",
                        lambda self: xplane.load(recorded, 1))
    manifest = json.loads((dummy_root / "BENCHMARK.json").read_text())
    real = {m["name"]: m for m in cells.load_manifest()["per_layer"]}
    for name in READERS:
        manifest["per_layer"].append(real[name])
        (dummy_root / f"yardstick/metrics/{name}.py").write_text(
            (cells.ROOT / f"yardstick/metrics/{name}.py").read_text())
    (dummy_root / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert run.main(["--workload", cell, "--seed", "7", "--seconds", "1",
                     "--trace", "1"], root=dummy_root) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    got = {name: line["metrics"][name]["value"] for name in READERS}
    assert 0 < got["loop_blocked_share"] < 100
    assert got["fit_call_lead_in_ms"] > 0
    assert got["steps_in_flight_p50"] >= 0
