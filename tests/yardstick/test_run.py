"""The command from its arguments to its last line, on the CPU: the
refusal of the machine is stepped over, and a traced run is given the
recorded trace in place of one the CPU cannot make."""

import json

import jax
import pytest

from yardstick import device, run, xplane
from yardstick.cells import ROOT
from yardstick.tracing import Window

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
RECORDED = ROOT / "yardstick" / "testdata" / "resnet50_fit_3steps.xplane.pb"


@pytest.fixture()
def on_cpu(monkeypatch):
    monkeypatch.setattr(device, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(device, "peak_bytes", lambda dev: 5_000_000_000)
    monkeypatch.setattr(Window, "device_trace",
                        lambda self: xplane.load(RECORDED, 1))


def last_lines(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return out[-2], json.loads(out[-1])


@pytest.mark.parametrize("cell, metrics", [
    ("dummy-mlp.fit", {"train_examples_per_s_per_chip", "setup_s"}),
    ("dummy-mlp.serve", {"serve_latency_p50_ms", "serve_latency_p99_ms",
                         "serve_goodput_per_s", "setup_s"}),
])
def test_untraced_line_has_the_contracts_keys_and_the_cells_end_to_end(
        dummy_root, on_cpu, capsys, cell, metrics):
    rc = run.main(["--workload", cell, "--seed", "5", "--seconds", "0.5",
                   "--trace", "0"], root=dummy_root)
    notes, line = last_lines(capsys)
    assert rc == 0 and set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": jax.device_count(),
                              "memory_peak_bytes": 5_000_000_000}
    # the values the run resolved, on the line before the last
    assert notes.startswith("notes ")
    resolved = json.loads(notes[len("notes "):])
    if cell.endswith(".fit"):
        assert resolved["fit.k_steps"] == 1 and resolved["feeder.depth"] == 2
        assert resolved["tuned_config"] is None


def test_traced_line_has_the_per_layer_metrics_and_the_breakdown(
        dummy_root, on_cpu, capsys):
    rc = run.main(["--workload", "dummy-mlp.fit", "--seed", "5", "--seconds",
                   "9", "--trace", "1"], root=dummy_root)
    _, line = last_lines(capsys)
    assert rc == 0 and set(line) == LINE_KEYS | {"breakdown"}
    # the traffic file's trace_seconds (1), not --seconds, was traced
    assert line["metrics"] == {"dummy_steps": {
        "value": float(line["attempted"]), "unit": "steps"}}
    assert line["device"]["busy_s"] > 0
    assert line["device"]["window_s"] == pytest.approx(1.613935876)
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    for rows in line["breakdown"].values():
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)


def test_a_reader_that_fails_or_finds_nothing_leaves_its_metric_out(
        dummy_root, on_cpu, capsys):
    manifest = json.loads((dummy_root / "BENCHMARK.json").read_text())
    for name, body in (("finds_nothing", "def read(obs):\n    return None\n"),
                       ("fails", "def read(obs):\n    return 1 / 0\n")):
        manifest["per_layer"].append(dict(manifest["per_layer"][0], name=name))
        (dummy_root / f"yardstick/metrics/{name}.py").write_text(body)
    (dummy_root / "BENCHMARK.json").write_text(json.dumps(manifest))
    run.main(["--workload", "dummy-mlp.fit", "--seed", "5", "--seconds", "1",
              "--trace", "1"], root=dummy_root)
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    assert set(line["metrics"]) == {"dummy_steps"}
    assert "ZeroDivisionError" in captured.err      # said, not swallowed


def test_a_cell_that_is_to_report_what_its_driver_lacks_is_an_error(
        dummy_root, on_cpu):
    manifest = json.loads((dummy_root / "BENCHMARK.json").read_text())
    manifest["end_to_end"][1]["workloads"].append("dummy-mlp.fit")
    (dummy_root / "BENCHMARK.json").write_text(json.dumps(manifest))
    with pytest.raises(KeyError, match="serve_latency_p50_ms"):
        run.main(["--workload", "dummy-mlp.fit", "--seed", "5", "--seconds",
                  "0.3", "--trace", "0"], root=dummy_root)


def test_an_unknown_cell_names_the_ones_there_are(dummy_root):
    with pytest.raises(KeyError, match="dummy-mlp.serve"):
        run.main(["--workload", "nope", "--seed", "1", "--seconds", "1",
                  "--trace", "0"], root=dummy_root)
