"""The two drivers at a preset small enough for the CPU, on cells that
exist only as new files (``conftest.py``): what the command does after
its refusal of anything but a TPU."""

import time

import jax
import pytest

from yardstick import cells
from yardstick.compiles import Compiles


def _run(root, cell_name, seconds=1.0, seed=3):
    cell = cells.resolve_cell(cell_name, root)
    driver = cells.load_driver(cell)
    return cell, driver.run(cell, seed, seconds, False, Compiles(),
                            jax.devices()[:cell.chips], time.perf_counter())


@pytest.mark.parametrize("cell_name", ["dummy-mlp.fit", "dummy-mlp.fit-dp4"])
def test_fit_loop_runs_a_cell_made_of_new_files(dummy_root, cell_name):
    cell, out = _run(dummy_root, cell_name)
    assert out.correct, out.notes
    assert out.attempted > 0 and out.failed == 0
    assert out.notes["loss_rel_err"] <= cell.config["loss_tolerance"]
    assert out.notes["loss_fell"] and out.notes["compiles_in_window"] == 0
    assert out.notes["fit.k_steps"] == 1 and out.notes["feeder.depth"] == 2
    rate = out.end_to_end["train_examples_per_s_per_chip"]
    workers = cell.traffic["workers"]
    assert rate == pytest.approx(
        out.attempted * cell.config["batch"] * workers
        / out.notes["window_s"] / workers)
    assert out.end_to_end["setup_s"] > 0
    # the window ends within a few steps of the deadline
    assert 1.0 <= out.notes["window_s"] < 3.0
    if workers > 1:
        assert out.notes["replicas_agree"] and out.notes["shards_ok"]
        assert out.notes["shard_rows"][0] == {
            d.id: cell.config["batch"] for d in jax.devices()[:workers]}


def test_fit_loop_says_incorrect_when_the_reference_disagrees(dummy_root):
    cell = cells.resolve_cell("dummy-mlp.fit", dummy_root)
    ref = dummy_root / "yardstick/reference/dummy_mlp.py"
    ref.write_text(ref.read_text().replace("jnp.tanh(h)", "jax.nn.relu(h)"))
    out = cells.load_driver(cell).run(cell, 3, 0.3, False, Compiles(),
                                      jax.devices()[:1], time.perf_counter())
    assert not out.correct
    assert out.notes["loss_rel_err"] > cell.config["loss_tolerance"]


def test_open_loop_serves_a_cell_made_of_new_files(dummy_root):
    cell, out = _run(dummy_root, "dummy-mlp.serve")
    assert out.correct, out.notes
    n = out.attempted
    assert n == out.notes["requests"] and 150 < n < 500     # 300/s for 1 s
    assert out.failed == 0
    e = out.end_to_end
    assert 0 < e["serve_latency_p50_ms"] <= e["serve_latency_p99_ms"]
    assert e["serve_goodput_per_s"] == pytest.approx(n / 1.0)
    assert out.notes["generator_lag_p99_ms"] < 50
    assert out.notes["reference_rel_err"] <= cell.config["output_tolerance"]
