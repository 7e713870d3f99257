"""``BENCHMARK.json`` against the contract's limits, and every name in it
against the files the harness will look for."""

import json
import re

import pytest

from yardstick import cells
from yardstick.cells import ROOT

MANIFEST = cells.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert all(isinstance(a, str) for a in MANIFEST["command"])
    assert len(MANIFEST["command"]) <= 32


def test_names_are_plain_and_used_once():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in MANIFEST[k]]
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    assert all(len(e["why"]) <= 200
               for k in ("configs", "workloads") for e in MANIFEST[k])


def test_cells_are_within_the_contracts_counts():
    w = MANIFEST["workloads"]
    assert 2 <= len(w) <= 24
    assert len({(c["config"], c["traffic"]) for c in w}) == len(w)
    assert all(c["chips"] in (1, 4) for c in w)
    assert sum(c["chips"] == 4 for c in w) <= max(1, len(w) // 4)
    used = {c["config"] for c in w}
    assert used == {c["name"] for c in MANIFEST["configs"]}


def test_metrics_are_within_the_contracts_limits():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert "bound" not in m
    cell_names = {c["name"] for c in MANIFEST["workloads"]}
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert set(m.get("workloads", cell_names)) <= cell_names


@pytest.mark.parametrize("cell_name",
                         [c["name"] for c in MANIFEST["workloads"]])
def test_every_cell_resolves_to_files_that_exist(cell_name):
    cell = cells.resolve_cell(cell_name)
    assert cell.config["name"] == cell_name.split(".")[0]
    for fn in ("build", "train_set", "check_batch",
               "train_flops_per_example"):
        assert callable(getattr(cells.load_build(cell), fn))
    assert callable(cells.load_reference(cell).loss)
    assert callable(cells.load_driver(cell).run)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        reader = cells.load_reader(cell, m["name"])
        assert callable(reader.read) and reader.__doc__
        # a per-layer metric is reported only where the metric it moves is
        assert m["moves"] in {e["name"] for e in cell.end_to_end}


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_configuration_files_state_their_source_and_cuts(config):
    path = ROOT / config["file"]
    assert any(str(path.relative_to(ROOT)).startswith(p + "/")
               for p in MANIFEST["paths"])
    body = json.loads(path.read_text())
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"]
    assert all(k in body for k in config["reduced"])
    assert not [k for k in config["reduced"] if re.search(
        r"(_dim|_rank|hidden|intermediate|latent|state|head|expansion|"
        r"width|per_tok)", k)], "a width may never be reduced"
    assert "assumed" in body and "batch" in body


def test_traffic_files_are_data_that_name_a_driver():
    for path in (ROOT / "yardstick" / "traffic").glob("*"):
        assert path.suffix == ".json", path
        body = json.loads(path.read_text())
        assert body["name"] == path.stem
        assert (ROOT / "yardstick" / "drivers"
                / f"{body['driver']}.py").is_file()
