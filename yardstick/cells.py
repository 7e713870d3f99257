"""``BENCHMARK.json`` and the files it names.

A cell joins a configuration, a traffic file and a chip count by name.
Nothing here knows a configuration, a traffic mix or a metric: a later PR
adds ``configs/<name>/``, ``traffic/<name>.json`` or ``metrics/<name>.py``
and a manifest entry, and edits no file that exists.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    root: Path                    # the checkout BENCHMARK.json was read from
    chips: int
    config: Dict[str, Any]        # the configuration file's contents
    config_dir: Path              # holds config.json and build.py
    traffic: Dict[str, Any]       # the traffic file's contents
    end_to_end: List[Dict[str, Any]]   # manifest entries this cell reports
    per_layer: List[Dict[str, Any]]


def load_manifest(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as fh:
        return json.load(fh)


def _by_name(entries: List[Dict[str, Any]], name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}; it has "
                   f"{sorted(e['name'] for e in entries)}")


def _reported_by(entries: List[Dict[str, Any]], cell: str):
    """A metric with no ``workloads`` key belongs to every cell."""
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def resolve_cell(name: str, root: Path = ROOT) -> Cell:
    manifest = load_manifest(root)
    w = _by_name(manifest["workloads"], name, "workload")
    c = _by_name(manifest["configs"], w["config"], "config")
    config_file = root / c["file"]
    with open(config_file) as fh:
        config = json.load(fh)
    traffic_file = root / "yardstick" / "traffic" / f"{w['traffic']}.json"
    with open(traffic_file) as fh:
        traffic = json.load(fh)
    return Cell(name=name, root=root, chips=int(w["chips"]), config=config,
                config_dir=config_file.parent, traffic=traffic,
                end_to_end=_reported_by(manifest["end_to_end"], name),
                per_layer=_reported_by(manifest["per_layer"], name))


def load_file_module(path: Path) -> ModuleType:
    """Import one of the yardstick's by-name files (a configuration's
    ``build.py``, a driver, a metric reader, a reference) from its path:
    configuration directories carry hyphens and are not packages."""
    if not path.is_file():
        raise FileNotFoundError(f"the yardstick expects {path}")
    mod_name = "yardstick_file_" + re.sub(r"\W", "_", str(path))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_build(cell: Cell) -> ModuleType:
    return load_file_module(cell.config_dir / "build.py")


def load_reference(cell: Cell) -> ModuleType:
    return load_file_module(cell.root / "yardstick" / "reference"
                            / f"{cell.config['reference']}.py")


def load_driver(cell: Cell) -> ModuleType:
    """Drivers are harness code: always the package's own."""
    return load_file_module(HERE / "drivers" / f"{cell.traffic['driver']}.py")


def load_reader(cell: Cell, metric: str) -> ModuleType:
    return load_file_module(cell.root / "yardstick" / "metrics"
                            / f"{metric}.py")
