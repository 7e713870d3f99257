"""The reader of ``moe_dispatch_blocks_mean`` on a registry filled by
hand."""

import types

from pytest import approx

from deeplearning4j_tpu.observe.registry import default_registry
from yardstick import cells

CELL = "qwen3-next-80b-a3b-ep16.fit-seq8k"
GAUGE = "dl4j_moe_dispatch_blocks"


def read():
    cell = cells.resolve_cell(CELL)
    return cells.load_reader(cell, "moe_dispatch_blocks_mean").read(
        types.SimpleNamespace(cell=cell))


def _forget(reg):
    metric = reg.get_metric(GAUGE)
    if metric is not None:
        metric._series.clear()


def test_mean_over_the_layers_of_the_programs_gauge():
    from deeplearning4j_tpu.observe.telemetry import publish_routing
    reg = default_registry()
    _forget(reg)
    # held assignments, largest load, mean load, dropped, blocks: the
    # third layer's held load has passed one block of 8,192
    publish_routing({"block0": [5120.0, 240.0, 160.0, 0.0, 1.0],
                     "block1": [4800.0, 450.0, 150.0, 0.0, 1.0],
                     "block2": [9000.0, 4100.0, 281.25, 0.0, 2.0],
                     "block3": [7900.0, 900.0, 246.875, 0.0, 1.0]})
    assert read() == approx(1.25)
    assert reg.get_metric(GAUGE).get(layer="block2") == 2.0


def test_a_program_without_the_gauge_gives_nothing_and_does_not_raise():
    """The parent commit publishes rows of four: the gauge is not there,
    or has no series."""
    from deeplearning4j_tpu.observe.telemetry import publish_routing
    reg = default_registry()
    _forget(reg)
    publish_routing({"block0": [5120.0, 240.0, 160.0, 0.0]})
    assert read() is None
