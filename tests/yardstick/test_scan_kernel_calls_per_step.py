"""The reader of ``scan_kernel_calls_per_step`` on a registry filled by
hand, in each cell that lists it."""

import types

import pytest

from deeplearning4j_tpu.observe.registry import default_registry
from yardstick import cells

GAUGE = "dl4j_step_kernel_calls"
CELLS = ["qwen3-next-80b-a3b-ep16.fit-seq8k",
         "phi4-mini-flash-vp8.fit-seq8k",
         "nemotron3-nano-30b-a3b-ep16.fit-seq8k"]


def read(name):
    cell = cells.resolve_cell(name)
    return cells.load_reader(cell, "scan_kernel_calls_per_step").read(
        types.SimpleNamespace(cell=cell))


@pytest.fixture()
def gauge():
    metric = default_registry().gauge(GAUGE, "kernel launches by scope")
    metric._series.clear()
    yield metric
    metric._series.clear()


@pytest.mark.parametrize("cell,calls,scans", [
    # three DeltaNet layers, one forward launch each
    (CELLS[0], {"gdn.scan": 6, "attn.gated": 2, "moe.experts": 0}, 6),
    # the forward kernel run again in each layer's recomputation
    (CELLS[0], {"gdn.scan": 9, "attn.gated": 2, "moe.experts": 0}, 9),
    (CELLS[1], {"ssm.scan": 4, "attn.window": 2, "attn.full": 2,
                "attn.cross": 2}, 4),
    (CELLS[2], {"ssd.scan": 8, "attn.causal": 2, "moe.experts": 0}, 8),
    # the plain scans: the scopes are there and hold no kernel
    (CELLS[2], {"ssd.scan": 0, "attn.causal": 0}, 0),
])
def test_sum_over_the_scan_scopes_of_the_programs_gauge(
        gauge, cell, calls, scans):
    for scope, n in calls.items():
        gauge.set(n, scope=scope)
    assert read(cell) == scans


@pytest.mark.parametrize("cell", CELLS)
def test_a_program_without_the_gauge_gives_nothing_and_does_not_raise(
        gauge, cell):
    """A program that publishes no such gauge; a model with no scan scope
    among its layers' publishes none that counts."""
    assert read(cell) is None
    gauge.set(3, scope="attn.gated")
    assert read(cell) is None
    default_registry()._metrics.pop(GAUGE)
    assert read(cell) is None
