"""The reader of ``attention_kernel_calls_per_step`` on a registry filled
by hand, in each cell that lists it."""

import types

import pytest

from deeplearning4j_tpu.observe.registry import default_registry
from yardstick import cells

GAUGE = "dl4j_step_kernel_calls"
CELLS = ["qwen3-next-80b-a3b-ep16.fit-seq8k",
         "phi4-mini-flash-vp8.fit-seq8k", "sdar-30b-a3b-ep8.fit-seq8k"]


def read(name):
    cell = cells.resolve_cell(name)
    return cells.load_reader(cell, "attention_kernel_calls_per_step").read(
        types.SimpleNamespace(cell=cell))


@pytest.fixture()
def gauge():
    metric = default_registry().gauge(GAUGE, "kernel launches by scope")
    metric._series.clear()
    yield metric
    metric._series.clear()


@pytest.mark.parametrize("cell,calls,attention", [
    (CELLS[0], {"gdn.scan": 9, "attn.gated": 3, "moe.experts": 0}, 3),
    (CELLS[1], {"attn.window": 3, "attn.full": 3, "attn.cross": 3,
                "ssm.scan": 0}, 9),
    (CELLS[2], {"attn.block_diffusion": 12, "attn.gated": 0}, 12),
    # the XLA attention path: the scopes are there and hold no kernel
    (CELLS[2], {"attn.block_diffusion": 0, "moe.experts": 0}, 0),
])
def test_sum_over_the_attention_scopes_of_the_programs_gauge(
        gauge, cell, calls, attention):
    for scope, n in calls.items():
        gauge.set(n, scope=scope)
    assert read(cell) == attention


@pytest.mark.parametrize("cell", CELLS)
def test_a_program_without_the_gauge_gives_nothing_and_does_not_raise(
        gauge, cell):
    """The parent commit publishes no such gauge; a model with no attention
    scope among its layers' publishes none that counts."""
    assert read(cell) is None
    gauge.set(9, scope="gdn.scan")
    assert read(cell) is None
    default_registry()._metrics.pop(GAUGE)
    assert read(cell) is None


def test_fit_under_a_tracer_publishes_the_gauge_for_every_declared_scope(
        gauge):
    """Through ``fit()``'s own step with the ``step_scopes`` span; the CPU's
    step launches no kernel, so every declared scope reads 0, and the
    reader 0."""
    import dataclasses
    import sys
    sys.path.insert(0, str(cells.ROOT / "tests" / "yardstick"))
    from test_phi4_mini_flash_reference import TINY
    from deeplearning4j_tpu.observe.tracer import SpanTracer
    cell = cells.resolve_cell(CELLS[1])
    cfg = {**cell.config, **TINY}
    build = cells.load_build(dataclasses.replace(cell, config=cfg))
    model = build.build(cfg, 1).init(1)
    model.tracer = SpanTracer()
    model.fit(build.train_set(cfg, 1, 2), epochs=1)
    assert [e for e in model.tracer.events if e["name"] == "step_scopes"]
    published = {dict(labels)["scope"]: n
                 for labels, n in gauge.series().items()}
    assert published == dict.fromkeys(
        ("ssm.proj", "ssm.conv", "ssm.scan", "ssm.out", "attn.window",
         "attn.full", "attn.cross", "gmu", "mlp.glu", "lm.head_loss"), 0)
    assert read(CELLS[1]) == 0
