"""The five per-layer readers that came with phi4-mini-flash-vp8, on
intervals made by hand: two whole steps of 100 ms, operations whose HLO
names a table maps to the program's named scopes."""

import numpy as np
import pytest
from pytest import approx

from yardstick import cells, scopes, xplane
from yardstick.compiles import Compiles
from yardstick.observed import Observed
from yardstick.spans import Spans

CELL = "phi4-mini-flash-vp8.fit-seq8k"
MS = 1e6                                            # ns
READERS = ("ssm_scan_ms_per_step", "ssm_scan_roofline",
           "window_attention_ms_per_step", "window_attention_roofline",
           "full_attention_ms_per_step")

# instruction -> op_name, as the compiled step's text gives them
TABLE = {
    "fusion.1": "jit(step)/jvp(ssm.scan)/while/body/while/body/mul",
    "fusion.2": "jit(step)/transpose(jvp(ssm.conv))/mul",
    "fusion.3": "jit(step)/checkpoint/rematted_computation/ssm.proj/dot",
    "fusion.4": "jit(step)/jvp(ssm.out)/dot_general",
    "pallas_call.5": "jit(step)/jvp(attn.window)/pallas_call",
    "fusion.6": "jit(step)/jvp(attn.window)/dot_general",
    "pallas_call.7": "jit(step)/jvp(attn.full)/pallas_call",
    "pallas_call.8": "jit(step)/transpose(jvp(attn.cross))/pallas_call",
    "fusion.9": "jit(step)/jvp(gmu)/dot_general",
    "fusion.10": "jit(step)/jvp(mlp.glu)/dot_general",
    "slice-start.11": "jit(step)/jvp(ssm.scan)/slice",
    "fusion.12": "jit(step)/lm.head_loss/reduce",
}
# (name, start ms, length ms) inside one step that begins at 0
OPS = [("fusion.1", 0, 30), ("fusion.2", 20, 20),      # union 0-40: 40 ms
       ("fusion.3", 40, 5), ("fusion.4", 45, 3),       # products: not the scan
       ("pallas_call.5", 48, 2), ("fusion.6", 50, 5),  # projections: not it
       ("pallas_call.7", 55, 9), ("pallas_call.8", 64, 11),
       ("fusion.9", 75, 5), ("fusion.10", 80, 8),
       ("slice-start.11", 0, 90),                       # in flight: ignored
       ("fusion.12", 90, 8)]


def observed(steps=2, period=100, table=TABLE, cut_at=None):
    """``cut_at``: the profiler's trace stops so many ms into the step
    after the whole ones, well before the window does."""
    names, start, end = [], [], []
    for s in range(steps):
        for name, at, length in OPS:
            names.append(name)
            start.append((10 + s * period + at) * MS)
            end.append((10 + s * period + at + length) * MS)
    # a step cut by the window's end: its operations do not count
    stub = 30 if cut_at is None else cut_at
    names.append("fusion.1")
    start.append((10 + steps * period) * MS)
    end.append((10 + steps * period + stub) * MS)
    order = np.argsort(start, kind="stable")
    ops = xplane.Line([names[i] for i in order], np.array(start)[order],
                      np.array(end)[order])
    runs = xplane.Line(["jit_step"] * (steps + 1),
                       np.array([(10 + s * period) * MS
                                 for s in range(steps + 1)]),
                       np.array([(10 + s * period + 99) * MS
                                 for s in range(steps)]
                                + [(10 + steps * period + stub) * MS]))
    hi = (10 + steps * period + (30 if cut_at is None else 3 * period)) * MS
    trace = xplane.DeviceTrace(ops=[ops], modules=[runs], lo=0.0, hi=hi,
                               perf_at_lo=0.0)
    compiles = Compiles.__new__(Compiles)
    compiles.seconds, compiles.cache_hits, compiles.in_window = 1.0, 1, 0
    told = [{"name": "step_scopes", "cat": "step", "ph": "X", "ts": 2e5,
             "dur": 0.0, "args": {"table": table}}] if table else []
    return Observed(cell=cells.resolve_cell(CELL),
                    spans=Spans(told, 0.0, (0.0, 1.0)), device=trace,
                    compiles=compiles, device_kind="TPU v5 lite",
                    memory_peak_bytes=1, facts={"steps": steps})


def read(obs, metric):
    return cells.load_reader(obs.cell, metric).read(obs)


@pytest.mark.parametrize("metric,ms", [
    ("ssm_scan_ms_per_step", 40.0),             # conv and scan, their union
    ("window_attention_ms_per_step", 2.0),      # the kernel, not the layer
    ("full_attention_ms_per_step", 20.0),       # full and cross together
])
def test_scope_times_are_unions_inside_whole_steps(metric, ms):
    assert read(observed(), metric) == approx(ms)


# the cell's own trace stops mid-step (PERF.md section 5): the step program
# running then ends where the trace does and is no whole step
@pytest.mark.parametrize("cut_at", [16, 45])
@pytest.mark.parametrize("metric,ms", [
    ("ssm_scan_ms_per_step", 40.0),
    ("window_attention_ms_per_step", 2.0),
    ("full_attention_ms_per_step", 20.0),
])
def test_a_step_the_profiler_cut_short_is_not_counted(metric, ms, cut_at):
    obs = observed(steps=4, cut_at=cut_at)
    assert len(xplane.step_runs(obs.device).start) == 5
    assert read(obs, metric) == approx(ms)


@pytest.mark.parametrize("metric,work,ms,bound", [
    ("ssm_scan_roofline", "ssm_scan_work", 40.0, "bytes"),
    ("window_attention_roofline", "window_attention_work", 2.0,
     "operations"),
])
def test_roofline_shares_take_the_larger_bound_from_the_builds_counts(
        metric, work, ms, bound):
    obs = observed()
    flops, nbytes = getattr(cells.load_build(obs.cell), work)(
        obs.cell.config)
    least = {"operations": flops / 197e12, "bytes": nbytes / 819e9}
    assert max(least, key=least.get) == bound
    assert read(obs, metric) == approx(100 * least[bound] * 1e3 / ms)
    assert 0 < read(obs, metric) < 100


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_the_span_gives_nothing_and_does_not_raise(
        metric):
    assert read(observed(table=None), metric) is None
    # nor does a step without such operations
    other = {name: "jit(step)/jvp(gdn.scan)/mul" for name in TABLE}
    assert read(observed(table=other), metric) is None


@pytest.mark.parametrize("metric", READERS)
def test_the_manifest_lists_each_reader_for_the_new_cell_alone(metric):
    manifest = cells.load_manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == metric]
    assert entry["workloads"] == [CELL]
    assert entry["layer"] == "layer_math"
    assert entry["moves"] == "train_examples_per_s_per_chip"
    assert entry["unit"] == ("%" if metric.endswith("roofline") else "ms")
    assert cells.load_reader(cells.resolve_cell(CELL), metric).__doc__


def test_the_blocks_declare_the_scopes_the_readers_join_on():
    from deeplearning4j_tpu.nn.layers.decoder import (CausalLMOutputLayer,
                                                      StateSpaceHybridBlock)
    declared = set(StateSpaceHybridBlock.named_scopes) | set(
        CausalLMOutputLayer.named_scopes)
    assert declared == {"ssm.proj", "ssm.conv", "ssm.scan", "ssm.out",
                        "attn.window", "attn.full", "attn.cross", "gmu",
                        "mlp.glu", "lm.head_loss"}
    for name in TABLE.values():
        assert scopes.in_scope(name, tuple(declared))


def test_the_step_a_graph_trains_with_carries_every_declared_scope():
    """The scopes reach the compiled step's text through ``fit()``'s own
    step of a ``ComputationGraph``, recomputation and backward included,
    and a tracer is handed the table."""
    import dataclasses
    import sys
    sys.path.insert(0, str(cells.ROOT / "tests" / "yardstick"))
    from test_phi4_mini_flash_reference import TINY
    from deeplearning4j_tpu.observe.tracer import SpanTracer
    cell = cells.resolve_cell(CELL)
    cfg = {**cell.config, **TINY}
    build = cells.load_build(dataclasses.replace(cell, config=cfg))
    model = build.build(cfg, 1).init(1)
    model.tracer = SpanTracer()
    model.fit(build.train_set(cfg, 1, 2), epochs=1)
    tables = [e["args"]["table"] for e in model.tracer.events
              if e["name"] == "step_scopes"]
    assert len(tables) == 1
    paths = list(tables[0].values())
    for scope in ("ssm.proj", "ssm.conv", "ssm.scan", "ssm.out",
                  "attn.window", "attn.full", "attn.cross", "gmu", "mlp.glu",
                  "lm.head_loss"):
        mine = [p for p in paths if scopes.in_scope(p, (scope,))]
        assert mine, scope
        if scope != "lm.head_loss":     # a block's recomputation, and its
            assert any("rematted_computation/" + scope in p     # backward
                       for p in mine), scope
            assert any("transpose(" in p and "rematted" not in p
                       for p in mine), scope
