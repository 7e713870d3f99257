"""The six per-layer readers that came with qwen3-next-80b-a3b-ep16, on
intervals made by hand: two whole steps of 100 ms, operations whose HLO
names a table maps to the program's named scopes, and routing gauges."""

import numpy as np
import pytest
from pytest import approx

from deeplearning4j_tpu.observe import scopes as program_scopes
from deeplearning4j_tpu.observe.registry import default_registry
from deeplearning4j_tpu.observe.telemetry import publish_routing
from yardstick import cells, scopes, xplane
from yardstick.compiles import Compiles
from yardstick.observed import Observed
from yardstick.spans import Spans

CELL = "qwen3-next-80b-a3b-ep16.fit-seq8k"
MS = 1e6                                            # ns

# instruction -> op_name, as the compiled step's text gives them
TABLE = {
    "fusion.1": "jit(step)/jvp(gdn.scan)/while/body/dot_general",
    "fusion.2": "jit(step)/transpose(jvp(gdn.conv))/mul",
    "fusion.3": "jit(step)/checkpoint/rematted_computation/gdn.proj/dot",
    "custom-call.4": "jit(step)/jvp(moe.experts)/ragged_dot",
    "sort.5": "jit(step)/jvp(moe.dispatch)/sort",
    "scatter.6": "jit(step)/transpose(jvp(moe.combine))/scatter-add",
    "fusion.7": "jit(step)/jvp(moe.shared)/dot_general",
    "pallas_call.8": "jit(step)/jvp(attn.gated)/pallas_call",
    "fusion.9": "jit(step)/jvp(attn.gated)/dot_general",
    "slice-start.10": "jit(step)/jvp(gdn.scan)/slice",
    "fusion.11": "jit(step)/lm.head_loss/reduce",
}
# (name, start ms, length ms) inside one step that begins at 0
OPS = [("fusion.1", 0, 30), ("fusion.2", 20, 20),      # union 0-40: 40 ms
       ("fusion.3", 40, 5),
       ("custom-call.4", 45, 3), ("sort.5", 48, 2), ("scatter.6", 55, 5),
       ("fusion.7", 60, 4),                             # shared: not counted
       ("pallas_call.8", 64, 12), ("fusion.9", 76, 6),
       ("slice-start.10", 0, 90),                       # in flight: ignored
       ("fusion.11", 90, 8)]


def observed(steps=2, period=100, table=TABLE):
    names, start, end = [], [], []
    for s in range(steps):
        for name, at, length in OPS:
            names.append(name)
            start.append((10 + s * period + at) * MS)
            end.append((10 + s * period + at + length) * MS)
    # a step cut by the window's end: its operations do not count
    names.append("fusion.1")
    start.append((10 + steps * period) * MS)
    end.append((10 + steps * period + 30) * MS)
    order = np.argsort(start, kind="stable")
    ops = xplane.Line([names[i] for i in order], np.array(start)[order],
                      np.array(end)[order])
    runs = xplane.Line(["jit_step"] * (steps + 1),
                       np.array([(10 + s * period) * MS
                                 for s in range(steps + 1)]),
                       np.array([(10 + s * period + 99) * MS
                                 for s in range(steps)]
                                + [(10 + steps * period + 30) * MS]))
    hi = (10 + steps * period + 30) * MS
    trace = xplane.DeviceTrace(ops=[ops], modules=[runs], lo=0.0, hi=hi,
                               perf_at_lo=0.0)
    compiles = Compiles.__new__(Compiles)
    compiles.seconds, compiles.cache_hits, compiles.in_window = 1.0, 1, 0
    # the program's span of each fit() call in the window; the newest holds
    told = [{"name": "step_scopes", "cat": "step", "ph": "X", "ts": at,
             "dur": 0.0, "args": {"table": t}}
            for at, t in ((1e5, {"stale": "x"}), (2e5, table))] if table \
        else []
    return Observed(cell=cells.resolve_cell(CELL),
                    spans=Spans(told, 0.0, (0.0, 1.0)), device=trace,
                    compiles=compiles, device_kind="TPU v5 lite",
                    memory_peak_bytes=1, facts={"steps": steps})


@pytest.fixture()
def obs():
    return observed()


def read(obs, metric):
    return cells.load_reader(obs.cell, metric).read(obs)


def test_scope_times_are_unions_inside_whole_steps(obs):
    assert read(obs, "gdn_scan_ms_per_step") == approx(40.0)
    assert read(obs, "moe_experts_ms_per_step") == approx(10.0)
    assert read(obs, "flash_attention_ms_per_step") == approx(12.0)


def test_roofline_shares_take_the_larger_bound_from_the_builds_counts(obs):
    build = cells.load_build(obs.cell)
    flops, nbytes = build.gdn_scan_work(obs.cell.config)
    # 0.275 TFLOP is 1.40 ms at 197 TFLOP/s, 2.43 GB is 2.96 ms at 819 GB/s
    assert flops / 197e12 < nbytes / 819e9
    assert read(obs, "gdn_scan_roofline") == approx(
        100 * nbytes / 819e9 * 1e3 / 40.0)
    flops, nbytes = build.moe_grouped_work(obs.cell.config)
    least_ms = max(flops / 197e12, nbytes / 819e9) * 1e3
    assert read(obs, "moe_grouped_matmul_roofline") == approx(
        100 * least_ms / 10.0)
    assert 0 < read(obs, "gdn_scan_roofline") < 100


def test_a_program_without_the_span_gives_nothing_and_does_not_raise():
    bare = observed(table=None)
    for metric in ("gdn_scan_ms_per_step", "gdn_scan_roofline",
                   "moe_experts_ms_per_step", "moe_grouped_matmul_roofline",
                   "flash_attention_ms_per_step"):
        assert read(bare, metric) is None
    # nor does a step without such operations, or a window without a step
    assert scopes.scope_ms_per_step(observed().device, TABLE,
                                    ("no.such.scope",)) is None
    assert scopes.scope_ms_per_step(observed(steps=0).device, TABLE,
                                    ("gdn.scan",)) is None


def test_expert_load_reads_the_programs_gauges(obs):
    reg = default_registry()
    for name in ("dl4j_moe_expert_load_max", "dl4j_moe_expert_load_mean"):
        metric = reg.get_metric(name)
        if metric is not None:
            metric._series.clear()
    # assignments held, largest load, mean load, dropped: two layers
    publish_routing({"block0": [5120.0, 240.0, 160.0, 0.0],
                     "block1": [4800.0, 450.0, 150.0, 0.0]})
    assert read(obs, "moe_expert_load_max_over_mean") == approx(
        (240 / 160 + 450 / 150) / 2)


def test_the_table_is_read_from_a_compiled_steps_text():
    text = '''
HloModule jit_step
%fused_computation.1 (p: f32[4]) -> f32[4] {
  ROOT %multiply.3 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(gdn.conv)/mul" source_file="a.py" source_line=3}
}
ENTRY %main {
  %fusion.12 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(gdn.conv)/mul" source_file="a.py"}
  %custom-call.2 = bf16[8,4]{1,0} custom-call(%a, %b), custom_call_target="x", metadata={op_name="jit(step)/transpose(jvp(moe.experts))/ragged_dot"}
  %copy.1 = f32[4]{0} copy(%fusion.12)
}'''
    table = program_scopes.scopes_in_hlo(text)
    assert table["fusion.12"].endswith("gdn.conv)/mul")
    assert "copy.1" not in table
    assert scopes.in_scope(table["custom-call.2"],
                           ("moe.route", "moe.experts"))
    assert scopes.in_scope(table["fusion.12"], ("gdn.conv",))
    assert not scopes.in_scope("jit(step)/gdn.convolution/mul",
                               ("gdn.conv",))


# ---- the chip check's verdict, on readings made by hand ---------------------

def _chip_check():
    cell = cells.resolve_cell(CELL)
    path = cells.ROOT / "yardstick" / "configs" / cell.config["name"]
    return cells.load_file_module(path / "chip_check.py")


SOUND = {"logits_rms_over_spread": 0.031, "loss_rel_err": 2e-5,
         "gradients": {"['mixer']['W_qkvz']": 0.055,
                       "['moe']['w_up']": 0.19, "['moe']['router']": 0.22}}
FLOAT8 = {"logits_rms_over_spread": 0.37, "loss_rel_err": 6e-5}


@pytest.mark.parametrize("system,float8,float32,faults", [
    (SOUND, FLOAT8, {}, []),
    ({**SOUND, "logits_rms_over_spread": 0.07}, FLOAT8, {},
     ["system over logits_rms_over_spread"]),
    ({**SOUND, "loss_rel_err": 2e-4}, FLOAT8, {}, ["system over loss"]),
    (SOUND, {"logits_rms_over_spread": 0.05, "loss_rel_err": 4.5e-4}, {},
     []),
    ({**SOUND, "gradients": {"['mixer']['W_qkvz']": 0.2,
                             "['moe']['w_up']": 0.4}}, FLOAT8, {},
     ["system over gradient_dense", "system over gradient_routed"]),
    (SOUND, {"logits_rms_over_spread": 0.05, "loss_rel_err": 6e-5}, {},
     ["reference_operands_float8 is inside every limit"]),
    (SOUND, FLOAT8, {"gradients": {"['moe']['w_down']": 0.1}},
     ["system_float32 gradient of ['moe']['w_down'] off by 0.1"]),
], ids=["sound", "logits", "loss", "float8_refused_by_the_loss_alone",
        "gradients", "float8_let_through",
        "float32_backward"])
def test_chip_check_refuses_what_is_over_a_limit(system, float8, float32,
                                                 faults):
    check = _chip_check()
    rows = {"system": system, "reference_operands_float8": float8,
            "system_float32": float32}
    assert check.verdict({"rows": rows}, 1.5e-4) == faults
