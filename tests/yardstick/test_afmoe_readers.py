"""The three per-layer readers that came with trinity-mini-ep8, on
intervals made by hand (two whole steps of 100 ms, operations whose HLO
names a table maps to the program's named scopes). Every entry is looked
up by name."""

import numpy as np
import pytest
from pytest import approx

from yardstick import cells, xplane
from yardstick.compiles import Compiles
from yardstick.observed import Observed
from yardstick.spans import Spans

CELL = "trinity-mini-ep8.fit-seq8k"
MS = 1e6                                            # ns
READERS = ("local_attention_ms_per_step", "local_attention_roofline",
           "global_attention_ms_per_step")

# instruction -> op_name, as the compiled step's text gives them
TABLE = {
    "fusion.1": "jit(step)/checkpoint/attn.window/dot_general",
    "pallas_call.2": "jit(step)/checkpoint/attn.window/pallas_call",
    "pallas_call.3": "jit(step)/transpose(jvp(attn.window))/pallas_call",
    "pallas_call.4": "jit(step)/checkpoint/attn.gated/pallas_call",
    "pallas_call.5": "jit(step)/transpose(jvp(attn.gated))/pallas_call",
    "fusion.6": "jit(step)/checkpoint/attn.gated/dot_general",
    "fusion.7": "jit(step)/checkpoint/block.norm/mul",
    "fusion.8": "jit(step)/jvp(moe.experts)/ragged_dot",
    "fusion.9": "jit(step)/checkpoint/mlp.glu/dot_general",
    "copy-start.10": "jit(step)/jvp(attn.window)/copy",
}
# (name, start ms, length ms) inside one step that begins at 0
OPS = [("fusion.1", 0, 8),                             # q/k/v: not a kernel
       ("pallas_call.2", 8, 12), ("pallas_call.3", 20, 24),    # 36 ms
       ("pallas_call.4", 46, 4), ("pallas_call.5", 50, 8),     # 12 ms
       ("fusion.6", 58, 3), ("fusion.7", 61, 3),
       ("fusion.8", 64, 20), ("fusion.9", 84, 10),
       ("copy-start.10", 0, 90)]                       # in flight: ignored


def observed(steps=2, period=100, table=TABLE, cut_at=None):
    names, start, end = [], [], []
    for s in range(steps):
        for name, at, length in OPS:
            names.append(name)
            start.append((10 + s * period + at) * MS)
            end.append((10 + s * period + at + length) * MS)
    stub = 30 if cut_at is None else cut_at
    names.append("pallas_call.2")
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


def test_the_windowed_maps_time_is_their_pallas_calls_alone():
    assert read(observed(), "local_attention_ms_per_step") == approx(36.0)


def test_the_full_maps_time_is_the_kernels_under_the_gated_scope():
    assert read(observed(), "global_attention_ms_per_step") == approx(12.0)


@pytest.mark.parametrize("cut_at", [16, 45])
def test_a_step_the_profiler_cut_short_is_not_counted(cut_at):
    obs = observed(steps=4, cut_at=cut_at)
    assert len(xplane.step_runs(obs.device).start) == 5
    assert read(obs, "local_attention_ms_per_step") == approx(36.0)
    assert read(obs, "global_attention_ms_per_step") == approx(12.0)


def test_the_roofline_share_is_bound_by_the_windows_operations():
    obs = observed()
    flops, nbytes = cells.load_build(obs.cell).local_attention_work(
        obs.cell.config)
    assert flops / 197e12 > nbytes / 819e9
    least_ms = flops / 197e12 * 1e3
    assert read(obs, "local_attention_roofline") == approx(
        100 * least_ms / 36.0)
    assert 18.0 < least_ms < 18.5          # one row a step: 3.61 TFLOP
    # at the least time the chip could take the share is 100 and no more:
    # the work counts the pairs inside the window, whatever the kernel
    # visits
    assert 50 < read(obs, "local_attention_roofline") < 100


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_the_span_gives_nothing_and_does_not_raise(
        metric):
    assert read(observed(table=None), metric) is None
    # nor does a step without such operations: another cell's
    other = {name: "jit(step)/jvp(ssd.scan)/pallas_call" for name in TABLE}
    assert read(observed(table=other), metric) is None


@pytest.mark.parametrize("metric", READERS)
def test_the_manifest_lists_each_reader_for_the_new_cell_alone(metric):
    manifest = cells.load_manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == metric]
    assert entry["workloads"] == [CELL]
    assert entry["layer"] == "layer_math"
    assert entry["moves"] == "train_examples_per_s_per_chip"
    assert entry["unit"] == ("ms" if metric.endswith("per_step") else "%")
    assert entry["better"] == ("lower" if metric.endswith("per_step")
                               else "higher")
    assert entry["source"] == "device_trace"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_the_manifest_names_the_configuration_the_cell_and_the_readers():
    """By name, never by place or by count: whatever a later PR appends,
    extends or reorders leaves this green."""
    manifest = cells.load_manifest()
    config, = [c for c in manifest["configs"]
               if c["name"] == "trinity-mini-ep8"]
    assert config["file"] == "yardstick/configs/trinity-mini-ep8/config.json"
    assert len(config["why"]) <= 200
    entry, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert entry == {
        "name": CELL, "config": "trinity-mini-ep8",
        "traffic": "fit-seq8k", "chips": 1, "why": entry["why"]}
    assert len(entry["why"]) <= 200 and "1/8" in entry["why"]
    reported = {m["name"] for m in cells.resolve_cell(CELL).per_layer}
    assert set(READERS) <= reported
    assert {"device_step_ms", "train_step_roofline", "device_idle_share",
            "device_peak_bytes", "loop_blocked_share"} <= reported


# ---- chip_check.verdict on made-up readings ---------------------------------

@pytest.fixture(scope="module")
def chip_check():
    return cells.load_file_module(
        cells.resolve_cell(CELL).config_dir / "chip_check.py")


def rows(chip_check, system=None, float8=None):
    half = {k: v / 2 for k, v in chip_check.LIMITS.items()}
    sound = {"logits_rms_over_spread": half["logits_rms_over_spread"],
             "loss_rel_err": 1e-5,
             "gradients": {"['mixer']['W_q']": half["gradient_dense"],
                           "['moe']['w_gate']": half["gradient_routed"],
                           "['moe']['router']": half["gradient_router"]}}
    low = {"logits_rms_over_spread": 0.5, "loss_rel_err": 1e-2,
           "gradients": {"['mixer']['W_q']": 1.0}}
    return {"rows": {"system": {**sound, **(system or {})},
                     "reference_operands_float8": {**low, **(float8 or {})}}}


def test_the_check_is_the_nemotron_one_on_this_cell(chip_check):
    assert chip_check.CELL == CELL
    assert chip_check.check.__globals__["CELL"] == CELL
    assert chip_check.verdict(rows(chip_check), 5e-4) == []


@pytest.mark.parametrize("kind,limit", [
    ("['mlp']['W1']", "gradient_dense"),
    ("['norm3']['w']", "gradient_dense"),
    ("['moe']['shared_up']", "gradient_dense"),
    ("['moe']['w_gate']", "gradient_routed"),
    ("['moe']['w_down']", "gradient_routed"),
    ("['moe']['router']", "gradient_router"),
])
def test_a_gradient_over_its_kinds_limit_is_a_fault(chip_check, kind, limit):
    over = {"gradients": {kind: 1.01 * chip_check.LIMITS[limit]}}
    assert chip_check.verdict(rows(chip_check, system=over), 5e-4) == [
        f"system over {limit}"]


def test_a_float8_control_inside_the_harness_limit_is_a_fault(chip_check):
    fault = ["reference_operands_float8 is inside the harness's limit"]
    assert chip_check.verdict(
        rows(chip_check, float8={"loss_rel_err": 4e-4}), 5e-4) == fault
    assert chip_check.verdict(
        rows(chip_check, system={"loss_rel_err": 6e-4}), 5e-4) == [
        "system over loss"]
