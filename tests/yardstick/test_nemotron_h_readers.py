"""The three per-layer readers that came with nemotron3-nano-30b-a3b-ep16,
on intervals made by hand (two whole steps of 100 ms, operations whose HLO
names a table maps to the program's named scopes); and
``chip_check.verdict`` on made-up readings."""

import numpy as np
import pytest
from pytest import approx

from yardstick import cells, xplane
from yardstick.compiles import Compiles
from yardstick.observed import Observed
from yardstick.spans import Spans

CELL = "nemotron3-nano-30b-a3b-ep16.fit-seq8k"
MS = 1e6                                            # ns
READERS = ("ssd_scan_ms_per_step", "ssd_scan_roofline",
           "causal_attention_ms_per_step")

# instruction -> op_name, as the compiled step's text gives them
TABLE = {
    "fusion.1": "jit(step)/checkpoint/ssd.proj/dot_general",
    "fusion.2": "jit(step)/checkpoint/ssd.conv/add",
    "fusion.3": "jit(step)/checkpoint/ssd.scan/while/body/dot_general",
    "fusion.4": "jit(step)/transpose(jvp(ssd.scan))/while/body/mul",
    "fusion.5": "jit(step)/jvp(ssd.out)/dot_general",
    "pallas_call.6": "jit(step)/checkpoint/attn.causal/pallas_call",
    "pallas_call.7": "jit(step)/transpose(jvp(attn.causal))/pallas_call",
    "fusion.8": "jit(step)/checkpoint/attn.causal/dot_general",
    "fusion.9": "jit(step)/jvp(moe.experts)/ragged_dot",
    "pallas_call.10": "jit(step)/jvp(attn.gated)/pallas_call",
    "copy-start.11": "jit(step)/jvp(ssd.scan)/copy",
}
# (name, start ms, length ms) inside one step that begins at 0
OPS = [("fusion.1", 0, 8),                             # projections: not it
       ("fusion.2", 8, 4), ("fusion.3", 12, 8),
       ("fusion.4", 20, 12),                           # union 8-32: 24 ms
       ("fusion.5", 32, 6),
       ("pallas_call.6", 40, 5), ("pallas_call.7", 47, 10),   # 15 ms
       ("fusion.8", 57, 3),                            # q/k/v: not a kernel
       ("fusion.9", 60, 20), ("pallas_call.10", 80, 5),        # other layers
       ("copy-start.11", 0, 90)]                       # in flight: ignored


def observed(steps=2, period=100, table=TABLE, cut_at=None):
    names, start, end = [], [], []
    for s in range(steps):
        for name, at, length in OPS:
            names.append(name)
            start.append((10 + s * period + at) * MS)
            end.append((10 + s * period + at + length) * MS)
    stub = 30 if cut_at is None else cut_at
    names.append("fusion.2")
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


def test_the_scans_time_is_the_union_of_conv_and_scan_inside_whole_steps():
    assert read(observed(), READERS[0]) == approx(24.0)


def test_the_kernels_time_is_the_pallas_calls_under_the_scope_alone():
    assert read(observed(), READERS[2]) == approx(15.0)


@pytest.mark.parametrize("cut_at", [16, 45])
def test_a_step_the_profiler_cut_short_is_not_counted(cut_at):
    obs = observed(steps=4, cut_at=cut_at)
    assert len(xplane.step_runs(obs.device).start) == 5
    assert read(obs, READERS[0]) == approx(24.0)
    assert read(obs, READERS[2]) == approx(15.0)


def test_the_roofline_share_is_bound_by_the_bytes_of_the_recurrence():
    obs = observed()
    flops, nbytes = cells.load_build(obs.cell).ssd_scan_work(obs.cell.config)
    assert nbytes / 819e9 > flops / 197e12
    least_ms = nbytes / 819e9 * 1e3
    assert read(obs, READERS[1]) == approx(100 * least_ms / 24.0)
    assert 1.5 < least_ms < 7.5             # one or two rows a step
    # at the least time the chip could take the share is 100 and no more:
    # the work does not depend on what implements the scan
    assert read(obs, READERS[1]) < 100


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_the_span_gives_nothing_and_does_not_raise(
        metric):
    assert read(observed(table=None), metric) is None
    # nor does a step without such operations: the parent's
    other = {name: "jit(step)/jvp(attn.gated)/pallas_call" for name in TABLE}
    assert read(observed(table=other), metric) is None


@pytest.mark.parametrize("metric", READERS)
def test_the_manifest_lists_each_reader_for_the_new_cell_alone(metric):
    manifest = cells.load_manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == metric]
    assert entry["workloads"] == [CELL]
    assert entry["layer"] == "layer_math"
    assert entry["moves"] == "train_examples_per_s_per_chip"
    assert entry["unit"] == ("ms" if metric.endswith("per_step") else "%")
    assert entry["source"] == "device_trace"
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_the_manifest_names_the_configuration_the_cell_and_the_readers():
    """By name, never by place or by count: whatever a later PR appends,
    extends or reorders leaves this green."""
    manifest = cells.load_manifest()
    config, = [c for c in manifest["configs"]
               if c["name"] == "nemotron3-nano-30b-a3b-ep16"]
    assert len(config["why"]) <= 200
    entry, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert entry == {
        "name": CELL, "config": "nemotron3-nano-30b-a3b-ep16",
        "traffic": "fit-seq8k", "chips": 1, "why": entry["why"]}
    assert len(entry["why"]) <= 200
    reported = {m["name"] for m in cells.resolve_cell(CELL).per_layer}
    assert set(READERS) <= reported
    assert {"device_step_ms", "train_step_roofline", "device_idle_share",
            "device_peak_bytes", "loop_blocked_share"} <= reported


# ---- chip_check.verdict on made-up readings ---------------------------------

@pytest.fixture(scope="module")
def chip_check():
    return cells.load_file_module(
        cells.resolve_cell(CELL).config_dir / "chip_check.py")


def rows(system=None, float8=None, f32=None):
    sound = {"logits_rms_over_spread": 0.038, "loss_rel_err": 6e-5,
             "gradients": {"['block0']['mixer']['W_in']": 0.044,
                           "['block1']['mixer']['w_up']": 0.146,
                           "['block1']['mixer']['router']": 0.047}}
    low = {"logits_rms_over_spread": 0.18, "loss_rel_err": 5e-3,
           "gradients": {"['block0']['mixer']['W_in']": 1.0}}
    out = {"system": {**sound, **(system or {})},
           "reference_operands_float8": {**low, **(float8 or {})}}
    if f32 is not None:
        out["system_float32"] = {"gradients": f32}
    return {"rows": out}


def test_sound_readings_give_no_fault(chip_check):
    assert chip_check.verdict(rows(), 5e-4) == []


@pytest.mark.parametrize("system,fault", [
    ({"logits_rms_over_spread": 0.1}, "system over logits_rms_over_spread"),
    ({"loss_rel_err": 6e-4}, "system over loss"),
    ({"gradients": {"['block5']['mixer']['W_o']": 0.5}},
     "system over gradient_dense"),
    ({"gradients": {"['block0']['mixer']['dt_bias']": 0.5}},
     "system over gradient_dense"),
    ({"gradients": {"['block1']['mixer']['w_down']": 0.5}},
     "system over gradient_routed"),
    ({"gradients": {"['block1']['mixer']['router']": 0.2}},
     "system over gradient_router"),
])
def test_the_system_over_a_limit_is_a_fault(chip_check, system, fault):
    assert chip_check.verdict(rows(system=system), 5e-4) == [fault]


def test_a_float8_control_inside_the_harness_limit_is_a_fault(chip_check):
    inside = {"logits_rms_over_spread": 0.03, "loss_rel_err": 1e-6,
              "gradients": {"['block0']['mixer']['W_in']": 0.01}}
    fault = ["reference_operands_float8 is inside the harness's limit"]
    assert chip_check.verdict(rows(float8=inside), 5e-4) == fault
    # the logits and the gradients refuse it here, but the harness has the
    # loss's limit alone: a control that one lets through is a fault
    assert chip_check.verdict(
        rows(float8={"loss_rel_err": 3e-4}), 5e-4) == fault
    assert chip_check.verdict(rows(float8={"loss_rel_err": 6e-4}), 5e-4) == []


def test_float32_gradients_away_from_the_reference_are_a_fault(chip_check):
    assert chip_check.verdict(
        rows(f32={"['block0']['mixer']['W_in']": 0.004}), 5e-4) == []
    fault, = chip_check.verdict(
        rows(f32={"['block0']['mixer']['W_in']": 0.05}), 5e-4)
    assert fault.startswith("system_float32 gradient of")
