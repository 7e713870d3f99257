"""The three per-layer readers that came with sdar-30b-a3b-ep8, on
intervals made by hand (two whole steps of 100 ms, operations whose HLO
names a table maps to the program's named scopes) and on gauges set by
hand; and ``chip_check.verdict`` on made-up readings."""

import numpy as np
import pytest
from pytest import approx

from deeplearning4j_tpu.observe.registry import default_registry
from yardstick import cells, xplane
from yardstick.compiles import Compiles
from yardstick.observed import Observed
from yardstick.spans import Spans

CELL = "sdar-30b-a3b-ep8.fit-seq8k"
MS = 1e6                                            # ns
READERS = ("block_diffusion_attention_ms_per_step",
           "block_diffusion_attention_roofline",
           "block_diffusion_kv_blocks_share")
GAUGES = ("dl4j_flash_kv_blocks_visited", "dl4j_flash_kv_blocks_total")

# instruction -> op_name, as the compiled step's text gives them
TABLE = {
    "fusion.1": "jit(step)/checkpoint/attn.block_diffusion/dot_general",
    "pallas_call.2": "jit(step)/checkpoint/attn.block_diffusion/pallas_call",
    "pallas_call.3": "jit(step)/transpose(jvp(attn.block_diffusion))/"
                     "pallas_call",
    "pallas_call.4": "jit(step)/transpose(jvp(attn.block_diffusion))/"
                     "pallas_call",
    "fusion.5": "jit(step)/jvp(moe.experts)/ragged_dot",
    "pallas_call.6": "jit(step)/jvp(attn.gated)/pallas_call",
    "copy-start.7": "jit(step)/jvp(attn.block_diffusion)/pallas_call",
    "fusion.8": "jit(step)/lm.head_loss/reduce",
}
# (name, start ms, length ms) inside one step that begins at 0
OPS = [("fusion.1", 0, 10),                            # projections: not it
       ("pallas_call.2", 10, 12), ("pallas_call.3", 22, 20),
       ("pallas_call.4", 40, 10),                      # union 10-50: 40 ms
       ("fusion.5", 50, 20), ("pallas_call.6", 70, 5),  # another layer's
       ("copy-start.7", 0, 90),                         # in flight: ignored
       ("fusion.8", 90, 8)]


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


@pytest.fixture()
def gauges():
    """The flash gauges as this test sets them, and as they were after."""
    reg = default_registry()
    pair = [reg.gauge(name, "set by a test") for name in GAUGES]
    before = [dict(g.series()) for g in pair]

    def set_(scope, visited, total):
        pair[0].set(visited, scope=scope)
        pair[1].set(total, scope=scope)
    yield set_
    for g, series in zip(pair, before):
        for key in list(g.series()):
            if key not in series:
                g.set(0.0, **dict(key))


def test_the_kernels_time_is_their_union_inside_whole_steps():
    assert read(observed(), READERS[0]) == approx(40.0)


@pytest.mark.parametrize("cut_at", [16, 45])
def test_a_step_the_profiler_cut_short_is_not_counted(cut_at):
    obs = observed(steps=4, cut_at=cut_at)
    assert len(xplane.step_runs(obs.device).start) == 5
    assert read(obs, READERS[0]) == approx(40.0)


def test_the_roofline_share_is_bound_by_the_operations_of_visible_pairs():
    obs = observed()
    cfg = obs.cell.config
    flops, nbytes = cells.load_build(obs.cell).block_diffusion_attention_work(
        cfg)
    t, b = cfg["seq_len"], cfg["block_length"]
    assert flops == 3 * cfg["num_hidden_layers"] * 4 * t * (t + b) * 32 * 128
    assert flops / 197e12 > 10 * nbytes / 819e9
    assert read(obs, READERS[1]) == approx(100 * flops / 197e12 * 1e3 / 40.0)
    # at the least time the chip could take, the share is 100 and no more
    least_ms = flops / 197e12 * 1e3
    assert 66 < least_ms < 68


def test_the_share_of_tiles_is_read_under_the_layers_scope(gauges):
    # another scope's pair is not read: what an earlier trace in this
    # process left under the layer's scope, or nothing, stays
    was = read(observed(), READERS[2])
    gauges("attn.window", 31, 256)
    assert read(observed(), READERS[2]) == was
    gauges("attn.block_diffusion", 80, 256)
    assert read(observed(), READERS[2]) == approx(31.25)
    gauges("attn.block_diffusion", 256, 256)
    assert read(observed(), READERS[2]) == approx(100.0)


@pytest.mark.parametrize("metric", READERS[:2])
def test_a_program_without_the_span_gives_nothing_and_does_not_raise(
        metric):
    assert read(observed(table=None), metric) is None
    # nor does a step without such operations: the parent's
    other = {name: "jit(step)/jvp(attn.gated)/pallas_call" for name in TABLE}
    assert read(observed(table=other), metric) is None


def test_a_program_without_the_gauges_gives_nothing(monkeypatch):
    from deeplearning4j_tpu.observe import registry
    empty = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "default_registry", lambda: empty)
    assert read(observed(), READERS[2]) is None
    empty.gauge(GAUGES[0], "x").set(31, scope="attn.window")
    empty.gauge(GAUGES[1], "x").set(64, scope="attn.window")
    assert read(observed(), READERS[2]) is None


@pytest.mark.parametrize("metric", READERS)
def test_the_manifest_lists_each_reader_for_the_new_cell_alone(metric):
    manifest = cells.load_manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == metric]
    assert entry["workloads"] == [CELL]
    assert entry["layer"] == "layer_math"
    assert entry["moves"] == "train_examples_per_s_per_chip"
    assert entry["unit"] == ("ms" if metric.endswith("per_step") else "%")
    assert entry["source"] == ("program_counter" if "kv_blocks" in metric
                               else "device_trace")


def test_the_manifest_gained_one_configuration_and_one_cell():
    manifest = cells.load_manifest()
    assert manifest["configs"][-1]["name"] == "sdar-30b-a3b-ep8"
    assert manifest["workloads"][-1] == {
        "name": CELL, "config": "sdar-30b-a3b-ep8", "traffic": "fit-seq8k",
        "chips": 1, "why": manifest["workloads"][-1]["why"]}
    assert len(manifest["workloads"][-1]["why"]) <= 200
    cell = cells.resolve_cell(CELL)
    reported = {m["name"] for m in cell.per_layer}
    assert set(READERS) <= reported
    assert {"device_step_ms", "train_step_roofline", "device_idle_share",
            "device_peak_bytes"} <= reported
    # the four moe_* lists are a benchmark PR's to extend
    assert not [n for n in reported if n.startswith("moe_")]


# ---- chip_check.verdict on made-up readings ---------------------------------

@pytest.fixture(scope="module")
def chip_check():
    return cells.load_file_module(
        cells.resolve_cell(CELL).config_dir / "chip_check.py")


def rows(system=None, float8=None, f32=None):
    sound = {"logits_rms_over_spread": 0.0075, "loss_rel_err": 5e-5,
             "gradients": {"['block0']['mixer']['W_q']": 0.015,
                           "['block0']['moe']['w_up']": 0.017,
                           "['block0']['moe']['router']": 0.019}}
    low = {"logits_rms_over_spread": 0.6, "loss_rel_err": 3e-3,
           "gradients": {"['block0']['mixer']['W_q']": 1.0}}
    out = {"system": {**sound, **(system or {})},
           "reference_operands_float8": {**low, **(float8 or {})}}
    if f32 is not None:
        out["system_float32"] = {"gradients": f32}
    return {"rows": out}


def test_sound_readings_give_no_fault(chip_check):
    assert chip_check.verdict(rows(), 3.5e-4) == []


@pytest.mark.parametrize("system,fault", [
    ({"logits_rms_over_spread": 0.07}, "system over logits_rms_over_spread"),
    ({"loss_rel_err": 4e-4}, "system over loss"),
    ({"gradients": {"['block1']['mixer']['W_o']": 0.5}},
     "system over gradient_dense"),
    ({"gradients": {"['block1']['moe']['w_down']": 0.5}},
     "system over gradient_routed"),
    ({"gradients": {"['block1']['moe']['router']": 0.5}},
     "system over gradient_router"),
])
def test_the_system_over_a_limit_is_a_fault(chip_check, system, fault):
    assert chip_check.verdict(rows(system=system), 3.5e-4) == [fault]


def test_a_float8_control_inside_every_limit_is_a_fault(chip_check):
    inside = {"logits_rms_over_spread": 0.006, "loss_rel_err": 1e-6,
              "gradients": {"['block0']['mixer']['W_q']": 0.01}}
    assert chip_check.verdict(rows(float8=inside), 3.5e-4) == [
        "reference_operands_float8 is inside every limit"]
    # refused by one limit is refused
    assert chip_check.verdict(
        rows(float8={**inside, "logits_rms_over_spread": 0.2}), 3.5e-4) == []


def test_float32_gradients_away_from_the_reference_are_a_fault(chip_check):
    assert chip_check.verdict(
        rows(f32={"['block0']['mixer']['W_q']": 0.004}), 3.5e-4) == []
    fault, = chip_check.verdict(
        rows(f32={"['block0']['mixer']['W_q']": 0.05}), 3.5e-4)
    assert fault.startswith("system_float32 gradient of")
