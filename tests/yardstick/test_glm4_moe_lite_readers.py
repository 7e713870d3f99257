"""The three per-layer readers that came with glm47-flash-ep8, on intervals
made by hand (two whole steps of 100 ms, operations whose HLO names a
table maps to the program's named scopes), and the chip check's verdict
on made-up readings. Every entry is looked up by name."""

import numpy as np
import pytest
from pytest import approx

from yardstick import cells, xplane
from yardstick.compiles import Compiles
from yardstick.observed import Observed
from yardstick.spans import Spans

CELL = "glm47-flash-ep8.fit-seq8k"
MS = 1e6                                            # ns
READERS = ("latent_attention_ms_per_step", "latent_attention_roofline",
           "mtp_ms_per_step")

# instruction -> op_name, as the compiled step's text gives them
TABLE = {
    "fusion.1": "jit(step)/checkpoint/attn.latent/dot_general",
    "fusion.2": "jit(step)/checkpoint/attn.latent/mul",
    "pallas_call.3": "jit(step)/checkpoint/attn.latent/pallas_call",
    "pallas_call.4": "jit(step)/transpose(jvp(attn.latent))/pallas_call",
    "fusion.5": "jit(step)/checkpoint/mtp/attn.latent/dot_general",
    "fusion.6": "jit(step)/checkpoint/mtp/jvp(moe.experts)/ragged_dot",
    "fusion.7": "jit(step)/jvp(lm.head_loss)/mtp/dot_general",
    "fusion.8": "jit(step)/checkpoint/block.norm/mul",
    "fusion.9": "jit(step)/jvp(moe.experts)/ragged_dot",
    "copy-start.10": "jit(step)/jvp(attn.latent)/copy",
}
# (name, start ms, length ms) inside one step that begins at 0
OPS = [("fusion.1", 0, 6), ("fusion.2", 6, 2),            # attn.latent:
       ("pallas_call.3", 8, 10), ("pallas_call.4", 18, 20),  # 38 ms
       ("fusion.5", 38, 4),                   # mtp and attn.latent: 4 ms
       ("fusion.6", 42, 8), ("fusion.7", 50, 6),   # mtp: 4 + 8 + 6 ms
       ("fusion.8", 56, 3), ("fusion.9", 59, 20),
       ("copy-start.10", 0, 90)]                       # in flight: ignored


def observed(steps=2, period=100, table=TABLE, cut_at=None):
    names, start, end = [], [], []
    for s in range(steps):
        for name, at, length in OPS:
            names.append(name)
            start.append((10 + s * period + at) * MS)
            end.append((10 + s * period + at + length) * MS)
    stub = 30 if cut_at is None else cut_at
    names.append("pallas_call.3")
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


def test_latent_attention_is_every_operation_under_its_scope():
    """Projections, norms and both kernels, the module's layer included:
    38 + 4 ms a step."""
    assert read(observed(), "latent_attention_ms_per_step") == approx(42.0)


def test_the_modules_time_is_every_operation_under_mtp():
    """The module's block (its latent attention and experts) and the
    second term of the head: 4 + 8 + 6 ms a step."""
    assert read(observed(), "mtp_ms_per_step") == approx(18.0)


@pytest.mark.parametrize("cut_at", [16, 45])
def test_a_step_the_profiler_cut_short_is_not_counted(cut_at):
    obs = observed(steps=4, cut_at=cut_at)
    assert len(xplane.step_runs(obs.device).start) == 5
    assert read(obs, "latent_attention_ms_per_step") == approx(42.0)
    assert read(obs, "mtp_ms_per_step") == approx(18.0)


def test_the_roofline_share_is_bound_by_the_operations():
    obs = observed()
    flops, nbytes = cells.load_build(obs.cell).latent_attention_work(
        obs.cell.config)
    assert flops / 197e12 > nbytes / 819e9
    least_ms = flops / 197e12 * 1e3
    assert 95.0 < least_ms < 95.5          # one row a step: 18.79 TFLOP
    assert read(obs, "latent_attention_roofline") == approx(
        100 * least_ms / 42.0)


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_the_span_gives_nothing_and_does_not_raise(
        metric):
    assert read(observed(table=None), metric) is None
    # nor does a step without such operations: another cell's
    other = {name: "jit(step)/jvp(attn.window)/pallas_call"
             for name in TABLE}
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
               if c["name"] == "glm47-flash-ep8"]
    assert config["file"] == "yardstick/configs/glm47-flash-ep8/config.json"
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert len(config["why"]) <= 200
    entry, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert entry == {
        "name": CELL, "config": "glm47-flash-ep8",
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


KINDS = {"['mixer']['W_kvb']": "gradient_latent",
         "['mixer']['kv_norm']": "gradient_latent",
         "['W_eh']": "gradient_w_eh",
         "['lm_head']['W']": "gradient_head",
         "['embed']['W']": "gradient_dense",
         "['mlp']['W1']": "gradient_dense",
         "['moe']['shared_up']": "gradient_dense",
         "['moe']['w_gate']": "gradient_routed",
         "['moe']['w_down']": "gradient_routed",
         "['moe']['router']": "gradient_router"}


def rows(chip_check, system=None, float8=None):
    half = {k: v / 2 for k, v in chip_check.LIMITS.items()}
    sound = {"logits_rms_over_spread": half["logits_rms_over_spread"],
             "mtp_logits_rms_over_spread":
                 half["mtp_logits_rms_over_spread"],
             "loss_rel_err": 1e-5,
             "gradients": {k: half[v] for k, v in KINDS.items()}}
    low = {"logits_rms_over_spread": 0.5, "mtp_logits_rms_over_spread": 0.5,
           "loss_rel_err": 1e-2, "gradients": {k: 1.0 for k in KINDS}}
    return {"rows": {"system": {**sound, **(system or {})},
                     "reference_operands_float8": {**low, **(float8 or {})}}}


def test_the_check_is_the_nemotron_ones_on_this_cell(chip_check):
    assert chip_check.CELL == CELL
    assert chip_check.CONTROLS["reference_operands_float8"] == {
        "control_operand_dtype": "float8_e4m3fn"}
    assert chip_check.MUST_BE_REFUSED == ("reference_operands_float8",)
    assert chip_check.verdict(rows(chip_check), 5e-4) == []


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_gradient_over_its_kinds_limit_is_a_fault(chip_check, kind):
    limit = KINDS[kind]
    assert chip_check.gradient_limit(kind) == limit
    over = {"gradients": {kind: 1.01 * chip_check.LIMITS[limit]}}
    assert chip_check.verdict(rows(chip_check, system=over), 5e-4) == [
        f"system over {limit}"]


@pytest.mark.parametrize("reading", ["logits_rms_over_spread",
                                     "mtp_logits_rms_over_spread"])
def test_either_heads_logits_over_their_limit_is_a_fault(chip_check,
                                                         reading):
    over = {reading: 1.01 * chip_check.LIMITS[reading]}
    assert chip_check.verdict(rows(chip_check, system=over), 5e-4) == [
        f"system over {reading}"]


def test_a_float8_control_inside_any_limit_is_a_fault(chip_check):
    control = "reference_operands_float8"
    assert chip_check.verdict(
        rows(chip_check, float8={"loss_rel_err": 4e-4}), 5e-4) == [
        f"{control} is inside loss"]
    assert chip_check.verdict(
        rows(chip_check, float8={"mtp_logits_rms_over_spread": 0.01}),
        5e-4) == [f"{control} is inside mtp_logits_rms_over_spread"]
    inside = {"gradients": {**{k: 1.0 for k in KINDS}, "['W_eh']": 0.01}}
    assert chip_check.verdict(rows(chip_check, float8=inside), 5e-4) == [
        f"{control} is inside gradient_w_eh"]
    assert chip_check.verdict(
        rows(chip_check, system={"loss_rel_err": 6e-4}), 5e-4) == [
        "system over loss"]


def test_the_float32_rows_gradients_are_held_to_the_reference(chip_check):
    result = rows(chip_check)
    result["rows"]["system_float32"] = {"gradients": {"['W_eh']": 0.05}}
    assert chip_check.verdict(result, 5e-4) == [
        "system_float32 gradient of ['W_eh'] off by 0.05"]
