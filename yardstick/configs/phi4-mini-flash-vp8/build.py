"""phi4-mini-flash-vp8: one chip's share of Phi-4-mini-flash-reasoning,
trained through ``fit()``.

The model is the zoo's ``Phi4MiniFlash`` (ordinary serialisable layers, a
``ComputationGraph`` whose edges carry what one block emits and another
reads) at the published widths: the published blocks 0, 1, 16, 17, 18 and
19 (Mamba, window attention; the memory-emitting Mamba, the full attention
whose keys and values are read again; a gated memory unit, a
cross-attention), an eighth of the vocabulary, the head tied to the
embedding. The set is the Qwen3-Next configuration's, made by its
``build.py``: 32 in-memory sequences of 8,192 seeded token ids, handed to
``fit()`` as a ``DataSet`` through ``ArrayDataSetIterator(shuffle=True,
drop_last=True)``, so the feeder runs as it does for a user.

Below the builders are the functions that count operations and bytes from
shapes alone, for the whole step (``train_flops_per_example``) and for the
kernels whose roofline shares the benchmark reports: the least work the
mathematics needs, whatever implements it, and no recomputation.
"""

from __future__ import annotations

from pathlib import Path

from deeplearning4j_tpu.zoo.models import Phi4MiniFlash
from yardstick import cells


def zoo_model(cfg, seed=0):
    from deeplearning4j_tpu.optimize.updaters import Adam
    return Phi4MiniFlash(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["published"]["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        sliding_window=cfg["sliding_window"],
        mb_per_layer=cfg["mb_per_layer"],
        layer_norm_eps=cfg["layer_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"], mamba_dt_rank=cfg["mamba_dt_rank"],
        layer_indices=tuple(cfg["layer_indices"]),
        initializer_range=cfg["initializer_range"],
        seq_len=cfg["seq_len"], recompute=cfg["recompute"],
        compute_dtype=cfg["compute_dtype"],
        updater=Adam(cfg["updater"]["learning_rate"]), seed=seed % 2**31)


def build(cfg, seed):
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph
    return ComputationGraph(zoo_model(cfg, seed).conf())


# the data is the Qwen3-Next configuration's: Zipf(1.1) ids over the slice
# with repeated spans, ``ArrayDataSetIterator(shuffle=True, drop_last=True)``,
# and ``check_batch``'s few rows for the comparison with the reference.
# Loaded from that configuration's file, so an edit to its data is an edit
# to this cell's: whoever changes ``_dataset`` there measures both cells
# (that file is the accepted benchmark's; a ``benchmark`` PR moves the maker
# to a module of neither configuration, PERF.md section 7)
_data = cells.load_file_module(
    Path(__file__).resolve().parents[1] / "qwen3-next-80b-a3b-ep16"
    / "build.py")
train_set, check_batch = _data.train_set, _data.check_batch


# ---- counted from shapes ---------------------------------------------------

def _sizes(cfg):
    h = cfg["hidden_size"]
    return {"h": h, "d": cfg["mamba_expand"] * h, "s": cfg["mamba_d_state"],
            "r": cfg["mamba_dt_rank"], "taps": cfg["mamba_d_conv"],
            "q": h, "kv": cfg["num_key_value_heads"]
            * (h // cfg["num_attention_heads"]),
            "dh": h // cfg["num_attention_heads"]}


def _kinds(cfg):
    """How many of the built blocks are of each kind."""
    model = zoo_model(cfg)
    kinds = {}
    for l in cfg["layer_indices"]:
        mixer, _, window = model.mixer_of(l)
        kind = "window_attention" if window else mixer
        kinds[kind] = kinds.get(kind, 0) + 1
    return kinds


def _matrix_params(cfg):
    """Matrix parameters a token is multiplied by, by kind of part."""
    z = _sizes(cfg)
    h, d = z["h"], z["d"]
    attn = h * (z["q"] + 2 * z["kv"]) + z["q"] * h
    return {"mlp": 3 * h * cfg["intermediate_size"],
            "mamba": h * 2 * d + d * (z["r"] + 2 * z["s"]) + z["r"] * d
            + d * h,
            "attention": attn, "window_attention": attn,
            "gated_memory": 2 * h * d,
            "cross_attention": 2 * h * z["q"]}


def parameter_count(cfg):
    """The parameters on the chip, by part (the configuration file's
    ``parameters``)."""
    z = _sizes(cfg)
    h, d, dh = z["h"], z["d"], z["dh"]
    mats = _matrix_params(cfg)
    differential = 4 * dh + 2 * dh              # lambda vectors, sub-norm
    mixers = {
        "mamba": mats["mamba"] + d * z["taps"] + d + d + d * z["s"] + d,
        "attention": mats["attention"] + (z["q"] + 2 * z["kv"]) + h
        + differential,
        "gated_memory": mats["gated_memory"],
        "cross_attention": mats["cross_attention"] + z["q"] + h
        + differential}
    mixers["window_attention"] = mixers["attention"]
    mlp_and_norms = mats["mlp"] + 4 * h
    kinds = _kinds(cfg)
    layers = sum(n * (mixers[k] + mlp_and_norms) for k, n in kinds.items())
    ends = cfg["vocab_size"] * h + 2 * h       # tied table, final LayerNorm
    return {"mlp_and_two_layernorms_per_block": mlp_and_norms,
            "mamba_mixer": mixers["mamba"],
            "self_attention_mixer": mixers["attention"],
            "gated_memory_unit_mixer": mixers["gated_memory"],
            "cross_attention_mixer": mixers["cross_attention"],
            "layers": layers, "tied_embedding_and_final_norm": ends,
            "on_the_chip": layers + ends,
            "bytes_at_16_per_parameter": 16 * (layers + ends)}


def _window_pairs(t, window):
    """(query, key) pairs a causal window of ``window`` lets through."""
    w = min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


def _attention_flops(cfg, pairs):
    """Forward operations of one differential-attention layer's two maps
    over ``pairs`` visible (query, key) pairs: per pair and head pair, two
    maps of QK^T over the head and PV over twice the head, two operations
    a multiply-add."""
    dh = cfg["hidden_size"] // cfg["num_attention_heads"]
    return pairs * (cfg["num_attention_heads"] // 2) * 2 * 2 * (dh + 2 * dh)


def _scan_flops_per_token(cfg):
    """The recurrence, per Mamba layer, forward: the decay's product and
    exponential, the write (two products and the add into the state), the
    read (a multiply-add): 7 operations per state element; the skip and
    the gate 4 a channel; the convolution two a tap and channel."""
    z = _sizes(cfg)
    return 7 * z["d"] * z["s"] + 4 * z["d"] + 2 * z["d"] * z["taps"]


def train_flops_per_example(cfg):
    """Floating-point operations one sequence needs in one optimizer
    step, from shapes only: 6 x the matrix parameters a token touches (the
    tied table once, as the head), differential attention over the visible
    pairs (half the square, or the window's band), the selective scan by
    its recurrence; forward plus twice that backward, **no
    recomputation**. Embedding lookups, norms, gates, softmaxes and the
    optimizer are not counted."""
    t = cfg["seq_len"]
    mats = _matrix_params(cfg)
    kinds = _kinds(cfg)
    matrices = (sum(n * (mats[k] + mats["mlp"]) for k, n in kinds.items())
                + cfg["hidden_size"] * cfg["vocab_size"])
    causal = t * (t + 1) // 2
    attention = (
        (kinds.get("attention", 0) + kinds.get("cross_attention", 0))
        * _attention_flops(cfg, causal)
        + kinds.get("window_attention", 0)
        * _attention_flops(cfg, _window_pairs(t, cfg["sliding_window"])))
    scan = kinds.get("mamba", 0) * t * _scan_flops_per_token(cfg)
    return 6 * t * matrices + 3 * (attention + scan)


def ssm_scan_work(cfg):
    """``(operations, bytes)`` one optimizer step needs, at least, for the
    Mamba layers' convolution, selective scan, skip and gate, forward and
    backward: the recurrence's operations; bytes for reading the
    channels before the convolution, z, the step (float32), B and C
    (float32) and writing the gated result, in the compute type where not
    said otherwise, and twice that backward (read what was read and the
    result's gradient, write the inputs' gradients)."""
    z = _sizes(cfg)
    t = cfg["seq_len"] * cfg["batch"]
    item = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    layers = _kinds(cfg).get("mamba", 0)
    per_token = 3 * z["d"] * item + z["d"] * 4 + 2 * z["s"] * 4
    return (3 * layers * t * _scan_flops_per_token(cfg),
            3 * layers * t * per_token)


def window_attention_work(cfg):
    """``(operations, bytes)`` one optimizer step needs, at least, for the
    window-attention layers' two maps, forward and backward: the pairs
    inside the window only (a kernel that computes every causal block does
    about eight times this at 8,192 positions and reads as an eighth of
    the share); bytes for reading q, k, v and writing the result of both
    maps in the compute type, and twice that backward."""
    z = _sizes(cfg)
    t = cfg["seq_len"] * cfg["batch"]
    item = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    layers = _kinds(cfg).get("window_attention", 0)
    pairs = cfg["batch"] * _window_pairs(cfg["seq_len"],
                                         cfg["sliding_window"])
    per_token = z["q"] + 2 * z["kv"] + 2 * z["q"]   # q, k, v; two maps out
    return (3 * layers * _attention_flops(cfg, pairs),
            3 * layers * t * per_token * item)
