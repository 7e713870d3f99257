"""qwen3-next-80b-a3b-ep16: one chip's share of Qwen3-Next-80B-A3B,
trained through ``fit()``.

The model is the zoo's ``Qwen3Next`` (ordinary serialisable layers, a
``MultiLayerNetwork``) at the published widths: one period of four blocks
(three Gated DeltaNets and one gated attention, each with the expert
layer), the 32 routed experts this chip holds of the router's 512, and an
eighth of the vocabulary. The set is 32 in-memory sequences of 8,192
seeded token ids, handed to ``fit()`` as a ``DataSet`` through
``ArrayDataSetIterator(shuffle=True, drop_last=True)``, so the feeder
runs as it does for a user.

Below the builders are the functions that count operations and bytes from
shapes alone, for the whole step (``train_flops_per_example``) and for the
kernels whose roofline shares the benchmark reports: the least work the
mathematics needs, whatever implements it, and no recomputation.
"""

from __future__ import annotations

import numpy as np


def held_experts(cfg):
    first = int(cfg.get("expert_parallel_rank", 0)) * cfg["num_experts"]
    return tuple(range(first, first + cfg["num_experts"]))


def zoo_model(cfg, seed=0):
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.zoo.models import Qwen3Next
    return Qwen3Next(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]),
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        num_experts=cfg["router_width"], held_experts=held_experts(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        seq_len=cfg["seq_len"], chunk_size=cfg["chunk_size"],
        recompute=cfg["recompute"], compute_dtype=cfg["compute_dtype"],
        updater=Adam(cfg["updater"]["learning_rate"]), seed=seed % 2**31)


def build(cfg, seed):
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    return MultiLayerNetwork(zoo_model(cfg, seed).conf())


def _token_ids(cfg, seed, n):
    """``n`` rows of ``seq_len`` ids from a Zipf draw over the slice
    (ranks scattered over the ids), with spans of a row's own earlier
    tokens copied forward over about a third of it."""
    rng = np.random.default_rng(seed)
    vocab, t = cfg["vocab_size"], cfg["seq_len"]
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -cfg["zipf_exponent"]
    by_rank = rng.permutation(vocab)
    ids = by_rank[rng.choice(vocab, size=(n, t), p=p / p.sum())]
    span = min(cfg["repeated_span"], t // 4)
    if span > 0:
        for row in ids:
            for _ in range(t // (3 * span)):
                src = rng.integers(0, t - 2 * span)
                dst = rng.integers(src + span, t - span + 1)
                row[dst:dst + span] = row[src:src + span]
    return ids.astype(np.int32)


def _dataset(cfg, seed, n):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.layers.decoder import next_token_labels
    ids = _token_ids(cfg, seed, n)
    return DataSet(ids, next_token_labels(ids))


def train_set(cfg, seed, batch):
    from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator
    return ArrayDataSetIterator(_dataset(cfg, seed, cfg["examples"]), batch,
                                shuffle=True, seed=seed % 2**31,
                                drop_last=True)


def check_batch(cfg, seed, rows):
    """A few sequences for the comparison with the plain reference."""
    return _dataset(cfg, seed + 1, rows)


# ---- counted from shapes ---------------------------------------------------

def _gdn_matrix_params(cfg):
    h = cfg["hidden_size"]
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return h * (2 * kd + 2 * vd) + h * 2 * cfg["linear_num_value_heads"] \
        + vd * h


def _attn_matrix_params(cfg):
    h = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return h * 2 * q + 2 * h * kv + q * h


def _expert_params(cfg, width):
    return 3 * cfg["hidden_size"] * width


def _layers(cfg):
    full = cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    return cfg["num_hidden_layers"] - full, full       # deltanet, attention


def parameter_count(cfg):
    """The parameters on the chip, by part (the configuration file's
    ``parameters``)."""
    h = cfg["hidden_size"]
    n_gdn, n_attn = _layers(cfg)
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    gdn = (_gdn_matrix_params(cfg)
           + (2 * kd + vd) * cfg["linear_conv_kernel_dim"]
           + 2 * cfg["linear_num_value_heads"]
           + cfg["linear_value_head_dim"] + 2 * h)
    attn = _attn_matrix_params(cfg) + 2 * cfg["head_dim"] + 2 * h
    moe_rest = (h * cfg["router_width"]
                + _expert_params(cfg, cfg["shared_expert_intermediate_size"])
                + h)
    expert = _expert_params(cfg, cfg["moe_intermediate_size"])
    held = cfg["num_experts"] * expert
    period = (n_gdn * gdn + n_attn * attn
              + cfg["num_hidden_layers"] * (moe_rest + held))
    ends = 2 * cfg["vocab_size"] * h + h          # embedding, head, norm
    return {"gated_deltanet_layer_outside_moe": gdn,
            "gated_attention_layer_outside_moe": attn,
            "moe_outside_routed_experts_per_layer": moe_rest,
            "one_routed_expert": expert,
            "routed_experts_held_per_layer": held,
            "layers": period, "embedding_head_and_final_norm": ends,
            "on_the_chip": period + ends,
            "bytes_at_16_per_parameter": 16 * (period + ends)}


def _delta_rule_flops_per_token(cfg):
    """The recurrent form, per layer, forward: decay the state, read it
    with k, write the rank-one update (a multiply and an add), read it
    with q: 7 operations per state element and value head."""
    state = cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]
    return 7 * state * cfg["linear_num_value_heads"]


def _conv_flops_per_token(cfg):
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return 2 * (2 * kd + vd) * cfg["linear_conv_kernel_dim"]


def _routed_assignments_per_token(cfg):
    """Expected assignments of one token that land on held experts."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_width"]


def train_flops_per_example(cfg):
    """Floating-point operations one sequence needs in one optimizer
    step, from shapes only: 6 x the matrix parameters a token touches
    (routed experts at the expected held assignments a token), causal
    attention at half the square, the delta rule by its recurrent form,
    the short convolution; forward plus twice that backward, **no
    recomputation**. Embedding lookups, norms, gates, softmaxes and the
    optimizer are not counted."""
    t = cfg["seq_len"]
    n_gdn, n_attn = _layers(cfg)
    moe = (cfg["hidden_size"] * cfg["router_width"]
           + _expert_params(cfg, cfg["shared_expert_intermediate_size"])
           + _routed_assignments_per_token(cfg)
           * _expert_params(cfg, cfg["moe_intermediate_size"]))
    matrices = (n_gdn * _gdn_matrix_params(cfg)
                + n_attn * _attn_matrix_params(cfg)
                + cfg["num_hidden_layers"] * moe
                + cfg["hidden_size"] * cfg["vocab_size"])
    # QK^T and PV, two operations a multiply-add, t/2 keys a query
    attention = n_attn * 4 * (t / 2) * cfg["num_attention_heads"] \
        * cfg["head_dim"]
    scan = n_gdn * (_delta_rule_flops_per_token(cfg)
                    + _conv_flops_per_token(cfg))
    return t * (6 * matrices + 3 * (attention + scan))


def gdn_scan_work(cfg):
    """``(operations, bytes)`` one optimizer step needs, at least, for
    the DeltaNet layers' convolution, delta rule and gated norm, forward
    and backward: the recurrent form's operations; bytes for reading the
    q/k/v channels, z and the two gates and writing the result in the
    compute type, and twice that backward (read what was read and the
    result's gradient, write the inputs' gradients)."""
    n_gdn, _ = _layers(cfg)
    t = cfg["seq_len"] * cfg["batch"]
    kd = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vd = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    item = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    flops = 3 * n_gdn * t * (_delta_rule_flops_per_token(cfg)
                             + _conv_flops_per_token(cfg))
    per_token = (2 * kd + vd) + vd + 2 * cfg["linear_num_value_heads"] + vd
    return flops, 3 * n_gdn * t * per_token * item


def moe_grouped_work(cfg):
    """``(operations, bytes)`` one optimizer step needs, at least, for the
    routed experts of every layer (router, dispatch, the three grouped
    products, combine), forward and backward: 6 x (router + expected held
    assignments x one expert) a token; bytes for reading the held experts'
    weights once forward, reading them and writing their gradients once
    backward, in the compute type, and the tokens in and out."""
    t = cfg["seq_len"] * cfg["batch"]
    item = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    expert = _expert_params(cfg, cfg["moe_intermediate_size"])
    router = cfg["hidden_size"] * cfg["router_width"]
    flops = 6 * t * (router + _routed_assignments_per_token(cfg) * expert)
    weights = cfg["num_experts"] * expert + router
    tokens = 2 * t * cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    return layers * flops, layers * 3 * (weights + tokens) * item
