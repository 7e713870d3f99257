"""trinity-mini-ep8: one chip's share of Arcee's Trinity-Mini (AFMoE),
trained through ``fit()``.

The model is the zoo's ``AFMoE`` (ordinary serialisable layers, a
``MultiLayerNetwork``) at the published widths: the first six of the 32
layers (two dense, then four expert layers; sliding, sliding, sliding,
full, sliding, sliding), the 16 routed experts this chip holds of the
router's 128, and an eighth of the vocabulary. The set is 32 in-memory
rows of 8,192 seeded token ids with their next-token labels, handed to
``fit()`` through ``ArrayDataSetIterator(shuffle=True, drop_last=True)``.

Below the builders are the functions that count operations and bytes from
shapes alone, for the whole step (``train_flops_per_example``) and for the
parts whose roofline shares the benchmark reports: the least work the
mathematics needs, whatever implements it, and no recomputation.
"""

from __future__ import annotations

from pathlib import Path

from yardstick import cells

SLIDING = "sliding_attention"


def held_experts(cfg):
    first = int(cfg.get("expert_parallel_rank", 0)) * cfg["num_experts"]
    return tuple(range(first, first + cfg["num_experts"]))


def zoo_model(cfg, seed=0):
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.zoo.models import AFMoE
    return AFMoE(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]),
        num_experts=cfg["router_width"], held_experts=held_experts(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        route_scale=cfg["route_scale"],
        load_balance_coeff=cfg["load_balance_coeff"],
        router_aux_loss_coef=cfg["router_aux_loss_coef"],
        rms_norm_eps=cfg["rms_norm_eps"], mup_enabled=cfg["mup_enabled"],
        initializer_range=cfg["initializer_range"], seq_len=cfg["seq_len"],
        recompute=cfg["recompute"], compute_dtype=cfg["compute_dtype"],
        updater=Adam(cfg["updater"]["learning_rate"]), seed=seed % 2**31)


def build(cfg, seed):
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    return MultiLayerNetwork(zoo_model(cfg, seed).conf())


def _dataset(cfg, seed, n):
    """``n`` rows of ``seq_len`` ids by the Qwen3-Next configuration's
    maker (loaded from its ``build.py`` as the other language-model
    configurations load it), with their next-token labels."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.layers.decoder import next_token_labels
    maker = cells.load_file_module(
        Path(__file__).resolve().parents[1] / "qwen3-next-80b-a3b-ep16"
        / "build.py")
    ids = maker._token_ids(cfg, seed, n)
    return DataSet(ids, next_token_labels(ids))


def train_set(cfg, seed, batch):
    from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator
    return ArrayDataSetIterator(_dataset(cfg, seed, cfg["examples"]), batch,
                                shuffle=True, seed=seed % 2**31,
                                drop_last=True)


def rows_with_labels(cfg, seed, rows):
    """A few seeded sequences that are not the training set's, with their
    next-token labels: the rows of the CPU tests and of ``chip_check.py``
    (logits, training loss, gradients)."""
    return _dataset(cfg, seed + 1, rows)


def check_batch(cfg, seed, rows):
    """The rows of the harness's one comparison with the plain reference:
    ``rows_with_labels`` **without a label**, so that the score compared
    is the routers' balance term alone, over every position of the rows,
    as in the Nemotron 3 Nano configuration (PERF.md section 6): that
    term's error is one-signed and grows with the square of the rounding
    step, where the next-token term's is a signed mean of rounding errors
    that a float8 control can draw inside any limit. The next-token term,
    the logits and the gradients are compared on the labelled rows by
    ``chip_check.py`` and, at small sizes, by the tests."""
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.layers.decoder import IGNORE_LABEL
    data = rows_with_labels(cfg, seed, rows)
    return DataSet(data.features, np.full_like(data.labels, IGNORE_LABEL))


# ---- counted from shapes ---------------------------------------------------

def _kinds(cfg):
    """``(sliding layers, full layers, dense layers, expert layers)``."""
    types = cfg["layer_types"]
    dense = min(cfg["num_dense_layers"], len(types))
    sliding = sum(t == SLIDING for t in types)
    return sliding, len(types) - sliding, dense, len(types) - dense


def _attn_matrix_params(cfg):
    """``W_q`` with the gate's columns, ``W_k``, ``W_v``, ``W_o``."""
    h = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return h * 2 * q + 2 * h * kv + q * h


def _glu_params(cfg, width):
    """A SwiGLU MLP or expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * width


def _routed_assignments_per_token(cfg):
    """Expected assignments of one token that land on held experts."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_width"]


def parameter_count(cfg):
    """The parameters on the chip, by part (the configuration file's
    ``parameters``). The routers' bias (``router_width`` numbers a layer)
    is a buffer in the model state: no gradient, no Adam moments, not a
    parameter."""
    h = cfg["hidden_size"]
    _, _, n_dense, n_expert = _kinds(cfg)
    attn = _attn_matrix_params(cfg) + 2 * cfg["head_dim"] + 4 * h
    mlp = _glu_params(cfg, cfg["intermediate_size"])
    router = h * cfg["router_width"]
    expert = _glu_params(cfg, cfg["moe_intermediate_size"])
    shared = expert
    held = cfg["num_experts"] * expert
    dense_layer = attn + mlp
    expert_layer = attn + router + shared + held
    layers = n_dense * dense_layer + n_expert * expert_layer
    ends = 2 * cfg["vocab_size"] * h + h          # embedding, head, norm
    return {"attention_with_norms": attn, "dense_mlp": mlp,
            "dense_layer": dense_layer, "router": router,
            "shared_expert": shared, "one_routed_expert": expert,
            "routed_experts_held_per_layer": held,
            "expert_layer": expert_layer, "layers": layers,
            "embedding_head_and_final_norm": ends,
            "on_the_chip": layers + ends,
            "bytes_at_16_per_parameter": 16 * (layers + ends)}


def _window_pairs(t, window):
    """(query, key) pairs a causal window of ``window`` lets through."""
    w = min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


def _attention_flops(cfg, pairs):
    """Forward operations of one attention layer over ``pairs`` visible
    (query, key) pairs: QK^T and PV over the head, for every query head,
    two operations a multiply-add."""
    return 4 * pairs * cfg["num_attention_heads"] * cfg["head_dim"]


def _attention_forward_flops(cfg):
    """``(windowed layers, full layers)``: forward operations of one
    sequence's maps."""
    t = cfg["seq_len"]
    sliding, full, _, _ = _kinds(cfg)
    return (sliding * _attention_flops(
                cfg, _window_pairs(t, cfg["sliding_window"])),
            full * _attention_flops(cfg, t * (t + 1) // 2))


def train_flops_per_example(cfg):
    """Floating-point operations one sequence needs in one optimizer
    step, from shapes only: 6 x the matrix parameters a token touches
    (routed experts at the expected held assignments a token), attention
    over the visible pairs alone (the window's on sliding layers, half the
    square on full ones); forward plus twice that backward, **no
    recomputation**. Embedding lookups, norms, the router's sigmoid and
    top-k, softmaxes and the optimizer are not counted."""
    t = cfg["seq_len"]
    sliding, full, n_dense, n_expert = _kinds(cfg)
    experts = (cfg["hidden_size"] * cfg["router_width"]
               + (1 + _routed_assignments_per_token(cfg))
               * _glu_params(cfg, cfg["moe_intermediate_size"]))
    matrices = ((sliding + full) * _attn_matrix_params(cfg)
                + n_dense * _glu_params(cfg, cfg["intermediate_size"])
                + n_expert * experts
                + cfg["hidden_size"] * cfg["vocab_size"])
    return 6 * t * matrices + 3 * sum(_attention_forward_flops(cfg))


def local_attention_work(cfg):
    """``(operations, bytes)`` one optimizer step needs, at least, for the
    windowed layers' maps, forward and backward: the pairs inside the
    window only (a kernel that computes every causal block does about 2.3
    times this at 8,192 positions and window 2,048, and reads as that much
    less of the share); bytes for reading q, k, v and writing the result
    in the compute type, and twice that backward."""
    t = cfg["seq_len"] * cfg["batch"]
    item = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    sliding = _kinds(cfg)[0]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    flops = cfg["batch"] * _attention_forward_flops(cfg)[0]
    return 3 * flops, 3 * sliding * t * (2 * q + 2 * kv) * item


def moe_grouped_work(cfg):
    """``(operations, bytes)`` one optimizer step needs, at least, for the
    routed experts of every expert layer (router, dispatch, the three
    grouped products of a gated expert, combine), forward and backward, as
    the Qwen3-Next configuration counts it: 6 x (router + expected held
    assignments x one expert) a token; bytes for reading the held experts'
    weights once forward, reading them and writing their gradients once
    backward, in the compute type, and the tokens in and out."""
    t = cfg["seq_len"] * cfg["batch"]
    item = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    expert = _glu_params(cfg, cfg["moe_intermediate_size"])
    router = cfg["hidden_size"] * cfg["router_width"]
    flops = 6 * t * (router + _routed_assignments_per_token(cfg) * expert)
    weights = cfg["num_experts"] * expert + router
    tokens = 2 * t * cfg["hidden_size"]
    layers = _kinds(cfg)[3]
    return layers * flops, layers * 3 * (weights + tokens) * item
