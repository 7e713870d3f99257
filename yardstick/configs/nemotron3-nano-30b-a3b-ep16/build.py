"""nemotron3-nano-30b-a3b-ep16: one chip's share of NVIDIA-Nemotron-3-Nano-
30B-A3B, trained through ``fit()``.

The model is the zoo's ``NemotronH`` (ordinary serialisable layers, a
``MultiLayerNetwork``) at the published widths: the first nine of the 52
layers (``MEMEM*EME``: four Mamba-2 layers, one attention, four expert
layers, one mixer a layer), the 8 routed experts this chip holds of the
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


def held_experts(cfg):
    first = int(cfg.get("expert_parallel_rank", 0)) * cfg["n_routed_experts"]
    return tuple(range(first, first + cfg["n_routed_experts"]))


def zoo_model(cfg, seed=0):
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.zoo.models import NemotronH
    return NemotronH(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        hybrid_override_pattern=cfg["hybrid_override_pattern"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
        ssm_state_size=cfg["ssm_state_size"], conv_kernel=cfg["conv_kernel"],
        chunk_size=cfg["chunk_size"], time_step_min=cfg["time_step_min"],
        time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        n_routed_experts=cfg["router_width"], held_experts=held_experts(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        bias_update_rate=cfg["bias_update_rate"],
        router_aux_loss_coef=cfg["router_aux_loss_coef"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        initializer_range=cfg["initializer_range"], seq_len=cfg["seq_len"],
        recompute=cfg["recompute"], compute_dtype=cfg["compute_dtype"],
        updater=Adam(cfg["updater"]["learning_rate"]), seed=seed % 2**31)


def build(cfg, seed):
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    return MultiLayerNetwork(zoo_model(cfg, seed).conf())


def _dataset(cfg, seed, n):
    """``n`` rows of ``seq_len`` ids by the Qwen3-Next configuration's
    maker (a Zipf draw, ranks scattered over the ids, spans of a row's own
    earlier tokens copied forward over about a third of it; loaded from
    its ``build.py`` as the Phi-4-mini-flash and SDAR configurations load
    it: to be moved to a module of no configuration, PERF.md section 7),
    with their next-token labels."""
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
    is the routers' balance term alone, over every position of the rows.
    ``fit_loop`` compares one number. Held separately on the chip, at
    seeded weights (PERF.md section 6, PR 37): the next-token term's error
    is a signed mean of rounding errors, in the float8 control a draw about
    zero that overlaps the system's and cancels the other term's on some
    seeds; the balance term's is one-signed (noisier picks follow the mean
    scores less) and grows with the square of the rounding step, the
    control's smallest over thirty times the system's largest: the term
    that says which precision computed layers 0-7. The next-token term,
    the logits and the gradients are compared on the labelled rows by
    ``chip_check.py`` and, at small sizes, by the tests."""
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.layers.decoder import IGNORE_LABEL
    data = rows_with_labels(cfg, seed, rows)
    return DataSet(data.features, np.full_like(data.labels, IGNORE_LABEL))


# ---- counted from shapes ---------------------------------------------------

def _kinds(cfg):
    """How many layers of each kind the pattern holds: M, *, E."""
    p = cfg["hybrid_override_pattern"]
    return p.count("M"), p.count("*"), p.count("E")


def _mamba_sizes(cfg):
    """Widths of ``x`` (and ``z``), of ``B`` (and ``C``), and the heads."""
    return (cfg["mamba_num_heads"] * cfg["mamba_head_dim"],
            cfg["n_groups"] * cfg["ssm_state_size"], cfg["mamba_num_heads"])


def _mamba_matrix_params(cfg):
    d, bc, h = _mamba_sizes(cfg)
    return cfg["hidden_size"] * (2 * d + 2 * bc + h) + d * cfg["hidden_size"]


def _attn_matrix_params(cfg):
    h = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return h * q + 2 * h * kv + q * h


def _expert_params(cfg, width):
    """A non-gated expert: ``W_up`` and ``W_down``."""
    return 2 * cfg["hidden_size"] * width


def parameter_count(cfg):
    """The parameters on the chip, by part (the configuration file's
    ``parameters``). The routers' score-correction bias (``router_width``
    numbers a layer) is a buffer in the model state: no gradient, no Adam
    moments, not a parameter."""
    h = cfg["hidden_size"]
    d, bc, heads = _mamba_sizes(cfg)
    n_m, n_a, n_e = _kinds(cfg)
    mamba = (_mamba_matrix_params(cfg)
             + (d + 2 * bc) * (cfg["conv_kernel"] + 1)    # filter and bias
             + 3 * heads + d                   # dt_bias, A_log, D; the norm
             + h)                              # the layer's RMSNorm
    attn = _attn_matrix_params(cfg) + h
    router = h * cfg["router_width"]
    shared = _expert_params(cfg, cfg["moe_shared_expert_intermediate_size"])
    expert = _expert_params(cfg, cfg["moe_intermediate_size"])
    held = cfg["n_routed_experts"] * expert
    experts = router + shared + held + h
    ends = 2 * cfg["vocab_size"] * h + h          # embedding, head, norm
    layers = n_m * mamba + n_a * attn + n_e * experts
    return {"mamba2_layer": mamba, "attention_layer": attn,
            "router": router, "shared_expert": shared,
            "one_routed_expert": expert,
            "routed_experts_held_per_layer": held, "expert_layer": experts,
            "layers": layers, "embedding_head_and_final_norm": ends,
            "on_the_chip": layers + ends,
            "bytes_at_16_per_parameter": 16 * (layers + ends)}


def _routed_assignments_per_token(cfg):
    """Expected assignments of one token that land on held experts."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_width"]


def _recurrence_flops_per_token(cfg):
    """The Mamba-2 recurrence, one layer, forward: decay the state, write
    the rank-one update (a multiply and an add), read it with C (a
    multiply and an add): 5 operations per state element and head."""
    return 5 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"]


def _scan_flops_per_token(cfg):
    """The recurrence with what the scope ``ssd.conv`` + ``ssd.scan``
    holds round it: the short convolution (a multiply-add a tap and its
    bias), SiLU, the skip, the gate and the grouped norm (about eight
    operations a channel)."""
    d, bc, _ = _mamba_sizes(cfg)
    return (_recurrence_flops_per_token(cfg)
            + (d + 2 * bc) * (2 * cfg["conv_kernel"] + 2) + 8 * d)


def train_flops_per_example(cfg):
    """Floating-point operations one sequence needs in one optimizer
    step, from shapes only: 6 x the matrix parameters a token touches
    (routed experts at the expected held assignments a token), causal
    attention at half the square, the Mamba-2 recurrence by its
    token-by-token form with its convolution, skip, gate and norm; forward
    plus twice that backward, **no recomputation**. Embedding lookups,
    the layers' norms, the router's sigmoid and top-k, softmaxes and the
    optimizer are not counted."""
    t = cfg["seq_len"]
    n_m, n_a, n_e = _kinds(cfg)
    experts = (cfg["hidden_size"] * cfg["router_width"]
               + _expert_params(cfg,
                                cfg["moe_shared_expert_intermediate_size"])
               + _routed_assignments_per_token(cfg)
               * _expert_params(cfg, cfg["moe_intermediate_size"]))
    matrices = (n_m * _mamba_matrix_params(cfg)
                + n_a * _attn_matrix_params(cfg) + n_e * experts
                + cfg["hidden_size"] * cfg["vocab_size"])
    # QK^T and PV, two operations a multiply-add, t/2 keys a query
    attention = n_a * 4 * (t / 2) * cfg["num_attention_heads"] \
        * cfg["head_dim"]
    return t * (6 * matrices
                + 3 * (attention + n_m * _scan_flops_per_token(cfg)))


def ssd_scan_work(cfg):
    """``(operations, bytes)`` one optimizer step needs, at least, for the
    Mamba-2 layers' convolution, recurrence, skip, gate and grouped norm,
    forward and backward: the token-by-token form's operations, the same
    whatever implements the scan, chunked or not; bytes for reading the
    channels before the convolution (``x``, ``B``, ``C``), ``dt`` and
    ``z`` and writing the normed result, in the compute type, and twice
    that backward (read what was read and the result's gradient, write
    the inputs' gradients)."""
    d, bc, heads = _mamba_sizes(cfg)
    t = cfg["seq_len"] * cfg["batch"]
    item = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    layers = _kinds(cfg)[0]
    per_token = ((d + 2 * bc) + heads + d + d) * item
    return (3 * layers * t * _scan_flops_per_token(cfg),
            3 * layers * t * per_token)


def moe_grouped_work(cfg):
    """``(operations, bytes)`` one optimizer step needs, at least, for the
    routed experts of every expert layer (router, dispatch, the two
    grouped products of a non-gated expert, combine), forward and
    backward, as the Qwen3-Next configuration counts it: 6 x (router +
    expected held assignments x one expert) a token; bytes for reading
    the held experts' weights once forward, reading them and writing
    their gradients once backward, in the compute type, and the tokens in
    and out."""
    t = cfg["seq_len"] * cfg["batch"]
    item = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    expert = _expert_params(cfg, cfg["moe_intermediate_size"])
    router = cfg["hidden_size"] * cfg["router_width"]
    flops = 6 * t * (router + _routed_assignments_per_token(cfg) * expert)
    weights = cfg["n_routed_experts"] * expert + router
    tokens = 2 * t * cfg["hidden_size"]
    layers = _kinds(cfg)[2]
    return layers * flops, layers * 3 * (weights + tokens) * item
