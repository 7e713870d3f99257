"""glm47-flash-ep8: one chip's share of Z.ai's GLM-4.7-Flash
(``glm4_moe_lite``), trained through ``fit()``.

The model is the zoo's ``GLM4MoeLite`` (ordinary serialisable layers, a
``ComputationGraph``) at the published widths: the first five of the 47
layers (the dense one, then four expert layers), the multi-token-prediction
module (the release's one), the 8 routed experts this chip holds of the
router's 64 in every expert layer, and an eighth of the vocabulary. The
set is 32 in-memory rows of 8,192 seeded token ids with their next-token
labels, handed to ``fit()`` through ``ArrayDataSetIterator(shuffle=True,
drop_last=True)``.

Below the build functions are the ones that count operations and bytes from
shapes alone, for the whole step (``train_flops_per_example``) and for the
part whose roofline share the benchmark reports
(``latent_attention_work``): the least work the mathematics needs,
whatever implements it, and no recomputation.
"""

from __future__ import annotations

from pathlib import Path

from yardstick import cells


def held_experts(cfg):
    first = int(cfg.get("expert_parallel_rank", 0)) * cfg["n_routed_experts"]
    return tuple(range(first, first + cfg["n_routed_experts"]))


def zoo_model(cfg, seed=0):
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.zoo.models import GLM4MoeLite
    return GLM4MoeLite(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        first_k_dense_replace=cfg["first_k_dense_replace"],
        n_routed_experts=cfg["router_width"],
        held_experts=held_experts(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        n_shared_experts=cfg["n_shared_experts"],
        num_nextn_predict_layers=cfg["num_nextn_predict_layers"],
        mtp_loss_weight=cfg["mtp_loss_weight"],
        bias_update_rate=cfg["bias_update_rate"],
        router_aux_loss_coef=cfg["router_aux_loss_coef"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"], seq_len=cfg["seq_len"],
        recompute=cfg["recompute"], compute_dtype=cfg["compute_dtype"],
        updater=Adam(cfg["updater"]["learning_rate"]), seed=seed % 2**31)


def build(cfg, seed):
    from deeplearning4j_tpu.models.computation_graph import (
        ComputationGraph)
    return ComputationGraph(zoo_model(cfg, seed).conf())


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
    (both heads' logits, both loss terms, gradients)."""
    return _dataset(cfg, seed + 1, rows)


def check_batch(cfg, seed, rows):
    """The rows of the harness's one comparison with the plain reference:
    ``rows_with_labels``, labels and all, so that the score compared is
    the whole training loss, both cross-entropies (the main head's and
    the module's on the token after the next, through the one head) and
    the routers' balance term (the four expert layers' and the
    module's)."""
    return rows_with_labels(cfg, seed, rows)


# ---- counted from shapes ---------------------------------------------------

def _mla_matrix_params(cfg):
    """``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o``."""
    h, d = cfg["num_attention_heads"], cfg["hidden_size"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    q, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * q + q * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
            + h * dv * d)


def _glu_params(cfg, width):
    """A SwiGLU MLP or expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * width


def _routed_assignments_per_token(cfg):
    """Expected assignments of one token that land on held experts."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_width"]


def _mla_layers(cfg):
    """Layers with latent attention: the main model's and the module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def _expert_layers(cfg):
    return (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
            + cfg["num_nextn_predict_layers"])


def parameter_count(cfg):
    """The parameters on the chip, by part (the configuration file's
    ``parameters``). The routers' bias (``router_width`` numbers a layer)
    is a buffer in the model state: no gradient, no Adam moments, not a
    parameter."""
    d = cfg["hidden_size"]
    mla = _mla_matrix_params(cfg) + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    norms = 2 * d
    mlp = _glu_params(cfg, cfg["intermediate_size"])
    router = d * cfg["router_width"]
    expert = _glu_params(cfg, cfg["moe_intermediate_size"])
    shared = cfg["n_shared_experts"] * expert
    held = cfg["n_routed_experts"] * expert
    dense_layer = mla + norms + mlp
    expert_layer = mla + norms + router + shared + held
    n_dense = cfg["first_k_dense_replace"]
    layers = (n_dense * dense_layer
              + (cfg["num_hidden_layers"] - n_dense) * expert_layer)
    mtp = 2 * d * d + 3 * d + expert_layer
    ends = 2 * cfg["vocab_size"] * d + d          # embedding, head, norm
    total = layers + mtp + ends
    return {"latent_attention_with_norms": mla, "dense_mlp": mlp,
            "dense_layer": dense_layer, "router": router,
            "shared_expert": shared, "one_routed_expert": expert,
            "routed_experts_held_per_layer": held,
            "expert_layer": expert_layer, "layers": layers,
            "mtp_module": mtp,
            "embedding_head_and_final_norm": ends,
            "on_the_chip": total, "bytes_at_16_per_parameter": 16 * total}


def _causal_pairs(t):
    return t * (t + 1) // 2


def _latent_attention_forward_flops(cfg):
    """Forward operations of one sequence's latent attention maps in every
    MLA layer: q k^T over ``qk_nope_head_dim + qk_rope_head_dim`` and p v
    over ``v_head_dim``, for every head and visible (query, key) pair,
    two operations a multiply-add."""
    head = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
            + cfg["v_head_dim"])
    return (_mla_layers(cfg) * 2 * _causal_pairs(cfg["seq_len"])
            * cfg["num_attention_heads"] * head)


def train_flops_per_example(cfg):
    """Floating-point operations one sequence needs in one optimizer
    step, from shapes only: 6 x the matrix parameters a token touches
    (routed experts at the expected held assignments a token; the head
    twice, once for each loss; the module's ``W_eh``), the causal maps over
    the visible pairs alone; forward plus twice that backward, **no
    recomputation**. Embedding lookups, norms, the rotary, the router's
    sigmoid and top-k, softmaxes and the optimizer are not counted."""
    d, t = cfg["hidden_size"], cfg["seq_len"]
    experts = (d * cfg["router_width"]
               + (cfg["n_shared_experts"]
                  + _routed_assignments_per_token(cfg))
               * _glu_params(cfg, cfg["moe_intermediate_size"]))
    heads = 1 + cfg["num_nextn_predict_layers"]
    matrices = (_mla_layers(cfg) * _mla_matrix_params(cfg)
                + cfg["first_k_dense_replace"]
                * _glu_params(cfg, cfg["intermediate_size"])
                + _expert_layers(cfg) * experts
                + cfg["num_nextn_predict_layers"] * 2 * d * d
                + heads * d * cfg["vocab_size"])
    return 6 * t * matrices + 3 * _latent_attention_forward_flops(cfg)


def latent_attention_work(cfg):
    """``(operations, bytes)`` one optimizer step needs, at least, for the
    latent attention of every MLA layer (the module's included), forward
    and backward, no recomputation: the five projections (6 x their
    parameters a token) and the causal pairs over 20 x (256 + 256) a pair
    (3 x the forward); bytes for reading the projections' weights and
    writing their gradients, and for the latents (``c_q``, ``c_kv``,
    ``k_r``), q, k, v and the result of every token, in the compute type,
    once forward and twice backward."""
    t = cfg["seq_len"] * cfg["batch"]
    item = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    layers = _mla_layers(cfg)
    weights = _mla_matrix_params(cfg)
    flops = (6 * t * layers * weights
             + 3 * cfg["batch"] * _latent_attention_forward_flops(cfg))
    latents = (cfg["q_lora_rank"] + cfg["kv_lora_rank"]
               + cfg["qk_rope_head_dim"])
    per_token = latents + h * (2 * qk + 2 * cfg["v_head_dim"])
    return flops, 3 * layers * (weights + t * per_token) * item
