"""sdar-30b-a3b-ep8: one chip's share of SDAR-30B-A3B-Chat, trained
through ``fit()``.

The model is the zoo's ``SDARMoE`` (ordinary serialisable layers, a
``MultiLayerNetwork``) at the published widths: four of the 48 blocks
(every one is grouped-query attention and the expert layer), the 16 routed
experts this chip holds of the router's 128, and an eighth of the
vocabulary. It is trained as a masked diffusion over blocks of four
tokens: the set is 32 in-memory rows of 8,192 clean seeded token ids,
handed to ``fit()`` through ``ArrayDataSetIterator(shuffle=True,
drop_last=True)`` with a ``BlockDiffusionNoiser`` set on it, which on the
prefetch thread makes of each row the 16,384 positions ``[noisy | clean]``
the step runs on and the labels with their 1/t weights, with fresh noise
for every batch.

Below the builders are the functions that count operations and bytes from
shapes alone, for the whole step (``train_flops_per_example``) and for the
kernels whose roofline shares the benchmark reports: the least work the
mathematics needs, whatever implements it, and no recomputation.
"""

from __future__ import annotations

from pathlib import Path

from yardstick import cells

# the check's rows take their noise from a generator of their own
CHECK_NOISE_SEED_OFFSET = 7919


def held_experts(cfg):
    first = int(cfg.get("expert_parallel_rank", 0)) * cfg["num_experts"]
    return tuple(range(first, first + cfg["num_experts"]))


def zoo_model(cfg, seed=0):
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.zoo.models import SDARMoE
    return SDARMoE(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        num_experts=cfg["router_width"], held_experts=held_experts(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        router_aux_loss_coef=cfg["router_aux_loss_coef"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["initializer_range"],
        block_length=cfg["block_length"],
        mask_token_id=cfg["mask_token_id"], seq_len=cfg["seq_len"],
        recompute=cfg["recompute"], compute_dtype=cfg["compute_dtype"],
        updater=Adam(cfg["updater"]["learning_rate"]), seed=seed % 2**31)


def build(cfg, seed):
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    return MultiLayerNetwork(zoo_model(cfg, seed).conf())


def _token_ids(cfg, seed, n):
    """``n`` rows of ``seq_len`` clean ids by the Qwen3-Next
    configuration's maker (a Zipf draw, ranks scattered over the ids,
    spans of a row's own earlier tokens copied forward over about a third
    of it; loaded from its ``build.py`` as the Phi-4-mini-flash
    configuration loads it: to be moved to a module of no configuration,
    PERF.md section 7), over the data ids ``0 .. vocab_size - 2``: the
    last id of the slice is ``[MASK]``."""
    maker = cells.load_file_module(
        Path(__file__).resolve().parents[1] / "qwen3-next-80b-a3b-ep16"
        / "build.py")
    return maker._token_ids({**cfg, "vocab_size": cfg["mask_token_id"]},
                            seed, n)


def noiser(cfg, seed):
    from deeplearning4j_tpu.datasets.diffusion import BlockDiffusionNoiser
    return BlockDiffusionNoiser(cfg["mask_token_id"], eps=cfg["noise_eps"],
                                seed=seed % 2**31)


def train_set(cfg, seed, batch):
    from deeplearning4j_tpu.datasets.dataset import (ArrayDataSetIterator,
                                                     DataSet)
    clean = DataSet(_token_ids(cfg, seed, cfg["examples"]), None)
    rows = ArrayDataSetIterator(clean, batch, shuffle=True,
                                seed=seed % 2**31, drop_last=True)
    rows.set_pre_processor(noiser(cfg, seed))
    return rows


def check_batch(cfg, seed, rows):
    """A few noised sequences for the comparison with the plain
    reference: features ``[xt | x0]`` and the labels with their weights,
    as the noiser makes them from clean rows, its generator seeded apart
    from the training set's."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    clean = DataSet(_token_ids(cfg, seed + 1, rows), None)
    return noiser(cfg, seed + CHECK_NOISE_SEED_OFFSET).pre_process(clean)


# ---- counted from shapes ---------------------------------------------------

def _attn_matrix_params(cfg):
    h = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return h * q + 2 * h * kv + q * h


def _expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def parameter_count(cfg):
    """The parameters on the chip, by part (the configuration file's
    ``parameters``)."""
    h = cfg["hidden_size"]
    attn = _attn_matrix_params(cfg) + 2 * cfg["head_dim"]
    norms = 2 * h
    router = h * cfg["router_width"]
    held = cfg["num_experts"] * _expert_params(cfg)
    layer = attn + norms + router + held
    ends = 2 * cfg["vocab_size"] * h + h          # embedding, head, norm
    total = cfg["num_hidden_layers"] * layer + ends
    return {"attention_with_qk_norms": attn, "block_norms": norms,
            "router": router, "one_routed_expert": _expert_params(cfg),
            "routed_experts_held_per_layer": held, "one_layer": layer,
            "layers": cfg["num_hidden_layers"] * layer,
            "embedding_head_and_final_norm": ends, "on_the_chip": total,
            "bytes_at_16_per_parameter": 16 * total}


def _routed_assignments_per_token(cfg):
    """Expected assignments of one token that land on held experts."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_width"]


def _visible_pairs(cfg):
    """(query, key) pairs of one head under the block-diffusion
    visibility: T (T + B) of the 4 T^2."""
    t = cfg["seq_len"]
    return t * (t + cfg["block_length"])


def _attention_flops_forward(cfg):
    """QK^T and PV over the visible pairs, two operations a
    multiply-add, every head, one layer."""
    return 4 * _visible_pairs(cfg) * cfg["num_attention_heads"] \
        * cfg["head_dim"]


def train_flops_per_example(cfg):
    """Floating-point operations one sequence needs in one optimizer
    step, from shapes only: 6 x the matrix parameters a position touches
    (routed experts at the expected held assignments a position) over the
    2 T positions of ``[noisy | clean]``, the head over the T noisy ones
    (the loss depends on no other logits), attention over the visible
    pairs; forward plus twice that backward, **no recomputation**.
    Embedding lookups, norms, rotary, softmaxes and the optimizer are not
    counted."""
    t = cfg["seq_len"]
    per_position = (_attn_matrix_params(cfg)
                    + cfg["hidden_size"] * cfg["router_width"]
                    + _routed_assignments_per_token(cfg)
                    * _expert_params(cfg))
    layers = cfg["num_hidden_layers"]
    matrices = 6 * layers * per_position * 2 * t
    head = 6 * cfg["hidden_size"] * cfg["vocab_size"] * t
    return matrices + head + 3 * layers * _attention_flops_forward(cfg)


def block_diffusion_attention_work(cfg):
    """``(operations, bytes)`` one optimizer step needs, at least, for
    the attention maps of every layer, forward and backward: the
    operations of the visible pairs; bytes for reading q, k, v and
    writing the result over the 2 T positions (every query head's; keys
    and values once a key/value head) in the compute type, and twice that
    backward (read what was read and the result's gradient, write the
    inputs' gradients)."""
    rows = cfg["batch"]
    item = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    layers = cfg["num_hidden_layers"]
    per_position = (2 * cfg["num_attention_heads"]
                    + 2 * cfg["num_key_value_heads"]) * cfg["head_dim"]
    return (3 * layers * rows * _attention_flops_forward(cfg),
            3 * layers * rows * 2 * cfg["seq_len"] * per_position * item)


def moe_grouped_work(cfg):
    """``(operations, bytes)`` one optimizer step needs, at least, for the
    routed experts of every layer (router, dispatch, the three grouped
    products, combine), forward and backward, as the Qwen3-Next
    configuration counts it: 6 x (router + expected held assignments x
    one expert) a position over the 2 T; bytes for reading the held
    experts' weights once forward, reading them and writing their
    gradients once backward, in the compute type, and the positions in
    and out."""
    t = 2 * cfg["seq_len"] * cfg["batch"]
    item = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    expert = _expert_params(cfg)
    router = cfg["hidden_size"] * cfg["router_width"]
    flops = 6 * t * (router + _routed_assignments_per_token(cfg) * expert)
    weights = cfg["num_experts"] * expert + router
    tokens = 2 * t * cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    return layers * flops, layers * 3 * (weights + tokens) * item
