"""Plain float32 SDAR-MoE, trained: a masked diffusion over blocks on a
Qwen3-MoE decoder (published model: ``huggingface.co/JetLM/
SDAR-30B-A3B-Chat``, ``config.json``, ``model_type: sdar_moe``; "SDAR: A
Synergistic Diffusion-AutoRegression Paradigm for Scalable Sequence
Generation", arXiv:2510.06303; the training layout is BD3-LM's,
arXiv:2503.09573).

Straightforward ``jax.numpy``: no kernels, no bfloat16, no sorting, every
contraction at ``default_matmul_precision("highest")``. It reads the
system's parameter tree (seeded random weights) and nothing else of the
program.

**Input.** A clean row ``x0`` of ``T`` ids and its noisy copy ``xt`` go
through the network as one row ``u = [xt | x0]`` of ``2 T`` ids. Slot ``s``
has position ``pos(s) = s mod T`` and block ``b(s) = pos(s) // B`` (``B`` =
``block_length``) and is noisy below ``T``, clean from there.

**Visibility** ``M[s, r]`` (query slot ``s``, key slot ``r``), built
literally in ``_visible``:

- ``s`` noisy, ``r`` noisy: ``b(r) == b(s)``;
- ``s`` noisy, ``r`` clean: ``b(r) <  b(s)``;
- ``s`` clean, ``r`` clean: ``b(r) <= b(s)``;
- ``s`` clean, ``r`` noisy: never.

**Block** l: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``;
``RMSNorm(x) = x rsqrt(mean(x^2) + eps) (1 + w)``.

- Attention: ``q = RoPE(RMSNorm(W_q a), pos)`` (``num_attention_heads`` of
  ``head_dim``), ``k = RoPE(RMSNorm(W_k a), pos)``, ``v = W_v a``
  (``num_key_value_heads``); the norms over the head; rotate-half rotary
  embedding on the whole head at ``rope_theta``; softmax over ``{r : M[s,
  r]}`` at 1/sqrt(head size), query head j reading key/value head ``j //
  (heads / key-value heads)``; output projection; no biases, no gate.
  Computed for a block of query rows at a time so that 16,384 positions
  fit; the scores of a block of rows are whole.
- Mixture of experts: softmax over all ``router_width`` experts, the
  ``num_experts_per_tok`` largest renormalised to sum 1, gated experts
  ``W_down(silu(W_gate x) * W_up x)``; no shared expert.

**Head and loss.** Final RMSNorm and untied head over the noisy half,
``logits = W_head RMSNorm(y[:, :T])``; with the hidden ids ``labels[..., 0]``
(below zero where a position hides none) and their weights ``labels[...,
1]`` (``1 / t_n``): ``L = 1 / (N T) sum_n sum_{i masked} w[n, i]
(logsumexp(logits[n, i]) - logits[n, i, x0[n, i]])``. No shift.

**Router auxiliary loss** (``router_aux_loss_coef`` c above 0): the
training loss is ``L + c sum_l A_l``, with for every layer l, over its 2 N
T tokens and ALL ``router_width`` E router outputs, held or not, ``A_l = E
sum_e f_e P_e``: ``f_e`` the assignments expert e received among the
tokens' top k over the number of tokens (the ``f_e`` sum to k; no gradient
passes through them), ``P_e`` the mean of the router's probability for e
(Switch Transformer, arXiv:2101.03961 eq. 4-6, as the Mixtral / Qwen3-MoE
trainers write it; they compute one ``A`` over the layers' tokens
together where this sums the layers' own: a departure).

Departures from the published model, each also in the configuration file:

- **held experts**: the sum over the top experts runs over those the chip
  holds (``held_experts(cfg)``); what the absent ones would add is left
  out, as in the system (the expert-parallel deployment's share).
- **sliced vocabulary**: embedding, head and loss are over ``vocab_size``
  rows, whatever slice that is; ``[MASK]`` is an id of the slice.
- RMSNorm weights are stored zero-centred (``w - 1``): the same function
  and gradients.
- the router auxiliary loss is each layer's own, summed (above); no
  dropout.

**Controls** (``chip_check.py`` only; no cell sets them). The limits of the
comparison are set between what the system reads and what this file reads
when it is itself computed in a lower precision, so the configuration may
carry ``control_operand_dtype`` (every matrix product's operands, q, k, v
and the attention's probabilities among them, rounded to that type and
back; accumulation stays float32). Absent, nothing is rounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_ROWS = 512        # attention is computed for this many rows at once


def held_experts(cfg):
    """Ids, among the router's outputs, of the experts this chip holds:
    the ``expert_parallel_rank``-th run of ``num_experts``."""
    first = int(cfg.get("expert_parallel_rank", 0)) * cfg["num_experts"]
    return tuple(range(first, first + cfg["num_experts"]))


def _low(cfg, x):
    """``x`` rounded to the control's type and back; ``x`` with none.
    bfloat16 goes through ``reduce_precision``, which no compiler pass
    removes; float8 has to be cast: its small exponent range keeps
    subnormals that ``reduce_precision`` would flush to zero."""
    dtype = cfg.get("control_operand_dtype")
    if dtype is None:
        return x
    if dtype == "bfloat16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x.astype(dtype).astype(x.dtype)


def _mm(cfg, a, b):
    return _low(cfg, a) @ _low(cfg, b)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rotate(x, pos, theta):
    """Rotate-half rotary embedding on the whole head of ``x`` (N, S, H,
    D) at positions ``pos`` (S,)."""
    d = x.shape[-1]
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    rotated_half = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated_half * sin


def _visible(s, r, t, b):
    """M[s, r] for slots ``s`` (rows) and ``r`` (columns) of ``2 t``."""
    s_noisy, r_noisy = s < t, r < t
    bs, br = (s % t) // b, (r % t) // b
    return ((s_noisy & r_noisy & (br == bs))
            | (s_noisy & ~r_noisy & (br < bs))
            | (~s_noisy & ~r_noisy & (br <= bs)))


def _attention(x, p, cfg):
    n, s2, _ = x.shape
    t = s2 // 2
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(s2) % t
    q = _mm(cfg, x, p["W_q"]).reshape(n, s2, h, dh)
    k = _mm(cfg, x, p["W_k"]).reshape(n, s2, hk, dh)
    v = _mm(cfg, x, p["W_v"]).reshape(n, s2, hk, dh)
    q = _rotate(_rms_norm(q, p["q_norm"], eps), pos, cfg["rope_theta"])
    k = _rotate(_rms_norm(k, p["k_norm"], eps), pos, cfg["rope_theta"])
    q, k, v = _low(cfg, q), _low(cfg, k), _low(cfg, v)
    group = h // hk
    rows = min(QUERY_ROWS, s2)
    pad = (-s2) % rows
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = qp.reshape(n, (s2 + pad) // rows, rows, hk, group, dh)
    slots = jnp.arange(s2)

    @jax.checkpoint        # a gradient keeps the block's rows, not its scores
    def block(args):
        qb, start = args                       # (N, rows, hk, group, dh)
        sc = jnp.einsum("nqkgd,ntkd->nkgqt", qb, k) / jnp.sqrt(float(dh))
        mine = jnp.minimum(start + jnp.arange(rows), s2 - 1)   # padded rows
        seen = _visible(mine[:, None], slots[None, :], t,
                        cfg["block_length"])
        sc = jnp.where(seen, sc, -jnp.inf)
        return jnp.einsum("nkgqt,ntkd->nqkgd",
                          _low(cfg, jax.nn.softmax(sc, -1)), v)

    starts = jnp.arange(blocks.shape[1]) * rows
    out = jax.lax.map(block, (jnp.moveaxis(blocks, 1, 0), starts))
    out = jnp.moveaxis(out, 0, 1).reshape(n, s2 + pad, h, dh)[:, :s2]
    return _mm(cfg, out.reshape(n, s2, h * dh), p["W_o"])


def _experts(x, p, cfg):
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    probs = jax.nn.softmax(_mm(cfg, x, p["router"]), -1)
    top, ids = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top = top / jnp.sum(top, -1, keepdims=True)

    def expert(y, xs):
        eid, wg, wu, wd = xs
        weight = jnp.sum(jnp.where(ids == eid, top, 0.0), -1)   # 0: not sent
        hidden = jax.nn.silu(_mm(cfg, x, wg)) * _mm(cfg, x, wu)
        return y + weight[:, None] * _mm(cfg, hidden, wd), None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (jnp.asarray(held_experts(cfg)), p["w_gate"], p["w_up"], p["w_down"]))
    # A_l: every router output counts, held or not
    e = probs.shape[-1]
    received = jnp.sum(jax.nn.one_hot(ids, e), (0, 1)) / x.shape[0]
    balance = e * jnp.sum(jax.lax.stop_gradient(received)
                          * jnp.mean(probs, 0))
    return y.reshape(shape), balance


def _block(cfg, p, x):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["norm1"]["w"], eps), p["mixer"], cfg)
    y, balance = _experts(_rms_norm(x, p["norm2"]["w"], eps), p["moe"], cfg)
    return x + y, balance


def _forward(cfg, params, ids, keep_block_inputs_only=False):
    """Logits of the noisy half (N, T, vocab_size) for ``ids`` (N, 2 T),
    and the layers' ``A_l`` summed. ``keep_block_inputs_only`` puts each
    block under ``jax.checkpoint`` so that a gradient at 16,384 positions
    fits the chip; the values are the same."""
    x = params["embed"]["W"][ids.astype(jnp.int32)]
    balance = 0.0
    for l in range(cfg["num_hidden_layers"]):
        block = functools.partial(_block, cfg)
        if keep_block_inputs_only:
            block = jax.checkpoint(block)
        x, a = block(params[f"block{l}"], x)
        balance = balance + a
    head = params["lm_head"]
    noisy = x[:, :x.shape[1] // 2]
    return _mm(cfg, _rms_norm(noisy, head["norm"]["w"], cfg["rms_norm_eps"]),
               head["W"]), balance


def _loss(cfg, params, ids, labels, keep_block_inputs_only=False):
    logits, balance = _forward(cfg, params, ids, keep_block_inputs_only)
    hidden = labels[..., 0].astype(jnp.int32)
    masked = hidden >= 0
    picked = jnp.take_along_axis(logits, jnp.maximum(hidden, 0)[..., None],
                                 -1)[..., 0]
    per = jax.nn.logsumexp(logits, -1) - picked
    n, t = hidden.shape
    return (jnp.sum(jnp.where(masked, labels[..., 1] * per, 0.0)) / (n * t)
            + cfg.get("router_aux_loss_coef", 0.0) * balance)


def _static(cfg):
    """What the arithmetic reads of the configuration, hashable: the
    static argument of the jitted functions."""
    keep = ("num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rope_theta", "block_length",
            "num_experts", "num_experts_per_tok", "norm_topk_prob",
            "rms_norm_eps", "expert_parallel_rank", "router_aux_loss_coef",
            "control_operand_dtype")
    return tuple((k, cfg[k]) for k in keep if k in cfg)


@functools.partial(jax.jit, static_argnums=0)
def _logits_f32(cfg, params, ids):
    with jax.default_matmul_precision("highest"):
        return _forward(dict(cfg), _f32(params), ids)[0]


@functools.partial(jax.jit, static_argnums=0)
def _loss_f32(cfg, params, ids, labels):
    with jax.default_matmul_precision("highest"):
        return _loss(dict(cfg), _f32(params), ids, labels)


def _f32(params):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


def logits(cfg, params, state, features):
    """(N, T, vocab_size) float32 logits of the noisy half for the ids
    ``features[0]`` (N, 2 T) = ``[xt | x0]``."""
    return _logits_f32(_static(cfg), params, jnp.asarray(features[0]))


def loss(cfg, params, state, features, labels):
    """The 1/t-weighted masked loss; ``labels[0]`` (N, T, 2) holds the
    hidden id (below zero where none) and its weight."""
    return _loss_f32(_static(cfg), params, jnp.asarray(features[0]),
                     jnp.asarray(labels[0], jnp.float32))


def loss_fn(cfg):
    """``(params, ids, labels) -> loss`` for ``jax.grad``: the gradient
    comparison of the tests and of the chip check."""
    static = dict(_static(cfg))

    def fn(params, ids, labels):
        with jax.default_matmul_precision("highest"):
            return _loss(static, params, ids, labels,
                         keep_block_inputs_only=True)
    return fn
