"""Plain float32 Qwen3-Next: hybrid Gated DeltaNet / gated attention
decoder with a mixture of gated experts (published model:
``huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct``, ``config.json`` and
the ``qwen3_next`` modelling code of the transformers library).

Straightforward ``jax.numpy``: no kernels, no bfloat16, no sorting, no
chunked scan, every contraction at ``default_matmul_precision("highest")``.
It reads the system's parameter tree (seeded random weights) and nothing
else of the program.

As published. Block l: ``h = x + Mixer_l(RMSNorm(x))``, ``y = h +
MoE(RMSNorm(h))``; ``RMSNorm(x) = x rsqrt(mean(x^2) + eps) (1 + w)``; the
mixer is gated attention where ``(l + 1) % full_attention_interval == 0``
and a Gated DeltaNet otherwise.

- Gated DeltaNet: projections to q, k (key heads), v, z (value heads) and
  to b, a (one each per value head); a causal depthwise convolution of
  ``linear_conv_kernel_dim`` taps and SiLU over the q, k, v channels;
  ``beta = sigmoid(b)``, ``alpha = exp(-exp(A_log) softplus(a +
  dt_bias))``; q, k L2-normalised over the head, q over sqrt(key size);
  each key head serves value heads/key heads value heads; per value head,
  **token by token**: ``S <- alpha S``, ``S <- S + k (beta (v - S^T
  k))^T``, ``o = S^T q``; RMSNorm over the head with a plain weight,
  times ``silu(z)``; output projection.
- Gated attention: per head a query and a gate; RMSNorm of q and k over
  the head; rotate-half rotary embedding on the first
  ``partial_rotary_factor`` of the head at ``rope_theta``; causal softmax
  at 1/sqrt(head size), query heads sharing key/value heads in groups;
  output projection of ``attn * sigmoid(gate)``. Computed in blocks of
  query rows so that 8,192 positions fit; the scores of a block are
  whole.
- Mixture of experts: softmax over all ``router_width`` experts, the
  ``num_experts_per_tok`` largest renormalised to sum 1, gated experts
  ``W_down(silu(W_gate x) * W_up x)``, one shared expert behind
  ``sigmoid(x . w_s)``.
- Final RMSNorm, untied head, mean next-token cross-entropy.

Departures from the published model, each also in the configuration file:

- **held experts**: the sum over the top experts runs over those the chip
  holds (``held_experts(cfg)``); what the absent ones would add is left
  out, as in the system (the expert-parallel deployment's share).
- **sliced vocabulary**: embedding, head and loss are over ``vocab_size``
  rows, whatever slice that is.
- no multi-token-prediction module, no router auxiliary loss, no dropout.
- the system stores ``in_proj_qkvz`` with columns ``[q | k | v | z]``
  head-major and ``in_proj_ba`` as ``[b | a]`` (the checkpoints interleave
  them by key head: a column permutation); this file reads that layout.

The per-token scan is cut into segments under ``jax.checkpoint`` so that
its gradient fits in memory: the arithmetic is the recurrence's own, token
by token, and only what is kept for the backward pass changes.

**Controls** (``chip_check.py`` only; no cell sets them). The limits of the
comparison are set between what the system reads and what this file reads
when it is itself computed in a lower precision, so the configuration may
carry ``control_operand_dtype`` (every matrix product's operands, and the
delta rule's q, k, v, rounded to that type and back; accumulation stays
float32) and ``control_state_dtype`` (the delta rule's state rounded after
every token). Absent, nothing is rounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_ROWS = 512        # attention is computed for this many rows at once
SCAN_SEGMENT = 64       # tokens per checkpointed segment of the recurrence


def held_experts(cfg):
    """Ids, among the router's outputs, of the experts this chip holds:
    the ``expert_parallel_rank``-th run of ``num_experts``."""
    first = int(cfg.get("expert_parallel_rank", 0)) * cfg["num_experts"]
    return tuple(range(first, first + cfg["num_experts"]))


def _low(cfg, x, key="control_operand_dtype"):
    """``x`` rounded to the control's type and back; ``x`` with none.
    bfloat16 goes through ``reduce_precision``, which no compiler pass
    removes: the TPU compiler keeps the excess precision of a float32 ->
    bfloat16 -> float32 round trip (the bfloat16 state read 0.0 apart on
    the chip). float8 has to be cast: its small exponent range keeps
    subnormals that ``reduce_precision`` would flush to zero."""
    dtype = cfg.get(key)
    if dtype is None:
        return x
    if dtype == "bfloat16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x.astype(dtype).astype(x.dtype)


def _mm(cfg, a, b):
    return _low(cfg, a) @ _low(cfg, b)


def _rms_norm(x, w, eps, zero_centered=True):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + w if zero_centered else w)


def _rotate(x, theta, rot):
    t = x.shape[1]
    half = rot // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    xr, rest = x[..., :rot], x[..., rot:]
    rotated_half = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + rotated_half * sin, rest], -1)


def _gated_attention(x, p, cfg):
    n, t, _ = x.shape
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    qg = _mm(cfg, x, p["W_q"]).reshape(n, t, h, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = _mm(cfg, x, p["W_k"]).reshape(n, t, hk, dh)
    v = _mm(cfg, x, p["W_v"]).reshape(n, t, hk, dh)
    rot = int(dh * cfg["partial_rotary_factor"])
    q = _rotate(_rms_norm(q, p["q_norm"], eps), cfg["rope_theta"], rot)
    k = _rotate(_rms_norm(k, p["k_norm"], eps), cfg["rope_theta"], rot)
    q, k, v = _low(cfg, q), _low(cfg, k), _low(cfg, v)
    group = h // hk
    rows = min(QUERY_ROWS, t)
    pad = (-t) % rows
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = qp.reshape(n, (t + pad) // rows, rows, hk, group, dh)
    kpos = jnp.arange(t)

    @jax.checkpoint        # a gradient keeps the block's rows, not its scores
    def block(args):
        qb, start = args                       # (N, rows, hk, group, dh)
        s = jnp.einsum("nqkgd,ntkd->nkgqt", qb, k) / jnp.sqrt(float(dh))
        qpos = start + jnp.arange(rows)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        return jnp.einsum("nkgqt,ntkd->nqkgd",
                          _low(cfg, jax.nn.softmax(s, -1)), v)

    starts = jnp.arange(blocks.shape[1]) * rows
    out = jax.lax.map(block, (jnp.moveaxis(blocks, 1, 0), starts))
    out = jnp.moveaxis(out, 0, 1).reshape(n, t + pad, h, dh)[:, :t]
    out = out * jax.nn.sigmoid(gate)
    return _mm(cfg, out.reshape(n, t, h * dh), p["W_o"])


def _delta_rule(cfg, q, k, v, alpha, beta):
    """(N, T, H, D) each, alpha and beta (N, T, H): the recurrence."""
    n, t, h, dk = q.shape
    seg = min(SCAN_SEGMENT, t)
    pad = (-t) % seg

    def token(s, xs):
        qt, kt, vt, at, bt = xs
        s = s * at[..., None, None]
        delta = (vt - jnp.einsum("nhk,nhkv->nhv", kt, s)) * bt[..., None]
        s = _low(cfg, s + kt[..., :, None] * delta[..., None, :],
                 "control_state_dtype")
        return s, jnp.einsum("nhk,nhkv->nhv", qt, s)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(token, s, xs)

    def by_segment(a, fill):
        a = jnp.moveaxis(a, 1, 0)
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=fill)
        return a.reshape(((t + pad) // seg, seg) + a.shape[1:])

    # padded tokens: alpha 1, beta 0, k 0 leave the state as it is
    xs = (by_segment(q, 0.0), by_segment(k, 0.0), by_segment(v, 0.0),
          by_segment(alpha, 1.0), by_segment(beta, 0.0))
    s0 = jnp.zeros((n, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(segment, s0, xs)
    o = o.reshape((t + pad,) + o.shape[2:])[:t]
    return jnp.moveaxis(o, 0, 1)


def _gated_deltanet(x, p, cfg):
    n, t, _ = x.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kd, vd = hk * dk, hv * dv
    qkvz = _mm(cfg, x, p["W_qkvz"])
    ba = _mm(cfg, x, p["W_ba"])
    qkv, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
    taps = p["conv_w"].shape[-1]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = jnp.zeros_like(qkv)
    for j in range(taps):
        conv = conv + padded[:, j:j + t] * p["conv_w"][:, j]
    qkv = jax.nn.silu(conv)
    q = qkv[..., :kd].reshape(n, t, hk, dk)
    k = qkv[..., kd:2 * kd].reshape(n, t, hk, dk)
    v = qkv[..., 2 * kd:].reshape(n, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    alpha = jnp.exp(-jnp.exp(p["A_log"])
                    * jax.nn.softplus(ba[..., hv:] + p["dt_bias"]))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(q / jnp.sqrt(float(dk)), hv // hk, axis=2)
    k = jnp.repeat(k, hv // hk, axis=2)
    o = _delta_rule(cfg, _low(cfg, q), _low(cfg, k), _low(cfg, v), alpha,
                    beta)
    o = _rms_norm(o, p["norm_w"], cfg["rms_norm_eps"], zero_centered=False)
    o = o * jax.nn.silu(z.reshape(n, t, hv, dv))
    return _mm(cfg, o.reshape(n, t, vd), p["W_o"])


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
    shared = _mm(cfg, jax.nn.silu(_mm(cfg, x, p["shared_gate"]))
                 * _mm(cfg, x, p["shared_up"]), p["shared_down"])
    y = y + jax.nn.sigmoid(_mm(cfg, x, p["shared_w"]))[:, None] * shared
    return y.reshape(shape)


def _block(cfg, l, p, x):
    eps = cfg["rms_norm_eps"]
    full = (l + 1) % cfg["full_attention_interval"] == 0
    mixer = _gated_attention if full else _gated_deltanet
    x = x + mixer(_rms_norm(x, p["norm1"]["w"], eps), p["mixer"], cfg)
    return x + _experts(_rms_norm(x, p["norm2"]["w"], eps), p["moe"], cfg)


def _logits(cfg, params, ids, keep_block_inputs_only=False):
    """``keep_block_inputs_only`` puts each block under ``jax.checkpoint``
    so that a gradient at 8,192 tokens fits the chip; the values are the
    same."""
    x = params["embed"]["W"][ids.astype(jnp.int32)]
    for l in range(cfg["num_hidden_layers"]):
        block = functools.partial(_block, cfg, l)
        if keep_block_inputs_only:
            block = jax.checkpoint(block)
        x = block(params[f"block{l}"], x)
    head = params["lm_head"]
    return _mm(cfg, _rms_norm(x, head["norm"]["w"], cfg["rms_norm_eps"]),
               head["W"])


def _loss(cfg, params, ids, labels, keep_block_inputs_only=False):
    logp = jax.nn.log_softmax(
        _logits(cfg, params, ids, keep_block_inputs_only), -1)
    labels = labels.astype(jnp.int32)
    keep = labels >= 0                  # a row's last position has no next
    picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                                 -1)[..., 0]
    return -jnp.sum(jnp.where(keep, picked, 0.0)) / jnp.sum(keep)


def _static(cfg):
    """What the arithmetic reads of the configuration, hashable: the
    static argument of the jitted functions."""
    keep = ("num_hidden_layers", "full_attention_interval",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "rms_norm_eps", "expert_parallel_rank",
            "control_operand_dtype", "control_state_dtype")
    return tuple((k, cfg[k]) for k in keep if k in cfg)


@functools.partial(jax.jit, static_argnums=0)
def _logits_f32(cfg, params, ids):
    with jax.default_matmul_precision("highest"):
        return _logits(dict(cfg), _f32(params), ids)


@functools.partial(jax.jit, static_argnums=0)
def _loss_f32(cfg, params, ids, labels):
    with jax.default_matmul_precision("highest"):
        return _loss(dict(cfg), _f32(params), ids, labels)


def _f32(params):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


def logits(cfg, params, state, features):
    """(N, T, vocab_size) float32 logits for token ids ``features[0]``."""
    return _logits_f32(_static(cfg), params, jnp.asarray(features[0]))


def loss(cfg, params, state, features, labels):
    """Mean next-token cross-entropy; ``labels[0]`` (N, T) holds the id
    after each position and a negative number where there is none."""
    return _loss_f32(_static(cfg), params, jnp.asarray(features[0]),
                     jnp.asarray(labels[0]))


def loss_fn(cfg):
    """``(params, ids, labels) -> loss`` for ``jax.grad``: the gradient
    comparison of the tests and of the chip check."""
    static = dict(_static(cfg))

    def fn(params, ids, labels):
        with jax.default_matmul_precision("highest"):
            return _loss(static, params, ids, labels,
                         keep_block_inputs_only=True)
    return fn
