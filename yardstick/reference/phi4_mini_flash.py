"""Plain float32 Phi-4-mini-flash: the decoder-hybrid-decoder of Mamba,
window and full differential attention, gated memory units and
cross-attention (published model: ``huggingface.co/microsoft/
Phi-4-mini-flash-reasoning``, ``config.json`` and its modelling code
``modeling_phi4flash.py``; arXiv:2507.06607, and arXiv:2410.05258 for
differential attention).

Straightforward ``jax.numpy``: no kernels, no bfloat16, no chunked scan,
no cache, every contraction at ``default_matmul_precision("highest")``. It
reads the system's parameter tree (seeded random weights) and nothing else
of the program.

As published. 32 layers, index ``l`` = 0..31; every layer is ``x = x +
Mixer_l(LN(x)); x = x + MLP(LN(x))``, LayerNorm with weight and bias, eps
1e-5; no dropout; **no positional encoding**. The mixer by index:

| ``l`` | mixer |
|---|---|
| even, 0..14 | Mamba |
| odd, 1..15 | differential self-attention, causal, window 512 (a query sees itself and the 511 positions before it) |
| 16 | Mamba that also emits its memory ``m`` |
| 17 | differential self-attention, causal, full; emits its K and V |
| even, 18..30 | Gated Memory Unit on ``m`` of layer 16 |
| odd, 19..31 | differential cross-attention, causal, full: its own queries on layer 17's K and V |

- MLP: ``g, u = split(h W1)`` (gate first); ``y = (u * silu(g)) W2``. No
  bias.
- Mamba (per channel ``c`` of 5120, state ``n`` of 16): ``xz = h W_in`` ->
  ``x, z``; ``x = silu(conv4_causal(x) + b_conv)``; ``dt_r, B, C = split(x
  W_x)`` (160, 16, 16); ``dt = softplus(dt_r W_dt + b_dt)``; ``A =
  -exp(A_log)``; **token by token** ``s_t[c,n] = exp(dt_t[c] A[c,n])
  s_(t-1)[c,n] + dt_t[c] B_t[n] x_t[c]``, ``s_0 = 0``; ``y_t[c] = sum_n
  C_t[n] s_t[c,n] + D[c] x_t[c]``; output ``(y * silu(z)) W_out``. In layer
  16 ``m = y`` (before the gate) is what the GMUs read.
- GMU: ``out = (m * silu(h W1)) W2``, no bias.
- Differential attention: ``q = h W_q + b`` (40 heads of 64), ``k, v`` (20
  heads of 64; cross layers take layer 17's ``k, v`` as they are).
  Adjacent heads pair: ``q1, q2 = q[2i], q[2i+1]`` (20 pairs), ``k1, k2 =
  k[2j], k[2j+1]``, ``v = [v[2j] | v[2j+1]]`` (width 128; 10 key/value
  pairs, pair ``i`` of the queries reads pair ``i // 2``). ``A1 =
  softmax(q1 k1^T / 8 + mask)``, ``A2 = softmax(q2 k2^T / 8 + mask)``;
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` with ``l`` the layer's published
  index; ``o = rmsnorm_128(A1 v - lambda A2 v) * w_sub * (1 -
  lambda_init)``; output ``concat(o) W_o + b_o``. Computed in blocks of
  query rows so that 8,192 positions fit; the scores of a block are whole.
- Head: final LayerNorm, logits ``= h E^T`` with ``E`` the embedding; no
  bias; mean next-token cross-entropy.

Departures from the published model, each also in the configuration file:

- **depth**: the layers built are ``layer_indices`` of the published 32,
  each with its published index (its mixer and its ``lambda_init``).
- **sliced vocabulary**: embedding (= head) and loss are over
  ``vocab_size`` rows, whatever slice that is.
- sizes and forms the catalog's config has no key for (``assumed`` in the
  configuration file): the Mamba sizes (state 16, 4 taps, expansion 2,
  ``dt_rank`` 160), biases on the attention projections, differential
  attention itself, the initial values.
- the system stores ``Wqkv`` with columns ``[q | k | v]``, each
  head-major; this file reads that layout.

The per-token scan is cut into segments under ``jax.checkpoint`` so that
its gradient fits in memory: the arithmetic is the recurrence's own, token
by token, and only what is kept for the backward pass changes.

**Controls** (``chip_check.py`` only; no cell sets them). The limits of the
comparison are set between what the system reads and what this file reads
when it is itself computed in a lower precision, so the configuration may
carry ``control_operand_dtype`` (every matrix product's operands, and the
attention's q, k, v and probabilities, rounded to that type and back;
accumulation stays float32). Absent, nothing is rounded.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

QUERY_ROWS = 512        # attention is computed for this many rows at once
SCAN_SEGMENT = 64       # tokens per checkpointed segment of the recurrence


def mixer_of(cfg, l):
    """``(kind, emits, window)`` of published layer ``l``."""
    half = cfg["published"]["num_hidden_layers"] // 2
    state_space = l % cfg["mb_per_layer"] == 0
    if l < half:
        return (("mamba", False, None) if state_space
                else ("attention", False, cfg["sliding_window"]))
    if l < half + 2:
        return ("mamba" if state_space else "attention"), True, None
    return ("gmu" if state_space else "cross"), False, None


def lambda_init(l):
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def _low(cfg, x):
    """``x`` rounded to the control's type and back; ``x`` with none."""
    dtype = cfg.get("control_operand_dtype")
    if dtype is None:
        return x
    if dtype == "bfloat16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x.astype(dtype).astype(x.dtype)


def _mm(cfg, a, b):
    return _low(cfg, a) @ _low(cfg, b)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["gamma"] + p["beta"]


def _mlp(cfg, x, p):
    gu = _mm(cfg, x, p["W1"])
    width = gu.shape[-1] // 2
    return _mm(cfg, gu[..., width:] * jax.nn.silu(gu[..., :width]), p["W2"])


def _scan(x, dt, a, b, c):
    """(N, T, D), (N, T, D), (D, S), (N, T, S), (N, T, S): the
    recurrence; returns y (N, T, D) without the skip."""
    n, t, d = x.shape
    seg = min(SCAN_SEGMENT, t)
    pad = (-t) % seg

    def token(s, xs):
        xt, dtt, bt, ct = xs                    # (N, D), (N, D), (N, S) x 2
        s = jnp.exp(dtt[..., None] * a) * s \
            + (dtt * xt)[..., None] * bt[:, None, :]
        return s, jnp.einsum("nds,ns->nd", s, ct)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(token, s, xs)

    def by_segment(v):
        v = jnp.pad(jnp.moveaxis(v, 1, 0), ((0, pad), (0, 0), (0, 0)))
        return v.reshape(((t + pad) // seg, seg) + v.shape[1:])

    # padded tokens have dt 0: decay 1, nothing written
    _, y = jax.lax.scan(segment, jnp.zeros((n, d, a.shape[-1]), x.dtype),
                        tuple(by_segment(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(y.reshape((t + pad,) + y.shape[2:])[:t], 0, 1)


def _mamba(cfg, x, p):
    """``(mixed, memory)``."""
    t = x.shape[1]
    d, s, r = p["A_log"].shape[0], p["A_log"].shape[1], p["W_dt"].shape[0]
    xz = _mm(cfg, x, p["W_in"])
    u, z = xz[..., :d], xz[..., d:]
    taps = p["conv_w"].shape[-1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = jnp.zeros_like(u)
    for j in range(taps):
        conv = conv + padded[:, j:j + t] * p["conv_w"][:, j]
    u = jax.nn.silu(conv + p["conv_b"])
    dbc = _mm(cfg, u, p["W_x"])
    dt = jax.nn.softplus(_mm(cfg, dbc[..., :r], p["W_dt"]) + p["b_dt"])
    y = _scan(u, dt, -jnp.exp(p["A_log"]), dbc[..., r:r + s],
              dbc[..., r + s:]) + p["D"] * u
    return _mm(cfg, y * jax.nn.silu(z), p["W_out"]), y


def _gmu(cfg, x, p, memory):
    return _mm(cfg, memory * jax.nn.silu(_mm(cfg, x, p["W1"])), p["W2"])


def _differential_attention(cfg, l, x, p, kv, window):
    """``(mixed, (k, v))``; ``kv`` None for a self-attention layer."""
    n, t, _ = x.shape
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // h
    qkv = _mm(cfg, x, p["W_qkv"]) + p["b_qkv"]
    if kv is None:
        q, k, v = (qkv[..., :h * dh], qkv[..., h * dh:(h + hk) * dh],
                   qkv[..., (h + hk) * dh:])
    else:
        q, (k, v) = qkv, kv
    pairs, kv_pairs = h // 2, hk // 2
    group = pairs // kv_pairs
    q = _low(cfg, q).reshape(n, t, kv_pairs, group, 2, dh)
    kp = _low(cfg, k).reshape(n, t, kv_pairs, 2, dh)
    vp = _low(cfg, v).reshape(n, t, kv_pairs, 2 * dh)
    lam0 = lambda_init(l)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)
    rows = min(QUERY_ROWS, t)
    pad = (-t) % rows
    qb = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 4)
    qb = qb.reshape((n, (t + pad) // rows, rows) + q.shape[2:])
    kpos = jnp.arange(t)

    @jax.checkpoint        # a gradient keeps the block's rows, not its scores
    def block(args):
        qr, start = args                # (N, rows, kv_pairs, group, 2, dh)
        s = jnp.einsum("nqjgmd,ntjmd->njgmqt", qr, kp) / math.sqrt(dh)
        qpos = start + jnp.arange(rows)
        seen = kpos[None, :] <= qpos[:, None]
        if window is not None:
            seen = seen & (kpos[None, :] > qpos[:, None] - window)
        a = _low(cfg, jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1))
        o = jnp.einsum("njgmqt,ntje->nqjgme", a, vp)
        return o[..., 0, :] - lam * o[..., 1, :]    # (N, rows, kvp, g, 2dh)

    starts = jnp.arange(qb.shape[1]) * rows
    o = jax.lax.map(block, (jnp.moveaxis(qb, 1, 0), starts))
    o = jnp.moveaxis(o, 0, 1).reshape(n, t + pad, pairs, 2 * dh)[:, :t]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["layer_norm_eps"])
    o = o * p["subln"] * (1.0 - lam0)
    return _mm(cfg, o.reshape(n, t, h * dh), p["W_o"]) + p["b_o"], (k, v)


def _block(cfg, l, p, x, shared):
    """``shared``: what earlier layers emitted (``memory``, ``kv``).
    Returns the block's output and what it emits itself."""
    eps = cfg["layer_norm_eps"]
    kind, emits, window = mixer_of(cfg, l)
    h = _layer_norm(x, p["norm1"], eps)
    emitted = {}
    if kind == "mamba":
        m, memory = _mamba(cfg, h, p["mixer"])
        emitted = {"memory": memory}
    elif kind == "gmu":
        m = _gmu(cfg, h, p["mixer"], shared["memory"])
    else:
        m, kv = _differential_attention(
            cfg, l, h, p["mixer"], shared["kv"] if kind == "cross" else None,
            window)
        emitted = {"kv": kv}
    x = x + m
    x = x + _mlp(cfg, _layer_norm(x, p["norm2"], eps), p["mlp"])
    return x, (emitted if emits else {})


def _logits(cfg, params, ids, keep_block_inputs_only=False):
    """``keep_block_inputs_only`` puts each block under ``jax.checkpoint``
    so that a gradient at 8,192 tokens fits the chip; the values are the
    same."""
    table = params["embed"]["W"]
    x = table[ids.astype(jnp.int32)]
    shared = {}
    for l in cfg["layer_indices"]:
        block = functools.partial(_block, cfg, l)
        if keep_block_inputs_only:
            block = jax.checkpoint(block)
        x, emitted = block(params[f"block{l}"], x, shared)
        shared = {**shared, **emitted}
    head = params["lm_head"]
    h = _layer_norm(x, head["norm"], cfg["layer_norm_eps"])
    return _mm(cfg, h, table.T if cfg["tie_word_embeddings"] else head["W"])


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
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "sliding_window", "mb_per_layer", "layer_norm_eps",
            "tie_word_embeddings", "control_operand_dtype")
    return tuple((k, cfg[k]) for k in keep if k in cfg) + (
        ("layer_indices", tuple(cfg["layer_indices"])),
        ("published", (("num_hidden_layers",
                        cfg["published"]["num_hidden_layers"]),)))


def _dict(static):
    cfg = dict(static)
    cfg["published"] = dict(cfg["published"])
    return cfg


@functools.partial(jax.jit, static_argnums=0)
def _logits_f32(cfg, params, ids):
    with jax.default_matmul_precision("highest"):
        return _logits(_dict(cfg), _f32(params), ids)


@functools.partial(jax.jit, static_argnums=0)
def _loss_f32(cfg, params, ids, labels):
    with jax.default_matmul_precision("highest"):
        return _loss(_dict(cfg), _f32(params), ids, labels)


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
    static = _dict(_static(cfg))

    def fn(params, ids, labels):
        with jax.default_matmul_precision("highest"):
            return _loss(static, params, ids, labels,
                         keep_block_inputs_only=True)
    return fn
