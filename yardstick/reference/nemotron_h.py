"""Plain float32 Nemotron-H, as Nemotron 3 Nano: Mamba-2 layers, causal
grouped-query attention without positions and a sigmoid-routed mixture of
non-gated relu^2 experts, one mixer a layer (published model:
``huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``,
``config.json``, ``model_type: nemotron_h``, and the family's modelling
code ``modeling_nemotron_h.py``; arXiv:2504.03624; the Mamba-2 layer is
arXiv:2405.21060, the router DeepSeek-V3's, arXiv:2412.19437).

Straightforward ``jax.numpy``: no kernels, no bfloat16, no chunked scan, no
sorting, every contraction at ``default_matmul_precision("highest")``. It
reads the system's parameter tree (seeded random weights), the routers'
bias from the model state it is handed, and nothing else of the program.

As published (``h`` the model width, every projection bias-free, ``eps`` =
``layer_norm_epsilon``). ``x_0 = Embedding(ids)``; for each character of
``hybrid_override_pattern``: ``x <- x + Mixer(RMSNorm(x))``; ``logits =
W_head RMSNorm(x)``, head untied; mean next-token cross-entropy.
``RMSNorm(x) = x rsqrt(mean(x^2) + eps) (1 + w)``.

- ``M``, Mamba-2 (``H`` = ``mamba_num_heads`` heads of ``P`` =
  ``mamba_head_dim``, ``G`` = ``n_groups`` groups of ``S`` =
  ``ssm_state_size``): ``[z | xBC | dt] = u W_in`` with widths ``H P`` |
  ``H P + 2 G S`` | ``H``; ``xBC <- silu(conv(xBC) + b_conv)``, causal,
  depthwise, ``conv_kernel`` taps over all its channels; ``[x | B | C] =
  xBC``, ``x`` as (H, P), ``B`` and ``C`` as (G, S), head ``j`` reading
  group ``j // (H / G)``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; **token by token** ``S_t[j] = exp(dt_t[j] A[j])
  S_(t-1)[j] + dt_t[j] x_t[j] B_t[g(j)]^T`` (P x S), ``S_0 = 0``,
  ``y_t[j] = S_t[j] C_t[g(j)] + D[j] x_t[j]``; ``o = GroupRMSNorm(y *
  silu(z))``: the gate first, then an RMSNorm over each of the G groups of
  ``H P / G`` channels, times a plain weight; the result ``o W_out``.
- ``*``, attention: ``q = u W_q`` (``num_attention_heads`` of
  ``head_dim``), ``k, v`` (``num_key_value_heads``); causal softmax at
  1/sqrt(head size), query head j reading key/value head ``j // (heads /
  key-value heads)``; ``W_o``. **No positional encoding, no q/k norm, no
  gate.** Computed for a block of query rows at a time so that 8,192
  positions fit; the scores of a block of rows are whole.
- ``E``, experts: ``s = sigmoid(u W_r)`` over all ``router_width``
  outputs; the ``num_experts_per_tok`` experts are the largest of ``s +
  b`` (``b`` the score-correction bias of the layer's state, through
  which no gradient passes); ``w_i = routed_scaling_factor s_i / (sum of
  the chosen s + 1e-20)``; ``out = sum_i w_i W_down_i relu(W_up_i u)^2 +
  W_down_s relu(W_up_s u)^2``: a dense loop over the held experts (every
  token through every held expert, times its weight or 0), the shared
  expert added ungated.

**Balance loss** (``router_aux_loss_coef`` c above 0): the training loss is
``L + c sum_l A_l`` over the expert layers, ``A_l = E sum_e f_e P_e`` over
the layer's tokens and ALL ``router_width`` E outputs: ``f_e`` the
assignments output e received over the number of tokens (no gradient
passes through them), ``P_e`` the mean of ``s_e / sum_j s_j``
(DeepSeek-V3 eq. 17-20 times k, over the layer's tokens together where
the paper takes each sequence's own).

Departures from the published model, each also in the configuration file:

- **depth**: the layers built are the configuration's
  ``hybrid_override_pattern``, a prefix of the published one.
- **held experts**: the sum over the chosen experts runs over those the
  chip holds (``held_experts(cfg)``); what the absent ones would add is
  left out, as in the system (the expert-parallel deployment's share).
- **sliced vocabulary**: embedding, head and loss are over ``vocab_size``
  rows, whatever slice that is.
- RMSNorm weights are stored zero-centred (``w - 1``) but for the
  Mamba-2 layer's grouped norm, whose weight is plain: the same function
  and gradients.

The per-token scan is cut into segments under ``jax.checkpoint`` so that
its gradient fits in memory: the arithmetic is the recurrence's own, token
by token, and only what is kept for the backward pass changes.

**Controls** (``chip_check.py`` only; no cell sets them). The limits of the
comparison are set between what the system reads and what this file reads
when it is itself computed in a lower precision, so the configuration may
carry ``control_operand_dtype`` (every matrix product's operands, the
router's, q, k, v and the attention's probabilities among them, rounded to
that type and back; accumulation stays float32). Absent, nothing is
rounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_ROWS = 512        # attention is computed for this many rows at once
SCAN_SEGMENT = 64       # tokens per checkpointed segment of the recurrence


def held_experts(cfg):
    """Ids, among the router's outputs, of the experts this chip holds:
    the ``expert_parallel_rank``-th run of ``n_routed_experts``."""
    first = int(cfg.get("expert_parallel_rank", 0)) * cfg["n_routed_experts"]
    return tuple(range(first, first + cfg["n_routed_experts"]))


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


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


# ---- M: Mamba-2 -------------------------------------------------------------

def _causal_conv(x, w, b):
    """``y[t, c] = b[c] + sum_j w[c, j] x[t - (K - 1) + j, c]``, zeros
    before the sequence: (N, T, C) with ``w`` (C, K)."""
    k, t = w.shape[-1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(xp[:, j:j + t] * w[:, j] for j in range(k))


def _scan(x, dt, a, b, c):
    """(N, T, H, P), (N, T, H), (H,), (N, T, H, S) x 2 (``B`` and ``C`` as
    each head reads them): the recurrence token by token from a zero
    state; returns y (N, T, H, P) without the skip."""
    n, t, h, p = x.shape
    seg = min(SCAN_SEGMENT, t)
    pad = (-t) % seg

    def token(s, xs):
        xt, dtt, bt, ct = xs            # (N, H, P), (N, H), (N, H, S) x 2
        s = jnp.exp(dtt * a)[..., None, None] * s \
            + (dtt[..., None] * xt)[..., None] * bt[..., None, :]
        return s, jnp.einsum("nhps,nhs->nhp", s, ct)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(token, s, xs)

    def by_segment(v):
        v = jnp.moveaxis(v, 1, 0)
        v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return v.reshape(((t + pad) // seg, seg) + v.shape[1:])

    # padded tokens have dt 0: decay 1, nothing written
    _, y = jax.lax.scan(segment, jnp.zeros((n, h, p, b.shape[-1]), x.dtype),
                        tuple(by_segment(v) for v in (x, dt, b, c)))
    return jnp.moveaxis(y.reshape((t + pad,) + y.shape[2:])[:t], 0, 1)


def _mamba2(cfg, u, p):
    n, t, _ = u.shape
    h, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, s = cfg["n_groups"], cfg["ssm_state_size"]
    d, bc = h * hp, g * s
    zxd = _mm(cfg, u, p["W_in"])
    z, xbc, dt = zxd[..., :d], zxd[..., d:2 * d + 2 * bc], \
        zxd[..., 2 * d + 2 * bc:]
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[..., :d].reshape(n, t, h, hp)
    per_head = h // g       # head j reads group j // per_head
    b = jnp.repeat(xbc[..., d:d + bc].reshape(n, t, g, s), per_head, 2)
    c = jnp.repeat(xbc[..., d + bc:].reshape(n, t, g, s), per_head, 2)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = _scan(x, dt, -jnp.exp(p["A_log"]), b, c) + p["D"][:, None] * x
    gated = (y.reshape(n, t, d) * jax.nn.silu(z)).reshape(n, t, g, d // g)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True)
        + cfg["layer_norm_epsilon"])
    return _mm(cfg, normed.reshape(n, t, d) * p["norm_w"], p["W_out"])


# ---- *: attention -------------------------------------------------------------

def _attention(cfg, u, p):
    n, t, _ = u.shape
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    q = _low(cfg, _mm(cfg, u, p["W_q"]).reshape(n, t, h, dh))
    k = _low(cfg, _mm(cfg, u, p["W_k"]).reshape(n, t, hk, dh))
    v = _low(cfg, _mm(cfg, u, p["W_v"]).reshape(n, t, hk, dh))
    group = h // hk
    rows = min(QUERY_ROWS, t)
    pad = (-t) % rows
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = qp.reshape(n, (t + pad) // rows, rows, hk, group, dh)
    keys = jnp.arange(t)

    @jax.checkpoint        # a gradient keeps the block's rows, not its scores
    def block(args):
        qb, start = args                       # (N, rows, hk, group, dh)
        sc = jnp.einsum("nqkgd,ntkd->nkgqt", qb, k) / jnp.sqrt(float(dh))
        mine = start + jnp.arange(rows)
        sc = jnp.where(keys[None, :] <= mine[:, None], sc, -jnp.inf)
        return jnp.einsum("nkgqt,ntkd->nqkgd",
                          _low(cfg, jax.nn.softmax(sc, -1)), v)

    starts = jnp.arange(blocks.shape[1]) * rows
    out = jax.lax.map(block, (jnp.moveaxis(blocks, 1, 0), starts))
    out = jnp.moveaxis(out, 0, 1).reshape(n, t + pad, h, dh)[:, :t]
    return _mm(cfg, out.reshape(n, t, h * dh), p["W_o"])


# ---- E: experts ---------------------------------------------------------------

def _experts(cfg, u, p, bias):
    """``(out, A_l)`` of one expert layer; ``bias`` (router_width,)."""
    shape = u.shape
    x = u.reshape(-1, shape[-1])
    scores = jax.nn.sigmoid(_mm(cfg, x, p["router"]))
    k = cfg["num_experts_per_tok"]
    _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    top = jnp.take_along_axis(scores, ids, -1)
    if cfg.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    top = top * cfg["routed_scaling_factor"]

    def expert(y, xs):
        eid, wu, wd = xs
        weight = jnp.sum(jnp.where(ids == eid, top, 0.0), -1)   # 0: not sent
        return y + weight[:, None] * _mm(cfg, _relu2(_mm(cfg, x, wu)), wd), \
            None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (jnp.asarray(held_experts(cfg)), p["w_up"], p["w_down"]))
    if "shared_up" in p:
        y = y + _mm(cfg, _relu2(_mm(cfg, x, p["shared_up"])),
                    p["shared_down"])
    # A_l: every router output counts, held or not
    e = scores.shape[-1]
    received = jnp.sum(jax.nn.one_hot(ids, e), (0, 1)) / x.shape[0]
    shares = scores / jnp.sum(scores, -1, keepdims=True)
    balance = e * jnp.sum(jax.lax.stop_gradient(received)
                          * jnp.mean(shares, 0))
    return y.reshape(shape), balance


# ---- the model ---------------------------------------------------------------

def _block(cfg, kind, p, bias, x):
    u = _rms_norm(x, p["norm"]["w"], cfg["layer_norm_epsilon"])
    if kind == "M":
        return x + _mamba2(cfg, u, p["mixer"]), 0.0
    if kind == "*":
        return x + _attention(cfg, u, p["mixer"]), 0.0
    y, balance = _experts(cfg, u, p["mixer"], bias)
    return x + y, balance


def _bias(cfg, state, name):
    """The router's bias an expert layer's state holds; zeros where the
    state has none (a model before its first step)."""
    held = (state or {}).get(name, {}).get("moe_router_bias")
    return (jnp.zeros((cfg["router_width"],), jnp.float32) if held is None
            else jnp.asarray(held, jnp.float32))


def _forward(cfg, params, state, ids, keep_block_inputs_only=False):
    """Logits (N, T, vocab_size) and the expert layers' ``A_l`` summed.
    ``keep_block_inputs_only`` puts each block under ``jax.checkpoint`` so
    that a gradient at 8,192 tokens fits the chip; the values are the
    same."""
    x = params["embed"]["W"][ids.astype(jnp.int32)]
    balance = 0.0
    for l, kind in enumerate(cfg["hybrid_override_pattern"]):
        block = functools.partial(_block, cfg, kind)
        if keep_block_inputs_only:
            block = jax.checkpoint(block)
        x, a = block(params[f"block{l}"], _bias(cfg, state, f"block{l}"), x)
        balance = balance + a
    head = params["lm_head"]
    return _mm(cfg, _rms_norm(x, head["norm"]["w"],
                              cfg["layer_norm_epsilon"]), head["W"]), balance


def _loss(cfg, params, state, ids, labels, keep_block_inputs_only=False):
    logits, balance = _forward(cfg, params, state, ids,
                               keep_block_inputs_only)
    logp = jax.nn.log_softmax(logits, -1)
    labels = labels.astype(jnp.int32)
    keep = labels >= 0                  # a row's last position has no next
    picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                                 -1)[..., 0]
    # rows without a label (the harness's check rows) leave the balance
    # term alone: no position to average over, a next-token term of zero
    return (-jnp.sum(jnp.where(keep, picked, 0.0))
            / jnp.maximum(jnp.sum(keep), 1)
            + cfg.get("router_aux_loss_coef", 0.0) * balance)


def _static(cfg):
    """What the arithmetic reads of the configuration, hashable: the
    static argument of the jitted functions."""
    keep = ("hybrid_override_pattern", "num_attention_heads",
            "num_key_value_heads", "head_dim", "mamba_num_heads",
            "mamba_head_dim", "n_groups", "ssm_state_size",
            "n_routed_experts", "router_width", "expert_parallel_rank",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "layer_norm_epsilon", "router_aux_loss_coef",
            "control_operand_dtype")
    return tuple((k, cfg[k]) for k in keep if k in cfg)


def _biases(cfg, state):
    """The routers' biases alone, as arrays: what the jitted functions
    take of the model state."""
    return {f"block{l}": {"moe_router_bias": _bias(cfg, state, f"block{l}")}
            for l, kind in enumerate(cfg["hybrid_override_pattern"])
            if kind == "E"}


@functools.partial(jax.jit, static_argnums=0)
def _logits_f32(cfg, params, state, ids):
    with jax.default_matmul_precision("highest"):
        return _forward(dict(cfg), _f32(params), state, ids)[0]


@functools.partial(jax.jit, static_argnums=0)
def _loss_f32(cfg, params, state, ids, labels):
    with jax.default_matmul_precision("highest"):
        return _loss(dict(cfg), _f32(params), state, ids, labels)


def _f32(params):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


def logits(cfg, params, state, features):
    """(N, T, vocab_size) float32 logits for token ids ``features[0]``."""
    return _logits_f32(_static(cfg), params, _biases(cfg, state),
                       jnp.asarray(features[0]))


def loss(cfg, params, state, features, labels):
    """Mean next-token cross-entropy (plus the balance term where the
    configuration has a coefficient); ``labels[0]`` (N, T) holds the id
    after each position and a negative number where there is none. Rows
    without a single label give the balance term alone."""
    return _loss_f32(_static(cfg), params, _biases(cfg, state),
                     jnp.asarray(features[0]), jnp.asarray(labels[0]))


def loss_fn(cfg, state=None):
    """``(params, ids, labels) -> loss`` for ``jax.grad``, the routers'
    bias from ``state`` (zeros without): the gradient comparison of the
    tests and of the chip check."""
    static = dict(_static(cfg))
    biases = _biases(static, state)

    def fn(params, ids, labels):
        with jax.default_matmul_precision("highest"):
            return _loss(static, params, biases, ids, labels,
                         keep_block_inputs_only=True)
    return fn
