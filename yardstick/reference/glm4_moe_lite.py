"""Plain float32 GLM-4.7-Flash (``glm4_moe_lite``): multi-head latent
attention with one rotary key shared by every head, a dense SwiGLU layer
first and then sigmoid-routed SwiGLU experts beside a shared one, and the
multi-token-prediction module, which scores the token after the next
through the main model's own head (published model:
``huggingface.co/zai-org/GLM-4.7-Flash``, ``config.json``; the layout is
DeepSeek-V3's, arXiv:2412.19437: MLA section 2.1.1, the router and its
bias section 2.1.2, MTP section 2.2).

Straightforward ``jax.numpy``: no kernels, no bfloat16, no sorting, every
contraction at ``default_matmul_precision("highest")``. It reads the
system's parameter tree (seeded random weights), the routers' bias from
the model state it is handed, and nothing else of the program.

As published (``d`` the model width, every projection bias-free, every
norm an RMSNorm at ``eps`` = ``rms_norm_eps``, ``RMSNorm(x) = x
rsqrt(mean(x^2) + eps) (1 + w)``, positions ``0 .. T - 1``). ``e =
Embedding(ids)``, ``x_0 = e``; for each layer l: ``h = x + MLA_l(N1(x))``,
``y = h + FFN_l(N2(h))``; ``h_T = RMSNorm_final(x_L)``, ``logits = h_T
W_head``, head untied.

- ``MLA``: ``c_q = RMSNorm_q(u W_qa)`` (``q_lora_rank``), ``[q_nope |
  q_rope] = c_q W_qb`` a head (``qk_nope_head_dim`` + ``qk_rope_head_dim``);
  ``[c_kv | k_r] = u W_kva``, ``c_kv <- RMSNorm_kv(c_kv)``
  (``kv_lora_rank``), ``[k_nope | v] = c_kv W_kvb`` a head (+
  ``v_head_dim``); rotate-half rotary at ``rope_theta`` on ``q_rope`` and
  on the ONE ``k_r``, which every head's key carries: ``q = [q_nope |
  q_rope]``, ``k = [k_nope | k_r]``; causal softmax at 1/sqrt(query head),
  an explicit mask over (query, key) positions, computed for a block of
  query rows at a time so that 8,192 positions fit; ``concat(o) W_o``.
- ``FFN_l``, ``l < first_k_dense_replace``: ``W_2(silu(u W_g) * u W_u)``
  of ``intermediate_size``.
- ``FFN_l`` after: ``s = sigmoid(u W_r)`` over all ``router_width``
  outputs (the published ``n_routed_experts``); the
  ``num_experts_per_tok`` experts are the largest of ``s + b`` (``b``
  the router's bias in the layer's state, through which no gradient
  passes); ``p_i = routed_scaling_factor s_i / (sum of the
  chosen s + 1e-20)``; ``out = sum_i p_i E_i(u) + E_shared(u)``, ``E(u) =
  W_down(silu(u W_gate) * u W_up)`` of ``moe_intermediate_size``: a dense
  loop over the held experts (every token through every held expert,
  times its weight or 0), the shared expert added ungated.
- **MTP** (``num_nextn_predict_layers`` 1): ``e'_i = e_{i+1}`` (zeros at
  the last position), ``u = [RMSNorm_e(e') ; RMSNorm_h(h_T)] W_eh``, ``g =
  RMSNorm_head(Block(u))`` with ``Block`` one more layer of the expert
  kind, ``logits_mtp = g W_head``, the SAME ``W_head``.

**Loss**: ``CE(logits, labels) + mtp_loss_weight CE(logits_mtp, labels')``,
``labels'[:, t] = labels[:, t + 1]`` (the token after the next; the last
two positions have none), each a mean over its positions with a label,
plus ``router_aux_loss_coef`` c times ``sum_l A_l`` over the expert layers
and the module's, ``A_l = E sum_e f_e P_e`` over the layer's tokens and ALL
``router_width`` E outputs: ``f_e`` the assignments output e received
over the number of tokens (no gradient passes through them), ``P_e`` the
mean of ``s_e / sum_j s_j`` (DeepSeek-V3 eq. 17-20 times k, over the
layer's tokens together).

Departures from the published model, each also in the configuration file:

- **depth**: the first ``num_hidden_layers`` layers and the module.
- **held experts**: the sum over the chosen experts runs over those the
  chip holds (``held_experts(cfg)``); what the absent ones would add is
  left out, as in the system (the expert-parallel deployment's share).
- **sliced vocabulary**: embedding, head and loss are over ``vocab_size``
  rows, whatever slice that is.
- RMSNorm weights are stored zero-centred (``w - 1``): the same function
  and gradients. ``W_qb`` holds each head's ``[nope | rope]`` side by
  side, ``W_kvb`` each head's ``[k_nope | v]``, the dense MLP's ``W1``
  the columns ``[gate | up]``, and the rotary pairs are rotate-half:
  column permutations of the release's matrices.

**Controls** (``chip_check.py`` only; no cell sets them). The
configuration may carry ``control_operand_dtype``: every matrix product's
operands, the router's, q, k, v and the attention's probabilities among
them, rounded to that type and back; accumulation stays float32. Absent,
nothing is rounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_ROWS = 512        # attention is computed for this many rows at once


def held_experts(cfg):
    """Ids, among the router's ``router_width`` outputs, of the experts
    this chip holds: the ``expert_parallel_rank``-th run of
    ``n_routed_experts``."""
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


def _swiglu(cfg, x, w_gate, w_up, w_down):
    return _mm(cfg, jax.nn.silu(_mm(cfg, x, w_gate)) * _mm(cfg, x, w_up),
               w_down)


def _rotate(x, theta):
    """Rotate-half rotary embedding on the last axis of (N, T, H, D)."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    rotated_half = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated_half * sin


# ---- latent attention -------------------------------------------------------

def _latent_attention(cfg, u, p):
    n, t, _ = u.shape
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    c_q = _rms_norm(_mm(cfg, u, p["W_qa"]), p["q_norm"], eps)
    q = _mm(cfg, c_q, p["W_qb"]).reshape(n, t, h, dn + dr)
    kv = _mm(cfg, u, p["W_kva"])
    c_kv = _rms_norm(kv[..., :r], p["kv_norm"], eps)
    k_r = _rotate(kv[..., r:].reshape(n, t, 1, dr), cfg["rope_theta"])
    kvb = _mm(cfg, c_kv, p["W_kvb"]).reshape(n, t, h, dn + dv)
    q = jnp.concatenate([q[..., :dn],
                         _rotate(q[..., dn:], cfg["rope_theta"])], -1)
    k = jnp.concatenate([kvb[..., :dn],
                         jnp.broadcast_to(k_r, (n, t, h, dr))], -1)
    q, k, v = _low(cfg, q), _low(cfg, k), _low(cfg, kvb[..., dn:])
    rows = min(QUERY_ROWS, t)
    pad = (-t) % rows
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    blocks = qp.reshape(n, (t + pad) // rows, rows, h, dn + dr)
    keys = jnp.arange(t)

    @jax.checkpoint        # a gradient keeps the block's rows, not its scores
    def block(args):
        qb, start = args                       # (N, rows, h, dn + dr)
        sc = jnp.einsum("nqhd,nthd->nhqt", qb, k) / jnp.sqrt(float(dn + dr))
        seen = keys[None, :] <= (start + jnp.arange(rows))[:, None]
        sc = jnp.where(seen, sc, -jnp.inf)
        return jnp.einsum("nhqt,nthd->nqhd",
                          _low(cfg, jax.nn.softmax(sc, -1)), v)

    starts = jnp.arange(blocks.shape[1]) * rows
    out = jax.lax.map(block, (jnp.moveaxis(blocks, 1, 0), starts))
    out = jnp.moveaxis(out, 0, 1).reshape(n, t + pad, h, dv)[:, :t]
    return _mm(cfg, out.reshape(n, t, h * dv), p["W_o"])


# ---- feed-forward ----------------------------------------------------------

def _dense(cfg, u, p):
    f = p["W2"].shape[0]
    return _swiglu(cfg, u, p["W1"][:, :f], p["W1"][:, f:], p["W2"])


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
        eid, wg, wu, wd = xs
        weight = jnp.sum(jnp.where(ids == eid, top, 0.0), -1)   # 0: not sent
        return y + weight[:, None] * _swiglu(cfg, x, wg, wu, wd), None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (jnp.asarray(held_experts(cfg)), p["w_gate"], p["w_up"],
         p["w_down"]))
    if "shared_up" in p:
        y = y + _swiglu(cfg, x, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
    # A_l: every router output counts, held or not
    e = scores.shape[-1]
    received = jnp.sum(jax.nn.one_hot(ids, e), (0, 1)) / x.shape[0]
    shares = scores / jnp.sum(scores, -1, keepdims=True)
    balance = e * jnp.sum(jax.lax.stop_gradient(received)
                          * jnp.mean(shares, 0))
    return y.reshape(shape), balance


# ---- the model -------------------------------------------------------------

def _block(cfg, dense, p, bias, x):
    eps = cfg["rms_norm_eps"]
    x = x + _latent_attention(cfg, _rms_norm(x, p["norm1"]["w"], eps),
                              p["mixer"])
    u = _rms_norm(x, p["norm2"]["w"], eps)
    if dense:
        f, balance = _dense(cfg, u, p["mlp"]), 0.0
    else:
        f, balance = _experts(cfg, u, p["moe"], bias)
    return x + f, balance


def _mtp(cfg, p, bias, h, e):
    """The module's normed output ``g``, and its expert layer's ``A``."""
    eps = cfg["rms_norm_eps"]
    later = jnp.concatenate([e[:, 1:], jnp.zeros_like(e[:, :1])], 1)
    u = _mm(cfg, jnp.concatenate([_rms_norm(later, p["enorm"]["w"], eps),
                                  _rms_norm(h, p["hnorm"]["w"], eps)], -1),
            p["W_eh"])
    g, balance = _block(cfg, False, p, bias, u)
    return _rms_norm(g, p["head_norm"]["w"], eps), balance


def _expert_layers(cfg):
    layers = [f"layer{l}" for l in range(cfg["first_k_dense_replace"],
                                         cfg["num_hidden_layers"])]
    return layers + ["mtp"]


def _bias(cfg, state, name):
    """The router's bias an expert layer's state holds; zeros where the
    state has none (a model before its first step)."""
    held = (state or {}).get(name, {}).get("moe_router_bias")
    return (jnp.zeros((cfg["router_width"],), jnp.float32)
            if held is None else jnp.asarray(held, jnp.float32))


def _forward(cfg, params, state, ids, keep_block_inputs_only=False):
    """``(logits, logits_mtp, sum of A_l)``.
    ``keep_block_inputs_only`` puts each block under ``jax.checkpoint`` so
    that a gradient at 8,192 tokens fits the chip; the values are the
    same."""
    eps = cfg["rms_norm_eps"]
    e = params["embed"]["W"][ids.astype(jnp.int32)]
    x, balance = e, 0.0

    def kept(fn):
        return jax.checkpoint(fn) if keep_block_inputs_only else fn

    for l in range(cfg["num_hidden_layers"]):
        name = f"layer{l}"
        block = kept(functools.partial(
            _block, cfg, l < cfg["first_k_dense_replace"]))
        x, a = block(params[name], _bias(cfg, state, name), x)
        balance = balance + a
    w = params["lm_head"]["W"]
    h = _rms_norm(x, params["norm"]["w"], eps)
    g, a = kept(functools.partial(_mtp, cfg))(
        params["mtp"], _bias(cfg, state, "mtp"), h, e)
    return _mm(cfg, h, w), _mm(cfg, g, w), balance + a


def _cross_entropy(logits, labels):
    """Mean over the positions with a label (0 where none has one)."""
    logp = jax.nn.log_softmax(logits, -1)
    keep = labels >= 0
    picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                                 -1)[..., 0]
    return (-jnp.sum(jnp.where(keep, picked, 0.0))
            / jnp.maximum(jnp.sum(keep), 1))


def later_labels(labels):
    """The module's labels: the next-token labels shifted left by one, a
    negative number where no token follows the next."""
    return jnp.concatenate(
        [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], 1)


def _terms(cfg, params, state, ids, labels, keep_block_inputs_only=False):
    """``(next-token CE, MTP CE, sum of A_l)``."""
    logits, mtp, balance = _forward(cfg, params, state, ids,
                                    keep_block_inputs_only)
    labels = labels.astype(jnp.int32)
    return (_cross_entropy(logits, labels),
            _cross_entropy(mtp, later_labels(labels)), balance)


def _loss(cfg, params, state, ids, labels, keep_block_inputs_only=False):
    main, later, balance = _terms(cfg, params, state, ids, labels,
                                  keep_block_inputs_only)
    return (main + cfg.get("mtp_loss_weight", 0.0) * later
            + cfg.get("router_aux_loss_coef", 0.0) * balance)


def _static(cfg):
    """What the arithmetic reads of the configuration, hashable: the
    static argument of the jitted functions."""
    keep = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rope_theta",
            "first_k_dense_replace", "n_routed_experts", "router_width",
            "expert_parallel_rank", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "mtp_loss_weight", "rms_norm_eps",
            "router_aux_loss_coef", "control_operand_dtype")
    return tuple((k, cfg[k]) for k in keep if k in cfg)


def _biases(cfg, state):
    """The routers' biases alone, as arrays: what the jitted functions
    take of the model state."""
    return {name: {"moe_router_bias": _bias(cfg, state, name)}
            for name in _expert_layers(cfg)}


def _f32(params):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


@functools.partial(jax.jit, static_argnums=0)
def _logits_f32(cfg, params, state, ids):
    with jax.default_matmul_precision("highest"):
        logits, mtp, _ = _forward(dict(cfg), _f32(params), state, ids)
        return logits, mtp


@functools.partial(jax.jit, static_argnums=0)
def _terms_f32(cfg, params, state, ids, labels):
    with jax.default_matmul_precision("highest"):
        return _terms(dict(cfg), _f32(params), state, ids, labels)


def logits(cfg, params, state, features):
    """(N, T, vocab_size) float32 next-token logits for token ids
    ``features[0]``."""
    return heads(cfg, params, state, features)[0]


def heads(cfg, params, state, features):
    """``(next-token logits, the module's logits)``, each (N, T,
    vocab_size) float32; the second None without a module."""
    return _logits_f32(_static(cfg), params, _biases(cfg, state),
                       jnp.asarray(features[0]))


def loss_terms(cfg, params, state, features, labels):
    """``(next-token CE, MTP CE, sum of the expert layers' A_l)``, before
    their weights."""
    return _terms_f32(_static(cfg), params, _biases(cfg, state),
                      jnp.asarray(features[0]), jnp.asarray(labels[0]))


def loss(cfg, params, state, features, labels):
    """The training loss: next-token cross-entropy, the module's on the
    token after the next times ``mtp_loss_weight``, the balance term where
    the configuration has a coefficient; ``labels[0]`` (N, T) holds the id
    after each position and a negative number where there is none. Rows
    without a single label give the balance term alone."""
    main, later, balance = loss_terms(cfg, params, state, features, labels)
    return (main + cfg.get("mtp_loss_weight", 0.0) * later
            + cfg.get("router_aux_loss_coef", 0.0) * balance)


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
