"""Plain float32 AFMoE, as Trinity-Mini: gated grouped-query attention over
a causal window with rotary positions on three layers of four and over the
whole past without positions on the fourth, sandwich norms, dense SwiGLU
layers first and then sigmoid-routed SwiGLU experts beside a shared one
(published model: ``huggingface.co/arcee-ai/Trinity-Mini``,
``config.json``, ``model_type: afmoe``, and the family's modelling code,
``AfmoeForCausalLM``: ``AfmoeAttention``, ``AfmoeDecoderLayer``,
``AfmoeTokenChoiceRouter``).

Straightforward ``jax.numpy``: no kernels, no bfloat16, no sorting, every
contraction at ``default_matmul_precision("highest")``. It reads the
system's parameter tree (seeded random weights), the routers' bias from
the model state it is handed, and nothing else of the program.

As published (``h`` the model width, every projection bias-free, every
norm an RMSNorm at ``eps`` = ``rms_norm_eps``, ``RMSNorm(x) = x
rsqrt(mean(x^2) + eps) (1 + w)``). ``x_0 = sqrt(h) Embedding(ids)``
(``mup_enabled``); for each layer l: ``h = x + N2(Attn_l(N1(x)))``, ``y =
h + N4(FFN_l(N3(h)))``, the block's own four norms; ``logits = W_head
RMSNorm(x)``, head untied; mean next-token cross-entropy.

- ``Attn_l``: ``q = u W_q``, ``g = u W_g`` (``num_attention_heads`` of
  ``head_dim`` each), ``k, v`` (``num_key_value_heads``); RMSNorm of q and
  k over the head; where ``layer_types[l]`` is ``sliding_attention``,
  rotate-half rotary on the whole head at ``rope_theta`` and a query at i
  sees the keys j with ``i - sliding_window < j <= i``; where it is
  ``full_attention``, no rotation and ``j <= i``: an explicit mask over
  the (query, key) positions. Softmax at 1/sqrt(head size), query head j
  reading key/value head ``j // (heads / key-value heads)``; ``W_o(attn *
  sigmoid(g))``. Computed for a block of query rows at a time so that
  8,192 positions fit; the scores of a block of rows are whole.
- ``FFN_l``, ``l < num_dense_layers``: ``W_2(silu(u W_g) * u W_u)`` of
  ``intermediate_size``.
- ``FFN_l`` after: ``s = sigmoid(u W_r)`` over all ``router_width``
  outputs; the ``num_experts_per_tok`` experts are the largest of ``s +
  b`` (``b`` the router's bias in the layer's state, through which no
  gradient passes); ``p_i = route_scale s_i / (sum of the chosen s +
  1e-20)`` (``route_norm``); ``out = sum_i p_i E_i(u) + E_shared(u)``,
  ``E(u) = W_down(silu(u W_gate) * u W_up)`` of ``moe_intermediate_size``:
  a dense loop over the held experts (every token through every held
  expert, times its weight or 0), the shared expert added ungated.

**Balance loss** (``router_aux_loss_coef`` c above 0): the training loss is
``L + c sum_l A_l`` over the expert layers, ``A_l = E sum_e f_e P_e`` over
the layer's tokens and ALL ``router_width`` E outputs: ``f_e`` the
assignments output e received over the number of tokens (no gradient
passes through them), ``P_e`` the mean of ``s_e / sum_j s_j``
(DeepSeek-V3 eq. 17-20 times k, over the layer's tokens together).

Departures from the published model, each also in the configuration file:

- **depth**: the layers built are the configuration's ``layer_types``, a
  prefix of the published ones.
- **held experts**: the sum over the chosen experts runs over those the
  chip holds (``held_experts(cfg)``); what the absent ones would add is
  left out, as in the system (the expert-parallel deployment's share).
- **sliced vocabulary**: embedding, head and loss are over ``vocab_size``
  rows, whatever slice that is.
- RMSNorm weights are stored zero-centred (``w - 1``): the same function
  and gradients. ``W_q`` holds each head's query and gate side by side
  (``[q | g]`` a head) and the dense MLP's ``W1`` the columns ``[gate |
  up]``: column permutations of the release's ``q_proj`` / ``gate_proj``
  and ``gate_proj`` / ``up_proj``.

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
SLIDING = "sliding_attention"


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


def _swiglu(cfg, x, w_gate, w_up, w_down):
    return _mm(cfg, jax.nn.silu(_mm(cfg, x, w_gate)) * _mm(cfg, x, w_up),
               w_down)


def _rotate(x, theta):
    """Rotate-half rotary embedding on the whole head of (N, T, H, D)."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]
    rotated_half = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated_half * sin


def visible(qpos, kpos, window):
    """The mask: key j is seen by query i where ``j <= i`` and, with a
    ``window``, ``j > i - window``."""
    seen = kpos[None, :] <= qpos[:, None]
    if window is not None:
        seen = seen & (kpos[None, :] > qpos[:, None] - window)
    return seen


# ---- attention --------------------------------------------------------------

def _attention(cfg, u, p, sliding):
    n, t, _ = u.shape
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    qg = _mm(cfg, u, p["W_q"]).reshape(n, t, h, 2 * dh)
    q, gate = qg[..., :dh], qg[..., dh:]
    k = _mm(cfg, u, p["W_k"]).reshape(n, t, hk, dh)
    v = _mm(cfg, u, p["W_v"]).reshape(n, t, hk, dh)
    q, k = _rms_norm(q, p["q_norm"], eps), _rms_norm(k, p["k_norm"], eps)
    if sliding:
        q, k = _rotate(q, cfg["rope_theta"]), _rotate(k, cfg["rope_theta"])
    window = cfg["sliding_window"] if sliding else None
    q, k, v = _low(cfg, q), _low(cfg, k), _low(cfg, v)
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
        sc = jnp.where(visible(start + jnp.arange(rows), keys, window), sc,
                       -jnp.inf)
        return jnp.einsum("nkgqt,ntkd->nqkgd",
                          _low(cfg, jax.nn.softmax(sc, -1)), v)

    starts = jnp.arange(blocks.shape[1]) * rows
    out = jax.lax.map(block, (jnp.moveaxis(blocks, 1, 0), starts))
    out = jnp.moveaxis(out, 0, 1).reshape(n, t + pad, h, dh)[:, :t]
    out = out * jax.nn.sigmoid(gate)
    return _mm(cfg, out.reshape(n, t, h * dh), p["W_o"])


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
    if cfg.get("route_norm", True):
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    top = top * cfg["route_scale"]

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

def _block(cfg, l, p, bias, x):
    eps = cfg["rms_norm_eps"]
    sliding = cfg["layer_types"][l] == SLIDING
    a = _attention(cfg, _rms_norm(x, p["norm1"]["w"], eps), p["mixer"],
                   sliding)
    x = x + _rms_norm(a, p["norm2"]["w"], eps)
    u = _rms_norm(x, p["norm3"]["w"], eps)
    if l < cfg["num_dense_layers"]:
        f, balance = _dense(cfg, u, p["mlp"]), 0.0
    else:
        f, balance = _experts(cfg, u, p["moe"], bias)
    return x + _rms_norm(f, p["norm4"]["w"], eps), balance


def _expert_layers(cfg):
    return range(cfg["num_dense_layers"], len(cfg["layer_types"]))


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
    if cfg.get("mup_enabled", True):
        x = x * jnp.sqrt(float(cfg["hidden_size"]))
    balance = 0.0
    for l in range(len(cfg["layer_types"])):
        block = functools.partial(_block, cfg, l)
        if keep_block_inputs_only:
            block = jax.checkpoint(block)
        x, a = block(params[f"block{l}"], _bias(cfg, state, f"block{l}"), x)
        balance = balance + a
    head = params["lm_head"]
    return _mm(cfg, _rms_norm(x, head["norm"]["w"], cfg["rms_norm_eps"]),
               head["W"]), balance


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
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rope_theta", "sliding_window", "num_dense_layers",
            "num_experts", "router_width", "expert_parallel_rank",
            "num_experts_per_tok", "route_norm", "route_scale",
            "rms_norm_eps", "mup_enabled", "router_aux_loss_coef",
            "control_operand_dtype")
    static = tuple((k, cfg[k]) for k in keep if k in cfg)
    return static + (("layer_types", tuple(cfg["layer_types"])),)


def _biases(cfg, state):
    """The routers' biases alone, as arrays: what the jitted functions
    take of the model state."""
    return {f"block{l}": {"moe_router_bias": _bias(cfg, state, f"block{l}")}
            for l in _expert_layers(cfg)}


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
