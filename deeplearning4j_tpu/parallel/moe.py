"""Expert parallelism: mixture-of-experts FFN with top-k routing.

ABSENT in the reference (SURVEY §2.11 row 7); designed fresh per SURVEY
§7.2 stage 7. Two paths, for two kinds of deployment:

- ``moe_ffn`` (layer ``MixtureOfExperts``): GShard/Switch-style dense
  dispatch. Routing builds (tokens, experts, capacity) dispatch/combine
  tensors so the whole layer is three einsums + the expert FFN — fully
  static shapes, MXU-friendly, no gather/scatter. **This path drops
  tokens**: what overflows an expert's capacity gets combine weight 0.
  Expert parallelism is expressed the XLA-native way: the expert-stacked
  weights and the (E, C, d) expert-batch tensor carry sharding
  constraints on the ``expert`` mesh axis, and GSPMD inserts the
  all-to-all dispatch/return collectives over ICI — no hand-written
  communication (the reference's Aeron mesh analog is the compiler). Its
  tensors grow with tokens x experts x capacity, so it is for small
  expert counts.
- ``held_experts_ffn`` (layer ``HeldExpertsMoE``): **drops no token**
  whatever the imbalance. The router scores all of the model's experts,
  the chip is told which of them it holds, the assignments that land on
  held experts are sorted by expert and go, a block of rows at a time
  and as many blocks as the step's routing filled, through grouped
  matrix products (``lax.ragged_dot``), experts without biases that are
  gated (SwiGLU, three products) or plain with a squared ReLU (two),
  under a softmax router or a sigmoid one with a score-correction bias:
  its time follows the load that landed here, not the most that could. What the absent experts would add is left out: this is the
  chip's share of an expert-parallel deployment, computed without the
  exchange, and the layer a held-experts deployment uses.
"""

from __future__ import annotations

import dataclasses
import functools
import operator
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

EXPERT_AXIS = "expert"

_default_mesh: Optional[Mesh] = None
_default_axis: str = EXPERT_AXIS


def set_default_mesh(mesh: Optional[Mesh], axis: str = EXPERT_AXIS) -> None:
    """Install the mesh used for expert-sharding constraints. Training
    code sets this once; layers then shard without threading a mesh
    through the (serializable) layer configs."""
    global _default_mesh, _default_axis
    _default_mesh = mesh
    _default_axis = axis


def _constrain(x: jnp.ndarray, spec: P) -> jnp.ndarray:
    if _default_mesh is None or _default_axis not in _default_mesh.shape:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_default_mesh, spec))


@dataclasses.dataclass
class MoEOutput:
    y: jnp.ndarray              # (tokens..., d_out) combined expert outputs
    aux_loss: jnp.ndarray       # load-balancing loss (scalar)
    router_z_loss: jnp.ndarray  # router logit magnitude penalty (scalar)


def route_top_k(logits: jnp.ndarray, k: int, capacity: int,
                token_mask: Optional[jnp.ndarray] = None):
    """Top-k routing → dense dispatch/combine tensors.

    logits: (T, E). token_mask: optional (T,) validity mask — masked
    (padding) tokens are never dispatched, consume no expert capacity,
    and are excluded from the aux/z statistics. Returns (dispatch
    (T,E,C) bool-ish float, combine (T,E,C) float, aux_loss, z_loss).
    Tokens overflowing an expert's capacity C are dropped (combine
    weight 0) — Switch semantics.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    tm = (jnp.ones((t,), jnp.float32) if token_mask is None
          else token_mask.reshape(-1).astype(jnp.float32))
    n_valid = jnp.maximum(jnp.sum(tm), 1.0)

    # aux loss (Switch eq.4): E * sum_e( frac_tokens_e * mean_prob_e ),
    # computed from the top-1 assignment over VALID tokens only.
    top1 = jnp.argmax(probs, -1)
    frac = jnp.sum(jax.nn.one_hot(top1, e, dtype=jnp.float32)
                   * tm[:, None], 0) / n_valid
    aux = e * jnp.sum(frac * jnp.sum(probs * tm[:, None], 0) / n_valid)
    z = jnp.sum(jax.nn.logsumexp(logits.astype(jnp.float32), -1) ** 2
                * tm) / n_valid

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    # Iterate the k choices (k is tiny and static); later choices see
    # occupancy from earlier ones via the running per-expert counts.
    counts = jnp.zeros((e,), jnp.int32)
    valid = tm > 0
    masked = probs * tm[:, None]
    for _ in range(k):
        choice = jnp.argmax(masked, -1)                     # (T,)
        gate = jnp.take_along_axis(masked, choice[:, None], 1)[:, 0]
        sel = jax.nn.one_hot(choice, e, dtype=jnp.int32)     # (T, E)
        # position of each token within its chosen expert's queue;
        # padding tokens don't advance the queue or claim a slot
        sel_eff = sel * valid[:, None].astype(jnp.int32)
        pos_in_expert = (jnp.cumsum(sel_eff, 0) - sel_eff) + counts[None, :]
        pos = jnp.sum(sel_eff * pos_in_expert, -1)           # (T,)
        keep = jnp.logical_and(pos < capacity, valid)
        oh_pos = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
        d = (sel_eff.astype(jnp.float32)[:, :, None] * oh_pos[:, None, :]
             * keep[:, None, None])
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        counts = counts + jnp.sum(sel_eff * keep[:, None].astype(jnp.int32),
                                  0)
        masked = masked * (1.0 - sel.astype(jnp.float32))    # exclude chosen
    return dispatch, combine, aux, z


def moe_ffn(x: jnp.ndarray,
            gate_w: jnp.ndarray,
            w_in: jnp.ndarray, b_in: jnp.ndarray,
            w_out: jnp.ndarray, b_out: jnp.ndarray,
            *,
            top_k: int = 2,
            capacity_factor: float = 1.25,
            activation=jax.nn.gelu,
            token_mask: Optional[jnp.ndarray] = None) -> MoEOutput:
    """Mixture-of-experts FFN over the last dim of ``x``.

    x: (..., d_model); gate_w: (d_model, E);
    w_in: (E, d_model, d_ff); b_in: (E, d_ff);
    w_out: (E, d_ff, d_model); b_out: (E, d_model).
    token_mask: optional validity mask broadcastable to x.shape[:-1]
    (padding tokens are not routed; their output is 0).
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    e = gate_w.shape[-1]
    capacity = max(1, int(capacity_factor * top_k * t / e))

    flat_mask = None
    if token_mask is not None:
        flat_mask = jnp.broadcast_to(
            token_mask, orig_shape[:-1]).reshape(-1)

    logits = xt @ gate_w.astype(xt.dtype)
    dispatch, combine, aux, z = route_top_k(logits, top_k, capacity,
                                            token_mask=flat_mask)
    dispatch = dispatch.astype(xt.dtype)
    combine = combine.astype(xt.dtype)

    # (T,E,C),(T,d) -> (E,C,d): the all-to-all boundary under GSPMD.
    expert_in = jnp.einsum("tec,td->ecd", dispatch, xt)
    expert_in = _constrain(expert_in, P(_default_axis))
    w_in = _constrain(w_in, P(_default_axis))
    w_out = _constrain(w_out, P(_default_axis))

    h = activation(jnp.einsum("ecd,edf->ecf", expert_in, w_in)
                   + b_in[:, None, :].astype(xt.dtype))
    expert_out = (jnp.einsum("ecf,efd->ecd", h, w_out)
                  + b_out[:, None, :].astype(xt.dtype))
    expert_out = _constrain(expert_out, P(_default_axis))

    y = jnp.einsum("tec,ecd->td", combine, expert_out)
    return MoEOutput(y.reshape(orig_shape[:-1] + (y.shape[-1],)),
                     aux.astype(jnp.float32), z.astype(jnp.float32))


# ---- held experts: no token dropped ---------------------------------------

# the routing counters a held-experts layer leaves in its state, in order
ROUTING_COUNTERS = ("assignments_held", "load_max", "load_mean", "dropped")


def router_probs(x, router_w, scoring: str = "softmax"):
    """The router's float32 scores over ALL experts: ``x`` (T, d),
    ``router_w`` (d, E) -> (T, E). ``scoring`` ``"softmax"``: a
    distribution over the experts; ``"sigmoid"``: each expert's own
    affinity in (0, 1) (DeepSeek-V3, arXiv:2412.19437)."""
    logits = jnp.einsum("td,de->te", x, router_w.astype(x.dtype),
                        preferred_element_type=jnp.promote_types(
                            jnp.float32, x.dtype))
    if scoring == "sigmoid":
        return jax.nn.sigmoid(logits)
    if scoring != "softmax":
        raise ValueError(f"scoring={scoring!r}: 'softmax' or 'sigmoid'")
    return jax.nn.softmax(logits, -1)


def top_k_weights(probs, top_k: int, norm_topk: bool = True, bias=None,
                  scale: float = 1.0):
    """The ``top_k`` largest of ``probs`` (T, E) and, with ``norm_topk``,
    their weights renormalised to sum 1. With ``bias`` (E,), a sigmoid
    router's score correction, the choice is of the largest ``probs +
    bias`` and the weights are the chosen experts' unbiased ``probs``
    (over their sum + 1e-20): the bias steers the load and reaches no
    weight, and no gradient reaches it. ``scale`` multiplies the weights
    (the family's ``routed_scaling_factor``). Returns ``(expert ids (T, k)
    int32, weights (T, k) float32)``."""
    if bias is None:
        weights, ids = jax.lax.top_k(probs, top_k)
    else:
        _, ids = jax.lax.top_k(
            probs + jax.lax.stop_gradient(bias).astype(probs.dtype), top_k)
        weights = jnp.take_along_axis(probs, ids, -1)
    if norm_topk:
        total = jnp.sum(weights, -1, keepdims=True)
        weights = weights / (total if bias is None else total + 1e-20)
    if scale != 1.0:
        weights = weights * scale
    return ids.astype(jnp.int32), weights


def route_top_k_probs(x, router_w, top_k: int, norm_topk: bool = True):
    """``top_k_weights`` of ``router_probs``: softmax over ALL experts in
    float32, then the ``top_k`` largest."""
    return top_k_weights(router_probs(x, router_w), top_k, norm_topk)


def assignments_received(ids, experts: int, dtype=jnp.float32):
    """How many of the assignments ``ids`` (T, k) each of the ``experts``
    router outputs received: (E,)."""
    return jnp.sum(ids[..., None] == jnp.arange(experts, dtype=ids.dtype),
                   (0, 1), dtype=dtype)


def load_balancing_loss(probs, ids):
    """The load-balancing auxiliary loss of a top-k router (Switch
    Transformer, arXiv:2101.03961 eq. 4-6, as the Mixtral / Qwen3-MoE
    trainers compute it for one layer): ``E * sum_e f_e P_e`` with ``f_e``
    the assignments expert e received over the tokens (so the ``f_e`` sum
    to k) and ``P_e`` the mean of its router probability. ``probs`` (T, E),
    ``ids`` (T, k). It reads k under an even router and ``E`` where every
    token picks the same k experts with all of its probability; the
    gradient reaches the router through ``P_e`` alone. All E outputs
    count, whichever experts are held."""
    t, e = probs.shape
    share = assignments_received(ids, e, probs.dtype) / t
    return e * jnp.sum(share * jnp.mean(probs, 0))


# the shortest block of the held experts' loop: toy shapes run one block
_MIN_DISPATCH_BLOCK = 256


def dispatch_block(tokens: int, top_k: int, held: int, experts: int) -> int:
    """Rows a pass of ``held_experts_ffn``'s loop takes for these shapes:
    the load of an even router, ``tokens * top_k * held / experts``,
    rounded up to a power of two (at least ``_MIN_DISPATCH_BLOCK``), and at
    most the ``tokens * min(top_k, held)`` rows that can land here."""
    even = -(-tokens * top_k * held // experts)
    return min(tokens * min(top_k, held),
               max(_MIN_DISPATCH_BLOCK, 1 << (even - 1).bit_length()))


def _block_rows(sel, ends, i, block: int, top_k: int):
    """Block ``i`` of the sorted assignments, rows ``[i * block, (i + 1) *
    block)``: their indices among the T * k, their tokens, the block's own
    group sizes and which of its rows hold an assignment."""
    start = i * block
    idx = jax.lax.dynamic_slice(sel, (start,), (block,))
    inside = jnp.clip(ends - start, 0, block)
    sizes = jnp.diff(inside, prepend=0)
    live = (jnp.arange(block) < inside[-1])[:, None]
    return idx, idx // top_k, sizes, live


def _gated(up, gate):
    return jax.nn.silu(gate) * up


def _relu2(up):
    return jnp.square(jax.nn.relu(up))


def _block_hidden(xs, w_in, sizes, live):
    """One block's gathered rows up to their experts' hidden
    pre-activations, one grouped product for each of ``w_in`` (``up`` and
    ``gate`` of a gated expert; ``up`` alone of a plain one). Rows past
    the last group belong to no expert: a grouped product leaves them
    unwritten (on the TPU: whatever the memory held, NaN included), so
    they go in as zeros and every product's result is zeroed there before
    it is used: a zero cotangent times a NaN is a NaN."""
    xs = jnp.where(live, xs, 0)
    return xs, tuple(jnp.where(live, jax.lax.ragged_dot(xs, w, sizes), 0)
                     for w in w_in)


# a grouped product's weight gradient: (rows, a), (rows, b) -> (G, a, b)
_ROWS_CONTRACTED = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _tokens_rows(rows, slots, n_held, most, scale=None):
    """``sum_j rows[slots[j]]`` for every token, in float32, over the first
    ``most`` of a token's slots: ``slots`` (k, T) are the places of a
    token's assignments among the sorted rows, its held ones first, so
    ``most`` passes reach every held assignment of every token. A slot at
    or past ``n_held`` is an absent expert's and adds nothing. ``scale``
    (k, T), where given, weights each row."""
    def one(j, total):
        at = slots[j]
        w = (at < n_held).astype(total.dtype)
        if scale is not None:
            w = w * scale[j]
        return total + jnp.take(rows, at, axis=0, mode="clip").astype(
            total.dtype) * w[:, None]
    return jax.lax.fori_loop(
        0, most, one, jnp.zeros((slots.shape[1], rows.shape[1]), jnp.float32))


def _row_buffers(n_blocks, rows: int, widths, dtype):
    """Zeroed ``(rows, width)`` buffers for the loop's passes to write
    their blocks into. The zero is read from ``n_blocks`` (never
    negative): XLA copies the broadcast of a constant without its name,
    and the writing of these zeros, 0.5 ms for 81,920 x 2,048, would count
    for no scope."""
    zero = jnp.minimum(n_blocks, 0).astype(dtype)
    return [jnp.full((rows, n), zero) for n in widths]


def _held_blocks(block: int, top_k: int, act):
    """``f(x, weights, w_in, w_down, sel, places, ends, n_blocks, most) ->
    y``: the first ``n_blocks`` blocks of the sorted assignments ``sel``
    dispatched and computed, one block a pass of a loop whose trip count
    is the traced ``n_blocks``, each pass leaving its rows in a buffer as
    long as ``sel``; then a second loop of ``most`` passes (the most held
    assignments any token has) gathers every token's rows back and sums
    them, weighted, in float32 (``_tokens_rows``): a gather of T rows a
    pass, where a scatter-add a block cost as much again for its fixed
    part. An expert is ``W_down act(x W_in[0], x W_in[1], ...)``: ``w_in``
    is a tuple of the experts' input-side matrices, ``(w_up, w_gate)``
    with ``act = _gated`` or ``(w_up,)`` with ``act = _relu2``, and a
    block costs ``len(w_in) + 1`` grouped products. Such loops have no
    reverse rule, so the backward is written here: the same two loops,
    each pass of the first computing its block's pre-activations again.
    The weights' gradients are no sums over the passes either: a pass
    leaves its rows of both operands in buffers, and one grouped product
    each after the loop reads the rows the routing filled, as the one
    long buffer's backward did."""

    def forward(x, weights, w_in, w_down, sel, places, ends, n_blocks, most):
        def body(i, rows):
            _, token, sizes, live = _block_rows(sel, ends, i, block, top_k)
            with jax.named_scope("moe.experts"):
                _, pre = _block_hidden(
                    jnp.take(x, token, axis=0), w_in, sizes, live)
                ys = jnp.where(live, jax.lax.ragged_dot(
                    act(*pre), w_down, sizes), 0)
                return jax.lax.dynamic_update_slice(rows, ys, (i * block, 0))

        with jax.named_scope("moe.dispatch"):
            rows = jax.lax.fori_loop(0, n_blocks, body, _row_buffers(
                n_blocks, sel.size, x.shape[1:], x.dtype)[0])
        with jax.named_scope("moe.combine"):
            # a token's held places first, each with its weight
            slots, p = jax.lax.sort((places, weights.T), dimension=0,
                                    num_keys=1)
            return _tokens_rows(rows, slots, ends[-1], most, p).astype(x.dtype)

    def forward_kept(*args):
        return forward(*args), args

    def backward(kept, dy):
        x, weights, w_in, w_down, sel, places, ends, n_blocks, most = kept
        p_flat = weights.reshape(-1)
        with jax.named_scope("moe.experts"):
            to_in = [jnp.swapaxes(w, 1, 2) for w in w_in]
            down_in = jnp.swapaxes(w_down, 1, 2)

        def body(i, carried):
            dp_flat, rows = carried
            idx, token, sizes, live = _block_rows(sel, ends, i, block, top_k)
            with jax.named_scope("moe.combine"):
                dout = jnp.take(dy, token, axis=0)
                dys = jnp.where(
                    live, dout * p_flat[idx][:, None], 0).astype(x.dtype)
            with jax.named_scope("moe.experts"):
                xs, pre = _block_hidden(
                    jnp.take(x, token, axis=0), w_in, sizes, live)
                h, pull = jax.vjp(act, *pre)
                ys = jnp.where(live, jax.lax.ragged_dot(h, w_down, sizes), 0)
                d_pre = pull(jnp.where(
                    live, jax.lax.ragged_dot(dys, down_in, sizes), 0))
                dxs = jnp.where(live, functools.reduce(operator.add, (
                    jax.lax.ragged_dot(d, w, sizes)
                    for d, w in zip(d_pre, to_in))), 0)
                rows = [jax.lax.dynamic_update_slice(a, b, (i * block, 0))
                        for a, b in zip(rows, (xs, h, *d_pre, dys, dxs))]
            with jax.named_scope("moe.combine"):
                # rows that hold no assignment add zeros
                return dp_flat.at[idx].add(jnp.sum(
                    dout.astype(dp_flat.dtype) * ys, -1)), rows

        d, f = x.shape[1], w_down.shape[1]
        with jax.named_scope("moe.dispatch"):
            dp_flat, (xs, h, *d_pre, dys, dxs) = jax.lax.fori_loop(
                0, n_blocks, body, (jnp.zeros_like(p_flat), _row_buffers(
                    n_blocks, sel.size, (d, *(f,) * (len(w_in) + 1), d, d),
                    x.dtype)))
        with jax.named_scope("moe.combine"):
            dx = _tokens_rows(dxs, jnp.sort(places, axis=0), ends[-1],
                              most).astype(x.dtype)
        with jax.named_scope("moe.experts"):
            sizes = jnp.diff(ends, prepend=0)
            dws = [jax.lax.ragged_dot_general(a, b, sizes, _ROWS_CONTRACTED)
                   for a, b in (*((xs, d_w) for d_w in d_pre), (h, dys))]
            # the barrier ties the products to ``dx``, which the layer
            # below waits for: left to the scheduler they run with the
            # optimizer's update at the step's end, and every layer's row
            # buffers live until then
            dx, *dws = jax.lax.optimization_barrier((dx, *dws))
        return (dx, dp_flat.reshape(weights.shape), tuple(dws[:-1]), dws[-1],
                None, None, None, None, None)

    f = jax.custom_vjp(forward)
    f.defvjp(forward_kept, backward)
    return f


def held_experts_ffn(x, router_w, w_gate, w_up, w_down, held, *,
                     top_k: int, norm_topk: bool = True,
                     balance: bool = False, scoring: str = "softmax",
                     router_bias=None, routed_scale: float = 1.0,
                     received: bool = False):
    """The held experts' part of a top-k mixture of experts, ``sum_{i in
    top-k(x), i held} p_i E_i(x)``, with no token dropped. An expert is
    gated (SwiGLU), ``E(x) = W_down (silu(W_gate x) * W_up x)``, three
    grouped products a block, or, where ``w_gate`` is None, plain with a
    squared ReLU, ``E(x) = W_down relu(W_up x)^2``, two.

    x: (T, d); router_w: (d, E) over all E experts; w_gate, w_up:
    (G, d, f) and w_down: (G, f, d) for the G experts held here, whose
    ids among the E are ``held`` (a static tuple, in the weights' order).
    The router (``router_probs``, ``top_k_weights``): ``scoring``
    ``"softmax"``, or ``"sigmoid"`` with ``router_bias`` (E,) added to the
    scores for the choice alone; ``routed_scale`` multiplies the weights.
    Returns ``(y (T, d) in x's type, counters float32[4])``, the counters
    in ``ROUTING_COUNTERS``' order; then, with ``balance``, the router's
    ``load_balancing_loss`` over all E outputs (sigmoid scores normalised
    to sum 1 a token: DeepSeek-V3's complementary balance loss, eq. 17-20,
    times k); then, with ``received``, the assignments each of the E
    outputs received, float32 (E,).

    The T * k assignments are sorted by held expert (those of absent
    experts last). The held ones go through a loop, ``dispatch_block``
    rows a pass and as many passes as the step's routing filled,
    ``ceil(assignments_held / block)``: a pass gathers its rows and the
    grouped products over the block's own group sizes compute the
    experts. A second loop takes the results back to the tokens, weighted
    and summed in float32: a pass gathers one row for every token, and
    there are as many passes as the token with the most held assignments
    has of them. So the step's time follows the load in steps of a block,
    up to the ``T * min(k, G)`` rows that can land here (a token picks an
    expert once), and nothing is dropped at any load."""
    t, d = x.shape
    e = router_w.shape[-1]
    g = len(held)
    rows = t * min(top_k, g)
    block = dispatch_block(t, top_k, g, e)
    with jax.named_scope("moe.route"):
        probs = router_probs(x, router_w, scoring)
        ids, weights = top_k_weights(probs, top_k, norm_topk, router_bias,
                                     routed_scale)
        # which held expert an assignment is, G where it is an absent
        # one's: by comparison, a table lookup of T * k integers takes the
        # TPU longer than a grouped product
        hit = ids[..., None] == jnp.asarray(held, jnp.int32)
        where = jnp.where(hit.any(-1), hit.argmax(-1), g).reshape(-1)
        sizes = jnp.sum(hit, (0, 1), dtype=jnp.int32)
        ends = jnp.cumsum(sizes)
        n_held = ends[-1]
        n_blocks = (n_held + block - 1) // block
    with jax.named_scope("moe.dispatch"):
        # whole blocks: the rows that pad the last one hold no assignment
        order = jnp.argsort(where, stable=True)
        sel = jnp.pad(order[:rows], (0, -rows % block))
        # (k, T): each token's places in that order; a held assignment's
        # place lies before every absent expert's
        places = jnp.argsort(order).reshape(t, top_k).T
        most = jnp.max(jnp.sum(places < n_held, axis=0))
    with jax.named_scope("moe.experts"):
        w_in = tuple(w.astype(x.dtype) for w in (
            (w_up,) if w_gate is None else (w_up, w_gate)))
        w_down = w_down.astype(x.dtype)
    y = _held_blocks(block, top_k, _relu2 if w_gate is None else _gated)(
        x, weights, w_in, w_down, sel, places, ends, n_blocks, most)
    out = [y, jnp.stack([
        n_held, jnp.max(sizes), n_held / g,
        n_held - jnp.minimum(n_held, rows)]).astype(jnp.float32)]
    with jax.named_scope("moe.route"):
        if balance:
            shares = (probs if scoring == "softmax" else
                      probs / jnp.sum(probs, -1, keepdims=True))
            out.append(load_balancing_loss(shares, ids))
        if received:
            out.append(assignments_received(ids, e))
    return tuple(out)
