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
  held experts are sorted by expert and go through grouped matrix
  products (``lax.ragged_dot``), gated (SwiGLU) experts without biases.
  What the absent experts would add is left out: this is the chip's share
  of an expert-parallel deployment, computed without the exchange, and
  the layer a held-experts deployment uses.
"""

from __future__ import annotations

import dataclasses
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


def route_top_k_probs(x, router_w, top_k: int, norm_topk: bool = True):
    """Softmax over ALL experts in float32, the ``top_k`` largest and, with
    ``norm_topk``, their weights renormalised to sum 1. ``x`` (T, d),
    ``router_w`` (d, E). Returns ``(expert ids (T, k) int32, weights
    (T, k) float32)``."""
    logits = jnp.einsum("td,de->te", x, router_w.astype(x.dtype),
                        preferred_element_type=jnp.promote_types(
                            jnp.float32, x.dtype))
    probs = jax.nn.softmax(logits, -1)
    weights, ids = jax.lax.top_k(probs, top_k)
    if norm_topk:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return ids.astype(jnp.int32), weights


def held_experts_ffn(x, router_w, w_gate, w_up, w_down, held, *,
                     top_k: int, norm_topk: bool = True):
    """The held experts' part of a top-k mixture of gated experts,
    ``sum_{i in top-k(x), i held} p_i W_down_i (silu(W_gate_i x) *
    W_up_i x)``, with no token dropped.

    x: (T, d); router_w: (d, E) over all E experts; w_gate, w_up:
    (G, d, f) and w_down: (G, f, d) for the G experts held here, whose
    ids among the E are ``held`` (a static tuple, in the weights' order).
    Returns ``(y (T, d) in x's type, counters float32[4])``, the counters
    in ``ROUTING_COUNTERS``' order.

    The T * k assignments are sorted by held expert (those of absent
    experts last); the rows of the first ``T * min(k, G)`` of them, the
    most that can land here (a token picks an expert once), are gathered,
    three grouped products over ``group_sizes`` compute the experts, and a
    weighted scatter-add in float32 takes the results back to the tokens.
    The buffer's length does not depend on the routing, so neither does
    the step's time: gather and scatter cost by the row, the grouped
    products only by the rows inside groups."""
    t, d = x.shape
    e = router_w.shape[-1]
    g = len(held)
    acc = jnp.promote_types(jnp.float32, x.dtype)
    with jax.named_scope("moe.route"):
        ids, weights = route_top_k_probs(x, router_w, top_k, norm_topk)
        local = jnp.full((e,), g, jnp.int32).at[jnp.asarray(held)].set(
            jnp.arange(g, dtype=jnp.int32))
        where = local[ids].reshape(-1)                  # (T*k,) in [0, G]
        sizes = jnp.bincount(where, length=g + 1)[:g].astype(jnp.int32)
        n_held = jnp.sum(sizes)
    rows = t * min(top_k, g)
    with jax.named_scope("moe.dispatch"):
        sel = jnp.argsort(where, stable=True)[:rows]
        token = sel // top_k
        # rows past the last group belong to no expert: the grouped
        # product leaves them unwritten (on the TPU: whatever the memory
        # held, NaN included), so they go in as zeros and every product's
        # result is zeroed there before it is used: a zero cotangent
        # times a NaN is a NaN
        live = (jnp.arange(rows) < n_held)[:, None]
        xs = jnp.where(live, jnp.take(x, token, axis=0), 0)
    with jax.named_scope("moe.experts"):
        w_gate, w_up, w_down = (w.astype(x.dtype)
                                for w in (w_gate, w_up, w_down))
        up = jnp.where(live, jax.lax.ragged_dot(xs, w_up, sizes), 0)
        gate = jnp.where(live, jax.lax.ragged_dot(xs, w_gate, sizes), 0)
        ys = jnp.where(live, jax.lax.ragged_dot(jax.nn.silu(gate) * up,
                                                w_down, sizes), 0)
    with jax.named_scope("moe.combine"):
        p = weights.reshape(-1)[sel].astype(acc)
        y = jnp.zeros((t, d), acc).at[token].add(ys.astype(acc) * p[:, None])
    counters = jnp.stack([
        n_held, jnp.max(sizes), n_held / g,
        n_held - jnp.minimum(n_held, rows)]).astype(jnp.float32)
    return y.astype(x.dtype), counters
