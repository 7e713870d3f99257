"""Dense / embedding / elementwise feed-forward layers.

Analogs of the reference's ``nn/conf/layers/DenseLayer``, ``EmbeddingLayer``,
``EmbeddingSequenceLayer``, ``ActivationLayer``, ``DropoutLayer``,
``AutoEncoder`` (deeplearning4j-nn/.../nn/layers/feedforward/). Forward math
only; backward is ``jax.grad``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import (
    ConvolutionalType,
    FeedForwardType,
    InputType,
    RecurrentType,
)
from deeplearning4j_tpu.nn.layers.base import FeedForwardLayer, Layer, LayerContext
from deeplearning4j_tpu.ops.activations import Activation
from deeplearning4j_tpu.ops.initializers import WeightInit
from deeplearning4j_tpu.utils.serde import register_serializable


@register_serializable
@dataclasses.dataclass(frozen=True)
class DenseLayer(FeedForwardLayer):
    """y = act(x @ W + b). W: (n_in, n_out) so the matmul hits the MXU with
    the feature axis on lanes; works on (N, F) and (N, T, F) inputs alike."""

    def output_type(self, input_type: InputType) -> InputType:
        if isinstance(input_type, RecurrentType):
            return RecurrentType(self.n_out, input_type.timesteps)
        return FeedForwardType(self.n_out)

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        kw, _ = jax.random.split(key)
        dt = self.param_dtype()
        params = {"W": self.weight_init.init(kw, (n_in, self.n_out), n_in,
                                             self.n_out, dt)}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), dt)
        return params

    def apply(self, params, state, x, ctx):
        ctx, dk = ctx.split_rng()
        x = self.maybe_dropout(x, ctx, dk)
        y = jnp.einsum("...i,io->...o", x, params["W"])
        if self.has_bias:
            y = y + params["b"]
        return self.activation.apply(y), state


@register_serializable
@dataclasses.dataclass(frozen=True)
class EmbeddingLayer(FeedForwardLayer):
    """Integer-index lookup (reference: EmbeddingLayer — a Dense layer whose
    input is an index; forward is a gather, backward a scatter-add, both of
    which XLA lowers to efficient dynamic-slice/segment ops on TPU)."""

    def output_type(self, input_type: InputType) -> InputType:
        return FeedForwardType(self.n_out)

    def initialize(self, key, input_type):
        n_in = self.n_in
        if n_in is None:
            raise ValueError("EmbeddingLayer requires explicit n_in (vocab size)")
        dt = self.param_dtype()
        params = {"W": self.weight_init.init(key, (n_in, self.n_out), n_in,
                                             self.n_out, dt)}
        if self.has_bias:
            params["b"] = jnp.zeros((self.n_out,), dt)
        return params

    def apply(self, params, state, x, ctx):
        idx = x.astype(jnp.int32)
        if idx.ndim > 1 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        y = jnp.take(params["W"], idx, axis=0)
        if self.has_bias:
            y = y + params["b"]
        return self.activation.apply(y), state


@register_serializable
@dataclasses.dataclass(frozen=True)
class EmbeddingSequenceLayer(FeedForwardLayer):
    """Sequence of indices (N, T) → (N, T, n_out) (reference:
    EmbeddingSequenceLayer)."""

    def output_type(self, input_type: InputType) -> InputType:
        t = input_type.timesteps if isinstance(input_type, RecurrentType) else None
        return RecurrentType(self.n_out, t)

    def initialize(self, key, input_type):
        if self.n_in is None:
            raise ValueError("EmbeddingSequenceLayer requires explicit n_in")
        dt = self.param_dtype()
        return {"W": self.weight_init.init(key, (self.n_in, self.n_out),
                                           self.n_in, self.n_out, dt)}

    def apply(self, params, state, x, ctx):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        return jnp.take(params["W"], idx, axis=0), state


def _type_for_trailing(shape):
    """Trailing (non-batch) dims → InputType, same family mapping as
    ReshapeVertex (1 → FF, 2 → (T, F) recurrent, 3 → NHWC conv)."""
    if len(shape) == 1:
        return FeedForwardType(shape[0])
    if len(shape) == 2:
        return RecurrentType(shape[1], shape[0])
    if len(shape) == 3:
        return ConvolutionalType(shape[0], shape[1], shape[2])
    raise ValueError(f"unsupported shape arity: {shape}")


@register_serializable
@dataclasses.dataclass(frozen=True)
class ReshapeLayer(Layer):
    """Reshape the trailing (non-batch) dims to ``shape``; one -1 allowed.

    Row-major (C-order) element order, matching Keras ``Reshape`` — the
    reference materializes that layer's ``target_shape`` via a dedicated
    preprocessor (KerasReshape.java:40,67); here it is a first-class
    shape-only layer."""
    shape: tuple = ()

    @property
    def has_params(self):
        return False

    def resolved_shape(self, input_type: InputType):
        total = 1
        for d in input_type.shape():
            if d < 0:
                raise ValueError(
                    "ReshapeLayer needs a fully-known input shape; got "
                    f"{input_type.shape()} (unknown timesteps)")
            total *= d
        s = [int(v) for v in self.shape]
        if s.count(-1) > 1:
            raise ValueError(f"ReshapeLayer shape {s} has multiple -1s")
        known = 1
        for v in s:
            if v != -1:
                known *= v
        if -1 in s:
            if known == 0 or total % known:
                raise ValueError(
                    f"cannot infer -1 in reshape {s} from {total} elements")
            s[s.index(-1)] = total // known
        elif known != total:
            raise ValueError(
                f"reshape {tuple(s)} incompatible with input "
                f"{input_type.shape()} ({total} elements)")
        return tuple(s)

    def output_type(self, input_type: InputType) -> InputType:
        return _type_for_trailing(self.resolved_shape(input_type))

    def apply(self, params, state, x, ctx):
        s = [int(v) for v in self.shape]
        return x.reshape((x.shape[0],) + tuple(s)), state


@register_serializable
@dataclasses.dataclass(frozen=True)
class PermuteLayer(Layer):
    """Transpose the trailing (non-batch) dims by 1-indexed ``dims``
    (Keras ``Permute`` convention: dims=(2, 1) swaps the first two
    non-batch axes). The reference silently lacks this — KerasReshape.java
    is its closest relative; we implement the real transpose."""
    dims: tuple = ()

    @property
    def has_params(self):
        return False

    def _perm(self, rank: int):
        dims = tuple(int(d) for d in self.dims)
        if sorted(dims) != list(range(1, rank + 1)):
            raise ValueError(
                f"PermuteLayer dims {dims} is not a permutation of "
                f"1..{rank}")
        return dims

    def output_type(self, input_type: InputType) -> InputType:
        shape = input_type.shape()
        if any(d < 0 for d in shape):
            raise ValueError(
                "PermuteLayer needs a fully-known input shape; got "
                f"{shape} (unknown timesteps)")
        dims = self._perm(len(shape))
        return _type_for_trailing(tuple(shape[d - 1] for d in dims))

    def apply(self, params, state, x, ctx):
        dims = self._perm(x.ndim - 1)
        return x.transpose((0,) + dims), state


@register_serializable
@dataclasses.dataclass(frozen=True)
class ElementWiseMultiplicationLayer(FeedForwardLayer):
    """out = act(x ⊙ w + b) with a learnable per-feature weight vector
    (reference: nn/conf/layers/misc/ElementWiseMultiplicationLayer.java +
    nn/layers/feedforward/elementwise/ElementWiseMultiplicationLayer.java
    — input and output sizes are equal; the configured weight init draws
    the vector with the layer's fan-in/fan-out, matching
    ElementWiseParamInitializer)."""

    def __post_init__(self):
        if self.n_in is not None and self.n_out and self.n_in != self.n_out:
            raise ValueError(
                "ElementWiseMultiplicationLayer must have the same input "
                f"and output size. Got n_in={self.n_in}, n_out={self.n_out}")

    def output_type(self, input_type: InputType) -> InputType:
        if isinstance(input_type, RecurrentType):
            return RecurrentType(self.resolved_n_out(input_type),
                                 input_type.timesteps)
        return FeedForwardType(self.resolved_n_out(input_type))

    def resolved_n_out(self, input_type):
        return self.n_out or self.resolved_n_in(input_type)

    def initialize(self, key, input_type):
        n = self.resolved_n_in(input_type)
        if self.n_out and self.n_out != n:
            raise ValueError(
                "ElementWiseMultiplicationLayer must have the same input "
                f"and output size. Got n_in={n}, n_out={self.n_out}")
        dt = self.param_dtype()
        params = {"W": self.weight_init.init(key, (n,), n, n, dt)}
        if self.has_bias:
            params["b"] = jnp.zeros((n,), dt)
        return params

    def apply(self, params, state, x, ctx):
        ctx, dk = ctx.split_rng()
        x = self.maybe_dropout(x, ctx, dk)
        y = x * params["W"]
        if self.has_bias:
            y = y + params["b"]
        return self.activation.apply(y), state


@register_serializable
@dataclasses.dataclass(frozen=True)
class ActivationLayer(Layer):
    """Standalone activation (reference: nn/conf/layers/ActivationLayer).
    ``alpha`` parameterizes LEAKYRELU (negative slope; the reference's
    ActivationLReLU(alpha)) and ELU — None keeps each function's
    default (leaky 0.01, elu 1.0)."""
    activation: Activation = Activation.RELU
    alpha: Optional[float] = None

    @property
    def has_params(self):
        return False

    def output_type(self, input_type):
        return input_type

    def apply(self, params, state, x, ctx):
        if self.alpha is not None:
            if self.activation == Activation.LEAKYRELU:
                return jax.nn.leaky_relu(x, self.alpha), state
            if self.activation == Activation.ELU:
                return jax.nn.elu(x, self.alpha), state
        return self.activation.apply(x), state


@register_serializable
@dataclasses.dataclass(frozen=True)
class DropoutLayer(Layer):
    """Standalone dropout layer (reference: nn/conf/layers/DropoutLayer).
    ``dropout`` field from the base config is the drop probability."""
    dropout: float = 0.5

    @property
    def has_params(self):
        return False

    def output_type(self, input_type):
        return input_type

    def apply(self, params, state, x, ctx):
        ctx, dk = ctx.split_rng()
        return self.maybe_dropout(x, ctx, dk), state


@register_serializable
@dataclasses.dataclass(frozen=True)
class AutoEncoder(FeedForwardLayer):
    """Denoising autoencoder layer (reference: nn/layers/feedforward/
    autoencoder/AutoEncoder.java). In a feed-forward stack it behaves as a
    dense encoder; ``reconstruct``/pretraining uses the tied decoder params.
    """
    corruption_level: float = 0.3

    def output_type(self, input_type):
        return FeedForwardType(self.n_out)

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        kw, kv = jax.random.split(key)
        dt = self.param_dtype()
        return {
            "W": self.weight_init.init(kw, (n_in, self.n_out), n_in, self.n_out, dt),
            "b": jnp.zeros((self.n_out,), dt),
            "vb": jnp.zeros((n_in,), dt),   # visible bias for reconstruction
        }

    def apply(self, params, state, x, ctx):
        y = jnp.einsum("...i,io->...o", x, params["W"]) + params["b"]
        return self.activation.apply(y), state

    def reconstruct(self, params, h):
        v = jnp.einsum("...o,io->...i", h, params["W"]) + params["vb"]
        return self.activation.apply(v)

    @property
    def supports_pretrain(self) -> bool:
        return True

    def pretrain_loss(self, params, x, key) -> jnp.ndarray:
        """Denoising-reconstruction loss (reference: AutoEncoder
        .computeGradientAndScore — corrupt, encode, decode, squared
        error)."""
        if self.corruption_level > 0.0 and key is not None:
            keep = jax.random.bernoulli(key, 1.0 - self.corruption_level,
                                        x.shape)
            xc = jnp.where(keep, x, 0.0)
        else:
            xc = x
        h = self.activation.apply(
            jnp.einsum("...i,io->...o", xc, params["W"]) + params["b"])
        v = self.activation.apply(
            jnp.einsum("...o,io->...i", h, params["W"]) + params["vb"])
        return jnp.mean(jnp.sum(jnp.square(x - v), axis=-1))


@register_serializable
@dataclasses.dataclass(frozen=True)
class MixtureOfExperts(FeedForwardLayer):
    """Sparse MoE FFN by dense dispatch with capacity (no reference
    analog — SURVEY §2.11 row 7 lists expert parallelism as ABSENT there;
    designed fresh per §7.2 stage 7). Top-k routed expert FFNs (biases,
    one activation) over the feature dim; expert weights are stacked
    (E, ...) so ``parallel.moe.set_default_mesh`` shards them over the
    ``expert`` mesh axis and GSPMD inserts the dispatch all-to-alls. The
    load-balancing + router-z losses are surfaced through layer state
    (``moe_aux_loss``) and added to the training loss by the models.

    **This layer drops tokens**: an expert takes ``capacity_factor * top_k
    * tokens / experts`` of them and what overflows gets weight 0 (Switch
    semantics), and its (tokens, experts, capacity) tensors suit small
    expert counts. ``HeldExpertsMoE`` below is the path that drops none
    and the one a held-experts (expert-parallel) deployment uses."""

    num_experts: int = 4
    hidden: int = 0              # d_ff; 0 → 4 * n_out
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    z_weight: float = 0.001

    def output_type(self, input_type: InputType) -> InputType:
        if isinstance(input_type, RecurrentType):
            return RecurrentType(self.n_out, input_type.timesteps)
        return FeedForwardType(self.n_out)

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        d_ff = self.hidden or 4 * self.n_out
        dt = self.param_dtype()
        kg, k1, k2 = jax.random.split(key, 3)
        e = self.num_experts
        return {
            "gate": self.weight_init.init(kg, (n_in, e), n_in, e, dt),
            "w_in": self.weight_init.init(k1, (e, n_in, d_ff), n_in, d_ff, dt),
            "b_in": jnp.zeros((e, d_ff), dt),
            "w_out": self.weight_init.init(k2, (e, d_ff, self.n_out), d_ff,
                                           self.n_out, dt),
            "b_out": jnp.zeros((e, self.n_out), dt),
        }

    def init_state(self, input_type):
        return {"moe_aux_loss": jnp.zeros((), jnp.float32)}

    def apply(self, params, state, x, ctx):
        from deeplearning4j_tpu.parallel.moe import moe_ffn
        ctx, dk = ctx.split_rng()
        x = self.maybe_dropout(x, ctx, dk)
        # (N, T) padding mask for sequence inputs: padded tokens are not
        # routed, consume no capacity, and don't skew the aux loss
        tmask = ctx.mask if (ctx.mask is not None and x.ndim == 3) else None
        out = moe_ffn(x, params["gate"], params["w_in"], params["b_in"],
                      params["w_out"], params["b_out"], top_k=self.top_k,
                      capacity_factor=self.capacity_factor,
                      activation=self.activation.apply, token_mask=tmask)
        aux = (self.aux_weight * out.aux_loss
               + self.z_weight * out.router_z_loss)
        return out.y, {"moe_aux_loss": aux}


@register_serializable
@dataclasses.dataclass(frozen=True)
class HeldExpertsMoE(FeedForwardLayer):
    """A chip's share of a mixture of experts, with no token dropped
    (``parallel.moe.held_experts_ffn``), plus the shared expert every chip
    computes alike:

        y = sum_{i in top-k(x), i held here} p_i E_i(x)  +  g(x) E_shared(x)

    No biases. The defaults are the Qwen3-Next / Qwen3-MoE families' (and
    what a configuration saved before the fields were there loads as);
    the other values are the Nemotron-H / DeepSeek-V3 families':

    - ``expert_form`` ``"gated"``: ``E(x) = W_down(silu(W_gate x) * W_up
      x)`` (SwiGLU; three grouped products a block). ``"relu2"``: the
      plain form ``E(x) = W_down relu(W_up x)^2`` (two; no ``w_gate`` is
      allocated). The shared expert has the routed experts' form.
    - ``router_scoring`` ``"softmax"``: ``p`` the float32 softmax over ALL
      ``num_experts``, its top ``top_k`` renormalised to sum 1
      (``norm_topk``). ``"sigmoid"``: each expert's own float32 sigmoid
      score ``s``; the ``top_k`` are the largest of ``s + b``, ``b`` the
      score-correction bias (``num_experts`` numbers in the layer's state,
      ``moe_router_bias``, which no gradient reaches), and ``p`` the
      chosen experts' unbiased ``s`` over their sum.
    - ``routed_scale`` multiplies ``p`` (``routed_scaling_factor``).
    - ``shared_gate``: ``g(x) = sigmoid(x . w_s)``; off, the shared expert
      is added ungated and no ``shared_w`` is allocated.
    - ``bias_update_rate`` u above 0 (sigmoid scoring): while training,
      each step moves the bias towards the even load by its own counts,
      ``b_e <- b_e + u sign(mean(c) - c_e)``, ``c_e`` the assignments
      output e received over all ``num_experts`` (DeepSeek-V3,
      arXiv:2412.19437 section 2.1.2); the new bias is the next step's.

    ``held_experts`` names the experts whose weights live here (default:
    all of them); the router keeps ``num_experts`` outputs whatever is
    held, and what the absent experts would add is left out.
    ``shared_hidden`` 0 leaves the shared expert out (a share that is not
    the one to count it).

    The layer's state carries the step's routing counters
    (``parallel.moe.ROUTING_COUNTERS``: assignments that landed on held
    experts, the largest and the mean load of a held expert, dropped
    assignments, always 0), fifth the blocks the step's dispatch loop
    ran (``ceil(assignments_held / parallel.moe.dispatch_block(...))``)
    and, under a sigmoid router, sixth the largest ``|b_e|``: float32[5]
    or [6] under ``moe_routing``.

    ``aux_loss_coef`` above 0 adds that many times the router's
    load-balancing loss (``parallel.moe.load_balancing_loss``, over all
    ``num_experts`` outputs, this layer's own tokens; a sigmoid router's
    scores normalised to sum 1 a token) to the training loss, through
    the state's ``moe_aux_loss`` as ``MixtureOfExperts`` does; at 0 the
    layer computes none."""
    num_experts: int = 8
    held_experts: Tuple[int, ...] = ()
    hidden: int = 0              # a routed expert's width
    shared_hidden: int = 0       # the shared expert's; 0: none
    top_k: int = 2
    norm_topk: bool = True
    init_std: float = 0.02
    aux_loss_coef: float = 0.0
    expert_form: str = "gated"
    router_scoring: str = "softmax"
    routed_scale: float = 1.0
    shared_gate: bool = True
    bias_update_rate: float = 0.0

    @property
    def held(self) -> Tuple[int, ...]:
        return (tuple(self.held_experts) if self.held_experts
                else tuple(range(self.num_experts)))

    @property
    def _gated(self) -> bool:
        return self.expert_form == "gated"

    @property
    def _biased(self) -> bool:
        return self.router_scoring == "sigmoid"

    def __post_init__(self):
        held = self.held
        if len(set(held)) != len(held) or not all(
                0 <= i < self.num_experts for i in held):
            raise ValueError(
                f"held_experts={held} must be distinct ids below "
                f"num_experts={self.num_experts}")
        if self.expert_form not in ("gated", "relu2"):
            raise ValueError(
                f"expert_form={self.expert_form!r}: 'gated' or 'relu2'")
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"router_scoring={self.router_scoring!r}: "
                             "'softmax' or 'sigmoid'")
        if self.bias_update_rate and not self._biased:
            raise ValueError("bias_update_rate moves a sigmoid router's "
                             "bias; router_scoring is 'softmax'")

    def output_type(self, input_type: InputType) -> InputType:
        if isinstance(input_type, RecurrentType):
            return RecurrentType(self.n_out, input_type.timesteps)
        return FeedForwardType(self.n_out)

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        dt = self.param_dtype()
        g, f, fs = len(self.held), self.hidden, self.shared_hidden
        ks = jax.random.split(key, 8)

        def normal(k, shape):
            return self.init_std * jax.random.normal(k, shape, dt)

        params = {
            "router": normal(ks[0], (n_in, self.num_experts)),
            "w_up": normal(ks[2], (g, n_in, f)),
            "w_down": normal(ks[3], (g, f, self.n_out)),
        }
        if self._gated:
            params["w_gate"] = normal(ks[1], (g, n_in, f))
        if fs:
            params.update(shared_up=normal(ks[5], (n_in, fs)),
                          shared_down=normal(ks[6], (fs, self.n_out)))
            if self._gated:
                params["shared_gate"] = normal(ks[4], (n_in, fs))
            if self.shared_gate:
                params["shared_w"] = normal(ks[7], (n_in,))
        return params

    @property
    def _row(self) -> int:
        """Length of the state's ``moe_routing`` row."""
        return 6 if self._biased else 5

    def init_state(self, input_type):
        state = {"moe_routing": jnp.zeros((self._row,), jnp.float32)}
        if self.aux_loss_coef:
            state["moe_aux_loss"] = jnp.zeros((), jnp.float32)
        if self._biased:
            state["moe_router_bias"] = jnp.zeros((self.num_experts,),
                                                 jnp.float32)
        return state

    def upgrade_state(self, saved):
        # a row saved with the four counters reads 0 blocks until a step
        row = saved["moe_routing"]
        return {**saved, "moe_routing": jnp.pad(
            row, (0, max(0, self._row - row.size)))}

    def _shared(self, params, xt):
        from deeplearning4j_tpu.parallel.moe import _gated, _relu2
        up = xt @ params["shared_up"]
        h = (_gated(up, xt @ params["shared_gate"]) if self._gated
             else _relu2(up))
        y = h @ params["shared_down"]
        if not self.shared_gate:
            return y
        gate = jax.nn.sigmoid(jnp.einsum(
            "td,d->t", xt, params["shared_w"],
            preferred_element_type=jnp.promote_types(jnp.float32, xt.dtype)))
        return y * gate[:, None].astype(y.dtype)

    def apply(self, params, state, x, ctx):
        from deeplearning4j_tpu.parallel.moe import (dispatch_block,
                                                     held_experts_ffn)
        xt = x.reshape(-1, x.shape[-1])
        bias = state["moe_router_bias"] if self._biased else None
        moving = bool(self.bias_update_rate) and ctx.train
        y, counters, *more = held_experts_ffn(
            xt, params["router"], params.get("w_gate"), params["w_up"],
            params["w_down"], self.held, top_k=self.top_k,
            norm_topk=self.norm_topk, balance=bool(self.aux_loss_coef),
            scoring=self.router_scoring, router_bias=bias,
            routed_scale=self.routed_scale, received=moving)
        row = [counters, jnp.ceil(counters[0] / dispatch_block(
            xt.shape[0], self.top_k, len(self.held), self.num_experts))]
        if self.shared_hidden:
            with jax.named_scope("moe.shared"):
                y = y + self._shared(params, xt)
        y = y.reshape(x.shape[:-1] + (self.n_out,))
        new_state = {}
        if self.aux_loss_coef:
            new_state["moe_aux_loss"] = self.aux_loss_coef * more[0]
        if self._biased:
            if moving:
                with jax.named_scope("moe.route"):
                    got = jax.lax.stop_gradient(more[-1])
                    bias = bias + self.bias_update_rate * jnp.sign(
                        jnp.mean(got) - got)
            new_state["moe_router_bias"] = bias
            row.append(jnp.max(jnp.abs(bias)))
        new_state["moe_routing"] = jnp.hstack(row)
        return y, new_state
