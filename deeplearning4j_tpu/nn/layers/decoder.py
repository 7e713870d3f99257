"""Decoder-only language-model layers: token embedding, the hybrid
decoder block, and the head with its next-token loss.

The block is the unit of the hybrid linear-attention mixture-of-experts
family (Qwen3-Next): pre-norm residuals round a token mixer and an
expert layer,

    h = x + Mixer(RMSNorm(x)),   y = h + MoE(RMSNorm(h))

where the mixer is a ``GatedDeltaNet`` (linear attention, a matrix state
per head) or a ``GatedAttention`` (causal softmax attention) and the
expert layer is a ``HeldExpertsMoE``. A model is a ``MultiLayerNetwork``
of ``TokenEmbedding``, blocks, ``CausalLMOutputLayer``: ordinary
serialisable layers, trained by ``fit()``.

``recompute`` wraps the block's ``apply`` in ``jax.checkpoint`` while
training, so the step ``fit()`` builds keeps only each block's input for
the backward pass and recomputes the block's internals there.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import InputType, RecurrentType
from deeplearning4j_tpu.nn.layers.attention import GatedAttention
from deeplearning4j_tpu.nn.layers.base import (
    FeedForwardLayer,
    Layer,
    LayerContext,
)
from deeplearning4j_tpu.nn.layers.feedforward import HeldExpertsMoE
from deeplearning4j_tpu.nn.layers.linear_attention import GatedDeltaNet
from deeplearning4j_tpu.nn.layers.normalization import RMSNorm, rms_norm
from deeplearning4j_tpu.utils.serde import register_serializable

GATED_DELTANET = "gated_deltanet"
GATED_ATTENTION = "gated_attention"

# labels below zero are positions without a next token (a row's last)
IGNORE_LABEL = -1


@register_serializable
@dataclasses.dataclass(frozen=True)
class TokenEmbedding(Layer):
    """Integer token ids (N, T) -> (N, T, n_out); the table starts
    normal(0, ``init_std``)."""
    vocab_size: int = 0
    n_out: int = 0
    init_std: float = 0.02

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    def initialize(self, key, input_type):
        return {"W": self.init_std * jax.random.normal(
            key, (self.vocab_size, self.n_out), self.param_dtype())}

    def apply(self, params, state, x, ctx):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        return jnp.take(params["W"], idx, axis=0), state


@register_serializable
@dataclasses.dataclass(frozen=True)
class HybridDecoderBlock(FeedForwardLayer):
    """One decoder block (module docstring). ``mixer`` picks the token
    mixer; the fields after it are the parts' own (``GatedAttention``,
    ``GatedDeltaNet``, ``HeldExpertsMoE``), kept flat so that the block
    serialises as one layer. ``n_out`` is the model width and equals the
    input's."""
    mixer: str = GATED_DELTANET
    # gated attention
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # gated deltanet
    n_key_heads: int = 16
    n_value_heads: int = 32
    key_head_dim: int = 128
    value_head_dim: int = 128
    conv_kernel: int = 4
    chunk_size: int = 64
    # experts
    num_experts: int = 8
    held_experts: Tuple[int, ...] = ()
    expert_hidden: int = 0
    shared_hidden: int = 0
    top_k: int = 2
    norm_topk: bool = True
    eps: float = 1e-6
    init_std: float = 0.02
    recompute: bool = False

    # the ``jax.named_scope`` names this block's parts put into a step
    named_scopes = ("gdn.proj", "gdn.conv", "gdn.scan", "gdn.out",
                    "attn.gated", "moe.route", "moe.dispatch", "moe.experts",
                    "moe.shared", "moe.combine")

    def __post_init__(self):
        if self.mixer not in (GATED_DELTANET, GATED_ATTENTION):
            raise ValueError(
                f"mixer={self.mixer!r}: {GATED_DELTANET!r} or "
                f"{GATED_ATTENTION!r}")

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    def _parts(self):
        w = self.n_out
        common = dict(n_in=w, n_out=w, dtype=self.dtype,
                      init_std=self.init_std)
        if self.mixer == GATED_ATTENTION:
            mixer = GatedAttention(
                n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                head_dim=self.head_dim, eps=self.eps,
                partial_rotary_factor=self.partial_rotary_factor,
                rope_theta=self.rope_theta, **common)
        else:
            mixer = GatedDeltaNet(
                n_key_heads=self.n_key_heads,
                n_value_heads=self.n_value_heads,
                key_head_dim=self.key_head_dim,
                value_head_dim=self.value_head_dim,
                conv_kernel=self.conv_kernel, chunk_size=self.chunk_size,
                eps=self.eps, **common)
        moe = HeldExpertsMoE(
            num_experts=self.num_experts, held_experts=self.held_experts,
            hidden=self.expert_hidden, shared_hidden=self.shared_hidden,
            top_k=self.top_k, norm_topk=self.norm_topk, **common)
        return mixer, moe, RMSNorm(eps=self.eps, dtype=self.dtype)

    def initialize(self, key, input_type):
        width = self.resolved_n_in(input_type)
        if self.n_out and width != self.n_out:
            raise ValueError(
                f"HybridDecoderBlock needs n_in == n_out (residuals); got "
                f"{width} vs {self.n_out}")
        mixer, moe, norm = self._parts()
        km, ke = jax.random.split(key)
        rt = RecurrentType(width, None)
        return {"norm1": norm.initialize(None, rt),
                "mixer": mixer.initialize(km, rt),
                "norm2": norm.initialize(None, rt),
                "moe": moe.initialize(ke, rt)}

    def init_state(self, input_type):
        return self._parts()[1].init_state(input_type)

    def upgrade_state(self, saved):
        return self._parts()[1].upgrade_state(saved)

    def _apply(self, params, state, x, ctx: LayerContext):
        mixer, moe, norm = self._parts()
        h, _ = norm.apply(params["norm1"], {}, x, ctx)
        m, _ = mixer.apply(params["mixer"], {}, h, ctx)
        x = x + m
        h, _ = norm.apply(params["norm2"], {}, x, ctx)
        f, new_state = moe.apply(params["moe"], state, h, ctx)
        return x + f, new_state

    def apply(self, params, state, x, ctx: LayerContext):
        if self.recompute and ctx.train:
            return jax.checkpoint(
                lambda p, s, a: self._apply(p, s, a, ctx))(params, state, x)
        return self._apply(params, state, x, ctx)


@register_serializable
@dataclasses.dataclass(frozen=True)
class CausalLMOutputLayer(FeedForwardLayer):
    """Final RMSNorm, an untied head over the (possibly sliced)
    vocabulary, and the next-token cross-entropy in float32.

    ``apply`` returns the logits (N, T, n_out), float32. ``compute_loss``
    takes integer labels (N, T): ``labels[n, t]`` is the id that follows
    position t, and a label below zero (``IGNORE_LABEL``: a row's last
    position) is left out of the mean. A mask (N,) or (N, T), as the
    feeder attaches, weights rows or positions."""
    eps: float = 1e-6
    init_std: float = 0.02
    has_bias: bool = False

    named_scopes = ("lm.head_loss",)

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        dt = self.param_dtype()
        return {"norm": {"w": jnp.zeros((n_in,), dt)},
                "W": self.init_std * jax.random.normal(
                    key, (n_in, self.n_out), dt)}

    def _logits(self, params, x):
        h = rms_norm(x, params["norm"]["w"], self.eps)
        return jnp.einsum(
            "nth,hv->ntv", h, params["W"].astype(h.dtype),
            preferred_element_type=jnp.promote_types(jnp.float32, h.dtype))

    def apply(self, params, state, x, ctx):
        return self._logits(params, x), state

    def compute_loss(self, params, state, x, labels, ctx):
        with jax.named_scope("lm.head_loss"):
            logits = self._logits(params, x)
            labels = labels.astype(jnp.int32)
            if labels.ndim == 3 and labels.shape[-1] == 1:
                labels = labels[..., 0]
            weight = (labels >= 0).astype(logits.dtype)
            if ctx.mask is not None:
                m = ctx.mask.astype(logits.dtype)
                weight = weight * (m[:, None] if m.ndim == 1 else m)
            picked = jnp.take_along_axis(
                logits, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
            per = jax.nn.logsumexp(logits, -1) - picked
            return jnp.sum(per * weight) / jnp.maximum(jnp.sum(weight), 1.0)


def next_token_labels(ids):
    """Labels for ``CausalLMOutputLayer`` from token ids (N, T), on the
    host: the ids shifted left by one, ``IGNORE_LABEL`` in the last
    column."""
    import numpy as np
    ids = np.asarray(ids)
    labels = np.full(ids.shape, IGNORE_LABEL, np.int32)
    labels[:, :-1] = ids[:, 1:]
    return labels
