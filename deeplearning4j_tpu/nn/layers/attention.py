"""Attention and transformer layers.

The reference has no attention layers (SURVEY §2.11: model/tensor/
sequence parallelism and attention are ABSENT — "the TPU build must
design these fresh", §7.2 stage 7). These are the framework-native
building blocks for the BERT-class import target (BASELINE config 3) and
for the long-context path: the same multi-head attention math runs
single-chip here and sequence-parallel via parallel/ring_attention.py.

TPU-first choices:
- one packed QKV projection (a single MXU matmul) instead of three;
- softmax in float32 regardless of compute dtype (bf16-safe);
- masks are (N, T) sequence masks as everywhere else in the framework;
- no data-dependent shapes: padding stays in the sequence, masked out.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import InputType, RecurrentType
from deeplearning4j_tpu.nn.layers.base import (
    FeedForwardLayer,
    Layer,
    LayerContext,
)
from deeplearning4j_tpu.nn.layers.normalization import LayerNormalization
from deeplearning4j_tpu.ops.activations import Activation
from deeplearning4j_tpu.ops.initializers import WeightInit
from deeplearning4j_tpu.ops.visibility import (
    BlockDiffusion,
    Causal,
    Visibility,
)
from deeplearning4j_tpu.utils.serde import register_serializable


def scaled_dot_product_attention(q, k, v, mask=None,
                                 visibility: Visibility = Visibility()):
    """Plain attention on (N, T, H, Dh) tensors; softmax in f32.

    ``mask``: (N, T_k) key validity mask. The single-chip reference path
    that parallel/ring_attention.py must match exactly. ``visibility``
    (``ops/visibility.py``: ``Causal()``, ``Causal(window)``,
    ``BlockDiffusion(T, B)``) says which keys a query sees; the whole
    (T_q, T_k) map is materialised here whatever it hides.

    Internal score order is (N, Tq, Tk, H) — HEAD TRAILING — so both
    contractions keep (h, dh) as the packed-QKV tensor's trailing dims
    and XLA never relayouts the projection output (the (n,h,q,k) order
    cost ~0.23 ms of transpose copies per layer per direction at the
    BERT profile shape; measured 5.87 → 5.33 ms/layer fwd+bwd,
    bitwise-equal outputs)."""
    dh = q.shape[-1]
    # at least f32 for the softmax; f64 inputs stay f64 (gradient checks)
    sdt = jnp.promote_types(jnp.float32, q.dtype)
    s = jnp.einsum("nqhd,nkhd->nqkh", q, k).astype(sdt)
    s = s / jnp.sqrt(jnp.asarray(dh, sdt))
    # large-FINITE mask value: -inf rows make softmax's VJP emit NaN even
    # when the forward output is where-guarded (NaN * 0 cotangent), so a
    # fully-padded sequence would poison the whole batch's gradients
    neg = jnp.asarray(jnp.finfo(sdt).min / 2, sdt)
    valid = None
    if visibility != Visibility():      # something is hidden
        tq, tk = s.shape[1], s.shape[2]
        seen = visibility.visible(jnp.arange(tq)[:, None, None],
                                  jnp.arange(tk)[None, :, None])
        s = jnp.where(seen[None], s, neg)
    if mask is not None:
        valid = mask[:, None, :, None].astype(bool)
        s = jnp.where(valid, s, neg)
    p = jax.nn.softmax(s, axis=2)
    if valid is not None:
        # fully-masked rows: uniform softmax garbage → exact zeros
        p = jnp.where(valid.any(axis=2, keepdims=True), p, 0.0)
    return jnp.einsum("nqkh,nkhd->nqhd", p.astype(v.dtype), v)


@register_serializable
@dataclasses.dataclass(frozen=True)
class SelfAttentionLayer(FeedForwardLayer):
    """Multi-head self-attention over (N, T, F) with residual-free output
    projection: y = Attn(xWq, xWk, xWv)Wo. n_out = model width."""
    n_heads: int = 4
    causal: bool = False
    # queries/keys/values all from the input (self-attention)

    def __post_init__(self):
        if self.n_out and self.n_out % self.n_heads != 0:
            raise ValueError(
                f"n_out={self.n_out} not divisible by n_heads={self.n_heads}")

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        kq, ko = jax.random.split(key)
        dt = self.param_dtype()
        params = {
            # packed QKV: one matmul on the MXU. Column order is HEAD-major
            # ((head, which, dh)), so a contiguous column shard of Wqkv is a
            # set of whole heads — Megatron-style tensor parallelism
            # (parallel/tensor_parallel.py) then shards heads with plain
            # GSPMD dim tiling, no strided resharding.
            "Wqkv": self.weight_init.init(kq, (n_in, 3 * self.n_out),
                                          n_in, self.n_out, dt),
            "Wo": self.weight_init.init(ko, (self.n_out, self.n_out),
                                        self.n_out, self.n_out, dt),
        }
        if self.has_bias:
            params["bqkv"] = jnp.zeros((3 * self.n_out,), dt)
            params["bo"] = jnp.zeros((self.n_out,), dt)
        return params

    def _qkv(self, params, x):
        qkv = jnp.einsum("ntf,fe->nte", x, params["Wqkv"])
        if self.has_bias:
            qkv = qkv + params["bqkv"]
        n, t, _ = qkv.shape
        h, dh = self.n_heads, self.n_out // self.n_heads
        qkv = qkv.reshape(n, t, h, 3, dh)
        return qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]

    def apply(self, params, state, x, ctx: LayerContext):
        ctx, dk = ctx.split_rng()
        x = self.maybe_dropout(x, ctx, dk)
        q, k, v = self._qkv(params, x)
        # helper-SPI dispatch: Pallas flash kernel on TPU, plain XLA
        # lowering elsewhere (ops/pallas_kernels.py)
        from deeplearning4j_tpu.ops.pallas_kernels import attention as _attn
        o = _attn(q, k, v, mask=ctx.mask,
                  visibility=Causal() if self.causal else Visibility())
        n, t = o.shape[0], o.shape[1]
        y = o.reshape(n, t, self.n_out)
        y = jnp.einsum("nte,eo->nto", y, params["Wo"])
        if self.has_bias:
            y = y + params["bo"]
        if ctx.mask is not None:
            y = y * ctx.mask[:, :, None].astype(y.dtype)
        return self.activation.apply(y), state


@register_serializable
@dataclasses.dataclass(frozen=True)
class LearnedPositionalEmbedding(Layer):
    """Adds a learned position embedding to (N, T, F) inputs (BERT-style).
    ``max_len`` bounds the trainable table; sequences must be ≤ max_len."""
    max_len: int = 512
    weight_init: WeightInit = WeightInit.XAVIER

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def initialize(self, key, input_type):
        f = input_type.shape()[-1]
        dt = self.param_dtype()
        if self.weight_init == WeightInit.XAVIER:
            # BERT-style truncated-scale init for position tables
            return {"P": 0.02 * jax.random.normal(key, (self.max_len, f),
                                                  dt)}
        return {"P": self.weight_init.init(key, (self.max_len, f),
                                           self.max_len, f, dt)}

    def apply(self, params, state, x, ctx):
        t = x.shape[1]
        return x + params["P"][:t].astype(x.dtype), state


@register_serializable
@dataclasses.dataclass(frozen=True)
class TransformerEncoderBlock(FeedForwardLayer):
    """Pre-LN transformer block: x + MHA(LN(x)); x + FFN(LN(x)).
    The composition unit for BERT-class models. ``n_out`` is the model
    width (must equal the input width — residuals), ``ffn_mult`` the MLP
    expansion."""
    n_heads: int = 4
    ffn_mult: int = 4
    causal: bool = False
    ffn_activation: Activation = Activation.GELU
    attn_dropout: float = 0.0

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    def _parts(self):
        width = self.n_out
        attn = SelfAttentionLayer(
            n_in=width, n_out=width, n_heads=self.n_heads,
            causal=self.causal, weight_init=self.weight_init,
            dropout=self.attn_dropout, dtype=self.dtype,
            has_bias=self.has_bias)
        ln1 = LayerNormalization(dtype=self.dtype)
        ln2 = LayerNormalization(dtype=self.dtype)
        return attn, ln1, ln2

    def initialize(self, key, input_type):
        width = self.resolved_n_in(input_type)
        if self.n_out and width != self.n_out:
            raise ValueError(
                f"TransformerEncoderBlock needs n_in == n_out "
                f"(residuals); got {width} vs {self.n_out}")
        attn, ln1, ln2 = self._parts()
        ka, k1, k2, kf1, kf2 = jax.random.split(key, 5)
        rt = RecurrentType(width, None)
        dt = self.param_dtype()
        hidden = self.ffn_mult * width
        params = {
            "attn": attn.initialize(ka, rt),
            "ln1": ln1.initialize(k1, rt),
            "ln2": ln2.initialize(k2, rt),
            "W1": self.weight_init.init(kf1, (width, hidden), width,
                                        hidden, dt),
            "W2": self.weight_init.init(kf2, (hidden, width), hidden,
                                        width, dt),
        }
        if self.has_bias:
            params["b1"] = jnp.zeros((hidden,), dt)
            params["b2"] = jnp.zeros((width,), dt)
        return params

    def apply(self, params, state, x, ctx: LayerContext):
        ctx, dk = ctx.split_rng()
        x = self.maybe_dropout(x, ctx, dk)
        attn, ln1, ln2 = self._parts()
        h, _ = ln1.apply(params["ln1"], {}, x, ctx)
        a, _ = attn.apply(params["attn"], {}, h, ctx)
        x = x + a
        h, _ = ln2.apply(params["ln2"], {}, x, ctx)
        f = jnp.einsum("ntf,fh->nth", h, params["W1"])
        if self.has_bias:
            f = f + params["b1"]
        f = self.ffn_activation.apply(f)
        f = jnp.einsum("nth,hf->ntf", f, params["W2"])
        if self.has_bias:
            f = f + params["b2"]
        y = x + f
        if ctx.mask is not None:
            y = y * ctx.mask[:, :, None].astype(y.dtype)
        return y, state


def rotary_embedding(x, positions, rotary_dim: int, theta: float):
    """Rotate-half rotary position embedding on the first ``rotary_dim``
    of the head's dimensions of ``x`` (N, T, H, Dh); the rest pass. The
    pair (i, i + rotary_dim/2) turns by ``positions * theta^(-2i /
    rotary_dim)``; angles in float32."""
    half = rotary_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                * 2.0 / rotary_dim))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]                 # (1, T, 1, half)
    sin = jnp.sin(angle)[None, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rotary_dim].astype(jnp.float32)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([turned.astype(x.dtype), x[..., rotary_dim:]], -1)


@register_serializable
@dataclasses.dataclass(frozen=True)
class GatedAttention(FeedForwardLayer):
    """Grouped-query softmax attention, bias-free; the per-head q/k
    RMSNorm, the sigmoid output gate, the share of the head that is
    rotated and which keys a query sees are fields. With ``qk_norm`` (the
    default, and what a configuration saved before the field was there
    loads as) ``q <- RMSNorm(q)``, ``k <- RMSNorm(k)`` over the head
    (zero-centred weights); without, neither norm nor their weights.
    Rotary on the first ``partial_rotary_factor`` of the head (1: the
    whole head; 0: no positional encoding at all, as the Nemotron-H
    family's attention); ``n_heads / n_kv_heads`` query heads share a
    key/value head. With ``output_gate`` (the default: Qwen3-Next's gated
    attention, and what a configuration saved before the field was there
    loads as) ``W_q`` gives each query head a query and a gate of
    ``head_dim`` each and the layer is ``W_o(attn * sigmoid(gate))``;
    without, ``W_q`` holds the queries alone and the layer is
    ``W_o(attn)``.

    ``block_length`` 0 is causal attention at positions ``0 .. T - 1``;
    a ``window`` narrows it to a query's own position and the ``window -
    1`` before it (``Causal(window)``: the flash kernels visit only the key
    blocks the window reaches). ``block_length`` B > 0 is a masked
    diffusion over blocks of B, trained: the input is ``[noisy | clean]``,
    two copies of a sequence of ``T / 2`` positions, slot ``s`` at rotary
    position ``s mod T / 2``, under ``ops.visibility.BlockDiffusion(T / 2,
    B)``.

    The head size is its own field, not ``n_out / n_heads``. ``n_out`` is
    the model width. Attention itself goes through
    ``ops.pallas_kernels.attention`` (the flash kernel from 1,024
    positions on a TPU, plain XLA else) with the key/value heads repeated
    for it, under the named scope ``attn.gated``, with blocks
    ``attn.block_diffusion``, with a window ``attn.window``, and
    ``attn.causal`` where it is causal and has neither gate nor q/k
    norm."""
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    eps: float = 1e-6
    init_std: float = 0.02
    output_gate: bool = True
    block_length: int = 0
    qk_norm: bool = True
    window: Optional[int] = None

    named_scopes = ("attn.gated", "attn.block_diffusion", "attn.causal",
                    "attn.window")

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads={self.n_heads} is not a multiple of "
                f"n_kv_heads={self.n_kv_heads}")
        if self.block_length < 0:
            raise ValueError(f"block_length={self.block_length}: 0 "
                             "(causal) or a block's length")
        if self.window is not None and (self.window < 1
                                        or self.block_length):
            raise ValueError(f"window={self.window}: a causal window of "
                             "one position or more, without block_length")

    @property
    def scope(self) -> str:
        if self.block_length:
            return "attn.block_diffusion"
        if self.window is not None:
            return "attn.window"
        return ("attn.gated" if self.output_gate or self.qk_norm
                else "attn.causal")

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        h, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        dt = self.param_dtype()
        kq, kk, kv, ko = jax.random.split(key, 4)

        def normal(k, shape):
            return self.init_std * jax.random.normal(k, shape, dt)

        params = {
            # per head: [query | gate], or the query alone
            "W_q": normal(kq, (n_in, h * (2 if self.output_gate else 1)
                               * dh)),
            "W_k": normal(kk, (n_in, hk * dh)),
            "W_v": normal(kv, (n_in, hk * dh)),
            "W_o": normal(ko, (h * dh, self.n_out)),
        }
        if self.qk_norm:
            params.update(q_norm=jnp.zeros((dh,), dt),
                          k_norm=jnp.zeros((dh,), dt))
        return params

    def apply(self, params, state, x, ctx: LayerContext):
        from deeplearning4j_tpu.nn.layers.normalization import rms_norm
        from deeplearning4j_tpu.ops.pallas_kernels import attention as _attn
        n, t, _ = x.shape
        h, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        if self.block_length and t % 2:
            raise ValueError(
                f"block_length={self.block_length} takes [noisy | clean], "
                f"an even number of positions; got {t}")
        with jax.named_scope(self.scope):
            q = jnp.einsum("ntf,fe->nte", x, params["W_q"])
            if self.output_gate:
                qg = q.reshape(n, t, h, 2 * dh)
                q, gate = qg[..., :dh], qg[..., dh:]
            else:
                q = q.reshape(n, t, h, dh)
            k = jnp.einsum("ntf,fe->nte", x, params["W_k"]).reshape(
                n, t, hk, dh)
            v = jnp.einsum("ntf,fe->nte", x, params["W_v"]).reshape(
                n, t, hk, dh)
            if self.qk_norm:
                q = rms_norm(q, params["q_norm"], self.eps)
                k = rms_norm(k, params["k_norm"], self.eps)
            rot = int(dh * self.partial_rotary_factor)
            pos = jnp.arange(t)
            vis = Causal(self.window)
            if self.block_length:
                pos = pos % (t // 2)
                vis = BlockDiffusion(t // 2, self.block_length)
            if rot:
                q = rotary_embedding(q, pos, rot, self.rope_theta)
                k = rotary_embedding(k, pos, rot, self.rope_theta)
            rep = h // hk
            if rep > 1:
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            o = _attn(q, k, v, mask=ctx.mask, visibility=vis,
                      scope=self.scope)
            if self.output_gate:
                o = o * jax.nn.sigmoid(
                    gate.astype(jnp.float32)).astype(o.dtype)
            y = jnp.einsum("nte,eo->nto", o.reshape(n, t, h * dh),
                           params["W_o"])
        return y, state


@register_serializable
@dataclasses.dataclass(frozen=True)
class LatentAttention(FeedForwardLayer):
    """Causal multi-head latent attention (MLA; DeepSeek-V2,
    arXiv:2405.04434, section 2.1), bias-free, rotary on a part of the
    head. With ``dn`` = ``qk_nope_head_dim``, ``dr`` = ``qk_rope_head_dim``
    and ``dv`` = ``v_head_dim``, at positions ``0 .. T - 1``:

        c_q = RMSNorm_q(x W_qa)                        (q_lora_rank)
        [q_nope | q_rope] = c_q W_qb                   (each head dn + dr)
        [c_kv | k_r] = x W_kva                         (kv_lora_rank + dr)
        c_kv <- RMSNorm_kv(c_kv)
        [k_nope | v] = c_kv W_kvb                      (each head dn + dv)
        q = [q_nope | RoPE(q_rope)],  k = [k_nope | RoPE(k_r)]
        o = softmax(q k^T / sqrt(dn + dr), causal) v
        y = concat_h(o) W_o

    The one rotary key ``k_r`` of ``dr`` is computed once a token and
    shared by every head. Rotary is rotate-half (``rotary_embedding``) on
    the ``dr`` dimensions at ``rope_theta``; both latent norms are
    zero-centred RMSNorms at ``eps``. ``W_qb`` holds each head's ``[nope |
    rope]`` side by side and ``W_kvb`` each head's ``[k_nope | v]``.

    Attention itself goes through ``ops.pallas_kernels.attention`` (the
    flash kernel from 1,024 positions on a TPU, plain XLA else) with
    queries and keys of ``dn + dr`` and values of ``dv``, and everything
    the layer computes runs under the named scope ``attn.latent``.
    Training only: no cache of the latents. ``n_out`` is the model
    width."""
    n_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    eps: float = 1e-5
    init_std: float = 0.02

    named_scopes = ("attn.latent",)

    def __post_init__(self):
        if min(self.q_lora_rank, self.kv_lora_rank, self.v_head_dim,
               self.qk_nope_head_dim + self.qk_rope_head_dim) < 1 \
                or self.qk_rope_head_dim % 2:
            raise ValueError(
                "LatentAttention needs latents and heads of one dimension "
                f"or more and an even rotary part; got q_lora_rank="
                f"{self.q_lora_rank}, kv_lora_rank={self.kv_lora_rank}, "
                f"qk_rope_head_dim={self.qk_rope_head_dim}")

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        h, dn, dr, dv = (self.n_heads, self.qk_nope_head_dim,
                         self.qk_rope_head_dim, self.v_head_dim)
        dt = self.param_dtype()
        ks = jax.random.split(key, 5)

        def normal(k, shape):
            return self.init_std * jax.random.normal(k, shape, dt)

        return {
            "W_qa": normal(ks[0], (n_in, self.q_lora_rank)),
            "q_norm": jnp.zeros((self.q_lora_rank,), dt),
            "W_qb": normal(ks[1], (self.q_lora_rank, h * (dn + dr))),
            # columns [c_kv | k_r]
            "W_kva": normal(ks[2], (n_in, self.kv_lora_rank + dr)),
            "kv_norm": jnp.zeros((self.kv_lora_rank,), dt),
            "W_kvb": normal(ks[3], (self.kv_lora_rank, h * (dn + dv))),
            "W_o": normal(ks[4], (h * dv, self.n_out)),
        }

    def apply(self, params, state, x, ctx: LayerContext):
        from deeplearning4j_tpu.nn.layers.normalization import rms_norm
        from deeplearning4j_tpu.ops.pallas_kernels import attention as _attn
        n, t, _ = x.shape
        h, dn, dr, dv = (self.n_heads, self.qk_nope_head_dim,
                         self.qk_rope_head_dim, self.v_head_dim)
        r = self.kv_lora_rank
        pos = jnp.arange(t)
        with jax.named_scope("attn.latent"):
            c_q = rms_norm(jnp.einsum("ntf,fe->nte", x, params["W_qa"]),
                           params["q_norm"], self.eps)
            q = jnp.einsum("ntc,ce->nte", c_q, params["W_qb"]).reshape(
                n, t, h, dn + dr)
            kv = jnp.einsum("ntf,fe->nte", x, params["W_kva"])
            c_kv = rms_norm(kv[..., :r], params["kv_norm"], self.eps)
            k_r = rotary_embedding(kv[..., r:].reshape(n, t, 1, dr), pos, dr,
                                   self.rope_theta)
            kvb = jnp.einsum("ntc,ce->nte", c_kv, params["W_kvb"]).reshape(
                n, t, h, dn + dv)
            q = jnp.concatenate([q[..., :dn], rotary_embedding(
                q[..., dn:], pos, dr, self.rope_theta)], -1)
            k = jnp.concatenate(
                [kvb[..., :dn], jnp.broadcast_to(k_r, (n, t, h, dr))], -1)
            o = _attn(q, k, kvb[..., dn:], mask=ctx.mask, visibility=Causal(),
                      scope="attn.latent")
            y = jnp.einsum("nte,eo->nto", o.reshape(n, t, h * dv),
                           params["W_o"])
        return y, state


def differential_lambda_init(layer_index: int) -> float:
    """``0.8 - 0.6 exp(-0.3 l)`` for the layer of depth ``l`` in the whole
    model (arXiv:2410.05258, section 3.1)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer_index)


@register_serializable
@dataclasses.dataclass(frozen=True)
class DifferentialAttention(FeedForwardLayer):
    """Causal differential attention with grouped keys and values: two
    softmax maps a pair of adjacent heads, their difference read out.

    ``q = h W_q + b`` (``n_heads`` of ``head_dim``), ``k``, ``v``
    (``n_kv_heads``). Adjacent heads pair: ``q1, q2 = q[2i], q[2i+1]``,
    ``k1, k2 = k[2j], k[2j+1]``, ``v = [v[2j] | v[2j+1]]`` (twice the head
    size); query pair ``i`` reads key/value pair ``i // (n_heads /
    n_kv_heads)``. ``A1 = softmax(q1 k1^T / sqrt(head_dim))``, ``A2``
    likewise; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``
    with ``lambda_init = differential_lambda_init(layer_index)``; ``o =
    RMSNorm(A1 v - lambda A2 v) * w * (1 - lambda_init)`` over the pair's
    ``2 * head_dim``; ``concat(o) W_o + b_o``. Softmax, lambda and the
    norm's statistics are float32.

    ``window`` narrows the causal mask to a query's own position and the
    ``window - 1`` before it. With ``cross`` the layer has no key or value
    projection: ``mix`` is handed the ``k`` and ``v`` (N, T, ``n_kv_heads
    * head_dim``) another layer projected, as they are. Both maps run as
    one call of ``ops.pallas_kernels.attention`` over ``n_heads`` heads
    (the ``q1`` pairs, then the ``q2`` pairs) with a value of twice the
    head size: the flash kernel from 1,024 positions on a TPU, plain XLA
    else. ``n_out`` is the model width."""
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    window: Optional[int] = None
    cross: bool = False
    layer_index: int = 0
    eps: float = 1e-5
    init_std: float = 0.02

    named_scopes = ("attn.window", "attn.full", "attn.cross")

    def __post_init__(self):
        if self.n_heads % 2 or self.n_kv_heads % 2 \
                or self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads={self.n_heads} and n_kv_heads={self.n_kv_heads}: "
                "both even (heads pair) and the first a multiple of the "
                "second")

    @property
    def scope(self) -> str:
        if self.cross:
            return "attn.cross"
        return "attn.full" if self.window is None else "attn.window"

    @property
    def extra_inputs(self):
        return ("k", "v") if self.cross else ()

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        h, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        dt = self.param_dtype()
        kq, ko, kl = jax.random.split(key, 3)
        cols = h * dh if self.cross else (h + 2 * hk) * dh
        lam = 0.1 * jax.random.normal(kl, (4, dh), dt)   # the paper's
        return {
            # columns [q | k | v], each head-major (cross: q alone)
            "W_qkv": self.init_std * jax.random.normal(kq, (n_in, cols), dt),
            "b_qkv": jnp.zeros((cols,), dt),
            "W_o": self.init_std * jax.random.normal(
                ko, (h * dh, self.n_out), dt),
            "b_o": jnp.zeros((self.n_out,), dt),
            "lambda_q1": lam[0], "lambda_k1": lam[1],
            "lambda_q2": lam[2], "lambda_k2": lam[3],
            "subln": jnp.ones((2 * dh,), dt),
        }

    def mix(self, params, x, *kv, mask=None):
        """``(mixed (N, T, n_out), (k, v))``: ``k`` and ``v`` (N, T,
        ``n_kv_heads * head_dim``) as projected here or, ``cross``, as
        handed in after ``x``."""
        from deeplearning4j_tpu.nn.layers.normalization import rms_norm
        from deeplearning4j_tpu.ops.pallas_kernels import attention as _attn
        n, t, _ = x.shape
        h, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        f32 = jnp.promote_types(jnp.float32, x.dtype)
        with jax.named_scope(self.scope):
            qkv = jnp.einsum("ntf,fe->nte", x, params["W_qkv"]) \
                + params["b_qkv"]
            if self.cross:
                q, (k, v) = qkv, kv
            else:
                q = qkv[..., :h * dh]
                k = qkv[..., h * dh:(h + hk) * dh]
                v = qkv[..., (h + hk) * dh:]
            q = q.reshape(n, t, h // 2, 2, dh)
            kp = k.reshape(n, t, hk // 2, 2, dh)
            vp = v.reshape(n, t, hk // 2, 2 * dh)
            rep = h // hk
            if rep > 1:
                kp = jnp.repeat(kp, rep, axis=2)
                vp = jnp.repeat(vp, rep, axis=2)
            # one call: the q1 maps as heads 0 .. h/2 - 1, the q2 maps after
            o = _attn(jnp.concatenate([q[:, :, :, 0], q[:, :, :, 1]], 2),
                      jnp.concatenate([kp[:, :, :, 0], kp[:, :, :, 1]], 2),
                      jnp.concatenate([vp, vp], 2),
                      mask=mask, visibility=Causal(self.window),
                      scope=self.scope)
            lam0 = differential_lambda_init(self.layer_index)
            lq1, lk1, lq2, lk2 = (params[name].astype(f32) for name in (
                "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
            lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
                   + lam0)
            o = (o[:, :, :h // 2].astype(f32)
                 - lam * o[:, :, h // 2:].astype(f32))
            o = rms_norm(o, params["subln"], self.eps, zero_centered=False)
            o = (o * (1.0 - lam0)).astype(x.dtype)
            y = jnp.einsum("nte,eo->nto", o.reshape(n, t, h * dh),
                           params["W_o"]) + params["b_o"]
        return y, (k, v)

    def apply(self, params, state, x, ctx: LayerContext):
        x = x if self.cross else (x,)
        return self.mix(params, *x, mask=ctx.mask)[0], state
