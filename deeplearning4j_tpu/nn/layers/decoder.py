"""Decoder-only language-model layers: token embedding, the hybrid
decoder blocks, and the head with its losses (next-token; masked
diffusion over blocks).

The block is the unit of the mixture-of-experts decoder families
(Qwen3-Next's hybrid of linear and softmax attention; SDAR's block
diffusion over a Qwen3-MoE decoder): pre-norm residuals round a token
mixer and an expert layer,

    h = x + Mixer(RMSNorm(x)),   y = h + MoE(RMSNorm(h))

where the mixer is a ``GatedDeltaNet`` (linear attention, a matrix state
per head) or a ``GatedAttention`` (softmax attention: causal with an
output gate, or ungated over ``[noisy | clean]`` under the
block-diffusion visibility) and the expert layer is a ``HeldExpertsMoE``.
A model is a ``MultiLayerNetwork`` of ``TokenEmbedding``, blocks,
``CausalLMOutputLayer``: ordinary serialisable layers, trained by
``fit()``.

``StateSpaceHybridBlock`` is the unit of the decoder-hybrid-decoder
family (Phi-4-mini-flash; arXiv:2507.06607): pre-LayerNorm residuals round
a token mixer and a dense gated MLP,

    h = x + Mixer(LayerNorm(x)),   y = h + MLP(LayerNorm(h))

with no positional encoding anywhere. The mixer is a ``MambaMixer``, a
``DifferentialAttention`` (causal, over a window or the whole sequence), a
``GatedMemoryUnit`` on the memory an earlier Mamba block emitted, or a
differential cross-attention on the keys and values an earlier attention
block projected. What one block emits and another reads are edges of a
``ComputationGraph`` (``extra_output_types`` / ``extra_inputs``): a model
is a graph of ``TokenEmbedding``, blocks and ``CausalLMOutputLayer``, in
which the head may read the embedding's table (``tied``) in place of a
matrix of its own.

``SingleMixerBlock`` is the unit of the Nemotron-H family
(arXiv:2504.03624; Nemotron 3 Nano): **one** pre-norm residual a layer,

    y = x + Mixer(RMSNorm(x))

where the mixer is a ``Mamba2Mixer``, a causal ``GatedAttention`` with
neither gate, q/k norm nor positional encoding, or the expert layer
itself (``HeldExpertsMoE`` with plain relu^2 experts under a sigmoid
router with a score-correction bias, the shared expert added ungated). A
model is a ``MultiLayerNetwork`` of ``TokenEmbedding``, blocks in the
order of the family's pattern string, ``CausalLMOutputLayer``. It shares
``_ResidualBlock`` with ``HybridDecoderBlock``: the output type, the
recomputation and the hand-off of the expert layer's state.

``SandwichDecoderBlock`` is the unit of the AFMoE family (Arcee's
Trinity): gated grouped-query attention and a feed-forward branch, each
between two RMSNorms of its own,

    h = x + N2(Attn(N1(x))),   y = h + N4(FFN(N3(h)))

where the attention is windowed with rotary positions or full without
any (``GatedAttention``'s ``window`` and rotary share), and the
feed-forward branch a dense gated MLP (``gated_mlp``, the leading layers)
or ``HeldExpertsMoE`` (gated experts under a sigmoid router, a shared
expert added ungated). It shares ``_ResidualBlock`` with the other two.

``LatentDecoderBlock`` is the unit of the GLM-4.7-Flash family
(``glm4_moe_lite``, DeepSeek-V3's layout): pre-norm residuals round
multi-head latent attention and a feed-forward branch,

    h = x + MLA(RMSNorm(x)),   y = h + FFN(RMSNorm(h))

with the same two feed-forward branches as ``SandwichDecoderBlock``. Its
subclass ``MultiTokenPredictionBlock`` is the family's
multi-token-prediction module: one more such block on the main model's
normed final state and the next token's embedding, whose output the
main model's own head (``MultiTokenLMOutputLayer``) scores against the
token after that. A model is a ``ComputationGraph``: the embedding's
output reaches the module, and the module's output the head, as edges.

``recompute`` wraps a block's ``apply`` in ``jax.checkpoint`` while
training, so the step ``fit()`` builds keeps each block's input for the
backward pass and recomputes the block's internals there, but for what a
Pallas kernel's forward writes where the mixer took that kernel
(``_recomputed``, by the names the kernels tag it with): only a second
launch of the forward kernel could give it back. A row and layer keeps,
at the cells' shapes:

- the flash-attention kernel: the attention's output, ``T x H x Dv`` at
  the compute type, and its logsumexp, ``T x H`` float32 (16,384
  positions and 32 heads of 128 in bfloat16: 134.2 + 2.1 MB, for 18.6 ms
  of kernel);
- the gated delta rule: its result ``o``, ``T x Hv x Dv`` float32, the
  state at every chunk border, ``T / C x Hv x Dk x Dv`` float32, and the
  inverses ``Tm``, ``T x Hv x 128`` at the compute type (8,192 positions,
  32 value heads of 128, chunks of 64, bfloat16: 134.2 + 268.4 + 67.1 =
  469.8 MB);
- the selective scan: its ``y``, ``T x D`` float32, and the border
  states, ``T / 64 x S x D`` float32 (8,192 positions, 5,120 channels, 16
  states: 167.8 + 41.9 = 209.7 MB);
- the Mamba-2 scan: its normed rows ``o``, ``T x H P`` at the compute
  type, and the border states, ``T / 128 x H P x S`` float32 (8,192
  positions, 64 heads of 64, 128 states, bfloat16: 67.1 + 134.2 = 201.3
  MB).

That is two to fourteen times the block's input. The plain forms (the XLA
attention path at short sequences, the plain scans at heads or states the
kernels do not take, both on the CPU) name nothing and keep nothing more. A model at its memory limit has
``recompute`` and the batch to give, as before.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import InputType, RecurrentType
from deeplearning4j_tpu.nn.layers.attention import (
    DifferentialAttention,
    GatedAttention,
    LatentAttention,
)
from deeplearning4j_tpu.nn.layers.base import (
    FeedForwardLayer,
    Layer,
    LayerContext,
)
from deeplearning4j_tpu.nn.layers.feedforward import HeldExpertsMoE
from deeplearning4j_tpu.nn.layers.linear_attention import GatedDeltaNet
from deeplearning4j_tpu.nn.layers.normalization import (
    LayerNormalization,
    RMSNorm,
    rms_norm,
)
from deeplearning4j_tpu.nn.layers.state_space import (
    SSD_CHUNK,
    GatedMemoryUnit,
    Mamba2Mixer,
    MambaMixer,
)
from deeplearning4j_tpu.utils.serde import register_serializable

GATED_DELTANET = "gated_deltanet"
GATED_ATTENTION = "gated_attention"
BLOCK_DIFFUSION_ATTENTION = "block_diffusion_attention"
MAMBA2 = "mamba2"
CAUSAL_ATTENTION = "causal_attention"
EXPERTS = "experts"
DENSE = "dense"
MAMBA = "mamba"
ATTENTION = "attention"
GATED_MEMORY = "gated_memory"
CROSS_ATTENTION = "cross_attention"

# labels below zero are positions without a next token (a row's last)
IGNORE_LABEL = -1


def _kept_names() -> Tuple[str, ...]:
    """The ``checkpoint_name`` tags a recomputing block keeps beside its
    arguments: what the flash-attention kernel and the three scan kernels
    write in their forward pass (module docstring). A name matches only
    where a mixer traced that kernel."""
    from deeplearning4j_tpu.ops import (
        pallas_delta_rule as gdn, pallas_kernels as flash,
        pallas_selective_scan as ssm, pallas_ssd_scan as ssd)
    return (flash.FLASH_OUT_NAME, flash.FLASH_LSE_NAME,
            gdn.DELTA_RULE_OUT_NAME, gdn.DELTA_RULE_STATES_NAME,
            gdn.DELTA_RULE_TM_NAME, ssm.SCAN_Y_NAME, ssm.SCAN_STATES_NAME,
            ssd.SSD_OUT_NAME, ssd.SSD_BORDERS_NAME)


def _recomputed(apply):
    """``apply`` under the blocks' ``jax.checkpoint``: its arguments are
    kept for the backward pass and, where a mixer ran a Pallas kernel, what
    that kernel's forward wrote (``_kept_names``); everything else is
    computed again there."""
    return jax.checkpoint(
        apply, policy=jax.checkpoint_policies.save_only_these_names(
            *_kept_names()))


@register_serializable
@dataclasses.dataclass(frozen=True)
class TokenEmbedding(Layer):
    """Integer token ids (N, T) -> (N, T, n_out); the table starts
    normal(0, ``init_std``). With ``emit_table`` the layer also emits the
    table itself (``table``, (vocab_size, n_out)) for a head that is tied
    to it: one leaf of the parameter tree with two uses, whose gradients
    add."""
    vocab_size: int = 0
    n_out: int = 0
    init_std: float = 0.02
    emit_table: bool = False

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    def extra_output_types(self, input_type):
        return ({"table": InputType.feed_forward(self.n_out)}
                if self.emit_table else {})

    def initialize(self, key, input_type):
        return {"W": self.init_std * jax.random.normal(
            key, (self.vocab_size, self.n_out), self.param_dtype())}

    def apply(self, params, state, x, ctx):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        y = self._scaled(jnp.take(params["W"], idx, axis=0))
        return ((y, params["W"]) if self.emit_table else y), state

    def _scaled(self, rows):
        return rows


@register_serializable
@dataclasses.dataclass(frozen=True)
class ScaledTokenEmbedding(TokenEmbedding):
    """``TokenEmbedding`` whose rows are multiplied by ``sqrt(n_out)`` on
    the way out (the AFMoE family's muP); the table and its initialisation
    are the same. A class of its own, so that the text a saved
    ``TokenEmbedding`` writes stays what it was."""

    def _scaled(self, rows):
        return rows * jnp.asarray(self.n_out ** 0.5, rows.dtype)


def gated_mlp_params(k_gate_up, k_down, width, hidden, init_std, dtype):
    """A dense gated MLP's two matrices, normal(0, ``init_std``): ``W1``
    (width, 2 hidden) with columns ``[gate | up]``, ``W2`` (hidden,
    width)."""
    return {"W1": init_std * jax.random.normal(
                k_gate_up, (width, 2 * hidden), dtype),
            "W2": init_std * jax.random.normal(k_down, (hidden, width),
                                               dtype)}


def gated_mlp(params, h):
    """``W2(silu(g) * u)`` with ``[g | u] = h W1`` (SwiGLU; the gate and
    the product in float32, the result in ``h``'s type), under the named
    scope ``mlp.glu``: the dense MLP of ``StateSpaceHybridBlock`` and of
    ``SandwichDecoderBlock``'s dense layers."""
    hidden = params["W2"].shape[0]
    with jax.named_scope("mlp.glu"):
        f32 = jnp.promote_types(jnp.float32, h.dtype)
        gu = jnp.einsum("ntf,fe->nte", h, params["W1"])
        g, u = gu[..., :hidden], gu[..., hidden:]
        act = (u.astype(f32) * jax.nn.silu(g.astype(f32))).astype(h.dtype)
        return jnp.einsum("nte,ef->ntf", act, params["W2"])


_MOE_SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.shared",
               "moe.combine")


def _sigmoid_experts(block, **common) -> HeldExpertsMoE:
    """The expert layer of ``SandwichDecoderBlock`` and
    ``LatentDecoderBlock``, from the block's fields: gated experts under a
    sigmoid router whose bias the load moves by ``bias_update_rate`` a
    step, the chosen scores renormalised and times ``routed_scale``, the
    shared expert added ungated."""
    return HeldExpertsMoE(
        num_experts=block.num_experts, held_experts=block.held_experts,
        hidden=block.expert_hidden, shared_hidden=block.shared_hidden,
        top_k=block.top_k, aux_loss_coef=block.router_aux_loss_coef,
        expert_form="gated", router_scoring="sigmoid",
        routed_scale=block.routed_scale, shared_gate=False,
        bias_update_rate=block.bias_update_rate, **common)


class _ResidualBlock:
    """What the decoder blocks of a ``MultiLayerNetwork`` share: the
    output's type, ``apply`` (``_apply`` under ``_recomputed`` while
    training with ``recompute``), and the block's state, which is its
    expert layer's (``_expert_layer``; None: the block has no state)."""

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    def _expert_layer(self) -> Optional[HeldExpertsMoE]:
        raise NotImplementedError

    def _check_width(self, input_type) -> int:
        width = self.resolved_n_in(input_type)
        if self.n_out and width != self.n_out:
            raise ValueError(
                f"{type(self).__name__} needs n_in == n_out (residuals); "
                f"got {width} vs {self.n_out}")
        return width

    def init_state(self, input_type):
        moe = self._expert_layer()
        return {} if moe is None else moe.init_state(input_type)

    def upgrade_state(self, saved):
        moe = self._expert_layer()
        return saved if moe is None else moe.upgrade_state(saved)

    def apply(self, params, state, x, ctx: LayerContext):
        if self.recompute and ctx.train:
            return _recomputed(
                lambda p, s, a: self._apply(p, s, a, ctx))(params, state, x)
        return self._apply(params, state, x, ctx)


@register_serializable
@dataclasses.dataclass(frozen=True)
class HybridDecoderBlock(_ResidualBlock, FeedForwardLayer):
    """One decoder block (module docstring). ``mixer`` picks the token
    mixer; the fields after it are the parts' own (``GatedAttention``,
    ``GatedDeltaNet``, ``HeldExpertsMoE``), kept flat so that the block
    serialises as one layer. ``n_out`` is the model width and equals the
    input's.

    ``recompute``: while training, keep the block's input for the backward
    pass and compute its internals again there; an attention mixer on the
    flash kernel keeps that kernel's output and logsumexp besides (``T x H
    x head_dim`` at the compute type and ``T x H`` float32 a row), and a
    DeltaNet mixer on the delta rule's kernels that rule's result, border
    states and inverses (module docstring), so either forward kernel runs
    once. Off: no ``jax.checkpoint``."""
    mixer: str = GATED_DELTANET
    # gated attention, and block-diffusion attention (no output gate;
    # ``block_length`` is its alone)
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    block_length: int = 4
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
    router_aux_loss_coef: float = 0.0
    eps: float = 1e-6
    init_std: float = 0.02
    recompute: bool = False

    # the ``jax.named_scope`` names this block's parts put into a step
    named_scopes = (("gdn.proj", "gdn.conv", "gdn.scan", "gdn.out")
                    + GatedAttention.named_scopes + _MOE_SCOPES)

    def __post_init__(self):
        kinds = (GATED_DELTANET, GATED_ATTENTION, BLOCK_DIFFUSION_ATTENTION)
        if self.mixer not in kinds:
            raise ValueError(
                f"mixer={self.mixer!r}: one of "
                + ", ".join(repr(k) for k in kinds))

    def _parts(self):
        w = self.n_out
        common = dict(n_in=w, n_out=w, dtype=self.dtype,
                      init_std=self.init_std)
        if self.mixer != GATED_DELTANET:
            diffusion = self.mixer == BLOCK_DIFFUSION_ATTENTION
            mixer = GatedAttention(
                n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                head_dim=self.head_dim, eps=self.eps,
                partial_rotary_factor=self.partial_rotary_factor,
                rope_theta=self.rope_theta, output_gate=not diffusion,
                block_length=self.block_length if diffusion else 0,
                **common)
        else:
            mixer = GatedDeltaNet(
                name=self.name, n_key_heads=self.n_key_heads,
                n_value_heads=self.n_value_heads,
                key_head_dim=self.key_head_dim,
                value_head_dim=self.value_head_dim,
                conv_kernel=self.conv_kernel, chunk_size=self.chunk_size,
                eps=self.eps, **common)
        moe = HeldExpertsMoE(
            num_experts=self.num_experts, held_experts=self.held_experts,
            hidden=self.expert_hidden, shared_hidden=self.shared_hidden,
            top_k=self.top_k, norm_topk=self.norm_topk,
            aux_loss_coef=self.router_aux_loss_coef, **common)
        return mixer, moe, RMSNorm(eps=self.eps, dtype=self.dtype)

    def _expert_layer(self):
        return self._parts()[1]

    def initialize(self, key, input_type):
        width = self._check_width(input_type)
        mixer, moe, norm = self._parts()
        km, ke = jax.random.split(key)
        rt = RecurrentType(width, None)
        return {"norm1": norm.initialize(None, rt),
                "mixer": mixer.initialize(km, rt),
                "norm2": norm.initialize(None, rt),
                "moe": moe.initialize(ke, rt)}

    def _apply(self, params, state, x, ctx: LayerContext):
        mixer, moe, norm = self._parts()
        h, _ = norm.apply(params["norm1"], {}, x, ctx)
        m, _ = mixer.apply(params["mixer"], {}, h, ctx)
        x = x + m
        h, _ = norm.apply(params["norm2"], {}, x, ctx)
        f, new_state = moe.apply(params["moe"], state, h, ctx)
        return x + f, new_state


@register_serializable
@dataclasses.dataclass(frozen=True)
class SingleMixerBlock(_ResidualBlock, FeedForwardLayer):
    """One layer of the Nemotron-H family (module docstring): a norm, one
    mixer, a residual. ``mixer`` picks the mixer; the fields after it are
    the parts' own (``Mamba2Mixer``, ``GatedAttention``,
    ``HeldExpertsMoE``), kept flat so that the block serialises as one
    layer. The attention has no gate, no q/k norm and no positional
    encoding; the experts are plain (``relu2``) under a sigmoid router
    whose bias the load moves by ``bias_update_rate`` a step, their
    weights times ``routed_scale``, the shared expert added ungated.
    ``n_out`` is the model width and equals the input's. An ``experts``
    block's state is its expert layer's; the others have none.

    ``recompute``: as ``HybridDecoderBlock``'s."""
    mixer: str = MAMBA2
    # causal attention
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    # mamba-2
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    d_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = SSD_CHUNK
    dt_min: float = 1e-3
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    # experts
    num_experts: int = 8
    held_experts: Tuple[int, ...] = ()
    expert_hidden: int = 0
    shared_hidden: int = 0
    top_k: int = 2
    norm_topk: bool = True
    routed_scale: float = 1.0
    bias_update_rate: float = 0.0
    router_aux_loss_coef: float = 0.0
    eps: float = 1e-5
    init_std: float = 0.02
    recompute: bool = False

    named_scopes = (Mamba2Mixer.named_scopes + GatedAttention.named_scopes
                    + _MOE_SCOPES)

    def __post_init__(self):
        if self.mixer not in (MAMBA2, CAUSAL_ATTENTION, EXPERTS):
            raise ValueError(
                f"mixer={self.mixer!r}: one of {MAMBA2!r}, "
                f"{CAUSAL_ATTENTION!r}, {EXPERTS!r}")

    def _mixer(self):
        common = dict(n_in=self.n_out, n_out=self.n_out, dtype=self.dtype,
                      init_std=self.init_std)
        if self.mixer == MAMBA2:
            return Mamba2Mixer(
                name=self.name, n_heads=self.mamba_heads,
                head_dim=self.mamba_head_dim, n_groups=self.n_groups,
                d_state=self.d_state, d_conv=self.conv_kernel,
                chunk_size=self.chunk_size, dt_min=self.dt_min,
                dt_max=self.dt_max, dt_floor=self.dt_floor, eps=self.eps,
                **common)
        if self.mixer == CAUSAL_ATTENTION:
            return GatedAttention(
                n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                head_dim=self.head_dim, eps=self.eps,
                partial_rotary_factor=0.0, output_gate=False,
                qk_norm=False, **common)
        return HeldExpertsMoE(
            num_experts=self.num_experts, held_experts=self.held_experts,
            hidden=self.expert_hidden, shared_hidden=self.shared_hidden,
            top_k=self.top_k, norm_topk=self.norm_topk,
            aux_loss_coef=self.router_aux_loss_coef, expert_form="relu2",
            router_scoring="sigmoid", routed_scale=self.routed_scale,
            shared_gate=False, bias_update_rate=self.bias_update_rate,
            **common)

    def _expert_layer(self):
        return self._mixer() if self.mixer == EXPERTS else None

    def initialize(self, key, input_type):
        rt = RecurrentType(self._check_width(input_type), None)
        return {"norm": RMSNorm(eps=self.eps, dtype=self.dtype).initialize(
                    None, rt),
                "mixer": self._mixer().initialize(key, rt)}

    def _apply(self, params, state, x, ctx: LayerContext):
        h, _ = RMSNorm(eps=self.eps, dtype=self.dtype).apply(
            params["norm"], {}, x, ctx)
        m, new_state = self._mixer().apply(params["mixer"], state, h, ctx)
        return x + m, new_state


@register_serializable
@dataclasses.dataclass(frozen=True)
class SandwichDecoderBlock(_ResidualBlock, FeedForwardLayer):
    """One layer of the AFMoE family (module docstring): gated grouped-query
    attention and a feed-forward branch, each between two RMSNorms of its
    own (the sandwich). The attention is ``GatedAttention`` with q/k norm
    and output gate; with a ``window`` it is a sliding layer's, rotary on
    the whole head, without one a full layer's, with no positional
    encoding. ``ffn`` picks the feed-forward branch,
    ``dense`` (``gated_mlp`` of ``mlp_hidden``) or ``experts``
    (``HeldExpertsMoE``: gated experts under a sigmoid router whose bias
    the load moves by ``bias_update_rate`` a step, weights times
    ``routed_scale``, the shared expert added ungated). The four norms run
    under the named scope ``block.norm``. ``n_out`` is the model width and
    equals the input's; an ``experts`` block's state is its expert
    layer's, a ``dense`` one has none.

    ``recompute``: as ``HybridDecoderBlock``'s."""
    ffn: str = DENSE
    # gated attention
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    window: Optional[int] = None
    rope_theta: float = 1e4
    # dense gated MLP
    mlp_hidden: int = 0
    # experts
    num_experts: int = 8
    held_experts: Tuple[int, ...] = ()
    expert_hidden: int = 0
    shared_hidden: int = 0
    top_k: int = 2
    routed_scale: float = 1.0
    bias_update_rate: float = 0.0
    router_aux_loss_coef: float = 0.0
    eps: float = 1e-5
    init_std: float = 0.02
    recompute: bool = False

    named_scopes = (GatedAttention.named_scopes + _MOE_SCOPES
                    + ("mlp.glu", "block.norm"))

    def __post_init__(self):
        if self.ffn not in (DENSE, EXPERTS):
            raise ValueError(f"ffn={self.ffn!r}: {DENSE!r} or {EXPERTS!r}")

    def _parts(self):
        common = dict(n_in=self.n_out, n_out=self.n_out, dtype=self.dtype,
                      init_std=self.init_std)
        mixer = GatedAttention(
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, eps=self.eps,
            partial_rotary_factor=0.0 if self.window is None else 1.0,
            rope_theta=self.rope_theta, window=self.window, **common)
        if self.ffn == DENSE:
            return mixer, None
        return mixer, _sigmoid_experts(self, **common)

    def _expert_layer(self):
        return self._parts()[1]

    def initialize(self, key, input_type):
        width = self._check_width(input_type)
        mixer, moe = self._parts()
        km, k1, k2 = jax.random.split(key, 3)
        rt = RecurrentType(width, None)
        norm = RMSNorm(eps=self.eps, dtype=self.dtype)
        params = {f"norm{i}": norm.initialize(None, rt)
                  for i in (1, 2, 3, 4)}
        params["mixer"] = mixer.initialize(km, rt)
        if moe is None:
            params["mlp"] = gated_mlp_params(k1, k2, width, self.mlp_hidden,
                                             self.init_std,
                                             self.param_dtype())
        else:
            params["moe"] = moe.initialize(k1, rt)
        return params

    def _apply(self, params, state, x, ctx: LayerContext):
        mixer, moe = self._parts()

        def norm(i, a):
            with jax.named_scope("block.norm"):
                return rms_norm(a, params[f"norm{i}"]["w"], self.eps)

        a, _ = mixer.apply(params["mixer"], {}, norm(1, x), ctx)
        x = x + norm(2, a)
        u = norm(3, x)
        if moe is None:
            f = gated_mlp(params["mlp"], u)
        else:
            f, state = moe.apply(params["moe"], state, u, ctx)
        return x + norm(4, f), state


@register_serializable
@dataclasses.dataclass(frozen=True)
class LatentDecoderBlock(_ResidualBlock, FeedForwardLayer):
    """One layer of the GLM-4.7-Flash family (module docstring): ``h = x +
    MLA(N1(x))``, ``y = h + FFN(N2(h))``. The attention is
    ``LatentAttention`` (its fields, kept flat here); ``ffn`` picks the
    feed-forward branch as ``SandwichDecoderBlock``'s does, ``dense``
    (``gated_mlp`` of ``mlp_hidden``) or ``experts`` (gated experts under a
    sigmoid router, the shared expert added ungated). The two norms run
    under the named scope ``block.norm``. ``n_out`` is the model width and
    equals the input's; an ``experts`` block's state is its expert
    layer's, a ``dense`` one has none.

    ``recompute``: as ``HybridDecoderBlock``'s; what the block keeps a row
    beside its input is the flash kernel's ``T x heads x v_head_dim`` at
    the compute type and ``T x heads`` float32."""
    ffn: str = DENSE
    # latent attention
    n_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    # dense gated MLP
    mlp_hidden: int = 0
    # experts
    num_experts: int = 8
    held_experts: Tuple[int, ...] = ()
    expert_hidden: int = 0
    shared_hidden: int = 0
    top_k: int = 2
    routed_scale: float = 1.0
    bias_update_rate: float = 0.0
    router_aux_loss_coef: float = 0.0
    eps: float = 1e-5
    init_std: float = 0.02
    recompute: bool = False

    named_scopes = (LatentAttention.named_scopes + _MOE_SCOPES
                    + ("mlp.glu", "block.norm"))

    def __post_init__(self):
        if self.ffn not in (DENSE, EXPERTS):
            raise ValueError(f"ffn={self.ffn!r}: {DENSE!r} or {EXPERTS!r}")

    def _parts(self):
        common = dict(n_in=self.n_out, n_out=self.n_out, dtype=self.dtype,
                      init_std=self.init_std)
        mixer = LatentAttention(
            n_heads=self.n_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            eps=self.eps, **common)
        if self.ffn == DENSE:
            return mixer, None
        return mixer, _sigmoid_experts(self, **common)

    def _expert_layer(self):
        return self._parts()[1]

    def _norm(self, params, name, a):
        with jax.named_scope("block.norm"):
            return rms_norm(a, params[name]["w"], self.eps)

    def _block_params(self, key, width):
        mixer, moe = self._parts()
        km, k1, k2 = jax.random.split(key, 3)
        rt = RecurrentType(width, None)
        norm = RMSNorm(eps=self.eps, dtype=self.dtype)
        params = {"norm1": norm.initialize(None, rt),
                  "mixer": mixer.initialize(km, rt),
                  "norm2": norm.initialize(None, rt)}
        if moe is None:
            params["mlp"] = gated_mlp_params(k1, k2, width, self.mlp_hidden,
                                             self.init_std,
                                             self.param_dtype())
        else:
            params["moe"] = moe.initialize(k1, rt)
        return params

    def initialize(self, key, input_type):
        return self._block_params(key, self._check_width(input_type))

    def _block(self, params, state, x, ctx: LayerContext):
        mixer, moe = self._parts()
        a, _ = mixer.apply(params["mixer"], {},
                           self._norm(params, "norm1", x), ctx)
        x = x + a
        u = self._norm(params, "norm2", x)
        if moe is None:
            f = gated_mlp(params["mlp"], u)
        else:
            f, state = moe.apply(params["moe"], state, u, ctx)
        return x + f, state

    def _apply(self, params, state, x, ctx: LayerContext):
        return self._block(params, state, x, ctx)


@register_serializable
@dataclasses.dataclass(frozen=True)
class MultiTokenPredictionBlock(LatentDecoderBlock):
    """The multi-token-prediction module of the GLM-4.7-Flash family
    (``num_nextn_predict_layers`` 1; DeepSeek-V3, arXiv:2412.19437 section
    2.2). From the main model's final state after its final norm, ``h``
    (N, T, n_out), and the extra input ``embedded``, the embedding layer's
    output for the same tokens:

        e'_i = Emb(t_{i+1})        (``embedded`` shifted left by one,
                                    zeros in the last slot)
        u = [RMSNorm_e(e') ; RMSNorm_h(h)] W_eh        (W_eh: 2 n_out x n_out)
        out = RMSNorm(Block(u))

    ``Block`` is the ``LatentDecoderBlock`` of this class's fields; ``out``
    goes to the main model's head (``MultiTokenLMOutputLayer``'s input
    ``mtp``), which scores position i against ``t_{i+2}``. Everything runs
    under the named scope ``mtp``. State and ``recompute``: the block's."""

    named_scopes = LatentDecoderBlock.named_scopes + ("mtp",)

    @property
    def extra_inputs(self):
        return ("embedded",)

    def initialize(self, key, input_type):
        width = self._check_width(input_type)
        kb, ke = jax.random.split(key)
        rt = RecurrentType(width, None)
        norm = RMSNorm(eps=self.eps, dtype=self.dtype)
        return {"enorm": norm.initialize(None, rt),
                "hnorm": norm.initialize(None, rt),
                "W_eh": self.init_std * jax.random.normal(
                    ke, (2 * width, width), self.param_dtype()),
                **self._block_params(kb, width),
                "head_norm": norm.initialize(None, rt)}

    def _apply(self, params, state, x, ctx: LayerContext):
        h, embedded = x
        with jax.named_scope("mtp"):
            later = jnp.concatenate(
                [embedded[:, 1:], jnp.zeros_like(embedded[:, :1])], 1)
            u = jnp.concatenate([self._norm(params, "enorm", later),
                                 self._norm(params, "hnorm", h)], -1)
            u = jnp.einsum("ntf,fe->nte", u, params["W_eh"])
            g, state = self._block(params, state, u, ctx)
            return self._norm(params, "head_norm", g), state


@register_serializable
@dataclasses.dataclass(frozen=True)
class StateSpaceHybridBlock(FeedForwardLayer):
    """One block of the decoder-hybrid-decoder family (module docstring).
    ``mixer`` picks the token mixer; the fields after it are the parts'
    own (``MambaMixer``, ``DifferentialAttention``, ``GatedMemoryUnit``),
    kept flat so that the block serialises as one layer. ``layer_index``
    is the block's depth in the whole published model (differential
    attention's ``lambda_init`` reads it), whatever part of that model is
    built. ``n_out`` is the model width and equals the input's.

    With ``emit`` a ``mamba`` block also emits ``memory`` (its scan's
    result before the gate) and an ``attention`` block ``k`` and ``v`` (its
    projected keys and values); a ``gated_memory`` block takes ``memory``
    and a ``cross_attention`` block ``k`` and ``v`` as further inputs.

    ``recompute``: as ``HybridDecoderBlock``'s; what an attention block
    keeps a row is ``T x H x 2 head_dim`` (a differential map's value head
    is twice the query's) at the compute type and ``T x H`` float32."""
    mixer: str = MAMBA
    emit: bool = False
    layer_index: int = 0
    # differential attention
    n_heads: int = 40
    n_kv_heads: int = 20
    head_dim: int = 64
    window: Optional[int] = None
    # mamba and the gated memory unit
    d_inner: int = 0
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0
    # gated MLP
    mlp_hidden: int = 0
    eps: float = 1e-5
    init_std: float = 0.02
    recompute: bool = False

    named_scopes = (MambaMixer.named_scopes
                    + DifferentialAttention.named_scopes
                    + GatedMemoryUnit.named_scopes + ("mlp.glu",))

    def __post_init__(self):
        if self.mixer not in (MAMBA, ATTENTION, GATED_MEMORY,
                              CROSS_ATTENTION):
            raise ValueError(
                f"mixer={self.mixer!r}: one of {MAMBA!r}, {ATTENTION!r}, "
                f"{GATED_MEMORY!r}, {CROSS_ATTENTION!r}")
        if self.emit and self.mixer not in (MAMBA, ATTENTION):
            raise ValueError(f"a {self.mixer!r} block emits nothing")

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    @property
    def extra_inputs(self):
        return {GATED_MEMORY: ("memory",),
                CROSS_ATTENTION: ("k", "v")}.get(self.mixer, ())

    def extra_output_types(self, input_type):
        if not self.emit:
            return {}
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        if self.mixer == MAMBA:
            return {"memory": RecurrentType(self.d_inner, t)}
        kv = RecurrentType(self.n_kv_heads * self.head_dim, t)
        return {"k": kv, "v": kv}

    def _mixer(self):
        common = dict(n_in=self.n_out, n_out=self.n_out, dtype=self.dtype,
                      init_std=self.init_std)
        if self.mixer == MAMBA:
            return MambaMixer(
                name=self.name, d_inner=self.d_inner, d_state=self.d_state,
                d_conv=self.d_conv, dt_rank=self.dt_rank, **common)
        if self.mixer == GATED_MEMORY:
            return GatedMemoryUnit(d_memory=self.d_inner, **common)
        return DifferentialAttention(
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, window=self.window,
            cross=self.mixer == CROSS_ATTENTION,
            layer_index=self.layer_index, eps=self.eps, **common)

    def initialize(self, key, input_type):
        width = self.resolved_n_in(input_type)
        if self.n_out and width != self.n_out:
            raise ValueError(
                f"StateSpaceHybridBlock needs n_in == n_out (residuals); "
                f"got {width} vs {self.n_out}")
        norm = LayerNormalization(eps=self.eps, dtype=self.dtype)
        km, k1, k2 = jax.random.split(key, 3)
        rt = RecurrentType(width, None)
        return {"norm1": norm.initialize(None, rt),
                "mixer": self._mixer().initialize(km, rt),
                "norm2": norm.initialize(None, rt),
                "mlp": gated_mlp_params(k1, k2, width, self.mlp_hidden,
                                        self.init_std, self.param_dtype())}

    def _apply(self, params, x, ctx: LayerContext):
        x, *extras = x if isinstance(x, tuple) else (x,)
        norm = LayerNormalization(eps=self.eps, dtype=self.dtype)
        h, _ = norm.apply(params["norm1"], {}, x, ctx)
        m, emitted = self._mixer().mix(params["mixer"], h, *extras,
                                       mask=ctx.mask)
        x = x + m
        h, _ = norm.apply(params["norm2"], {}, x, ctx)
        y = x + gated_mlp(params["mlp"], h)
        return (y,) + tuple(emitted) if self.emit else y

    def apply(self, params, state, x, ctx: LayerContext):
        if self.recompute and ctx.train:
            return _recomputed(
                lambda p, a: self._apply(p, a, ctx))(params, x), state
        return self._apply(params, x, ctx), state


@register_serializable
@dataclasses.dataclass(frozen=True)
class CausalLMOutputLayer(FeedForwardLayer):
    """A final norm, a head over the (possibly sliced) vocabulary, and a
    cross-entropy in float32: next-token, or masked diffusion's, by the
    layout of the labels.

    ``norm`` is ``"rms"`` (zero-centred RMSNorm) or ``"layer"`` (LayerNorm
    with weight and bias). The head is a matrix of its own or, with
    ``tied``, the embedding's table: the layer then takes ``table``
    (n_out, n_in) as a further input (``TokenEmbedding.emit_table``) and
    holds the norm alone.

    ``apply`` returns the logits (N, T, n_out), float32. Which loss
    ``compute_loss`` computes, the labels' layout says:

    - integer labels (N, T) or (N, T, 1): **next-token**. ``labels[n, t]``
      is the id that follows position t, and a label below zero
      (``IGNORE_LABEL``: a row's last position) is left out of the mean,
      which is over the positions that count. A mask (N,) or (N, T), as
      the feeder attaches, weights rows or positions.
    - float labels (N, T, 2) (``datasets.diffusion.BlockDiffusionNoiser``
      makes them): **masked diffusion**, for an input of ``2 T``
      positions ``[noisy | clean]``. ``labels[n, t, 0]`` is the id that
      position t of the noisy half hides (``IGNORE_LABEL`` where it hides
      none) and ``labels[n, t, 1]`` the weight of its loss, ``1 / t_n``
      for the row's noise level (0 where nothing is hidden). Norm and
      head run over the first T positions only, the position's own
      logits predict its own token (no shift), and the weighted sum is
      divided by the positions, ``N T`` (under a mask: those it keeps),
      not by the weights: ``1 / (N T) sum_n sum_{t masked} (1 / t_n)
      (logsumexp(logits[n, t]) - logits[n, t, x0[n, t]])``."""
    eps: float = 1e-6
    init_std: float = 0.02
    has_bias: bool = False
    norm: str = "rms"
    tied: bool = False

    named_scopes = ("lm.head_loss",)

    def __post_init__(self):
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"norm={self.norm!r}: 'rms' or 'layer'")

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    @property
    def extra_inputs(self):
        return ("table",) if self.tied else ()

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        dt = self.param_dtype()
        if self.norm == "layer":
            params = {"norm": LayerNormalization(
                eps=self.eps, dtype=self.dtype).initialize(
                    None, RecurrentType(n_in, None))}
        else:
            params = {"norm": {"w": jnp.zeros((n_in,), dt)}}
        if not self.tied:
            params["W"] = self.init_std * jax.random.normal(
                key, (n_in, self.n_out), dt)
        return params

    def _logits(self, params, x):
        x, *table = x if isinstance(x, tuple) else (x,)
        if self.norm == "layer":
            h, _ = LayerNormalization(eps=self.eps).apply(
                params["norm"], {}, x, None)
        else:
            h = rms_norm(x, params["norm"]["w"], self.eps)
        acc = jnp.promote_types(jnp.float32, h.dtype)
        if self.tied:
            return jnp.einsum("nth,vh->ntv", h, table[0].astype(h.dtype),
                              preferred_element_type=acc)
        return jnp.einsum("nth,hv->ntv", h, params["W"].astype(h.dtype),
                          preferred_element_type=acc)

    def apply(self, params, state, x, ctx):
        return self._logits(params, x), state

    def compute_loss(self, params, state, x, labels, ctx):
        if labels.ndim == 3 and labels.shape[-1] == 2:
            return self._diffusion_loss(params, x, labels, ctx)
        with jax.named_scope("lm.head_loss"):
            return _next_token_loss(self._logits(params, x),
                                    _id_labels(labels), ctx.mask)

    def _diffusion_loss(self, params, x, labels, ctx):
        """The masked-diffusion loss of the class docstring."""
        with jax.named_scope("lm.head_loss"):
            x, *table = x if isinstance(x, tuple) else (x,)
            t = labels.shape[1]
            if x.shape[1] != 2 * t:
                raise ValueError(
                    f"labels (N, {t}, 2) go with 2 * {t} positions [noisy "
                    f"| clean]; the head was handed {x.shape[1]}")
            logits = self._logits(params, (x[:, :t], *table))
            ids = labels[..., 0].astype(jnp.int32)
            weight = jnp.where(ids >= 0, labels[..., 1], 0.0).astype(
                logits.dtype)
            kept = jnp.ones(ids.shape, logits.dtype)
            if ctx.mask is not None:
                m = ctx.mask.astype(logits.dtype)
                kept = kept * (m[:, None] if m.ndim == 1 else m)
            per = _token_losses(logits, ids)
            return jnp.sum(per * weight * kept) / jnp.maximum(
                jnp.sum(kept), 1.0)


@register_serializable
@dataclasses.dataclass(frozen=True)
class MultiTokenLMOutputLayer(FeedForwardLayer):
    """The head shared by the next-token loss and a multi-token-prediction
    module's (``MultiTokenPredictionBlock``), as a ``ComputationGraph``'s
    output: the input ``h`` and the further input ``mtp`` (both (N, T,
    n_in), normed already by the layers that give them) go through the
    one matrix ``W``,

        L = CE(h W, labels) + mtp_weight CE(g W, labels')

    with next-token ``labels`` (``CausalLMOutputLayer``'s, mask and all)
    and ``labels'[:, t] = labels[:, t + 1]``: the token after the next,
    the last two positions counting for nothing. The embedding and ``W``
    take gradients from both terms. ``apply`` returns the next-token
    logits alone (N, T, n_out), float32.

    ``compute_loss`` returns the loss and the layer's new state, whose
    ``lm_loss_terms`` (float32[2], no gradient) holds the step's two
    means, next-token then multi-token, before the weight: what the fit
    loop publishes beside the routing counters. The second term's head and
    loss run under the named scope ``mtp``."""
    init_std: float = 0.02
    has_bias: bool = False
    mtp_weight: float = 0.3

    named_scopes = ("lm.head_loss", "mtp")

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    @property
    def extra_inputs(self):
        return ("mtp",)

    def initialize(self, key, input_type):
        return {"W": self.init_std * jax.random.normal(
            key, (self.resolved_n_in(input_type), self.n_out),
            self.param_dtype())}

    def init_state(self, input_type):
        return {"lm_loss_terms": jnp.zeros((2,), jnp.float32)}

    def _logits(self, params, h):
        return jnp.einsum("nth,hv->ntv", h, params["W"].astype(h.dtype),
                          preferred_element_type=jnp.promote_types(
                              jnp.float32, h.dtype))

    def _loss(self, params, h, labels, mask):
        with jax.named_scope("lm.head_loss"):
            return _next_token_loss(self._logits(params, h), labels, mask)

    def apply(self, params, state, x, ctx):
        return self._logits(params, x[0]), state

    def compute_loss(self, params, state, x, labels, ctx):
        h, g = x
        labels = _id_labels(labels)
        if labels.ndim != 2:
            raise ValueError("a multi-token head takes next-token labels "
                             f"(N, T); got {labels.shape}")
        later = jnp.concatenate(
            [labels[:, 1:], jnp.full_like(labels[:, :1], IGNORE_LABEL)], 1)
        main = self._loss(params, h, labels, ctx.mask)
        with jax.named_scope("mtp"):
            mtp = self._loss(params, g, later, ctx.mask)
        terms = jax.lax.stop_gradient(
            jnp.stack([main, mtp]).astype(jnp.float32))
        return main + self.mtp_weight * mtp, {"lm_loss_terms": terms}


def _id_labels(labels):
    """Next-token labels (N, T) or (N, T, 1) as int32 (N, T)."""
    labels = labels.astype(jnp.int32)
    return labels[..., 0] if labels.ndim == 3 and labels.shape[-1] == 1 \
        else labels


def _next_token_loss(logits, labels, mask):
    """The mean next-token cross-entropy of ``CausalLMOutputLayer``'s
    docstring: ``labels`` int32 (N, T), those below zero left out, a mask
    (N,) or (N, T) weighting rows or positions."""
    weight = (labels >= 0).astype(logits.dtype)
    if mask is not None:
        m = mask.astype(logits.dtype)
        weight = weight * (m[:, None] if m.ndim == 1 else m)
    per = _token_losses(logits, labels)
    return jnp.sum(per * weight) / jnp.maximum(jnp.sum(weight), 1.0)


def _token_losses(logits, ids):
    """``logsumexp(logits) - logits[ids]`` position by position (ids
    below zero read id 0: their positions carry no weight)."""
    picked = jnp.take_along_axis(
        logits, jnp.maximum(ids, 0)[..., None], -1)[..., 0]
    return jax.nn.logsumexp(logits, -1) - picked


def next_token_labels(ids):
    """Labels for ``CausalLMOutputLayer`` from token ids (N, T), on the
    host: the ids shifted left by one, ``IGNORE_LABEL`` in the last
    column."""
    import numpy as np
    ids = np.asarray(ids)
    labels = np.full(ids.shape, IGNORE_LABEL, np.int32)
    labels[:, :-1] = ids[:, 1:]
    return labels
