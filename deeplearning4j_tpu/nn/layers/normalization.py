"""Normalization layers.

Analogs of the reference's ``BatchNormalization``
(deeplearning4j-nn/.../nn/layers/normalization/BatchNormalization.java:41,
cuDNN helper hook at :57) and ``LocalResponseNormalization``. Batch-norm
running statistics live in the layer **state** pytree (not params), updated
functionally during training — the analog of the reference's
``globalMean``/``globalVar`` params, but without in-place mutation so the
whole train step stays a pure jitted function.

Also includes LayerNorm — absent from the reference but required by the
transformer models this framework targets (BERT import path).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, LayerContext
from deeplearning4j_tpu.utils.serde import register_serializable


@register_serializable
@dataclasses.dataclass(frozen=True)
class BatchNormalization(Layer):
    """Normalizes over all axes except the last (feature/channel) axis —
    correct for both (N, F) dense and (N, H, W, C) NHWC conv activations."""
    decay: float = 0.9           # running-average momentum (reference: decay)
    eps: float = 1e-5
    gamma_init: float = 1.0
    beta_init: float = 0.0
    lock_gamma_beta: bool = False
    use_global_stats_in_train: bool = False  # reference: useLogStd/global flag

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def _nf(self, input_type: InputType) -> int:
        return input_type.shape()[-1]

    def initialize(self, key, input_type):
        nf = self._nf(input_type)
        dt = self.param_dtype()
        if self.lock_gamma_beta:
            return {}
        return {"gamma": jnp.full((nf,), self.gamma_init, dt),
                "beta": jnp.full((nf,), self.beta_init, dt)}

    def init_state(self, input_type):
        nf = self._nf(input_type)
        return {"mean": jnp.zeros((nf,), jnp.float32),
                "var": jnp.ones((nf,), jnp.float32)}

    def apply(self, params, state, x, ctx):
        axes = tuple(range(x.ndim - 1))
        # stats in (at least) float32; promotes to f64 under gradient checks
        sdt = jnp.promote_types(jnp.float32, x.dtype)
        if ctx.train and not self.use_global_stats_in_train:
            xf = x.astype(sdt)
            mean = jnp.mean(xf, axis=axes)
            var = jnp.var(xf, axis=axes)
            new_state = {
                "mean": (self.decay * state["mean"]
                         + (1 - self.decay) * mean).astype(jnp.float32),
                "var": (self.decay * state["var"]
                        + (1 - self.decay) * var).astype(jnp.float32),
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = jnp.asarray(1.0, sdt) / jnp.sqrt(var.astype(sdt) + self.eps)
        y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
        if not self.lock_gamma_beta:
            y = y * params["gamma"] + params["beta"]
        return y, new_state


@register_serializable
@dataclasses.dataclass(frozen=True)
class LocalResponseNormalization(Layer):
    """Cross-channel LRN (reference: LocalResponseNormalization; cuDNN helper
    CudnnLocalResponseNormalizationHelper). NHWC: normalize along last axis."""
    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    @property
    def has_params(self):
        return False

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def apply(self, params, state, x, ctx):
        half = self.n // 2
        sq = jnp.square(x)
        # Sum over a sliding window of channels via padding + cumulative trick.
        pad = [(0, 0)] * (x.ndim - 1) + [(half, half)]
        sq_pad = jnp.pad(sq, pad)
        windows = [sq_pad[..., i:i + x.shape[-1]] for i in range(self.n)]
        ssum = sum(windows)
        denom = jnp.power(self.k + self.alpha * ssum, self.beta)
        return x / denom, state


@register_serializable
@dataclasses.dataclass(frozen=True)
class LayerNormalization(Layer):
    """Per-example normalization over the feature axis (no reference analog;
    needed for transformer parity — BERT import, TextGen models)."""
    eps: float = 1e-5

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def initialize(self, key, input_type):
        nf = input_type.shape()[-1]
        dt = self.param_dtype()
        return {"gamma": jnp.ones((nf,), dt), "beta": jnp.zeros((nf,), dt)}

    def apply(self, params, state, x, ctx):
        xf = x.astype(jnp.promote_types(jnp.float32, x.dtype))
        # Single-pass moments: E[x²]−E[x]² puts both reductions directly
        # on xf, so XLA emits one multi-output fusion reading the
        # activation once.  jnp.var chains its reduction behind the mean,
        # which costs a second full read of xf (the 57 GB/s LayerNorm
        # fusions in the BERT step profile — PERF_ANALYSIS).  f32
        # accumulation keeps the cancellation benign for activations;
        # the max(·, 0) guards the roundoff-negative corner.
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.maximum(
            jnp.mean(xf * xf, axis=-1, keepdims=True) - mean * mean, 0.0)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        y = y.astype(x.dtype)
        return y * params["gamma"] + params["beta"], state


def rms_norm(x, weight, eps: float = 1e-6, zero_centered: bool = True):
    """``x * rsqrt(mean(x^2) + eps) * (1 + w)`` over the last axis
    (``* w`` when not ``zero_centered``), statistics in float32; the
    result has ``x``'s type."""
    xf = x.astype(jnp.promote_types(jnp.float32, x.dtype))
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    w = weight.astype(y.dtype)
    return (y * (1.0 + w if zero_centered else w)).astype(x.dtype)


@register_serializable
@dataclasses.dataclass(frozen=True)
class RMSNorm(Layer):
    """Root-mean-square normalization over the feature axis, no mean and
    no bias. ``zero_centered`` stores the scale as its distance from one
    (weight initialised to 0, applied as ``1 + w``), the form the
    Qwen3-Next family trains; otherwise the weight starts at 1."""
    eps: float = 1e-6
    zero_centered: bool = True

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def initialize(self, key, input_type):
        nf = input_type.shape()[-1]
        fill = 0.0 if self.zero_centered else 1.0
        return {"w": jnp.full((nf,), fill, self.param_dtype())}

    def apply(self, params, state, x, ctx):
        return rms_norm(x, params["w"], self.eps, self.zero_centered), state
