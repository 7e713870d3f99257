"""Fused ResNet bottleneck block layer.

One layer = the whole bottleneck residual unit
(1×1 conv → BN → ReLU → 3×3 conv → BN → ReLU → 1×1 conv → BN →
(+shortcut) → ReLU). Each conv returns its output together with the
per-channel sums its BatchNorm needs (ops/fused_conv.py
``conv_bn_stats_xla``: plain XLA convs, Gram-matrix statistics for the
expanding projections, so the 4f-channel activations are never re-read
for a statistics pass), and each BatchNorm's normalize+ReLU is applied
as a per-channel scale+shift on the consumer conv's input.

This is the block-granular analog of the reference's per-layer cuDNN
helper tier (CudnnConvolutionHelper.java:62, SURVEY §2.4): the zoo's
ResNet50 uses it when built with ``fused_blocks=True``; the math is
IDENTICAL to the unfused conv/BN/activation composition (equivalence
tested in tests/test_fused_conv.py / tests/test_fused_block.py).

Eval mode uses running stats — pure elementwise normalize that XLA
fuses fine — through the same convs with the running-stat scale/shift
on their inputs.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import ConvolutionalType, InputType
from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.ops.fused_conv import (
    conv_bn_stats_xla,
    stats_to_scale_shift,
)
from deeplearning4j_tpu.ops.initializers import WeightInit
from deeplearning4j_tpu.utils.serde import register_serializable


@register_serializable
@dataclasses.dataclass(frozen=True)
class FusedBottleneckBlock(Layer):
    """ResNet-v1 bottleneck: f→f→4f channels, stride on the first 1×1
    (and the projection shortcut when ``downsample``)."""
    filters: int = 64
    stride: int = 1
    downsample: bool = False
    eps: float = 1e-5
    decay: float = 0.9

    # ---- shape ----------------------------------------------------------
    def _out_hw(self, it: ConvolutionalType) -> Tuple[int, int]:
        return (-(-it.height // self.stride), -(-it.width // self.stride))

    def output_type(self, input_type: InputType) -> InputType:
        it = input_type
        h, w = self._out_hw(it)
        return ConvolutionalType(h, w, self.filters * 4)

    # ---- params / state -------------------------------------------------
    def _bns(self):
        names = ["bn1", "bn2", "bn3"]
        if self.downsample:
            names.append("bnds")
        return names

    def initialize(self, key, input_type):
        cin = input_type.channels
        f, f4 = self.filters, self.filters * 4
        dt = self.param_dtype()
        ks = jax.random.split(key, 4)
        he = WeightInit.HE_NORMAL
        params = {
            "W1": he.init(ks[0], (cin, f), cin, f, dt),
            "W2": he.init(ks[1], (3, 3, f, f), 9 * f, 9 * f, dt),
            "W3": he.init(ks[2], (f, f4), f, f4, dt),
        }
        if self.downsample:
            params["Wds"] = he.init(ks[3], (cin, f4), cin, f4, dt)
        widths = {"bn1": f, "bn2": f, "bn3": f4, "bnds": f4}
        for bn in self._bns():
            params[f"{bn}_gamma"] = jnp.ones((widths[bn],), dt)
            params[f"{bn}_beta"] = jnp.zeros((widths[bn],), dt)
        return params

    def init_state(self, input_type):
        f, f4 = self.filters, self.filters * 4
        widths = {"bn1": f, "bn2": f, "bn3": f4, "bnds": f4}
        st = {}
        for bn in self._bns():
            st[f"{bn}_mean"] = jnp.zeros((widths[bn],), jnp.float32)
            st[f"{bn}_var"] = jnp.ones((widths[bn],), jnp.float32)
        return st

    # ---- forward --------------------------------------------------------
    def apply(self, params, state, x, ctx):
        f32 = jnp.float32
        train = ctx.train
        new_state = dict(state)

        def bn_form(name, stats, count):
            """(scale, shift) for the normalize folded into the NEXT
            conv's input; updates running stats in train mode."""
            gamma = params[f"{name}_gamma"].astype(f32)
            beta = params[f"{name}_beta"].astype(f32)
            if train and stats is not None:
                inv, shift, mean, var = stats_to_scale_shift(
                    stats, count, gamma, beta, self.eps)
                new_state[f"{name}_mean"] = (
                    self.decay * state[f"{name}_mean"]
                    + (1 - self.decay) * mean).astype(f32)
                new_state[f"{name}_var"] = (
                    self.decay * state[f"{name}_var"]
                    + (1 - self.decay) * var).astype(f32)
                return inv, shift
            var = state[f"{name}_var"].astype(f32)
            mean = state[f"{name}_mean"].astype(f32)
            inv = gamma * jax.lax.rsqrt(var + self.eps)
            return inv, beta - mean * inv

        ones = jnp.ones((x.shape[-1],), f32)
        zeros = jnp.zeros((x.shape[-1],), f32)

        y1, st1 = conv_bn_stats_xla(x, params["W1"], ones, zeros,
                                    False, False, self.stride)
        m1 = y1.size // y1.shape[-1]
        s1, b1 = bn_form("bn1", st1, m1)

        y2, st2 = conv_bn_stats_xla(y1, params["W2"], s1, b1,
                                    True, True, 1)
        m2 = y2.size // y2.shape[-1]
        s2, b2 = bn_form("bn2", st2, m2)

        y3, st3 = conv_bn_stats_xla(y2, params["W3"], s2, b2,
                                    True, True, 1)
        m3 = y3.size // y3.shape[-1]
        s3, b3 = bn_form("bn3", st3, m3)

        # Tail normalize+add+ReLU, 4-D in the compute dtype: a reshape
        # to (M, C) would itself be a relayout.
        main = y3 * s3.astype(y3.dtype) + b3.astype(y3.dtype)
        if self.downsample:
            yds, stds = conv_bn_stats_xla(x, params["Wds"], ones, zeros,
                                          False, False, self.stride)
            sds, bds = bn_form("bnds", stds, yds.size // yds.shape[-1])
            shortcut = yds * sds.astype(y3.dtype) + bds.astype(y3.dtype)
        else:
            shortcut = x
        out = jnp.maximum(main + shortcut, 0.0).astype(x.dtype)
        return out, new_state
