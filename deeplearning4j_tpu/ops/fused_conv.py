"""Conv + batch-norm statistics for the fused ResNet bottleneck block.

``FusedBottleneckBlock`` (nn/layers/fused.py) runs each conv of a
bottleneck through ``conv_bn_stats_xla``: plain XLA ops that return the
conv output together with its per-channel ``(Σy, Σy²)``, with the
PRODUCER's BatchNorm normalize+ReLU applied to the input first (BN
normalize is a per-channel scale+shift once the statistics are known).
The surrounding jnp code derives mean/var from the sums
(``stats_to_scale_shift``), and autodiff carries the batch-statistics
gradient.

The ResNet-50 64×64 step is bound by HBM traffic, and the 4f-channel
outputs of the expanding 1×1 convs dominate what a BatchNorm statistics
pass reads. For those convs the statistics come from the input side (a
Gram matrix, see ``conv_bn_stats_xla``), so the wide activation is never
re-read for them.

Why plain XLA: hand-written Pallas conv+BN kernels lost to this path on
the chip every time they were measured (PERF.md §6, PR 29: 57.2 against
35.9 ms a step; PERF_ANALYSIS.md r4: custom calls pin a layout, and XLA
copies every activation into and out of it), and PR 29 removed them.

``_conv_reference`` is the tests' and the on-chip check's reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# The Gram statistics pay an M·cin² MXU contraction to avoid an
# M·cout·2-byte read of the conv output. The naive roofline (bf16 183 TF/s
# vs 819 GB/s) suggests profit until cin² ≈ 450·cout, but measured e2e the
# wide-cin stages give the win back (threshold 400 → 41.4k vs 64 →
# 43.5-45.2k img/s — PERF_ANALYSIS.md r4): the direct statistics
# reductions XLA fuses for those stages are cheaper than the extra
# contraction. 64 is the measured optimum.
_GRAM_MAX_CIN_SQ_PER_COUT = 64


def _norm_in(x, scale, shift, relu_in: bool, norm_in: bool):
    if not norm_in:
        return x
    e = x.astype(jnp.float32) * scale + shift
    if relu_in:
        e = jnp.maximum(e, 0.0)
    return e.astype(x.dtype)


def _conv_reference(x, w, scale, shift, relu_in, norm_in, stride):
    e = _norm_in(x, scale, shift, relu_in, norm_in)
    if w.ndim == 2:                                     # 1×1
        if stride != 1:
            e = e[:, ::stride, ::stride, :]
        y = jnp.einsum("nhwc,co->nhwo", e, w,
                       preferred_element_type=jnp.float32)
    else:                                               # 3×3 SAME, stride 1
        y = lax.conv_general_dilated(
            e, w, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)
    sums = jnp.stack([jnp.sum(y, axis=(0, 1, 2)),
                      jnp.sum(y * y, axis=(0, 1, 2))])
    return y.astype(x.dtype), sums


@jax.custom_vjp
def _gram(e):
    """G = eᵀe over all leading axes, f32-accumulated from the compute
    dtype (bf16 on the MXU). The custom VJP exists because einsum with
    ``preferred_element_type=f32`` cannot be transposed by autodiff (an
    f32 cotangent against bf16 operands); de = e·(dG + dGᵀ) is the
    exact gradient."""
    return jnp.einsum("nhwa,nhwb->ab", e, e,
                      preferred_element_type=jnp.float32)


def _gram_fwd(e):
    return _gram(e), e


def _gram_bwd(e, dg):
    d = (dg + dg.T).astype(e.dtype)
    return (jnp.einsum("ab,nhwb->nhwa", d, e),)


_gram.defvjp(_gram_fwd, _gram_bwd)


def conv_bn_stats_xla(x, w, scale, shift, relu_in: bool = True,
                      norm_in: bool = True, stride: int = 1):
    """y = conv(relu?(x·scale + shift)) ⊕ per-channel (Σy, Σy²), in plain
    jnp ops (no custom calls, no custom VJP but ``_gram``'s).

    ``w`` (Cin, Cout) is a 1×1 conv (with optional spatial ``stride``);
    ``w`` (3, 3, Cin, Cout) a SAME 3×3 conv. Returns ``(y, stats)`` with
    ``stats`` float32 (2, Cout). The stats output is differentiable,
    which is what makes the surrounding BatchNorm's batch-statistics
    gradient exact.

    **Gram-matrix statistics** for expanding 1×1 convs. For
    ``y = e @ W``:  ``Σᵢ yᵢ = (Σᵢ eᵢ) @ W``  and
    ``Σᵢ yᵢ² = diag(Wᵀ (eᵀe) W)`` — so the batch statistics of the
    OUTPUT are computed from the (smaller) input side plus a
    weights-sized contraction, and XLA never re-reads the Cout-sized
    activation for a stats pass. Worth it exactly when Cout > Cin (the
    bottleneck's expand and downsample projections — the 4f-channel
    activations that dominate BN-stat traffic) and Cin is narrow
    (``_GRAM_MAX_CIN_SQ_PER_COUT``); other convs use the direct
    reduction, which autodiff also differentiates exactly."""
    e = _norm_in(x, scale, shift, relu_in, norm_in)
    f32 = jnp.float32
    w = w.astype(e.dtype)       # compute-dtype matmul/conv (MXU bf16)
    if w.ndim == 2:
        if stride != 1:
            e = e[:, ::stride, ::stride, :]
        cin, cout = w.shape
        # 4-D einsum, NOT a reshape-to-2D matmul: the flatten forces a
        # physical relayout between conv-tiled and matmul-tiled forms
        # (measured −8k img/s on the ResNet50 step). No
        # preferred_element_type — its transpose rule would pair an f32
        # cotangent with the bf16 weights and fail to differentiate.
        y = jnp.einsum("nhwc,co->nhwo", e, w)
        if cout > cin and cin * cin <= _GRAM_MAX_CIN_SQ_PER_COUT * cout:
            wf = w.astype(f32)
            gram = _gram(e)
            colsum = jnp.sum(e.astype(f32), axis=(0, 1, 2))
            s1 = colsum @ wf
            s2 = jnp.einsum("ac,ab,bc->c", wf, gram, wf)
            return y.astype(x.dtype), jnp.stack([s1, s2])
    else:
        y = lax.conv_general_dilated(
            e, w, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    yf = y.astype(f32)
    sums = jnp.stack([jnp.sum(yf, axis=(0, 1, 2)),
                      jnp.sum(yf * yf, axis=(0, 1, 2))])
    return y.astype(x.dtype), sums


def stats_to_scale_shift(stats, count, gamma, beta, eps):
    """(Σy, Σy²) → the (scale, shift) form of BN normalize+affine, plus
    (mean, var) for the running-average update. Biased variance, exactly
    like jnp.var / the BatchNormalization layer."""
    f32 = jnp.float32
    mean = stats[0].astype(f32) / count
    var = jnp.maximum(stats[1].astype(f32) / count - mean * mean, 0.0)
    inv = gamma.astype(f32) * lax.rsqrt(var + eps)
    return inv, beta.astype(f32) - mean * inv, mean, var
