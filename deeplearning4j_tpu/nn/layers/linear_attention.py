"""Linear-attention layers: the Gated DeltaNet.

A Gated DeltaNet head keeps a matrix state ``S`` (key size x value size)
in place of a growing key/value cache and updates it by the gated delta
rule, one token at a time:

    S_t = alpha_t (I - beta_t k_t k_t^T) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

(alpha_t in (0, 1] is the head's decay, beta_t in (0, 1) its write
strength). ``recurrent_gated_delta_rule`` is that recurrence as written,
a ``lax.scan`` over tokens: the oracle of the tests and the form a decode
step takes. ``chunk_gated_delta_rule`` is what a training step runs: the
sequence is cut into chunks, the products of Householder-like factors
inside a chunk are brought into WY form (one unit-lower-triangular inverse
per chunk, taken by repeated squaring since the strict triangle is
nilpotent), and only the state at the chunk borders is carried by a
``lax.scan`` over chunks, so nearly all the work is matrix products.
Gradients are autodiff's through both: they are the plain forms, and the
oracles of ``ops/pallas_delta_rule.py``, which runs the chunked rule as
Pallas TPU kernels with a backward written by hand. ``GatedDeltaNet``
calls that module's ``gated_delta_rule``, which picks the kernels or
``chunk_gated_delta_rule`` from the backend and the shapes it is handed.

Types in the chunked form, plain or kernels: gates, decay sums and the
carried state are float32; matrix products take their operands in the
compute type (bfloat16 under the bf16 policy, as the published kernels
do) and accumulate in float32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import InputType, RecurrentType
from deeplearning4j_tpu.nn.layers.base import FeedForwardLayer, LayerContext
from deeplearning4j_tpu.nn.layers.normalization import rms_norm
from deeplearning4j_tpu.utils.serde import register_serializable


def causal_depthwise_conv(x, w):
    """``y[t, c] = sum_j w[c, j] x[t - (K - 1) + j, c]`` over (N, T, C)
    with ``w`` (C, K): each channel's own short filter over the present
    and the K - 1 positions before it, zeros before the sequence."""
    k = w.shape[-1]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * w[:, j] for j in range(k))


def l2_normalize(x, eps: float = 1e-6):
    xf = x.astype(jnp.promote_types(jnp.float32, x.dtype))
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + eps)


def recurrent_gated_delta_rule(q, k, v, g, beta, initial_state=None):
    """The gated delta rule token by token. ``q``, ``k`` (N, T, H, Dk),
    ``v`` (N, T, H, Dv), ``g`` = log alpha and ``beta`` (N, T, H).
    Returns ``(o (N, T, H, Dv), final state (N, H, Dk, Dv))`` in float32
    (float64 inputs stay float64)."""
    dt = jnp.promote_types(jnp.float32, q.dtype)
    q, k, v, g, beta = (a.astype(dt) for a in (q, k, v, g, beta))
    n, _, h, dk = q.shape
    s0 = (jnp.zeros((n, h, dk, v.shape[-1]), dt) if initial_state is None
          else initial_state.astype(dt))

    def step(s, xs):
        qt, kt, vt, gt, bt = xs                     # (N, H, D) / (N, H)
        s = s * jnp.exp(gt)[..., None, None]
        seen = jnp.einsum("nhk,nhkv->nhv", kt, s)
        delta = (vt - seen) * bt[..., None]
        s = s + kt[..., :, None] * delta[..., None, :]
        return s, jnp.einsum("nhk,nhkv->nhv", qt, s)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    s, o = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 1), s


def _unit_lower_inverse(a, dot):
    """``(I + a)^-1`` for ``a`` strictly lower triangular (..., C, C):
    with b = -a nilpotent, sum_k b^k = prod_j (I + b^(2^j)), log2(C)
    factors of matrix products and no substitution loop. ``dot`` is the
    caller's einsum (its operand type, float32 accumulation)."""
    c = a.shape[-1]
    power = -a
    inv = jnp.eye(c, dtype=a.dtype) + power
    span = 2
    while span < c:
        power = dot("...ij,...jk->...ik", power, power)
        inv = inv + dot("...ij,...jk->...ik", inv, power)
        span *= 2
    return inv


def chunk_gated_delta_rule(q, k, v, g, beta, chunk_size: int = 64,
                           initial_state=None, matmul_dtype=None):
    """The gated delta rule in chunks; same arguments and results as
    ``recurrent_gated_delta_rule``. ``chunk_size`` need not divide T (the
    tail is padded with tokens that leave the state alone).
    ``matmul_dtype`` is the type of the matrix products' operands
    (default: ``v``'s, at least float32 accumulation either way)."""
    mm = jnp.dtype(matmul_dtype or v.dtype)
    f32 = jnp.promote_types(jnp.float32, mm)
    n, t, h, dk = q.shape
    dv = v.shape[-1]
    c = int(chunk_size)
    pad = (-t) % c
    nc = (t + pad) // c

    def chunks(a):          # (N, T, H, ...) -> (N, H, nc, C, ...)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = jnp.moveaxis(a, 2, 1)
        return a.reshape((n, h, nc, c) + a.shape[3:])

    def dot(spec, a, b):
        return jnp.einsum(spec, a.astype(mm), b.astype(mm),
                          preferred_element_type=f32)

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
    gc = jnp.cumsum(g, -1)                                  # (N,H,nc,C)
    tri = jnp.tril(jnp.ones((c, c), bool))
    # decay from position j to position i of one chunk, j <= i; masked
    # before the exponential, whose argument above the diagonal is > 0
    decay = jnp.exp(jnp.where(tri, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    k_beta = k.astype(f32) * beta[..., None]
    v_beta = v.astype(f32) * beta[..., None]
    a = dot("...id,...jd->...ij", k_beta, k) * decay
    a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), a, 0.0)
    inv = _unit_lower_inverse(a, dot)                          # (N,H,nc,C,C)
    u = dot("...ij,...jd->...id", inv, v_beta)
    w = dot("...ij,...jd->...id", inv, k_beta * jnp.exp(gc)[..., None])
    local = dot("...id,...jd->...ij", q, k) * decay         # within chunk
    q_in = q.astype(f32) * jnp.exp(gc)[..., None]
    k_out = k.astype(f32) * jnp.exp(gc[..., -1:] - gc)[..., None]
    carry_decay = jnp.exp(gc[..., -1])                      # (N,H,nc)

    s0 = (jnp.zeros((n, h, dk, dv), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(s, xs):
        u_i, w_i, local_i, q_i, k_i, d_i = xs
        v_new = u_i - dot("nhck,nhkv->nhcv", w_i, s)
        o_i = (dot("nhck,nhkv->nhcv", q_i, s)
               + dot("nhij,nhjv->nhiv", local_i, v_new))
        s = (s * d_i[..., None, None]
             + dot("nhck,nhcv->nhkv", k_i, v_new))
        return s, o_i

    xs = (u, w.astype(mm), local.astype(mm), q_in.astype(mm),
          k_out.astype(mm), carry_decay)
    s, o = jax.lax.scan(step, s0,
                        tuple(jnp.moveaxis(x, 2, 0) for x in xs))
    o = jnp.moveaxis(o, 0, 2).reshape(n, h, nc * c, dv)[:, :, :t]
    return jnp.moveaxis(o, 1, 2), s


@register_serializable
@dataclasses.dataclass(frozen=True)
class GatedDeltaNet(FeedForwardLayer):
    """Gated DeltaNet token mixer over (N, T, F), bias-free:

    ``[q, k, v, z] = x W_qkvz``, ``[b, a] = x W_ba``; a causal depthwise
    convolution of ``conv_kernel`` taps and SiLU over the q, k and v
    channels; ``beta = sigmoid(b)``, ``log alpha = -exp(A_log) *
    softplus(a + dt_bias)`` in float32; q and k L2-normalised over the
    head, q scaled by 1/sqrt(key size); each key head serves
    ``n_value_heads / n_key_heads`` value heads; the gated delta rule per
    value head (chunked); a per-head RMSNorm of the result gated by
    ``silu(z)`` (plain weight); the output projection.

    ``W_qkvz``'s columns are laid out ``[q | k | v | z]``, each head-major
    (the published checkpoints interleave them by key head: a column
    permutation, applied on import). ``n_out`` is the model width."""
    n_key_heads: int = 16
    n_value_heads: int = 32
    key_head_dim: int = 128
    value_head_dim: int = 128
    conv_kernel: int = 4
    chunk_size: int = 64
    eps: float = 1e-6
    init_std: float = 0.02

    def __post_init__(self):
        if self.n_value_heads % self.n_key_heads:
            raise ValueError(
                f"n_value_heads={self.n_value_heads} is not a multiple of "
                f"n_key_heads={self.n_key_heads}")

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    @property
    def _dims(self):
        kd = self.n_key_heads * self.key_head_dim
        vd = self.n_value_heads * self.value_head_dim
        return kd, vd

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        kd, vd = self._dims
        dt = self.param_dtype()
        ks = jax.random.split(key, 6)
        nv = self.n_value_heads

        def normal(k, shape):
            return self.init_std * jax.random.normal(k, shape, dt)

        return {
            "W_qkvz": normal(ks[0], (n_in, 2 * kd + 2 * vd)),
            "W_ba": normal(ks[1], (n_in, 2 * nv)),
            # the depthwise filter starts as a torch Conv1d does: uniform
            # in +-1/sqrt(taps)
            "conv_w": jax.random.uniform(
                ks[2], (2 * kd + vd, self.conv_kernel), dt,
                -1.0, 1.0) / jnp.sqrt(float(self.conv_kernel)),
            # decay rates log-uniform in [1, 16], as published
            "A_log": jnp.log(jax.random.uniform(ks[3], (nv,), dt, 1.0,
                                                16.0)),
            "dt_bias": jnp.ones((nv,), dt),
            "norm_w": jnp.ones((self.value_head_dim,), dt),
            "W_o": normal(ks[4], (vd, self.n_out)),
        }

    def apply(self, params, state, x, ctx: LayerContext):
        from deeplearning4j_tpu.ops.pallas_delta_rule import (
            gated_delta_rule)
        n, t, _ = x.shape
        kd, vd = self._dims
        hk, hv = self.n_key_heads, self.n_value_heads
        f32 = jnp.promote_types(jnp.float32, x.dtype)
        with jax.named_scope("gdn.proj"):
            qkvz = jnp.einsum("ntf,fe->nte", x, params["W_qkvz"])
            ba = jnp.einsum("ntf,fe->nte", x, params["W_ba"])
            qkv, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
        with jax.named_scope("gdn.conv"):
            qkv = jax.nn.silu(causal_depthwise_conv(qkv, params["conv_w"]))
        with jax.named_scope("gdn.scan"):
            q = qkv[..., :kd].reshape(n, t, hk, self.key_head_dim)
            k = qkv[..., kd:2 * kd].reshape(n, t, hk, self.key_head_dim)
            v = qkv[..., 2 * kd:].reshape(n, t, hv, self.value_head_dim)
            beta = jax.nn.sigmoid(ba[..., :hv].astype(f32))
            g = (-jnp.exp(params["A_log"].astype(f32))
                 * jax.nn.softplus(ba[..., hv:].astype(f32)
                                   + params["dt_bias"].astype(f32)))
            q = l2_normalize(q, self.eps) / jnp.sqrt(
                jnp.asarray(self.key_head_dim, f32))
            k = l2_normalize(k, self.eps)
            o, _ = gated_delta_rule(
                q.astype(x.dtype), k.astype(x.dtype), v, g, beta,
                chunk_size=self.chunk_size, layer=self.name or "gdn")
            z = z.reshape(n, t, hv, self.value_head_dim)
            o = rms_norm(o, params["norm_w"], self.eps,
                         zero_centered=False)
            o = (o * jax.nn.silu(z.astype(f32))).astype(x.dtype)
        with jax.named_scope("gdn.out"):
            y = jnp.einsum("nte,eo->nto", o.reshape(n, t, vd),
                           params["W_o"])
        return y, state
