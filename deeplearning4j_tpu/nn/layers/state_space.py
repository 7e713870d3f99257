"""State-space layers: the selective scan (Mamba-1), the state-space
duality scan (Mamba-2) and their token mixers.

**Mamba-1.** A selective state-space layer keeps, for each of its ``D``
channels, a state of ``S`` numbers and moves it by a transition that
depends on the token:

    s_t[c, n] = exp(dt_t[c] A[c, n]) s_(t-1)[c, n] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] s_t[c, n]

(``A`` < 0 the channel's decay rates, ``dt_t`` > 0 the token's step,
``B_t`` and ``C_t`` what the token writes and reads). There are no matrix
products in it: ``D * S`` multiply-adds a token, one after another.

``selective_scan_step`` is one token of that recurrence, the form a decode
step takes; ``selective_scan_recurrent`` runs it over a sequence as
written (a ``lax.scan`` over tokens, the oracle of the tests);
``selective_scan_chunked`` is the training scan in plain JAX: the same
token loop cut into chunks, each chunk under ``jax.checkpoint`` inside a
``lax.scan`` over chunks, so that the backward pass keeps the state at
the chunk borders only (``T / chunk`` states of ``D * S``) and recomputes
a chunk's states while it differentiates that chunk. Neither pass holds a
(T, D, S) tensor. Everything here is float32, whatever the type of ``x``.
``MambaMixer`` calls ``ops.pallas_selective_scan.selective_scan``, which
runs the same scan as Pallas kernels on a TPU and this form elsewhere.

Inside the loops the state is laid out (N, S, D), channels last, and the
per-token inputs are rows of (T, N * D) arrays: both fill the TPU's 8 x
128 tiles at any batch size.

**Mamba-2** (state-space duality, arXiv:2405.21060). The channels are
``H`` heads of ``P``; a head has **one scalar decay** where Mamba-1 has a
rate for every (channel, state) pair, and a state of ``P x S``; ``B_t``
and ``C_t`` (``G`` groups of ``S``) are shared by the ``H / G`` heads of a
group:

    S_t[j] = exp(dt_t[j] A[j]) S_(t-1)[j] + dt_t[j] x_t[j] B_t[g(j)]^T
    y_t[j] = S_t[j] C_t[g(j)]

With a scalar decay the recurrence over a chunk of ``Q`` tokens is a
masked matrix product: ``ssd_chunked``, the training form, takes the
decays' cumulative sums inside the chunk, whose differences give the
lower-triangular ``L[i, j]`` = decay from token j to token i, computes
``(L o C B^T) (dt x)`` as two batched matrix products, the state the
chunk leaves behind ``(decay to the chunk's end * dt x)^T B`` as a third
and what the state it began with adds to its rows, ``exp(cum) C S``, as a
fourth; a ``lax.scan`` over the chunks carries the (H, P, S) state, each
chunk under ``jax.checkpoint``, so the backward pass keeps the border
states alone. Every exponent is a difference of cumulative sums of
``dt A`` <= 0 taken forward in time, so none is positive and nothing is
divided by a decay. ``ssd_step`` is one token (a decode step's form),
``ssd_recurrent`` the recurrence as written (the tests' oracle). ``dt``,
``A``, the state and ``y`` are float32; the matrix products take their
operands in ``x``'s type (bfloat16 under the bf16 policy, as the
published kernels do) and accumulate in float32. ``Mamba2Mixer`` calls
``ops.pallas_ssd_scan.ssd_scan``, which runs the same chunks as Pallas
kernels on a TPU (one launch a pass, a group's heads and their state in
VMEM, the backward by hand) and ``ssd_chunked`` elsewhere: off the TPU,
at sizes that fill no lane tile, at another chunk than 128. ``ssd_chunked``
is also the form that continues from a state (``ssd_step``'s prefix).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import InputType, RecurrentType
from deeplearning4j_tpu.nn.layers.base import FeedForwardLayer, LayerContext
from deeplearning4j_tpu.nn.layers.linear_attention import (
    causal_depthwise_conv)
from deeplearning4j_tpu.nn.layers.normalization import rms_norm
from deeplearning4j_tpu.utils.serde import register_serializable


def selective_scan_step(state, x_t, dt_t, a_t, b_t, c_t):
    """One token. ``state`` (N, S, D); ``x_t``, ``dt_t`` (N, D); ``a_t``
    = A transposed (S, D); ``b_t``, ``c_t`` (N, S). Returns the new state
    and ``y_t`` (N, D)."""
    decay = jnp.exp(dt_t[:, None, :] * a_t)
    state = decay * state + b_t[:, :, None] * (dt_t * x_t)[:, None, :]
    return state, jnp.sum(c_t[:, :, None] * state, axis=1)


def _time_major(x, dt, b, c):
    """(N, T, ...) float32 -> rows of (T, N * ...), and their sizes."""
    f32 = jnp.promote_types(jnp.float32, x.dtype)
    n, t, d = x.shape
    s = b.shape[-1]
    rows = tuple(jnp.moveaxis(v.astype(f32), 1, 0).reshape(t, -1)
                 for v in (x, dt, b, c))
    return rows, (n, t, d, s, f32)


def _token(a_t, n, d, s):
    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state, y_t = selective_scan_step(
            state, x_t.reshape(n, d), dt_t.reshape(n, d), a_t,
            b_t.reshape(n, s), c_t.reshape(n, s))
        return state, y_t.reshape(n * d)
    return token


def selective_scan_recurrent(x, dt, a, b, c, initial_state=None):
    """The recurrence token by token. ``x``, ``dt`` (N, T, D); ``a`` (D,
    S); ``b``, ``c`` (N, T, S). Returns ``(y (N, T, D), final state (N, S,
    D))`` in float32 (float64 inputs stay float64)."""
    rows, (n, t, d, s, f32) = _time_major(x, dt, b, c)
    s0 = (jnp.zeros((n, s, d), f32) if initial_state is None
          else initial_state.astype(f32))
    state, y = jax.lax.scan(_token(a.astype(f32).T, n, d, s), s0, rows)
    return jnp.moveaxis(y.reshape(t, n, d), 0, 1), state


# tokens a chunk of the training scan: at 8,192 tokens of 5,120 channels x
# 16 states the backward pass keeps 128 border states (42 MB) and
# recomputes one chunk's 64 (21 MB) at a time
CHUNK = 64
# the step a channel starts with, drawn log-uniformly (the family's)
DT_INIT_RANGE = (1e-3, 0.1)


def selective_scan_chunked(x, dt, a, b, c, chunk_size: int = CHUNK):
    """The same ``y`` from a zero state, in chunks of ``chunk_size``
    tokens (module docstring); ``chunk_size`` need not divide T (the tail
    is padded with tokens of step 0, which leave the state alone)."""
    rows, (n, t, d, s, f32) = _time_major(x, dt, b, c)
    size = int(chunk_size)
    pad = (-t) % size
    chunks = tuple(
        jnp.pad(r, ((0, pad), (0, 0))).reshape((t + pad) // size, size, -1)
        for r in rows)
    token = _token(a.astype(f32).T, n, d, s)

    @jax.checkpoint
    def chunk(state, xs):
        return jax.lax.scan(token, state, xs)

    _, y = jax.lax.scan(chunk, jnp.zeros((n, s, d), f32), chunks)
    return jnp.moveaxis(y.reshape(t + pad, n, d)[:t], 0, 1)


# ---- Mamba-2: the state-space duality scan ---------------------------------

# tokens a chunk of the training form (the published ``chunk_size``)
SSD_CHUNK = 128

SSD_CHUNKS_GAUGE = (
    "dl4j_ssd_chunks",
    "chunks one pass of the Mamba-2 scan (ssd_chunked) walks, as the step "
    "was last traced (label: the layer)")


def _per_head(v, heads: int):
    """``B`` or ``C`` (..., G, S) as each of the ``heads`` reads it: head
    ``j`` its group ``j // (heads / G)``."""
    return jnp.repeat(v, heads // v.shape[-2], axis=-2)


def ssd_step(state, x_t, dt_t, a, b_t, c_t):
    """One token. ``state`` (N, H, P, S); ``x_t`` (N, H, P); ``dt_t`` (N,
    H); ``a`` (H,); ``b_t``, ``c_t`` (N, G, S). Returns the new state and
    ``y_t`` (N, H, P)."""
    heads = x_t.shape[-2]
    b_t, c_t = _per_head(b_t, heads), _per_head(c_t, heads)
    state = (jnp.exp(dt_t * a)[..., None, None] * state
             + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
    return state, jnp.einsum("nhps,nhs->nhp", state, c_t)


def _ssd_initial(initial_state, n, h, p, s, dtype):
    return (jnp.zeros((n, h, p, s), dtype) if initial_state is None
            else initial_state.astype(dtype))


def ssd_recurrent(x, dt, a, b, c, initial_state=None):
    """The recurrence token by token. ``x`` (N, T, H, P); ``dt`` (N, T,
    H); ``a`` (H,); ``b``, ``c`` (N, T, G, S). Returns ``(y (N, T, H, P),
    final state (N, H, P, S))`` in float32 (float64 inputs stay
    float64)."""
    f32 = jnp.promote_types(jnp.float32, x.dtype)
    n, _, h, p = x.shape
    xs = tuple(jnp.moveaxis(v.astype(f32), 1, 0) for v in (x, dt, b, c))

    def token(state, xs):
        return ssd_step(state, xs[0], xs[1], a.astype(f32), *xs[2:])

    state, y = jax.lax.scan(
        token, _ssd_initial(initial_state, n, h, p, b.shape[-1], f32), xs)
    return jnp.moveaxis(y, 0, 1), state


def ssd_chunked(x, dt, a, b, c, chunk_size: int = SSD_CHUNK,
                initial_state=None):
    """The same ``(y, final state)`` in chunks of ``chunk_size`` tokens
    (module docstring); ``chunk_size`` need not divide T (the tail is
    padded with tokens of step 0, which leave the state alone). The
    matrix products' operands are of ``x``'s type; ``y`` and the state are
    float32."""
    mm = x.dtype
    f32 = jnp.promote_types(jnp.float32, mm)
    n, t, h, p = x.shape
    g, s = b.shape[-2:]
    r = h // g                                  # heads that share a group
    q = int(chunk_size)
    pad = (-t) % q

    def chunks(v):          # (N, T, ...) -> (chunks, N, Q, ...)
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return jnp.moveaxis(v.reshape((n, -1, q) + v.shape[2:]), 1, 0)

    def dot(spec, u, v):
        return jnp.einsum(spec, u.astype(mm), v.astype(mm),
                          preferred_element_type=f32)

    a = a.astype(f32)
    lower = jnp.tril(jnp.ones((q, q), bool))

    @jax.checkpoint
    def chunk(state, xs):
        x_c, dt_c, b_c, c_c = xs        # (N, Q, H, P), (N, Q, H), (N, Q, G, S)
        cum = jnp.cumsum(dt_c * a, axis=1)      # <= 0, falling along Q
        by_head = jnp.moveaxis(cum, 1, 2).reshape(n, g, r, q)
        # decay from token j to token i >= j; masked before the
        # exponential, whose argument above the diagonal is > 0
        decay = jnp.exp(jnp.where(
            lower, by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
        step = jnp.moveaxis(dt_c, 1, 2).reshape(n, g, r, 1, q)
        scores = dot("nigs,njgs->ngij", c_c, b_c)[:, :, None]
        y = dot("ngrij,njgrp->nigrp", scores * decay * step,
                x_c.reshape(n, q, g, r, p))
        # what the state the chunk began with adds to its rows
        began = dot("nigs,ngrps->nigrp", c_c, state.reshape(n, g, r, p, s))
        y = y.reshape(n, q, h, p) + jnp.exp(cum)[..., None] * began.reshape(
            n, q, h, p)
        # the state it leaves: each token's write decayed to the chunk's end
        last = cum[:, -1]
        left = x_c.astype(f32) * (jnp.exp(last[:, None] - cum) * dt_c)[
            ..., None]
        wrote = dot("njgrp,njgs->ngrps", left.reshape(n, q, g, r, p), b_c)
        state = (jnp.exp(last)[..., None, None] * state
                 + wrote.reshape(n, h, p, s))
        return state, y

    state, y = jax.lax.scan(
        chunk, _ssd_initial(initial_state, n, h, p, s, f32),
        (chunks(x), chunks(dt.astype(f32)), chunks(b), chunks(c)))
    return jnp.moveaxis(y, 0, 1).reshape(n, t + pad, h, p)[:, :t], state



@register_serializable
@dataclasses.dataclass(frozen=True)
class MambaMixer(FeedForwardLayer):
    """Mamba-1 token mixer over (N, T, F):

    ``[x | z] = h W_in``; ``x <- silu(conv(x) + b_conv)`` (causal,
    depthwise, ``d_conv`` taps); ``[dt_r | B | C] = x W_x`` (``dt_rank``,
    ``d_state``, ``d_state``); ``dt = softplus(dt_r W_dt + b_dt)``; ``A =
    -exp(A_log)``; the selective scan; ``y <- y + D x``; the result
    ``(y * silu(z)) W_out``. ``dt``, ``A``, the state and ``y`` are
    float32. ``mix`` also returns ``y`` before the gate: the memory a
    gated memory unit of a later layer reads.

    Matrices start normal(0, ``init_std``), the filter uniform in
    +-1/sqrt(taps), ``A_log`` = log(1..``d_state``) in every channel, ``D``
    = 1, and ``b_dt`` the inverse softplus of a log-uniform draw in
    [1e-3, 0.1] (``DT_INIT_RANGE``). ``n_out`` is the model width."""
    d_inner: int = 0
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0
    init_std: float = 0.02

    named_scopes = ("ssm.proj", "ssm.conv", "ssm.scan", "ssm.out")

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        d, s, r = self.d_inner, self.d_state, self.dt_rank
        dt = self.param_dtype()
        ks = jax.random.split(key, 6)

        def normal(k, shape):
            return self.init_std * jax.random.normal(k, shape, dt)

        step = jnp.exp(jax.random.uniform(
            ks[4], (d,), dt, *(math.log(v) for v in DT_INIT_RANGE)))
        return {
            "W_in": normal(ks[0], (n_in, 2 * d)),
            "conv_w": jax.random.uniform(
                ks[1], (d, self.d_conv), dt, -1.0, 1.0)
            / jnp.sqrt(float(self.d_conv)),
            "conv_b": jnp.zeros((d,), dt),
            "W_x": normal(ks[2], (d, r + 2 * s)),
            "W_dt": normal(ks[3], (r, d)),
            # softplus(b_dt) = step
            "b_dt": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, s + 1, dtype=dt)), (d, s)),
            "D": jnp.ones((d,), dt),
            "W_out": normal(ks[5], (d, self.n_out)),
        }

    def mix(self, params, x, mask=None):
        """``(mixed (N, T, n_out), (memory (N, T, d_inner),))``: the form
        the three mixers of a ``StateSpaceHybridBlock`` share, ``(params,
        x, *extra inputs, mask) -> (mixed, what the mixer emits)``."""
        from deeplearning4j_tpu.ops.pallas_selective_scan import (
            selective_scan)
        d, s, r = self.d_inner, self.d_state, self.dt_rank
        f32 = jnp.promote_types(jnp.float32, x.dtype)
        with jax.named_scope("ssm.proj"):
            xz = jnp.einsum("ntf,fe->nte", x, params["W_in"])
            u, z = xz[..., :d], xz[..., d:]
        with jax.named_scope("ssm.conv"):
            u = jax.nn.silu(
                causal_depthwise_conv(u.astype(f32),
                                      params["conv_w"].astype(f32))
                + params["conv_b"].astype(f32)).astype(x.dtype)
        with jax.named_scope("ssm.proj"):
            dbc = jnp.einsum("ntd,de->nte", u, params["W_x"],
                             preferred_element_type=f32)
            step = jax.nn.softplus(
                jnp.einsum("ntr,rd->ntd", dbc[..., :r].astype(x.dtype),
                           params["W_dt"], preferred_element_type=f32)
                + params["b_dt"].astype(f32))
        with jax.named_scope("ssm.scan"):
            y = selective_scan(
                u, step, -jnp.exp(params["A_log"].astype(f32)),
                dbc[..., r:r + s], dbc[..., r + s:],
                layer=self.name or "ssm")
            y = y + params["D"].astype(f32) * u.astype(f32)
            gated = (y * jax.nn.silu(z.astype(f32))).astype(x.dtype)
        with jax.named_scope("ssm.out"):
            out = jnp.einsum("ntd,do->nto", gated, params["W_out"])
        return out, (y.astype(x.dtype),)

    def apply(self, params, state, x, ctx: LayerContext):
        return self.mix(params, x)[0], state


def grouped_rms_norm(x, w, groups: int, eps: float):
    """RMSNorm over each of ``groups`` equal runs of the last axis of
    ``x``, times the plain weight ``w`` (one a channel); statistics in
    float32, the result in ``x``'s type."""
    grouped = x.shape[:-1] + (groups, -1)
    return rms_norm(x.reshape(grouped), w.reshape(groups, -1), eps,
                    zero_centered=False).reshape(x.shape)


def gated_group_norm(y, x, z, skip, w, groups: int, eps: float):
    """What a ``Mamba2Mixer`` makes of its scan's ``y`` (N, T, H, P),
    float32: ``GroupRMSNorm((y + skip x) * silu(z))`` for ``x`` (N, T, H,
    P), ``skip`` (H,), ``z`` (N, T, H P) and the norm's weight ``w`` (H
    P,), in float32; the result (N, T, H P) in ``x``'s type. The skip runs
    over the channels as the projections have them: a (T, H, P) array with
    ``P`` under a lane tile is another layout on a TPU, each way there and
    back a copy of the rows."""
    n, t, h, p = x.shape
    f32 = jnp.promote_types(jnp.float32, x.dtype)
    gated = (y.reshape(n, t, h * p)
             + jnp.repeat(skip.astype(f32), p)
             * x.reshape(n, t, h * p).astype(f32)) * jax.nn.silu(
                 z.astype(f32))
    return grouped_rms_norm(gated, w, groups, eps).astype(x.dtype)


@register_serializable
@dataclasses.dataclass(frozen=True)
class Mamba2Mixer(FeedForwardLayer):
    """Mamba-2 token mixer over (N, T, F) (arXiv:2405.21060, as the
    Nemotron-H family writes it), bias-free but for the convolution's:

    ``[z | xBC | dt] = h W_in`` with widths ``H P`` | ``H P + 2 G S`` |
    ``H``; ``xBC <- silu(conv(xBC) + b_conv)`` (causal, depthwise,
    ``d_conv`` taps, over all its channels); ``[x | B | C] = xBC``, ``x``
    as (H, P), ``B`` and ``C`` as (G, S); ``dt = softplus(dt + dt_bias)``,
    ``A = -exp(A_log)`` (one a head); the state-space duality scan in
    chunks of ``chunk_size``, ``y <- y + D x`` (``D`` one a head) and the
    gated norm below (``ops.pallas_ssd_scan.ssd_scan``: all three in the
    Pallas kernels on a TPU at chunks of 128, states and a group's
    channels that fill lane tiles; the plain ``ssd_chunked`` and
    ``gated_group_norm`` anywhere else; the choice rests on the input's
    shapes and the backend, no field selects it); ``o = GroupRMSNorm(y * silu(z))``: the gate first, then
    an RMSNorm over each of the ``G`` groups of ``H P / G`` channels times
    a plain weight; the result ``o W_out``. ``dt``, ``A``, the state and
    ``y`` are float32.

    Matrices start normal(0, ``init_std``), the filter uniform in
    +-1/sqrt(taps) with a zero bias, ``A_log`` = log(1..H), ``D`` = 1, the
    norm's weight 1, and ``dt_bias`` the inverse softplus of a log-uniform
    draw in [``dt_min``, ``dt_max``] floored at ``dt_floor``. ``n_out`` is
    the model width. With a name the layer publishes, when the step is
    traced, the chunks a pass walks as the gauge ``dl4j_ssd_chunks`` and
    how many of them go through the kernels as ``dl4j_ssd_kernel_chunks``
    (0: the plain form)."""
    n_heads: int = 64
    head_dim: int = 64
    n_groups: int = 8
    d_state: int = 128
    d_conv: int = 4
    chunk_size: int = SSD_CHUNK
    dt_min: float = 1e-3
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    eps: float = 1e-5
    init_std: float = 0.02

    named_scopes = ("ssd.proj", "ssd.conv", "ssd.scan", "ssd.out")

    def __post_init__(self):
        if self.n_heads % self.n_groups:
            raise ValueError(
                f"n_heads={self.n_heads} is not a multiple of "
                f"n_groups={self.n_groups}")

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    @property
    def _dims(self):
        """Widths of ``x`` (and ``z``) and of ``B`` (and ``C``)."""
        return self.n_heads * self.head_dim, self.n_groups * self.d_state

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        d, bc = self._dims
        h = self.n_heads
        dt = self.param_dtype()
        ks = jax.random.split(key, 4)
        step = jnp.maximum(self.dt_floor, jnp.exp(jax.random.uniform(
            ks[2], (h,), dt, math.log(self.dt_min), math.log(self.dt_max))))
        return {
            "W_in": self.init_std * jax.random.normal(
                ks[0], (n_in, 2 * d + 2 * bc + h), dt),
            "conv_w": jax.random.uniform(
                ks[1], (d + 2 * bc, self.d_conv), dt, -1.0, 1.0)
            / jnp.sqrt(float(self.d_conv)),
            "conv_b": jnp.zeros((d + 2 * bc,), dt),
            # softplus(dt_bias) = step
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jnp.arange(1, h + 1, dtype=dt)),
            "D": jnp.ones((h,), dt),
            "norm_w": jnp.ones((d,), dt),
            "W_out": self.init_std * jax.random.normal(
                ks[3], (d, self.n_out), dt),
        }

    def apply(self, params, state, x, ctx: LayerContext):
        from deeplearning4j_tpu.ops.pallas_ssd_scan import ssd_scan
        n, t, _ = x.shape
        d, bc = self._dims
        h, p, g = self.n_heads, self.head_dim, self.n_groups
        f32 = jnp.promote_types(jnp.float32, x.dtype)
        if self.name:
            from deeplearning4j_tpu.observe.registry import default_registry
            default_registry().gauge(*SSD_CHUNKS_GAUGE).set(
                -(-t // self.chunk_size), layer=self.name)
        with jax.named_scope("ssd.proj"):
            zxd = jnp.einsum("ntf,fe->nte", x, params["W_in"])
            z, xbc = zxd[..., :d], zxd[..., d:2 * d + 2 * bc]
            step = jax.nn.softplus(zxd[..., 2 * d + 2 * bc:].astype(f32)
                                   + params["dt_bias"].astype(f32))
        with jax.named_scope("ssd.conv"):
            xbc = jax.nn.silu(
                causal_depthwise_conv(xbc.astype(f32),
                                      params["conv_w"].astype(f32))
                + params["conv_b"].astype(f32)).astype(x.dtype)
        with jax.named_scope("ssd.scan"):
            o = ssd_scan(
                xbc[..., :d].reshape(n, t, h, p), step,
                -jnp.exp(params["A_log"].astype(f32)),
                xbc[..., d:d + bc].reshape(n, t, g, self.d_state),
                xbc[..., d + bc:].reshape(n, t, g, self.d_state),
                z, params["D"], params["norm_w"], self.eps,
                chunk_size=self.chunk_size, layer=self.name or None)
        with jax.named_scope("ssd.out"):
            out = jnp.einsum("ntd,do->nto", o, params["W_out"])
        return out, state


@register_serializable
@dataclasses.dataclass(frozen=True)
class GatedMemoryUnit(FeedForwardLayer):
    """Gated memory unit: ``(m * silu(h W1)) W2`` over (N, T, F), bias-
    free, where ``m`` (N, T, ``d_memory``) is the memory an earlier
    ``MambaMixer`` emitted (its scan's result before the gate): a layer
    that re-reads another layer's state at the cost of two products.
    ``n_out`` is the model width."""
    d_memory: int = 0
    init_std: float = 0.02

    named_scopes = ("gmu",)

    def output_type(self, input_type: InputType) -> InputType:
        t = (input_type.timesteps
             if isinstance(input_type, RecurrentType) else None)
        return RecurrentType(self.n_out, t)

    def initialize(self, key, input_type):
        n_in = self.resolved_n_in(input_type)
        dt = self.param_dtype()
        k1, k2 = jax.random.split(key)
        return {"W1": self.init_std * jax.random.normal(
                    k1, (n_in, self.d_memory), dt),
                "W2": self.init_std * jax.random.normal(
                    k2, (self.d_memory, self.n_out), dt)}

    def mix(self, params, x, memory, mask=None):
        with jax.named_scope("gmu"):
            f32 = jnp.promote_types(jnp.float32, x.dtype)
            gate = jax.nn.silu(jnp.einsum(
                "ntf,fd->ntd", x, params["W1"], preferred_element_type=f32))
            gated = (memory.astype(f32) * gate).astype(x.dtype)
            return jnp.einsum("ntd,do->nto", gated, params["W2"]), ()

    @property
    def extra_inputs(self):
        return ("memory",)

    def apply(self, params, state, x, ctx: LayerContext):
        return self.mix(params, *x)[0], state
