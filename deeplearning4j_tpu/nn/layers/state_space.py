"""State-space layers: the selective scan (Mamba-1) and its token mixer.

A selective state-space layer keeps, for each of its ``D`` channels, a
state of ``S`` numbers and moves it by a transition that depends on the
token:

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
