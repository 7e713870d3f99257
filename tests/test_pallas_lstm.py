"""Fused Pallas LSTM recurrence (ops/pallas_lstm.py): equivalence with
the lax.scan cell — forward, custom-VJP gradients, masking, TBPTT
carries — plus the helper-SPI dispatch rules.

All kernel tests run in interpret mode (CPU); on-TPU timing lives in
benchmarks/lstm_crossover.py.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.nn.layers.recurrent import LSTM, GravesLSTM
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.ops import pallas_lstm
from deeplearning4j_tpu.ops.activations import Activation


def _ref_scan(zx, h0, c0, wh, mask=None):
    """The lax.scan cell, verbatim semantics of LSTM._cell (gate-major,
    sigmoid gates, tanh activation, carry-freezing mask)."""
    t, n, g4 = zx.shape
    h = g4 // 4

    def cell(carry, inp):
        h_prev, c_prev = carry
        zx_t, m = inp if mask is not None else (inp, None)
        z = zx_t + h_prev @ wh
        i = jax.nn.sigmoid(z[:, :h])
        f = jax.nn.sigmoid(z[:, h:2 * h])
        o = jax.nn.sigmoid(z[:, 2 * h:3 * h])
        g = jnp.tanh(z[:, 3 * h:])
        c = f * c_prev + i * g
        hy = o * jnp.tanh(c)
        if m is not None:
            mm = m[:, None]
            hy = mm * hy + (1 - mm) * h_prev
            c = mm * c + (1 - mm) * c_prev
        return (hy, c), hy

    inputs = zx if mask is None else (zx, mask)
    (hT, cT), ys = jax.lax.scan(cell, (h0, c0), inputs)
    return ys, hT, cT


def _inputs(rng, t=7, n=4, h=8, dtype=jnp.float32):
    zx = jnp.asarray(rng.normal(size=(t, n, 4 * h)), dtype)
    wh = jnp.asarray(rng.normal(size=(h, 4 * h)) * 0.3, dtype)
    h0 = jnp.asarray(rng.normal(size=(n, h)), dtype)
    c0 = jnp.asarray(rng.normal(size=(n, h)), dtype)
    mask = jnp.asarray(rng.random((t, n)) > 0.3, dtype)
    return zx, h0, c0, wh, mask


@pytest.mark.parametrize("use_mask", [False, True])
@pytest.mark.parametrize("block_t", [1, 4])
def test_forward_matches_scan(rng, use_mask, block_t):
    zx, h0, c0, wh, mask = _inputs(rng)
    m = mask if use_mask else None
    ys_f, hT_f, cT_f = pallas_lstm.lstm_fused(zx, h0, c0, wh, m,
                                              block_t=block_t)
    ys_r, hT_r, cT_r = _ref_scan(zx, h0, c0, wh, m)
    np.testing.assert_allclose(ys_f, ys_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(hT_f, hT_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(cT_f, cT_r, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("use_mask", [False, True])
@pytest.mark.parametrize("block_t", [1, 4])
def test_gradients_match_scan(rng, use_mask, block_t):
    """Custom-VJP vs autodiff-through-scan on a loss touching all three
    outputs (ys, hT, cT) and all four diff inputs (zx, h0, c0, Wh)."""
    zx, h0, c0, wh, mask = _inputs(rng)
    m = mask if use_mask else None

    def loss(fn):
        def f(zx, h0, c0, wh):
            ys, hT, cT = fn(zx, h0, c0, wh)
            return (jnp.sum(ys * ys) + jnp.sum(2.0 * hT)
                    + jnp.sum(jnp.tanh(cT)))
        return jax.grad(f, argnums=(0, 1, 2, 3))(zx, h0, c0, wh)

    gf = loss(lambda *a: pallas_lstm.lstm_fused(*a, m, block_t=block_t))
    gr = loss(lambda *a: _ref_scan(*a, m))
    for a, b, name in zip(gf, gr, ("dzx", "dh0", "dc0", "dWh")):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-5,
                                   err_msg=name)


def test_masked_ticks_freeze_carry(rng):
    """A fully-masked tail must leave (hT, cT) at their values from the
    last unmasked tick, and contribute zero gradient."""
    zx, h0, c0, wh, _ = _inputs(rng, t=8)
    mask = jnp.ones((8, 4), jnp.float32).at[5:].set(0.0)
    ys, hT, cT = pallas_lstm.lstm_fused(zx, h0, c0, wh, mask)
    np.testing.assert_allclose(hT, ys[4], rtol=1e-6)
    # tail outputs equal the frozen carry (the LAYER zeroes them)
    np.testing.assert_allclose(ys[7], ys[4], rtol=1e-6)

    # gradient w.r.t. masked-tick inputs is exactly zero
    g = jax.grad(lambda zx: jnp.sum(
        pallas_lstm.lstm_fused(zx, h0, c0, wh, mask)[1] ** 2))(zx)
    np.testing.assert_array_equal(np.asarray(g[5:]), 0.0)
    assert np.abs(np.asarray(g[:5])).max() > 0.0


def test_tbptt_chunked_carry_matches_full(rng):
    """Two fused chunks chained through (hT, cT) == one full pass — the
    invariant TBPTT relies on."""
    zx, h0, c0, wh, mask = _inputs(rng, t=10)
    ys, hT, cT = pallas_lstm.lstm_fused(zx, h0, c0, wh, mask)
    ys_a, h_a, c_a = pallas_lstm.lstm_fused(zx[:6], h0, c0, wh, mask[:6])
    ys_b, h_b, c_b = pallas_lstm.lstm_fused(zx[6:], h_a, c_a, wh, mask[6:])
    np.testing.assert_allclose(np.concatenate([ys_a, ys_b]), ys,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h_b, hT, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(c_b, cT, rtol=2e-5, atol=2e-5)


def test_bfloat16_close_to_scan(rng):
    zx, h0, c0, wh, mask = _inputs(rng, dtype=jnp.bfloat16)
    ys_f, hT_f, _ = pallas_lstm.lstm_fused(zx, h0, c0, wh, mask)
    ys_r, hT_r, _ = _ref_scan(zx, h0, c0, wh, mask)
    np.testing.assert_allclose(np.asarray(ys_f, np.float32),
                               np.asarray(ys_r, np.float32),
                               rtol=0.05, atol=0.05)
    assert ys_f.dtype == jnp.bfloat16


class TestLayerWiring:
    def _layer_out(self, monkeypatch, impl, mask=None, layer_cls=LSTM,
                   **kw):
        monkeypatch.setenv(pallas_lstm._IMPL_ENV, impl)
        layer = layer_cls(n_out=8, n_in=5, name="l", **kw)
        params = layer.initialize(jax.random.PRNGKey(0),
                                  InputType.recurrent(5, 6))
        x = jnp.asarray(np.random.default_rng(3).normal(size=(4, 6, 5)),
                        jnp.float32)
        ctx = LayerContext(train=False, mask=mask)
        out, st = layer.apply(params, {}, x, ctx)
        return out, st

    @pytest.mark.parametrize("use_mask", [False, True])
    def test_apply_fused_equals_scan(self, monkeypatch, use_mask):
        mask = (jnp.ones((4, 6), jnp.float32).at[:, 4:].set(0.0)
                if use_mask else None)
        out_s, st_s = self._layer_out(monkeypatch, "scan", mask)
        out_f, st_f = self._layer_out(monkeypatch, "fused", mask)
        np.testing.assert_allclose(out_f, out_s, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(st_f["last_h"], st_s["last_h"],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(st_f["last_c"], st_s["last_c"],
                                   rtol=2e-5, atol=2e-5)
        if use_mask:  # layer zeroes masked outputs in both impls
            np.testing.assert_array_equal(
                np.asarray(out_f[:, 4:]), 0.0)

    def test_fused_route_actually_taken(self, monkeypatch):
        calls = []
        orig = pallas_lstm.lstm_fused
        monkeypatch.setattr(pallas_lstm, "lstm_fused",
                            lambda *a, **k: calls.append(1) or orig(*a, **k))
        self._layer_out(monkeypatch, "fused")
        assert calls
        calls.clear()
        self._layer_out(monkeypatch, "scan")
        assert not calls

    def test_graves_and_nondefault_stay_on_scan(self, monkeypatch):
        """Peepholes / non-default activations / hidden_major are not
        what the kernel computes — they must never route to it."""
        assert not GravesLSTM(n_out=8, n_in=5)._fused_eligible()
        assert not LSTM(n_out=8, n_in=5,
                        gate_layout="hidden_major")._fused_eligible()
        assert not LSTM(n_out=8, n_in=5,
                        gate_activation=Activation.HARDSIGMOID
                        )._fused_eligible()
        assert LSTM(n_out=8, n_in=5)._fused_eligible()

        calls = []
        orig = pallas_lstm.lstm_fused
        monkeypatch.setattr(pallas_lstm, "lstm_fused",
                            lambda *a, **k: calls.append(1) or orig(*a, **k))
        self._layer_out(monkeypatch, "fused", layer_cls=GravesLSTM)
        assert not calls

    def test_layer_gradients_match(self, monkeypatch):
        layer = LSTM(n_out=8, n_in=5, name="l")
        params = layer.initialize(jax.random.PRNGKey(0),
                                  InputType.recurrent(5, 6))
        x = jnp.asarray(np.random.default_rng(3).normal(size=(4, 6, 5)),
                        jnp.float32)
        mask = jnp.ones((4, 6), jnp.float32).at[:, 4:].set(0.0)

        def grads(impl):
            monkeypatch.setenv(pallas_lstm._IMPL_ENV, impl)
            def f(p, x):
                out, _ = layer.apply(p, {}, x,
                                     LayerContext(train=False, mask=mask))
                return jnp.sum(out * out)
            return jax.grad(f, argnums=(0, 1))(params, x)

        gs, gf = grads("scan"), grads("fused")
        np.testing.assert_allclose(gf[1], gs[1], rtol=5e-4, atol=1e-5)
        for k in ("Wx", "Wh", "b"):
            np.testing.assert_allclose(gf[0][k], gs[0][k], rtol=5e-4,
                                       atol=1e-5, err_msg=k)


class TestDispatch:
    def test_auto_is_scan_without_measured_thresholds(self, monkeypatch):
        """Honest-threshold discipline: with no crossover measurements
        recorded, auto must not route to the kernel anywhere."""
        monkeypatch.delenv(pallas_lstm._IMPL_ENV, raising=False)
        monkeypatch.setattr(pallas_lstm, "_MEASURED_FUSED_WINS", ())
        assert pallas_lstm.choose_impl(256, 512, 128,
                                       backend="tpu") == "scan"
        assert pallas_lstm.choose_impl(256, 512, 128,
                                       backend="cpu") == "scan"

    def test_measured_rule_routes_on_tpu_only(self, monkeypatch):
        monkeypatch.delenv(pallas_lstm._IMPL_ENV, raising=False)
        monkeypatch.setattr(pallas_lstm, "_MEASURED_FUSED_WINS",
                            ((64, 256, 32),))
        assert pallas_lstm.choose_impl(256, 512, 128,
                                       backend="tpu") == "fused"
        assert pallas_lstm.choose_impl(32, 512, 128,
                                       backend="tpu") == "scan"
        assert pallas_lstm.choose_impl(256, 512, 128,
                                       backend="cpu") == "scan"

    def test_compiled_kernel_refuses_unaligned_hidden(self, monkeypatch):
        """H=200 (TextGenerationLSTM's default) cannot be lane-sliced by
        the compiled kernel: auto never routes it there, and a forced
        fused call raises with the reason instead of falling back."""
        monkeypatch.delenv(pallas_lstm._IMPL_ENV, raising=False)
        monkeypatch.setattr(pallas_lstm, "_MEASURED_FUSED_WINS",
                            ((1, 1, 1),))
        assert pallas_lstm.choose_impl(64, 200, 32,
                                       backend="tpu") == "scan"
        assert pallas_lstm.choose_impl(64, 256, 32,
                                       backend="tpu") == "fused"
        z = jnp.zeros((4, 8, 800), jnp.float32)
        hc = jnp.zeros((8, 200), jnp.float32)
        wh = jnp.zeros((200, 800), jnp.float32)
        with pytest.raises(ValueError, match="multiple of 128"):
            pallas_lstm.lstm_fused(z, hc, hc, wh, interpret=False)

    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv(pallas_lstm._IMPL_ENV, "fused")
        assert pallas_lstm.choose_impl(1, 1, 1, backend="cpu") == "fused"
        monkeypatch.setenv(pallas_lstm._IMPL_ENV, "scan")
        monkeypatch.setattr(pallas_lstm, "_MEASURED_FUSED_WINS",
                            ((1, 1, 1),))
        assert pallas_lstm.choose_impl(256, 512, 128,
                                       backend="tpu") == "scan"
