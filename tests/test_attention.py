"""Attention layers + ring-attention sequence parallelism.

The correctness pattern follows SURVEY §4's "accelerated-vs-reference
equivalence" idea: the sequence-parallel ring implementation must equal
the single-chip attention bit-for-practical-purposes, on the virtual
8-device CPU mesh (conftest.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from deeplearning4j_tpu.nn.layers.attention import (
    LearnedPositionalEmbedding,
    SelfAttentionLayer,
    TransformerEncoderBlock,
    scaled_dot_product_attention,
)
from deeplearning4j_tpu.ops.visibility import Causal, Visibility
from deeplearning4j_tpu.parallel.ring_attention import ring_self_attention


def _qkv(n=2, t=16, h=4, dh=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(n, t, h, dh))
                             .astype(np.float32))
    return mk(), mk(), mk()


def _mesh():
    return Mesh(np.array(jax.devices()[:8]), ("sp",))


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_single_chip(self, causal):
        q, k, v = _qkv()
        want = scaled_dot_product_attention(
            q, k, v, visibility=Causal() if causal else Visibility())
        got = ring_self_attention(q, k, v, _mesh(), causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_masked_matches_single_chip(self):
        q, k, v = _qkv(seed=1)
        mask = jnp.asarray((np.random.default_rng(2)
                            .random((2, 16)) > 0.3).astype(np.float32))
        want = scaled_dot_product_attention(q, k, v, mask=mask)
        got = ring_self_attention(q, k, v, _mesh(), mask=mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_fully_masked_sample_has_finite_gradients(self):
        """Regression: a fully-padded sequence in the batch must not
        poison gradients with NaN (softmax-VJP over -inf rows)."""
        q, k, v = _qkv(n=2, t=8, seed=9)
        mask = jnp.asarray(np.stack([np.ones(8), np.zeros(8)])
                           .astype(np.float32))

        def loss_single(q, k, v):
            return jnp.sum(scaled_dot_product_attention(
                q, k, v, mask=mask) ** 2)

        g = jax.grad(loss_single)(q, k, v)
        assert np.isfinite(np.asarray(g)).all()

        mesh = _mesh()

        def loss_ring(q, k, v):
            return jnp.sum(ring_self_attention(
                q, k, v, mesh, mask=mask) ** 2)

        gr = jax.grad(loss_ring)(q, k, v)
        assert np.isfinite(np.asarray(gr)).all()
        np.testing.assert_allclose(np.asarray(gr), np.asarray(g),
                                   rtol=1e-4, atol=1e-5)

    def test_gradients_flow_through_ring(self):
        q, k, v = _qkv(t=8, seed=3)
        mesh = _mesh()

        def loss_ring(q, k, v):
            return jnp.sum(ring_self_attention(q, k, v, mesh) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(scaled_dot_product_attention(q, k, v) ** 2)

        g_ring = jax.grad(loss_ring)(q, k, v)
        g_ref = jax.grad(loss_ref)(q, k, v)
        np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-5)


class TestAttentionLayers:
    def test_self_attention_in_network(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.models.multi_layer_network import (
            MultiLayerNetwork)
        from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.inputs import InputType
        from deeplearning4j_tpu.nn.layers.output import RnnOutputLayer
        from deeplearning4j_tpu.ops.losses import LossFunction
        from deeplearning4j_tpu.optimize.updaters import Adam

        conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
                .list()
                .layer(LearnedPositionalEmbedding(max_len=32))
                .layer(TransformerEncoderBlock(n_out=16, n_heads=4))
                .layer(RnnOutputLayer(n_out=3,
                                      loss=LossFunction.MCXENT))
                .set_input_type(InputType.recurrent(16, 10)).build())
        m = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(4, 10, 16)).astype(np.float32)
        y = np.zeros((4, 10, 3), np.float32)
        y[..., 0] = 1.0
        before = m.score(DataSet(x, y))
        for _ in range(10):
            m.fit(DataSet(x, y))
        assert m.score(DataSet(x, y)) < before
        out = m.output(x)
        assert out.shape == (4, 10, 3)

    def test_causal_mask_blocks_future(self):
        layer = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2, causal=True)
        from deeplearning4j_tpu.nn.inputs import InputType
        from deeplearning4j_tpu.nn.layers.base import LayerContext
        params = layer.initialize(jax.random.PRNGKey(0),
                                  InputType.recurrent(8, 6))
        x = jnp.asarray(np.random.default_rng(1).normal(
            size=(1, 6, 8)).astype(np.float32))
        y1, _ = layer.apply(params, {}, x, LayerContext())
        # changing the future must not change step 0
        x2 = x.at[:, 3:].set(0.0)
        y2, _ = layer.apply(params, {}, x2, LayerContext())
        np.testing.assert_allclose(np.asarray(y1[:, :3]),
                                   np.asarray(y2[:, :3]), rtol=1e-5,
                                   atol=1e-6)

    def test_attention_gradient_check(self):
        """Finite-difference vs autodiff on the attention layer — the
        reference's gradient-check backbone (GradientCheckUtil.java:109)
        applied to the new layer family."""
        from deeplearning4j_tpu.gradientcheck.gradient_check_util import (
            check_gradients)
        from deeplearning4j_tpu.nn.inputs import InputType
        from deeplearning4j_tpu.nn.layers.base import LayerContext
        layer = SelfAttentionLayer(n_in=6, n_out=6, n_heads=2)
        params = layer.initialize(jax.random.PRNGKey(0),
                                  InputType.recurrent(6, 5))
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 5, 6)))

        def loss(p):
            y, _ = layer.apply(p, {}, x, LayerContext())
            return jnp.sum(y ** 2)

        assert check_gradients(loss, params, max_rel_error=1e-5)

    def test_positional_embedding_shape(self):
        from deeplearning4j_tpu.nn.inputs import InputType
        from deeplearning4j_tpu.nn.layers.base import LayerContext
        pe = LearnedPositionalEmbedding(max_len=16)
        params = pe.initialize(jax.random.PRNGKey(0),
                               InputType.recurrent(4, 8))
        x = jnp.zeros((2, 8, 4))
        y, _ = pe.apply(params, {}, x, LayerContext())
        assert y.shape == (2, 8, 4)
        assert not np.allclose(np.asarray(y), 0.0)


class TestUlyssesAttention:
    """All-to-all sequence parallelism (the alternative SP strategy to
    the ring): same math as single-chip attention."""

    def _mesh4(self):
        return Mesh(np.array(jax.devices()[:4]), ("sp",))

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_single_chip(self, causal):
        from deeplearning4j_tpu.parallel.ring_attention import (
            ulysses_self_attention)
        q, k, v = _qkv()
        want = scaled_dot_product_attention(
            q, k, v, visibility=Causal() if causal else Visibility())
        got = ulysses_self_attention(q, k, v, self._mesh4(), causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_masked_matches_single_chip(self):
        from deeplearning4j_tpu.parallel.ring_attention import (
            ulysses_self_attention)
        q, k, v = _qkv(seed=3)
        mask = jnp.asarray((np.random.default_rng(4)
                            .random((2, 16)) > 0.3).astype(np.float32))
        want = scaled_dot_product_attention(q, k, v, mask=mask)
        got = ulysses_self_attention(q, k, v, self._mesh4(), mask=mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_gradients_match_single_chip(self):
        from deeplearning4j_tpu.parallel.ring_attention import (
            ulysses_self_attention)
        q, k, v = _qkv(t=8, seed=5)
        mesh = self._mesh4()

        def loss_sp(q, k, v):
            return jnp.sum(
                ulysses_self_attention(q, k, v, mesh, causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(
                scaled_dot_product_attention(q, k, v,
                                             visibility=Causal()) ** 2)

        g_sp = jax.grad(loss_sp, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_sp, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_heads_divisibility_enforced(self):
        from deeplearning4j_tpu.parallel.ring_attention import (
            ulysses_self_attention)
        q, k, v = _qkv(h=3)
        with pytest.raises(ValueError, match="divisible"):
            ulysses_self_attention(q, k, v, self._mesh4())
