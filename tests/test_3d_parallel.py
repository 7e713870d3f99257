"""3D dp×tp×pp composition (VERDICT r4 #3): one mesh carrying data,
model, and pipe axes — GSPMD dp batch sharding + Megatron TP inside each
stage + the circular pipeline schedule (shard_map manual over 'pipe'
only). Golden-tested against the sequential single-stack math, plus
sharded checkpoint save→restore across DIFFERENT 3D layouts."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.pipeline import (
    PIPE_AXIS, PipelinedTransformerLM, restack_stages)


VOCAB, WIDTH, T = 16, 8, 6


def _mesh(dp, tp, pp):
    devs = np.asarray(jax.devices()[: dp * tp * pp]).reshape(dp, tp, pp)
    return Mesh(devs, ("data", "model", PIPE_AXIS))


def _lm(mesh, n_layers):
    return PipelinedTransformerLM(vocab=VOCAB, width=WIDTH, n_heads=2,
                                  n_layers=n_layers, max_len=T,
                                  mesh=mesh, remat=True)


def _data(batch, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, VOCAB, (batch, T))),
            jnp.asarray(rng.integers(0, VOCAB, (batch, T))))


class Test3DComposition:
    def test_pipelined_tp_matches_sequential(self):
        mesh = _mesh(2, 2, 2)
        lm = _lm(mesh, n_layers=4)
        params = lm.shard_params(lm.init(jax.random.PRNGKey(3)))
        assert not params["blocks"]["attn"]["Wqkv"].sharding \
            .is_fully_replicated
        toks, tgts = _data(8)
        toks = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
        tgts = jax.device_put(tgts, NamedSharding(mesh, P("data", None)))
        with mesh:
            pipelined = float(jax.jit(lm.loss)(params, toks, tgts))
            ref = float(lm.loss(params, toks, tgts, pipelined=False))
        assert pipelined == pytest.approx(ref, rel=1e-5)

    def test_3d_train_step_moves_params(self):
        mesh = _mesh(2, 2, 2)
        lm = _lm(mesh, n_layers=4)
        params = lm.shard_params(lm.init(jax.random.PRNGKey(4)))
        toks, tgts = _data(8, seed=1)

        @jax.jit
        def step(p, toks, tgts):
            loss, g = jax.value_and_grad(lm.loss)(p, toks, tgts)
            return jax.tree_util.tree_map(
                lambda a, b: a - 0.1 * b, p, g), loss

        with mesh:
            p1, l1 = step(params, toks, tgts)
            p2, l2 = step(p1, toks, tgts)
        assert np.isfinite(float(l1)) and float(l2) < float(l1)
        # TP sharding survives the update
        assert not p2["blocks"]["attn"]["Wqkv"].sharding \
            .is_fully_replicated


class Test3DCheckpointResharding:
    def test_cross_layout_restore(self, tmp_path):
        """Save on a 2dp×2tp×2pp layout (circular, 2 stages × 2
        repeats), restore onto 1dp×2tp×4pp (4 straight stages) — the
        stage-dim restack + explicit target shardings must reproduce
        the exact same function."""
        from types import SimpleNamespace

        from deeplearning4j_tpu.optimize.solver import TrainState
        from deeplearning4j_tpu.parallel.checkpoint import (
            restore_sharded, save_sharded)

        mesh_a = _mesh(2, 2, 2)
        lm_a = _lm(mesh_a, n_layers=4)
        params_a = lm_a.shard_params(lm_a.init(jax.random.PRNGKey(7)))
        toks, tgts = _data(4, seed=2)
        with mesh_a:
            ref = float(jax.jit(lm_a.loss)(params_a, toks, tgts))

        ts = TrainState(params_a, {}, {}, jnp.zeros((), jnp.int32))
        path = save_sharded(ts, str(tmp_path))

        mesh_b = _mesh(1, 2, 4)
        lm_b = _lm(mesh_b, n_layers=4)
        tmpl = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params_a)
        shim = SimpleNamespace(train_state=TrainState(
            tmpl, {}, {}, jnp.zeros((), jnp.int32)))
        restored = restore_sharded(
            shim, path, mesh=mesh_b,
            param_shardings=lm_b.param_shardings(tmpl))
        params_b = dict(restored.params)
        # layout A stores device-major (2 stages × 2 repeats): global
        # stage order [0,2,1,3]; layout B (4 stages × 1) wants [0,1,2,3]
        params_b["blocks"] = restack_stages(
            params_b["blocks"], from_devices=2, to_devices=4)
        with mesh_b:
            got = float(jax.jit(lm_b.loss)(params_b, toks, tgts))
        assert got == pytest.approx(ref, rel=1e-5)

    def test_restack_roundtrip(self):
        x = {"w": jnp.arange(8.0).reshape(8, 1)}
        there = restack_stages(x, from_devices=4, to_devices=2)
        back = restack_stages(there, from_devices=2, to_devices=4)
        np.testing.assert_array_equal(back["w"], x["w"])
