"""Persisted AOT executable cache tests (PR 6, parallel/aot_cache.py).

The cache contract: a fresh PROCESS that points at a saved cache reaches
``assert_warm()`` with zero live compiles and produces outputs bitwise
equal to an uncached engine; ANY fingerprint divergence (weights, shapes,
serving contract, versions) falls through to live compile — the cache can
make a cold start fast, never wrong.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from deeplearning4j_tpu.observe.registry import MetricsRegistry
from deeplearning4j_tpu.parallel.aot_cache import (
    AOTExecutableCache,
    fingerprint,
)
from deeplearning4j_tpu.parallel.serving import ServingEngine

N_IN = 5
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_model(seed: int = 1):
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer
    from deeplearning4j_tpu.nn.layers.output import OutputLayer
    from deeplearning4j_tpu.ops.losses import LossFunction
    from deeplearning4j_tpu.optimize.updaters import Adam
    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater(Adam(1e-2)).list()
            .layer(DenseLayer(n_out=8))
            .layer(OutputLayer(n_out=3, loss=LossFunction.MCXENT))
            .set_input_type(InputType.feed_forward(N_IN)).build())
    return MultiLayerNetwork(conf).init()


def _engine(model, cache_dir, **kw):
    kw.setdefault("batch_limit", 4)
    kw.setdefault("feature_shape", (N_IN,))
    kw.setdefault("registry", MetricsRegistry())
    return ServingEngine(model, aot_cache_dir=cache_dir,
                         model_version="t1", **kw)


# child script: load the cache in a FRESH process (the only honest test
# of a cold start), prove zero live compiles + bitwise-equal output
_CHILD = """
import json, sys
import numpy as np
sys.path.insert(0, {root!r})
from tests.test_aot_cache import _tiny_model, _engine
from deeplearning4j_tpu.observe.registry import MetricsRegistry

reg = MetricsRegistry()
eng = _engine(_tiny_model(), {cache!r}, registry=reg)
try:
    eng.assert_warm()
    x = np.asarray(json.loads({x!r}), np.float32)
    out = eng.output(x)
    stats = eng.stats()
finally:
    eng.shutdown()
live = 0.0
m = reg.get_metric("dl4j_serving_compiles_total")
for key, v in m.series().items():
    if ("phase", "live") in key:
        live += v
print(json.dumps({{"out": np.asarray(out).tolist(),
                   "aot": stats["aot_cache"],
                   "live_compiles": live,
                   "recompiles": stats["recompiles_after_warmup"]}}))
"""


class TestRoundTrip:
    def test_fresh_process_loads_warm_bitwise(self, tmp_path):
        cache = str(tmp_path / "aot")
        m = _tiny_model()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(3, N_IN)).astype(np.float32)
        # process A: cold cache -> live warmup, auto-save
        eng = _engine(m, cache)
        try:
            want = eng.output(x)
            assert eng.aot_cache.state == "cold"       # saved from cold
            assert os.path.exists(os.path.join(cache, "manifest.json"))
        finally:
            eng.shutdown()
        # process B (fresh python): must load every bucket, compile
        # nothing live, and reproduce process A's bytes exactly
        child = _CHILD.format(root=_ROOT, cache=cache,
                              x=json.dumps(x.tolist()))
        proc = subprocess.run(
            [sys.executable, "-c", child], cwd=_ROOT,
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr[-2000:]
        got = json.loads(proc.stdout.strip().splitlines()[-1])
        assert got["aot"]["state"] == "warm"
        assert got["aot"]["hits"] > 0
        assert got["live_compiles"] == 0.0
        assert got["recompiles"] == 0
        assert np.array_equal(
            np.asarray(got["out"], np.float32), want)

    def test_same_process_second_engine_hits(self, tmp_path):
        cache = str(tmp_path / "aot")
        m = _tiny_model()
        e1 = _engine(m, cache)
        e1.shutdown()
        e2 = _engine(m, cache)
        try:
            assert e2.aot_cache.state == "warm"
            assert e2.aot_cache.hits > 0
            e2.assert_warm()
        finally:
            e2.shutdown()


class TestFingerprint:
    def test_weights_divergence_misses(self, tmp_path):
        cache = str(tmp_path / "aot")
        e1 = _engine(_tiny_model(seed=1), cache)
        e1.shutdown()
        # different weights, same everything else -> mismatch, live path
        e2 = _engine(_tiny_model(seed=2), cache)
        try:
            assert e2.aot_cache.state == "mismatch"
            assert "weights_sha256" in e2.aot_cache.reason
            e2.assert_warm()            # live warmup still ran
            x = np.zeros((2, N_IN), np.float32)
            e2.output(x)
        finally:
            e2.shutdown()

    def test_contract_divergence_misses(self, tmp_path):
        cache = str(tmp_path / "aot")
        m = _tiny_model()
        e1 = _engine(m, cache, batch_limit=4)
        e1.shutdown()
        # a different ladder is a different serving contract
        e2 = _engine(m, cache, batch_limit=8)
        try:
            assert e2.aot_cache.state == "mismatch"
            assert "serving" in e2.aot_cache.reason
        finally:
            e2.shutdown()

    def test_corrupt_manifest_falls_through(self, tmp_path):
        cache = str(tmp_path / "aot")
        m = _tiny_model()
        e1 = _engine(m, cache)
        e1.shutdown()
        with open(os.path.join(cache, "manifest.json"), "w") as f:
            f.write("{not json")
        e2 = _engine(m, cache)
        try:
            assert e2.aot_cache.state == "mismatch"
            assert "manifest" in e2.aot_cache.reason
            e2.assert_warm()
        finally:
            e2.shutdown()

    def test_corrupt_blob_partial_load(self, tmp_path):
        cache = str(tmp_path / "aot")
        m = _tiny_model()
        e1 = _engine(m, cache)
        e1.shutdown()
        with open(os.path.join(cache, "bucket_2.f32.stablehlo"),
                  "wb") as f:
            f.write(b"garbage")
        e2 = _engine(m, cache)
        try:
            # the other buckets still load; bucket 2 warms live
            assert e2.aot_cache.state == "warm"
            assert e2.aot_cache.misses >= 1
            e2.assert_warm()
            x = np.zeros((2, N_IN), np.float32)
            assert np.array_equal(e2.output(x), np.asarray(m.output(x)))
        finally:
            e2.shutdown()

    def test_fingerprint_covers_the_contract(self):
        m = _tiny_model()
        params = m.train_state.params
        mstate = m.train_state.model_state
        fp = fingerprint(params, mstate, feature_shape=(N_IN,),
                         dtype=np.float32, ladder=(1, 2, 4),
                         bf16=False, model_version="v1")
        for key in ("weights_sha256", "params_spec", "jax", "jaxlib",
                    "backend", "serving", "model_version"):
            assert key in fp, key
        assert fp["serving"]["ladder"] == [1, 2, 4]


class TestPrecisionEntries:
    """Format-2 manifests hold one entry per precision: an int8 save
    must never satisfy an f32 lookup (and vice versa), while both
    coexist in one cache dir with precision-tagged blobs."""

    def _int8_engine(self, model, cache, **kw):
        from deeplearning4j_tpu.parallel.quant import PrecisionPolicy
        rng = np.random.default_rng(7)
        feats = rng.normal(size=(32, N_IN)).astype(np.float32)
        return _engine(model, cache,
                       precision=PrecisionPolicy.int8(feats), **kw)

    def test_precisions_coexist_and_never_cross(self, tmp_path):
        cache = str(tmp_path / "aot")
        m = _tiny_model()
        # f32 saves first
        e1 = _engine(m, cache)
        e1.shutdown()
        # int8 must NOT hit the f32 entry: cold, with a reason that
        # names the diverged axis
        e2 = self._int8_engine(m, cache)
        try:
            assert e2.aot_cache.state == "cold"
            assert "int8" in e2.aot_cache.reason
            e2.assert_warm()
        finally:
            e2.shutdown()
        with open(os.path.join(cache, "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["format_version"] == 2
        assert sorted(manifest["entries"]) == ["f32", "int8"]
        blobs = sorted(os.listdir(cache))
        assert any(b.endswith(".f32.stablehlo") for b in blobs)
        assert any(b.endswith(".int8.stablehlo") for b in blobs)
        # both precisions now warm-load from the same dir
        for build in (lambda: _engine(m, cache),
                      lambda: self._int8_engine(m, cache)):
            e = build()
            try:
                assert e.aot_cache.state == "warm"
                assert e.aot_cache.hits > 0
                e.assert_warm()
            finally:
                e.shutdown()

    def test_calibration_divergence_named_in_reason(self, tmp_path):
        cache = str(tmp_path / "aot")
        m = _tiny_model()
        e1 = self._int8_engine(m, cache)
        e1.shutdown()
        # tamper with the stored calibration hash: the mismatch reason
        # must name the exact diverged field
        path = os.path.join(cache, "manifest.json")
        with open(path) as f:
            manifest = json.load(f)
        fp = manifest["entries"]["int8"]["fingerprint"]
        fp["serving"]["calibration"] = "deadbeef" * 8
        with open(path, "w") as f:
            json.dump(manifest, f)
        e2 = self._int8_engine(m, cache)
        try:
            assert e2.aot_cache.state == "mismatch"
            assert "serving.calibration" in e2.aot_cache.reason
            e2.assert_warm()
        finally:
            e2.shutdown()


# child: calibrate + quantize in a FRESH process and report the scale
# bits, the calibration hash, and the engine's AOT fingerprint — run
# twice, everything must be bitwise identical (the determinism the
# int8 cache entry's reuse story rests on)
_CALIB_CHILD = """
import json, sys
import numpy as np
sys.path.insert(0, {root!r})
from tests.test_aot_cache import _tiny_model, N_IN
from deeplearning4j_tpu.parallel.aot_cache import fingerprint
from deeplearning4j_tpu.parallel.quant import (
    PrecisionPolicy, quantize_model)

m = _tiny_model()
rng = np.random.default_rng(21)
feats = rng.normal(size=(64, N_IN)).astype(np.float32)
qm = quantize_model(m, PrecisionPolicy.int8(feats))
fp = fingerprint(qm.params, m.train_state.model_state,
                 feature_shape=(N_IN,), dtype=np.float32,
                 ladder=(1, 2, 4), precision="int8",
                 calibration=qm.calibration_hash(), model_version="t1")
print(json.dumps({{
    "scales": {{k: float(np.float32(v)).hex()
               for k, v in sorted(qm.calibration.scales.items())}},
    "calib_hash": qm.calibration.hash(),
    "provenance": qm.calibration_hash(),
    "fingerprint": fp}}, sort_keys=True))
"""


class TestCalibrationDeterminism:
    def test_two_fresh_processes_bitwise_identical(self):
        child = _CALIB_CHILD.format(root=_ROOT)
        runs = []
        for _ in range(2):
            proc = subprocess.run(
                [sys.executable, "-c", child], cwd=_ROOT,
                capture_output=True, text=True, timeout=300,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            assert proc.returncode == 0, proc.stderr[-2000:]
            runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
        a, b = runs
        assert a["scales"] == b["scales"]       # bit-exact hex floats
        assert a["calib_hash"] == b["calib_hash"]
        assert a["provenance"] == b["provenance"]
        assert a["fingerprint"] == b["fingerprint"]
        assert a["fingerprint"]["serving"]["precision"] == "int8"
        assert a["fingerprint"]["serving"]["calibration"] == \
            a["provenance"]


# child script: report the compile-cache directory in effect after each
# step that used to move it (package import, AOT store, serving engine)
_CACHE_DIR_CHILD = """
import json, sys
sys.path.insert(0, {root!r})
import jax
import deeplearning4j_tpu
seen = [jax.config.jax_compilation_cache_dir]
from tests.test_aot_cache import AOTExecutableCache, _engine, _tiny_model
AOTExecutableCache({other!r} + "/store")
seen.append(jax.config.jax_compilation_cache_dir)
eng = _engine(_tiny_model(), {other!r} + "/engine")
eng.shutdown()
seen.append(jax.config.jax_compilation_cache_dir)
print(json.dumps(seen))
"""


# the second "unset" process only has to agree on the path
_CACHE_DIR_IMPORT_ONLY = """
import json, sys
sys.path.insert(0, {root!r})
import jax
import deeplearning4j_tpu
print(json.dumps([jax.config.jax_compilation_cache_dir]))
"""


class TestCompileCacheRule:
    """One rule (deeplearning4j_tpu/__init__.py): JAX_COMPILATION_CACHE_DIR
    wins untouched; unset, one fixed path inside the checkout."""

    def _child(self, tmp_path, env_dir, script=_CACHE_DIR_CHILD):
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        proc = subprocess.run(
            [sys.executable, "-c", script.format(
                root=_ROOT, other=str(tmp_path / "other"))],
            cwd=str(tmp_path), capture_output=True, text=True,
            timeout=300, env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def test_env_dir_is_never_overridden(self, tmp_path):
        d = str(tmp_path / "from_env")
        assert self._child(tmp_path, d) == [d, d, d]

    def test_unset_is_one_fixed_path_in_checkout(self, tmp_path):
        fixed = os.path.join(_ROOT, ".jax_cache")
        assert self._child(tmp_path, None) == [fixed] * 3
        assert self._child(tmp_path, None,
                           _CACHE_DIR_IMPORT_ONLY) == [fixed]
