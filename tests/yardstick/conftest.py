"""A whole second benchmark made of new files only.

``dummy_root`` is a directory that holds a ``BENCHMARK.json`` and, under
``yardstick/``, one configuration, its reference, two traffic mixes and
one per-layer metric that the real tree does not have. The harness code
stays the repo's own. Running its cells shows that a later PR can add a
configuration, a traffic mix, a metric and a cell without editing a file
that exists, and gives the drivers a preset small enough for the CPU.
"""

import json
import textwrap

import pytest

CONFIG = {
    "name": "dummy-mlp", "source": "none: a test preset",
    "features": 6, "hidden": 8, "num_classes": 3,
    "batch": 16, "examples": 128, "reference": "dummy_mlp",
    "loss_tolerance": 1e-4, "output_tolerance": 1e-4,
}

BUILD = '''
import numpy as np


def build(cfg, seed):
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer
    from deeplearning4j_tpu.nn.layers.output import OutputLayer
    from deeplearning4j_tpu.ops.activations import Activation
    from deeplearning4j_tpu.ops.losses import LossFunction
    from deeplearning4j_tpu.optimize.updaters import Adam
    conf = (NeuralNetConfiguration.Builder().seed(seed).updater(Adam(1e-2))
            .list().layer(DenseLayer(n_out=cfg["hidden"],
                                     activation=Activation.TANH))
            .layer(OutputLayer(n_out=cfg["num_classes"],
                               loss=LossFunction.MCXENT))
            .set_input_type(InputType.feed_forward(cfg["features"])).build())
    return MultiLayerNetwork(conf)


def _dataset(cfg, seed, n):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    x = np.random.default_rng(seed).normal(
        size=(n, cfg["features"])).astype(np.float32)
    y = np.eye(cfg["num_classes"], dtype=np.float32)[
        np.argmax(x[:, :cfg["num_classes"]], 1)]
    return DataSet(x, y)


def train_set(cfg, seed, batch):
    from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator
    return ArrayDataSetIterator(_dataset(cfg, seed, cfg["examples"]), batch,
                                shuffle=True, seed=seed, drop_last=True)


def check_batch(cfg, seed, rows):
    return _dataset(cfg, seed + 1, rows)


def request_rows(cfg, seed, n):
    return _dataset(cfg, seed, n).features


def feature_shape(cfg):
    return (cfg["features"],)


def train_flops_per_example(cfg):
    return 6 * (cfg["features"] * cfg["hidden"]
                + cfg["hidden"] * cfg["num_classes"])
'''

REFERENCE = '''
import jax
import jax.numpy as jnp


def _logits(params, x):
    names = sorted(params)           # layer_0, layer_1
    h = x @ params[names[0]]["W"] + params[names[0]]["b"]
    h = jnp.tanh(h)
    return h @ params[names[1]]["W"] + params[names[1]]["b"]


def loss(cfg, params, state, features, labels):
    logp = jax.nn.log_softmax(_logits(params, jnp.asarray(features[0])))
    return -jnp.mean(jnp.sum(labels[0] * logp, -1))


def predict(cfg, params, state, features):
    return jax.nn.softmax(_logits(params, jnp.asarray(features[0])))
'''

METRIC = '''
"""A metric the real tree does not have: steps the driver counted."""


def read(obs):
    return obs.facts.get("steps")
'''


def _traffic(name, **kw):
    return dict({"name": name, "trace_seconds": 1}, **kw)


TRAFFIC = {
    "dummy-fit": _traffic("dummy-fit", driver="fit_loop", workers=1,
                          epochs_per_call=2, warmup_steps=2, check_rows=8),
    "dummy-fit-dp4": _traffic("dummy-fit-dp4", driver="fit_loop", workers=4,
                              epochs_per_call=2, warmup_steps=2,
                              check_rows=8),
    "dummy-serve": _traffic(
        "dummy-serve", driver="open_loop", batch_limit=8, rate_per_s=300.0,
        limit_ms=250.0, request_pool_rows=256, calibration_rows=32,
        check_requests=8,
        rows=[{"share": 0.6, "low": 1, "high": 1},
              {"share": 0.3, "low": 2, "high": 4},
              {"share": 0.1, "low": 5, "high": 11}]),
}

MANIFEST = {
    "command": ["python3", "-m", "yardstick.run"],
    "paths": ["yardstick"],
    "run_seconds": 1,
    "configs": [{"name": "dummy-mlp", "source": "none",
                 "file": "yardstick/configs/dummy-mlp/config.json",
                 "reduced": [], "why": "a test preset"}],
    "workloads": [
        {"name": f"dummy-mlp.{t[6:]}", "config": "dummy-mlp", "traffic": t,
         "chips": 4 if t.endswith("dp4") else 1, "why": "a test cell"}
        for t in TRAFFIC],
    "end_to_end": [
        {"name": "train_examples_per_s_per_chip", "unit": "examples/s/chip",
         "better": "higher", "bound": 0.1, "source": "host_clock",
         "workloads": ["dummy-mlp.fit", "dummy-mlp.fit-dp4"]},
        {"name": "serve_latency_p50_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock",
         "workloads": ["dummy-mlp.serve"]},
        {"name": "serve_latency_p99_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock",
         "workloads": ["dummy-mlp.serve"]},
        {"name": "serve_goodput_per_s", "unit": "requests/s",
         "better": "higher", "bound": 0.1, "source": "host_clock",
         "workloads": ["dummy-mlp.serve"]},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
         "source": "host_clock"}],
    "per_layer": [
        {"name": "dummy_steps", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "step",
         "moves": "train_examples_per_s_per_chip",
         "workloads": ["dummy-mlp.fit", "dummy-mlp.fit-dp4"]}],
}


@pytest.fixture()
def dummy_root(tmp_path):
    ys = tmp_path / "yardstick"
    for sub in ("configs/dummy-mlp", "reference", "traffic", "metrics"):
        (ys / sub).mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(MANIFEST))
    (ys / "configs/dummy-mlp/config.json").write_text(json.dumps(CONFIG))
    (ys / "configs/dummy-mlp/build.py").write_text(textwrap.dedent(BUILD))
    (ys / "reference/dummy_mlp.py").write_text(textwrap.dedent(REFERENCE))
    (ys / "metrics/dummy_steps.py").write_text(textwrap.dedent(METRIC))
    for name, body in TRAFFIC.items():
        (ys / f"traffic/{name}.json").write_text(json.dumps(body))
    return tmp_path
