"""Model serialization — zip checkpoint format.

Analog of the reference's ``ModelSerializer``
(deeplearning4j-nn/.../util/ModelSerializer.java — writeModel:109 writes
``configuration.json``, ``coefficients.bin``, ``updaterState.bin``).
Same zip layout idea, arrays stored as .npy entries:

    configuration.json    — MultiLayerConfiguration / CGC JSON (serde)
    params/<path>.npy     — one entry per parameter leaf
    state/<path>.npy      — non-trainable state (BN stats)
    updater/<path>.npy    — optimizer state leaves (optional, for exact resume)
    meta.json             — model class, iteration/epoch counters

Path encoding: pytree paths joined with '/'. Restores are exact: a model
saved with its updater resumes training bit-identically (the reference's
``restoreMultiLayerNetwork(..., loadUpdater=true)``).
"""

from __future__ import annotations

import io
import json
import zipfile
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.optimize.solver import TrainState
from deeplearning4j_tpu.utils import serde


import functools


@functools.cache
def _ensure_registry():
    """Import every module that registers serializable config types, so a
    checkpoint loads in a fresh interpreter without the caller having
    imported the layer zoo first (the reference gets this for free from
    classpath scanning — NeuralNetConfiguration.java:434). Walks the whole
    ``nn`` package so newly added layer modules register automatically;
    cached so repeated restores skip the filesystem walk."""
    import importlib
    import pkgutil

    import deeplearning4j_tpu.nn as nn_pkg
    for info in pkgutil.walk_packages(nn_pkg.__path__,
                                      prefix="deeplearning4j_tpu.nn."):
        importlib.import_module(info.name)
    importlib.import_module("deeplearning4j_tpu.optimize.updaters")
    importlib.import_module("deeplearning4j_tpu.optimize.schedules")


def _flatten_with_paths(tree) -> Dict[str, np.ndarray]:
    flat = {}
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in leaves_with_paths:
        key = "/".join(_path_part(p) for p in path)
        flat[key] = np.asarray(leaf)  # host-sync-ok: checkpoint save copies to host by design
    return flat


def _path_part(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return f"#{p.idx}"
    return str(p)


def _unflatten_like(template, flat: Dict[str, np.ndarray]):
    """Rebuild arrays into the same treedef as ``template``."""
    leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    new_leaves = []
    for path, leaf in leaves_with_paths:
        key = "/".join(_path_part(p) for p in path)
        if key not in flat:
            raise KeyError(f"checkpoint missing array: {key}")
        arr = flat[key]
        # jnp.array(copy=True), never asarray: on the CPU backend asarray
        # zero-copy aliases any 64-byte-aligned host array (astype/reshape
        # to the same dtype/shape are no-ops that keep the alias), and a
        # donated train step after restore would then hand XLA a buffer
        # numpy still owns — intermittent heap corruption on restore->fit
        new_leaves.append(
            jnp.array(arr, copy=True).astype(leaf.dtype).reshape(leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def _write_tree(zf: zipfile.ZipFile, prefix: str, tree):
    for key, arr in _flatten_with_paths(tree).items():
        buf = io.BytesIO()
        np.save(buf, arr)
        zf.writestr(f"{prefix}/{key}.npy", buf.getvalue())


def _read_tree(zf: zipfile.ZipFile, prefix: str) -> Dict[str, np.ndarray]:
    out = {}
    plen = len(prefix) + 1
    for name in zf.namelist():
        if name.startswith(prefix + "/") and name.endswith(".npy"):
            with zf.open(name) as f:
                out[name[plen:-4]] = np.load(io.BytesIO(f.read()))
    return out


def save_model(model, path: str, save_updater: bool = False):
    """reference: ModelSerializer.writeModel:109."""
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph
    from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork

    if model.train_state is None:
        model.init()
    with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as zf:
        zf.writestr("configuration.json", model.conf.to_json())
        _write_tree(zf, "params", model.train_state.params)
        _write_tree(zf, "state", model.train_state.model_state)
        if save_updater:
            _write_tree(zf, "updater", model.train_state.opt_state)
        meta = {
            "model_class": type(model).__name__,
            "iteration": int(model.train_state.iteration),
            "epoch": model.epoch_count,
            "has_updater": save_updater,
            "framework_version": "0.2.0",
            # packed-QKV column order for attention layers; 0.1.0
            # checkpoints (no tag) used which-major ([q|k|v] blocks)
            "qkv_layout": "head_major",
        }
        zf.writestr("meta.json", json.dumps(meta))


def _named_layers(model) -> Dict[str, Any]:
    if hasattr(model, "layers"):          # MultiLayerNetwork
        return {l.name: l for l in model.layers}
    return {n.name: n.layer for n in model._layer_nodes}  # ComputationGraph


def _migrate_qkv_layout(model, params):
    """Upgrade pre-0.2.0 checkpoints: attention QKV packing changed from
    which-major ([q|k|v] column blocks) to head-major ((head, which, dh))
    so tensor parallelism can shard whole heads with contiguous tiles.
    Returns params with every Wqkv/bqkv re-packed; other leaves shared."""
    from deeplearning4j_tpu.nn.layers.attention import (
        SelfAttentionLayer, TransformerEncoderBlock)

    def repack(p, n_heads, n_out):
        dh = n_out // n_heads
        out = dict(p)
        if "Wqkv" in p:
            w = p["Wqkv"]
            f = w.shape[0]
            out["Wqkv"] = (w.reshape(f, 3, n_heads, dh)
                           .transpose(0, 2, 1, 3).reshape(f, 3 * n_out))
        if "bqkv" in p:
            out["bqkv"] = (p["bqkv"].reshape(3, n_heads, dh)
                           .transpose(1, 0, 2).reshape(-1))
        return out

    new = dict(params)
    for name, layer in _named_layers(model).items():
        lp = new.get(name)
        if not isinstance(lp, dict):
            continue
        if isinstance(layer, TransformerEncoderBlock) and "attn" in lp:
            lp = dict(lp)
            lp["attn"] = repack(lp["attn"], layer.n_heads, layer.n_out)
            new[name] = lp
        elif isinstance(layer, SelfAttentionLayer) and "Wqkv" in lp:
            new[name] = repack(lp, layer.n_heads, layer.n_out)
    return new


def _upgrade_layer_states(model, flat: Dict[str, np.ndarray]):
    """Saved state arrays by path, each layer's own passed through its
    ``upgrade_state``: a layer whose state changed form since the file
    was written says there how the old form reads now."""
    out = dict(flat)
    for name, layer in _named_layers(model).items():
        prefix = f"{name}/"
        saved = {k[len(prefix):]: v for k, v in flat.items()
                 if k.startswith(prefix)}
        if saved:
            out.update({prefix + k: v
                        for k, v in layer.upgrade_state(saved).items()})
    return out


def _migrate_qkv_opt_state(model, opt_state):
    """Apply the same which-major → head-major repack to optimizer-state
    leaves that mirror an attention param (Adam mu/nu etc.): each leaf's
    path names the layer and ends in Wqkv/bqkv. Without this, restored
    moments pair with the wrong weight columns after migration."""
    from deeplearning4j_tpu.nn.layers.attention import (
        SelfAttentionLayer, TransformerEncoderBlock)
    heads = {}
    for name, layer in _named_layers(model).items():
        if isinstance(layer, (SelfAttentionLayer, TransformerEncoderBlock)):
            heads[name] = (layer.n_heads, layer.n_out)

    def fix(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        last = keys[-1] if keys else None
        if last not in ("Wqkv", "bqkv"):
            return leaf
        layer_name = next((k for k in keys if k in heads), None)
        if layer_name is None:
            return leaf
        n_heads, n_out = heads[layer_name]
        dh = n_out // n_heads
        if last == "Wqkv" and leaf.ndim == 2 \
                and leaf.shape[1] == 3 * n_out:
            f = leaf.shape[0]
            return (leaf.reshape(f, 3, n_heads, dh)
                    .transpose(0, 2, 1, 3).reshape(f, 3 * n_out))
        if last == "bqkv" and leaf.ndim == 1 \
                and leaf.shape[0] == 3 * n_out:
            return (leaf.reshape(3, n_heads, dh)
                    .transpose(1, 0, 2).reshape(-1))
        return leaf

    flat, tree = jax.tree_util.tree_flatten_with_path(opt_state)
    return jax.tree_util.tree_unflatten(
        tree, [fix(p, l) for p, l in flat])


def _restore(path: str, expected_class: str, loader, load_updater: bool):
    _ensure_registry()
    with zipfile.ZipFile(path, "r") as zf:
        meta = json.loads(zf.read("meta.json"))
        if meta["model_class"] != expected_class:
            raise TypeError(f"checkpoint holds a {meta['model_class']}, not a"
                            f" {expected_class}")
        conf = loader(zf.read("configuration.json").decode())
        from deeplearning4j_tpu.models.computation_graph import ComputationGraph
        from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
        cls = (MultiLayerNetwork if expected_class == "MultiLayerNetwork"
               else ComputationGraph)
        model = cls(conf)
        model.init()
        migrate = meta.get("qkv_layout") != "head_major"
        params = _unflatten_like(model.train_state.params, _read_tree(zf, "params"))
        if migrate:
            params = _migrate_qkv_layout(model, params)
        state = _unflatten_like(
            model.train_state.model_state,
            _upgrade_layer_states(model, _read_tree(zf, "state")))
        opt_state = model.train_state.opt_state
        if load_updater and meta.get("has_updater"):
            opt_state = _unflatten_like(opt_state, _read_tree(zf, "updater"))
            if migrate:
                opt_state = _migrate_qkv_opt_state(model, opt_state)
        model.train_state = TrainState(params, state, opt_state,
                                       jnp.asarray(meta["iteration"], jnp.int32))
        model.epoch_count = meta.get("epoch", 0)
        return model


def restore_model(path: str, load_updater: bool = False):
    """Class-agnostic restore: reads the checkpoint's own class tag
    (reference analog: ModelGuesser.loadModelGuess for DL4J zips)."""
    with zipfile.ZipFile(path, "r") as zf:
        cls_name = json.loads(zf.read("meta.json"))["model_class"]
    if cls_name == "MultiLayerNetwork":
        return restore_multi_layer_network(path, load_updater)
    return restore_computation_graph(path, load_updater)


def restore_multi_layer_network(path: str, load_updater: bool = False):
    """reference: ModelSerializer.restoreMultiLayerNetwork."""
    from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
    return _restore(path, "MultiLayerNetwork",
                    MultiLayerConfiguration.from_json, load_updater)


def restore_computation_graph(path: str, load_updater: bool = False):
    """reference: ModelSerializer.restoreComputationGraph."""
    from deeplearning4j_tpu.nn.graph.config import ComputationGraphConfiguration
    return _restore(path, "ComputationGraph",
                    ComputationGraphConfiguration.from_json, load_updater)
