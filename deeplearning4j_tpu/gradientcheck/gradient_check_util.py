"""Finite-difference gradient checking — the correctness backbone.

Analog of the reference's ``GradientCheckUtil``
(deeplearning4j-nn/.../gradientcheck/GradientCheckUtil.java:54 —
checkGradients:109; formula (C(w+ε)−C(w−ε))/2ε per parameter with
relative-error thresholds, double precision). Sixteen reference test suites
hang off that one utility (SURVEY §4); ours serves the same role.

Implementation: runs under ``jax.experimental.enable_x64`` with the whole
parameter pytree cast to float64, compares ``jax.grad`` against central
differences per scalar parameter. Since jax.grad IS the production backward
path (there are no hand-written gradients to diverge), this validates layer
forward math, masking, and loss wiring end-to-end.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


def check_gradients(
    loss_fn: Callable,
    params,
    epsilon: float = 1e-6,
    max_rel_error: float = 1e-5,
    min_abs_error: float = 1e-8,
    max_params_per_leaf: int = 16,
    seed: int = 0,
    verbose: bool = True,
) -> bool:
    """Compare analytic vs numeric gradients.

    loss_fn(params) -> scalar. Subsamples up to ``max_params_per_leaf``
    scalar entries per leaf (the reference checks every parameter; sampling
    keeps CI fast at equal coverage confidence for randomly-initialized
    nets).
    """
    with jax.enable_x64(True):
        params64 = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a), jnp.float64)
            if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a,
            params)
        grad_fn = jax.grad(lambda p: jnp.asarray(loss_fn(p), jnp.float64))
        analytic = grad_fn(params64)

        flat_p, treedef = jax.tree_util.tree_flatten(params64)
        flat_g = jax.tree_util.tree_leaves(analytic)
        rng = np.random.default_rng(seed)
        total_checked = 0
        max_err = 0.0
        failures = []

        for li, (leaf, g) in enumerate(zip(flat_p, flat_g)):
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                continue
            n = int(np.prod(leaf.shape)) if leaf.shape else 1
            idxs = (np.arange(n) if n <= max_params_per_leaf
                    else rng.choice(n, max_params_per_leaf, replace=False))
            leaf_np = np.asarray(leaf).reshape(-1)
            g_np = np.asarray(g).reshape(-1)
            for idx in idxs:
                orig = leaf_np[idx]

                def loss_at(v):
                    leaf_mod = leaf_np.copy()
                    leaf_mod[idx] = v
                    new_leaf = jnp.asarray(leaf_mod.reshape(leaf.shape))
                    new_flat = list(flat_p)
                    new_flat[li] = new_leaf
                    p = jax.tree_util.tree_unflatten(treedef, new_flat)
                    return float(loss_fn(p))

                numeric = (loss_at(orig + epsilon) - loss_at(orig - epsilon)) \
                    / (2 * epsilon)
                an = float(g_np[idx])
                abs_err = abs(an - numeric)
                denom = max(abs(an), abs(numeric))
                rel_err = abs_err / denom if denom > 0 else 0.0
                total_checked += 1
                max_err = max(max_err, rel_err if abs_err > min_abs_error else 0.0)
                if rel_err > max_rel_error and abs_err > min_abs_error:
                    failures.append((li, int(idx), an, numeric, rel_err))

        if verbose and failures:
            for li, idx, an, nu, re in failures[:10]:
                print(f"  leaf {li} [{idx}]: analytic={an:.8g} "
                      f"numeric={nu:.8g} rel_err={re:.3g}")
        if verbose:
            print(f"gradient check: {total_checked} params checked, "
                  f"{len(failures)} failures, max rel err {max_err:.3g}")
        return len(failures) == 0


def check_model_gradients(model, dataset, **kwargs) -> bool:
    """Convenience wrapper: checks d(loss)/d(params) for a built model on one
    minibatch — the shape the reference's 16 gradient-check suites use."""
    if model.train_state is None:
        model.init()
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork

    # Keep everything numpy-float64 here: jnp.asarray would truncate to f32
    # outside the enable_x64 scope that check_gradients opens.
    features = np.asarray(dataset.features, np.float64)
    labels = np.asarray(dataset.labels, np.float64)
    fmask = (None if dataset.features_mask is None
             else np.asarray(dataset.features_mask, np.float64))
    lmask = (None if dataset.labels_mask is None
             else np.asarray(dataset.labels_mask, np.float64))
    state = jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float64)
        if np.issubdtype(np.asarray(a).dtype, np.floating) else a,
        model.train_state.model_state)

    if isinstance(model, MultiLayerNetwork):
        def loss_fn(p):
            loss, _ = model._loss(p, state, features, labels, fmask, lmask,
                                  None, jnp.zeros((), jnp.int32))
            return loss
    else:
        def loss_fn(p):
            loss, _ = model._loss(p, state, (features,), (labels,),
                                  (fmask,) if fmask is not None else None,
                                  (lmask,) if lmask is not None else None,
                                  None, jnp.zeros((), jnp.int32))
            return loss

    return check_gradients(loss_fn, model.train_state.params, **kwargs)
