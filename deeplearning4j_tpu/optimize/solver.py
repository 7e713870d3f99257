"""Training core: optimizer assembly + the jitted train step.

Analog of the reference's Solver/ConvexOptimizer stack
(deeplearning4j-nn/.../optimize/Solver.java:43,
solvers/StochasticGradientDescent.java:42, BaseOptimizer.java:54) redesigned
for XLA: the whole step — forward, backward, gradient transform, parameter
update — is ONE jitted pure function with donated buffers, so XLA plans
memory across the entire step (the reference needs workspaces + flattened
views to get the same effect; see SURVEY §7.1).

Per-layer updater overrides and frozen layers map to
``optax.multi_transform`` over top-level parameter keys — the analog of the
reference's UpdaterBlock grouping (nn/updater/UpdaterBlock.java:25).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from deeplearning4j_tpu.optimize.updaters import (
    GradientNormalizationConfig,
    NoOp,
    Updater,
)


class TrainState(NamedTuple):
    """Pytree carried across iterations. ``model_state`` holds non-trainable
    layer state (BN running stats, last RNN hidden states). ``telemetry``
    carries the on-device metrics ring buffer (observe/telemetry.py) when
    a collector is attached; the default is an empty pytree so untracked
    code constructing 4-field TrainStates keeps working."""
    params: Any
    model_state: Any
    opt_state: Any
    iteration: jnp.ndarray  # int32 scalar
    telemetry: Any = ()


def build_optimizer(
    layer_names: Tuple[str, ...],
    layer_updaters: Dict[str, Optional[Updater]],
    frozen: Dict[str, bool],
    global_updater: Updater,
    grad_norm: Optional[GradientNormalizationConfig] = None,
) -> optax.GradientTransformation:
    """Assemble the gradient transformation for a model.

    Layers with ``updater=None`` use the global updater; frozen layers get
    ``set_to_zero`` (reference: FrozenLayer wraps the layer with a NoOp
    updater — nn/conf/layers/misc/FrozenLayer.java).
    """
    groups: Dict[str, optax.GradientTransformation] = {
        "__global__": global_updater.to_optax()}
    labels: Dict[str, str] = {}
    for name in layer_names:
        if frozen.get(name, False):
            groups.setdefault("__frozen__", NoOp().to_optax())
            labels[name] = "__frozen__"
        elif layer_updaters.get(name) is not None:
            groups[name] = layer_updaters[name].to_optax()
            labels[name] = name
        else:
            labels[name] = "__global__"

    if len(set(labels.values())) == 1 and "__global__" in set(labels.values()):
        tx = groups["__global__"]
    else:
        tx = optax.multi_transform(groups, labels)

    clip = grad_norm.to_optax() if grad_norm is not None else None
    if clip is not None:
        tx = optax.chain(clip, tx)
    return tx


LossFn = Callable[..., Tuple[jnp.ndarray, Any]]


def make_train_step(loss_fn: LossFn, tx: optax.GradientTransformation,
                    donate: bool = True, constrain_fn=None,
                    telemetry=None):
    """Build the jitted train step.

    ``loss_fn(params, model_state, features, labels, fmask, lmask, rng,
    iteration) -> (loss, new_model_state)``

    Returns ``step(train_state, features, labels, fmask, lmask, rng) ->
    (new_train_state, loss)``. The train state is donated: XLA reuses the
    parameter/optimizer buffers in place, halving peak HBM — the analog of
    the reference's workspace reuse (WorkspaceMode; SURVEY §2.14).

    ``telemetry``: optional ``TelemetrySpec`` (observe/telemetry.py).
    When given, the step computes the spec's metrics from the in-flight
    loss/grads/updates and appends one row to the on-device ring buffer
    carried in ``TrainState.telemetry`` — no host interaction; the host
    fetches the ring in one transfer every N steps.
    """

    def step(ts: TrainState, features, labels, fmask, lmask, rng):
        def lf(params):
            return loss_fn(params, ts.model_state, features, labels, fmask,
                           lmask, rng, ts.iteration)

        (loss, new_ms), grads = jax.value_and_grad(lf, has_aux=True)(ts.params)
        updates, new_opt = tx.update(grads, ts.opt_state, ts.params)
        new_params = optax.apply_updates(ts.params, updates)
        if constrain_fn is not None:
            new_params = constrain_fn(new_params)
        buf = ts.telemetry
        if telemetry is not None:
            buf = telemetry.record(buf, loss=loss, grads=grads,
                                   params=new_params,
                                   prev_params=ts.params,
                                   iteration=ts.iteration)
        return TrainState(new_params, new_ms, new_opt, ts.iteration + 1,
                          buf), loss

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_scan_train_step(loss_fn: LossFn, tx: optax.GradientTransformation,
                         donate: bool = True, constrain_fn=None,
                         shadow_cast=None, telemetry=None):
    """Multi-step variant of ``make_train_step``: one dispatch runs K
    optimizer steps via ``lax.scan`` over pre-staged batches.

    Why this exists: each host→device dispatch carries fixed overhead
    (buffer-handle marshalling; benchmarks/step_overhead.py measures
    it — not measured on a directly attached chip yet), so per-step
    dispatch caps small-step throughput. Scanning K steps device-side
    amortizes it K× and lets XLA overlap the scan with host work — the
    TPU analog of the reference keeping its fit loop inside one native
    workspace iteration.

    This is the step behind ``fit(..., k_steps=K)``: the DeviceFeeder
    (datasets/feeder.py) stages K prefetched batches as one stacked
    (K, B, ...) device array (ragged tails padded to the bucket size
    with a zero labels mask, so the whole epoch keeps one compiled
    signature) and the fit loop dispatches them here. ``None`` masks
    scan through as empty pytrees — a mask must be None for ALL K
    batches or an array for all K, which the feeder's bucket
    normalization guarantees.

    ``shadow_cast``: optional ``params -> low-precision params`` (e.g.
    ``lambda p: cast_params(p, "bfloat16")``). When given, the scan
    carries a CAST SHADOW of the parameters next to the f32 masters:
    forward/backward consume the shadow (the model's internal
    ``cast_params`` becomes an identity on already-bf16 leaves), the
    optimizer updates the f32 masters, and the shadow is refreshed in
    the update's epilogue — where XLA fuses the cast with the parameter
    write instead of re-reading every f32 master at the top of the next
    step's loss (the ~6.8 ms/step recast measured on the BERT fine-tune
    config, PERF_ANALYSIS r5). Numerics are unchanged: the values the
    matmuls see are bit-identical either way.

    Returns ``steps(train_state, features, labels, fmask, lmask, rng) ->
    (new_train_state, per-step losses)`` where features/labels (and
    masks, if given) carry a leading K dim.
    """

    def one(carry, xs):
        ts, shadow = carry if shadow_cast is not None else (carry, None)
        work = shadow if shadow_cast is not None else ts.params
        features, labels, fmask, lmask, i = xs
        def lf(params):
            return loss_fn(params, ts.model_state, features, labels, fmask,
                           lmask, i[0], ts.iteration)
        (loss, new_ms), grads = jax.value_and_grad(lf, has_aux=True)(work)
        if shadow_cast is not None:
            # master-precision grads for the f32 optimizer state
            grads = jax.tree_util.tree_map(
                lambda g, p: g.astype(p.dtype), grads, ts.params)
        updates, new_opt = tx.update(grads, ts.opt_state, ts.params)
        new_params = optax.apply_updates(ts.params, updates)
        if constrain_fn is not None:
            new_params = constrain_fn(new_params)
        buf = ts.telemetry
        if telemetry is not None:
            # identical row math to the unscanned step: per inner step,
            # from the same in-flight loss/grads/updates
            buf = telemetry.record(buf, loss=loss, grads=grads,
                                   params=new_params,
                                   prev_params=ts.params,
                                   iteration=ts.iteration)
        new_ts = TrainState(new_params, new_ms, new_opt,
                            ts.iteration + 1, buf)
        if shadow_cast is not None:
            return (new_ts, shadow_cast(new_params)), loss
        return new_ts, loss

    def steps(ts: TrainState, features, labels, fmask, lmask, rng):
        k = features[0].shape[0] if isinstance(features, tuple) \
            else features.shape[0]
        keys = jax.random.split(rng, k)[:, None]
        init = (ts, shadow_cast(ts.params)) if shadow_cast is not None \
            else ts
        out, losses = jax.lax.scan(one, init,
                                   (features, labels, fmask, lmask, keys))
        if shadow_cast is not None:
            out = out[0]
        return out, losses

    return jax.jit(steps, donate_argnums=(0,) if donate else ())


def make_eval_step(forward_fn):
    """Jitted inference step: forward_fn(params, model_state, x, mask)."""
    return jax.jit(forward_fn)


def make_constrain_fn(layers):
    """Post-update parameter projection from per-layer constraint configs
    (reference: conf/constraint/ applied in BaseMultiLayerUpdater.update
    after the updater step). Returns None when no layer has constraints."""
    constrained = {l.name: l.constraints for l in layers if l.constraints}
    if not constrained:
        return None

    def constrain(params):
        out = dict(params)
        for name, constraints in constrained.items():
            p = out.get(name)
            if not p:
                continue
            for c in constraints:
                p = c.apply(p)
            out[name] = p
        return out

    return constrain
