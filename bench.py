"""Benchmark: ResNet-50 training throughput (images/sec/chip).

The BASELINE.json headline metric (ResNet50 on TinyImageNet-shaped data,
64x64x3, 200 classes, bf16, batch 384). One process, one chip; it
refuses to run where JAX finds no TPU — a number from another backend is
not this metric.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "device": ...}
``vs_baseline`` is vs round-1's recorded number for this exact config
(BASELINE.md: 29,119 img/s/chip; the reference publishes none).
"""

import json
import sys
import time

import numpy as np

BATCH, K, DISPATCHES, WARMUP = 384, 170, 2, 1
ROUND1_IMAGES_PER_SEC = 29119.0


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py measures a TPU chip; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind}). Not run.")

    import jax.numpy as jnp
    import jax.random as jrandom

    from deeplearning4j_tpu.optimize.solver import make_scan_train_step
    from deeplearning4j_tpu.optimize.updaters import Nesterovs
    from deeplearning4j_tpu.zoo.models import ResNet50

    # Fused bottleneck blocks and the space-to-depth stem: the
    # configuration PERF_ANALYSIS r4/r5 arrived at, batch 384 its sweet
    # spot.
    model = ResNet50(num_classes=200, height=64, width=64, channels=3,
                     compute_dtype="bfloat16",
                     updater=Nesterovs(1e-2, 0.9), fused_blocks=True,
                     s2d_stem=True).init()

    # K optimizer steps per dispatch (lax.scan in optimize/solver.py:
    # make_scan_train_step). Batches are staged device-side once
    # (broadcast view) so dispatches don't re-transfer data — the
    # shapes, not the contents, determine the timing.
    def loss_fn(params, mstate, feats, labels, fmask, lmask, rng, it):
        return model._loss(params, mstate, (feats,), (labels,), fmask,
                           lmask, rng, it)

    steps_fn = make_scan_train_step(loss_fn, model._tx)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(BATCH, 64, 64, 3)).astype(np.float32))
    y = np.zeros((BATCH, 200), np.float32)
    y[np.arange(BATCH), rng.integers(0, 200, BATCH)] = 1.0
    xs = jnp.broadcast_to(x, (K,) + x.shape)
    ys = jnp.broadcast_to(jnp.asarray(y), (K,) + y.shape)
    key = jrandom.PRNGKey(0)

    ts = model.train_state
    for i in range(WARMUP):
        ts, losses = steps_fn(ts, xs, ys, None, None,
                              jrandom.fold_in(key, i))
    jax.block_until_ready(losses)

    t0 = time.perf_counter()
    for i in range(DISPATCHES):
        ts, losses = steps_fn(ts, xs, ys, None, None,
                              jrandom.fold_in(key, WARMUP + i))
    jax.block_until_ready(losses)
    dt = time.perf_counter() - t0
    if not np.isfinite(np.asarray(losses)).all():
        sys.exit("bench.py: non-finite loss; no metric reported")
    images_per_sec = DISPATCHES * K * BATCH / dt
    print(json.dumps({
        "metric": "resnet50_64x64_bfloat16_train_images_per_sec_per_chip"
                  "_tpu",
        "value": round(images_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(images_per_sec / ROUND1_IMAGES_PER_SEC, 3),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }))


if __name__ == "__main__":
    main()
