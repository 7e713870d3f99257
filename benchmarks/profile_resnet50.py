"""ResNet50 train-step decomposition on the real chip (VERDICT weak#2).

Times the full train step and its pieces separately (a host transfer
ends each timing block), pulls XLA's compiled cost analysis (FLOPs /
bytes) for each executable, and prints a roofline table: where the gap
between the measured matmul roofline and the model step goes.
PERF_ANALYSIS.md records the conclusions.

Run: python benchmarks/profile_resnet50.py [batch]
"""

import sys
import time

import numpy as np


def timed_scalar(fn, *args, n=20, warmup=3):
    """fn must return a scalar-ish array; host-fetch syncs the stream."""
    for _ in range(warmup):
        out = fn(*args)
    float(np.asarray(out).ravel()[0])
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    float(np.asarray(out).ravel()[0])
    return (time.perf_counter() - t0) / n


def cost(compiled):
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return float(ca.get("flops", 0)), float(ca.get("bytes accessed", 0))
    except Exception:
        return 0.0, 0.0


def main():
    import jax
    import jax.numpy as jnp
    import jax.random as jrandom

    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
    from deeplearning4j_tpu.optimize.updaters import Nesterovs
    from deeplearning4j_tpu.zoo.models import ResNet50

    model = ResNet50(num_classes=200, height=64, width=64, channels=3,
                     compute_dtype="bfloat16",
                     updater=Nesterovs(1e-2, 0.9)).init()
    ts = model.train_state

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 64, 64, 3)).astype(np.float32))
    idx = rng.integers(0, 200, batch)
    y = np.zeros((batch, 200), np.float32)
    y[np.arange(batch), idx] = 1.0
    y = jnp.asarray(y)
    key = jrandom.PRNGKey(0)

    # ---- matmul roofline on this chip ------------------------------------
    m = 8192
    a = jnp.asarray(rng.normal(size=(m, m)), jnp.bfloat16)
    b = jnp.asarray(rng.normal(size=(m, m)), jnp.bfloat16)
    jmm = jax.jit(lambda a, b: jnp.sum((a @ b).astype(jnp.float32)))
    t_mm = timed_scalar(jmm, a, b, n=50)
    mm_tflops = 2 * m ** 3 / t_mm / 1e12

    # ---- piece 1: forward loss only --------------------------------------
    def fwd(params, mstate, x, y, key):
        loss, _ = model._loss(params, mstate, (x,), (y,), None, None, key,
                              ts.iteration)
        return loss

    jfwd = jax.jit(fwd)
    c_fwd = jfwd.lower(ts.params, ts.model_state, x, y, key).compile()
    t_fwd = timed_scalar(jfwd, ts.params, ts.model_state, x, y, key)

    # ---- piece 2: forward + backward (scalar probe on one grad leaf) -----
    def fwd_bwd(params, mstate, x, y, key):
        g = jax.grad(lambda p: fwd(p, mstate, x, y, key))(params)
        # touch every leaf so nothing is DCE'd, return a scalar
        return sum(jnp.sum(l.astype(jnp.float32) ** 2)
                   for l in jax.tree_util.tree_leaves(g))

    jfb = jax.jit(fwd_bwd)
    c_fb = jfb.lower(ts.params, ts.model_state, x, y, key).compile()
    t_fb = timed_scalar(jfb, ts.params, ts.model_state, x, y, key)

    # ---- piece 3: full train step (fwd+bwd+optimizer, donated) -----------
    step = model._build_train_step()
    n_steps, warm = 20, 3
    for i in range(warm):
        ts, loss = step(ts, (x,), (y,), None, None, jrandom.fold_in(key, i))
    float(loss)
    t0 = time.perf_counter()
    for i in range(n_steps):
        ts, loss = step(ts, (x,), (y,), None, None,
                        jrandom.fold_in(key, warm + i))
    float(loss)
    t_step = (time.perf_counter() - t0) / n_steps

    f_fwd, by_fwd = cost(c_fwd)
    f_fb, by_fb = cost(c_fb)

    print(f"batch={batch}")
    print(f"matmul roofline: {mm_tflops:.1f} TFLOP/s "
          f"({t_mm * 1e3:.2f} ms for {m}x{m}x{m})")
    for name, t, fl, by in (("fwd", t_fwd, f_fwd, by_fwd),
                            ("fwd+bwd", t_fb, f_fb, by_fb)):
        tf = fl / t / 1e12 if fl else 0
        gbs = by / t / 1e9 if by else 0
        print(f"{name:8s}: {t * 1e3:7.2f} ms  {fl / 1e9:8.1f} GFLOP  "
              f"{tf:6.1f} TFLOP/s  {by / 1e6:8.0f} MB  {gbs:7.0f} GB/s")
    print(f"step    : {t_step * 1e3:7.2f} ms  "
          f"({batch / t_step:,.0f} img/s)")
    print(f"optimizer+cast overhead vs fwd+bwd: "
          f"{(t_step - t_fb) * 1e3:.2f} ms")


if __name__ == "__main__":
    main()
