"""Fused-Pallas-LSTM vs XLA-scan crossover sweep.

Measures forward+backward wall time of the two recurrence
implementations over a (batch, hidden, T) grid and prints the winner
per geometry — the measurement source for
``ops/pallas_lstm._MEASURED_FUSED_WINS`` (the dispatch table routes to
the fused kernel ONLY where this bench shows it winning; the attention
crossover discipline from round 5).

Methodology matches benchmarks/attn_crossover.py: K iterations chained
inside one jitted dispatch (per-dispatch overhead would otherwise
swamp per-tick effects), gradients taken through a sum loss, best of R
repetitions, a host read as the sync.

Run on hardware:
    python benchmarks/lstm_crossover.py                  # default grid
    python benchmarks/lstm_crossover.py --quick          # BASELINE geometry only
    python benchmarks/lstm_crossover.py --block-t 1 4 8  # sweep tick blocking
"""

import argparse
import functools
import time

import numpy as np


def bench(step, args, k=10, reps=3):
    """Median-free best-of-reps timing of ``k`` chained calls inside one
    jit. Returns seconds per call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def many(args):
        def body(carry, _):
            out = step(*carry)
            # chain: mix each output back into the inputs so XLA cannot
            # hoist or dedupe iterations
            new_args = tuple(a + 0.0 * jnp.sum(o) for a, o in
                             zip(carry, out)) if isinstance(out, tuple) \
                else tuple(a + 0.0 * jnp.sum(out) for a in carry)
            return new_args, ()
        out, _ = jax.lax.scan(body, args, None, length=k)
        return out

    r = many(args)  # compile + warm
    np.asarray(jax.tree_util.tree_leaves(r)[0])
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        r = many(args)
        np.asarray(jax.tree_util.tree_leaves(r)[0])
        best = min(best, (time.perf_counter() - t0) / k)
    return best


def make_steps(batch, hidden, seq, dtype, block_t):
    """Returns (scan_step, fused_step): each maps (zx, h0, c0, wh) ->
    grads of a sum loss through the full recurrence (fwd+bwd)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import pallas_lstm

    def scan_fwd(zx, h0, c0, wh):
        h = hidden

        def cell(carry, zx_t):
            h_prev, c_prev = carry
            z = zx_t + h_prev @ wh
            i = jax.nn.sigmoid(z[:, :h])
            f = jax.nn.sigmoid(z[:, h:2 * h])
            o = jax.nn.sigmoid(z[:, 2 * h:3 * h])
            g = jnp.tanh(z[:, 3 * h:])
            c = f * c_prev + i * g
            hy = o * jnp.tanh(c)
            return (hy, c), hy

        (hT, cT), ys = jax.lax.scan(cell, (h0, c0), zx)
        return ys, hT, cT

    def fused_fwd(zx, h0, c0, wh):
        return pallas_lstm.lstm_fused(zx, h0, c0, wh, None,
                                      block_t=block_t, interpret=False)

    def grad_step(fwd):
        def loss(zx, h0, c0, wh):
            ys, hT, cT = fwd(zx, h0, c0, wh)
            return (jnp.sum(ys.astype(jnp.float32) ** 2)
                    + jnp.sum(hT.astype(jnp.float32))
                    + jnp.sum(cT.astype(jnp.float32)))
        return jax.grad(loss, argnums=(0, 1, 2, 3))
    return grad_step(scan_fwd), grad_step(fused_fwd)


def run_geometry(batch, hidden, seq, dtype, block_t, k, reps):
    import jax.numpy as jnp
    dt = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    rng = np.random.default_rng(0)
    zx = jnp.asarray(rng.normal(size=(seq, batch, 4 * hidden)) * 0.1, dt)
    h0 = jnp.zeros((batch, hidden), dt)
    c0 = jnp.zeros((batch, hidden), dt)
    wh = jnp.asarray(rng.normal(size=(hidden, 4 * hidden)) * 0.05, dt)
    scan_step, fused_step = make_steps(batch, hidden, seq, dt, block_t)
    args = (zx, h0, c0, wh)
    t_scan = bench(scan_step, args, k=k, reps=reps)
    try:
        t_fused = bench(fused_step, args, k=k, reps=reps)
    except Exception as e:  # kernel refused this geometry (e.g. VMEM)
        print(f"  fused FAILED ({type(e).__name__}) "
              f"b={batch} h={hidden} T={seq} bt={block_t}")
        return None
    tokens = batch * seq
    print(f"b={batch:5d} h={hidden:4d} T={seq:4d} {dtype} bt={block_t}: "
          f"scan {t_scan*1e3:8.3f} ms ({tokens/t_scan/1e6:7.2f} Mtok/s)  "
          f"fused {t_fused*1e3:8.3f} ms ({tokens/t_fused/1e6:7.2f} Mtok/s)  "
          f"speedup {t_scan/t_fused:5.2f}x  "
          f"winner={'FUSED' if t_fused < t_scan else 'scan'}")
    return (batch, hidden, seq, block_t, t_scan, t_fused)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="BASELINE TextGenerationLSTM geometry only")
    ap.add_argument("--dtype", default="bf16", choices=["f32", "bf16"])
    ap.add_argument("--block-t", type=int, nargs="+", default=[1])
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()

    import jax
    backend = jax.default_backend()
    print(f"backend={backend} dtype={args.dtype}")
    if backend != "tpu":
        print("WARNING: not a TPU — fused kernel would run in interpret "
              "mode; timings below are meaningless for dispatch tables.")

    if args.quick:
        grid = [(256, 512, 128)]
    else:
        grid = [(b, h, t)
                for b in (64, 256, 1024)
                for h in (256, 512, 1024)
                for t in (32, 128, 512)]
        # decode-shape geometries: the generation/ engine's tick is a
        # T=1 step over a small slot batch (continuous batching keeps
        # batch at the slot-bucket sizes). Swept here so the dispatch
        # table has the decode consumer's shapes ready the first time a
        # chip session runs this — a fused win at T=1 would move the
        # serving tick, not just training.
        grid += [(b, h, 1)
                 for b in (1, 8, 16)
                 for h in (256, 512)]

    wins = []
    for (b, h, t) in grid:
        for bt in args.block_t:
            r = run_geometry(b, h, t, args.dtype, bt, args.k, args.reps)
            if r is not None and r[5] < r[4]:
                wins.append(r)
    if wins:
        print("\nfused wins at (batch, hidden, seq, block_t):")
        for b, h, t, bt, ts, tf in wins:
            print(f"  ({b}, {h}, {t})  bt={bt}  {ts/tf:.2f}x")
        print("-> encode as rules in ops/pallas_lstm._MEASURED_FUSED_WINS")
    else:
        print("\nfused never won: keep _MEASURED_FUSED_WINS empty "
              "(auto-dispatch stays on scan) and record the post-mortem "
              "in PERF_ANALYSIS.md")


if __name__ == "__main__":
    main()
