"""Flash-attention BACKWARD bench: Pallas dq/dk/dv kernels vs (a) the
jnp/scan blockwise reference VJP and (b) plain XLA attention's autodiff,
at long sequence lengths (VERDICT r3 #2 acceptance: measured bwd
ms/layer beats the XLA VJP at T=4096/16384).

Run on the TPU chip:  python benchmarks/flash_bwd_bench.py
"""

import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def bench_grad(grad_fn, q, k, v, iters=8):
    """K iterations inside ONE jitted dispatch (amortizing per-dispatch
    overhead), chained through a scalar so no iteration can be CSE'd or
    deduped."""
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def many(q, k, v):
        def body(i, qc):
            gq, gk, gv = grad_fn(qc, k, v)
            # chain ALL THREE grads into the carry — consuming only gq
            # lets XLA dead-code-eliminate the whole dK/dV kernel
            # (verified: optimized HLO shrinks ~32%)
            return qc + (gq + gk + gv).astype(qc.dtype) * 1e-6
        return jnp.sum(lax.fori_loop(0, iters, body, q)
                       .astype(jnp.float32))

    float(many(q, k, v))                        # compile + warm
    best = float("inf")
    for rep in range(1, 4):
        # distinct inputs, SAME dtype (an f32 promotion would silently
        # retrace), and sync by VALUE fetch
        q2 = (q.astype(jnp.float32) + rep * 1e-3).astype(q.dtype)
        jax.block_until_ready(q2)
        t0 = time.perf_counter()
        float(many(q2, k, v))
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1000.0


def bench_fwd(fn, q, k, v, iters=8):
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def many(q, k, v):
        def body(i, qc):
            o = fn(qc, k, v)
            return qc + o.astype(qc.dtype) * 1e-6    # real data dep
        return jnp.sum(lax.fori_loop(0, iters, body, q)
                       .astype(jnp.float32))

    float(many(q, k, v))
    best = float("inf")
    for rep in range(1, 4):
        q2 = (q.astype(jnp.float32) + rep * 1e-3).astype(q.dtype)
        jax.block_until_ready(q2)
        t0 = time.perf_counter()
        float(many(q2, k, v))
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1000.0


def run(t, h=16, dh=64, n=1, causal=True, dtype=jnp.bfloat16,
        iters=None):
    iters = iters if iters is not None else (32 if t <= 8192 else 8)
    from deeplearning4j_tpu.nn.layers.attention import (
        scaled_dot_product_attention)
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
    from deeplearning4j_tpu.ops.visibility import Causal, Visibility
    vis = Causal() if causal else Visibility()

    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.normal(0, 1, (n, t, h, dh)), dtype)
    q, k, v = mk(), mk(), mk()

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, visibility=vis)
                       .astype(jnp.float32) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(scaled_dot_product_attention(q, k, v,
                                                    visibility=vis)
                       .astype(jnp.float32) ** 2)

    g_xla = jax.grad(loss_xla, argnums=(0, 1, 2))
    fwd_flash = functools.partial(flash_attention, visibility=vis)

    res = {"t": t, "fwd_flash_ms": bench_fwd(fwd_flash, q, k, v, iters=iters)}

    os.environ["DL4J_FLASH_BWD"] = "pallas"
    jax.clear_caches()
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))
    res["fwdbwd_pallas_ms"] = bench_grad(g_flash, q, k, v, iters=iters)

    os.environ["DL4J_FLASH_BWD"] = "xla"
    jax.clear_caches()
    g_flash2 = jax.grad(loss_flash, argnums=(0, 1, 2))
    res["fwdbwd_scanref_ms"] = bench_grad(g_flash2, q, k, v, iters=iters)
    gb = jax.jit(g_flash2)(q, k, v)     # traced while env=xla
    gb = [jnp.asarray(np.asarray(a)) for a in gb]
    os.environ["DL4J_FLASH_BWD"] = "pallas"
    jax.clear_caches()

    try:
        res["fwdbwd_xla_ms"] = bench_grad(g_xla, q, k, v, iters=iters)
    except Exception as e:          # 16k*16k scores may OOM in XLA
        res["fwdbwd_xla_ms"] = f"OOM ({type(e).__name__})"
    # numeric agreement spot check (bf16 tolerance)
    ga = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(ga, gb))
    res["pallas_vs_scanref_max_abs_err"] = err
    return res


if __name__ == "__main__":
    for t in (4096, 16384):
        print(run(t))
