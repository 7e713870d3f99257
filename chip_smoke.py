"""On-chip smoke: the quickest proof that the system still starts on a TPU.

One process, one chip (``--chips 4``: one process, one four-chip host).
Drives the main path once through the entry points a user calls, at the
full width of ResNet-50 64x64 bf16 batch 384 (the BASELINE config), from
a seed, with no network and no files outside the checkout:

  phase=device   what JAX found; anything but a TPU exits non-zero here,
                 before any model is built
  phase=train    ``model.fit(iterator)`` through the device feeder with
                 k_steps=1 (make_train_step) and k_steps>1
                 (make_scan_train_step); the fed losses must equal the
                 synchronous ``prefetch=0`` loop bit for bit
  phase=serve    the fitted model behind ``ServingEngine``; requests of
                 1, 3, 32 and 50 rows (the split path) against
                 ``model.output``
  phase=kernels  flash attention and the Pallas LSTM recurrence,
                 compiled (interpret=False), the flash backward's one
                 launch at the SDAR cell's block-diffusion shape against
                 its two-launch path, and the fused block's conv
                 + BN statistics (Gram and direct), forward and
                 backward, against plain XLA; the held experts' loop over
                 blocks of rows at routings that fill one block and
                 several; the gated delta rule's kernels at the
                 Qwen3-Next cell's shapes, the selective scan's at the
                 Phi-4-mini-flash cell's and the Mamba-2 scan's at the
                 Nemotron 3 Nano cell's, each against its plain chunked
                 form
  phase=feed_race  the input pipeline alone, with no train step, so that
                 the prefetch thread gathers flat out into buffers it
                 uses again: 200 shuffled batches of the ResNet cell's
                 1280 rows through ``DeviceFeeder`` (``--chips 4``: 40 of
                 5120 rows through ``ParallelWrapper``'s feeder and its
                 ``put``), every staged device batch against an
                 independent gather from a copy of the set on the device;
                 and when the runtime lets go of a host array it is given

``--phases a,b`` runs only the phases named.

Each phase prints ``phase=<name> ok|FAIL seconds=<t> ...``; seconds are
set-up information, not a result. Any FAIL exits non-zero. On success
the last line of stdout is ``{"ok": true, "device": {...}}``.
"""

import argparse
import json
import sys
import time
import traceback

import numpy as np

BATCH = 384
K1_STEPS = 6                 # k_steps=1 steps (also the prefetch=0 replay)
K_STEPS, K_DISPATCHES = 4, 3  # scanned dispatches after them
SERVE_SIZES = (1, 3, 32, 50)
# the feed race: (rows a batch, batches) by chips, over the ResNet cells' set
RACE = {1: (1280, 200), 4: (5120, 40)}
RACE_SET_ROWS, RACE_SEED, RACE_IN_FLIGHT = 25600, 5, 2
# max |got - want| / max |want| against the XLA reference
TOL = {"bfloat16": 4e-2, "float32": 2e-2}


# ---- shared helpers ------------------------------------------------------

class _Compiles:
    """Backend-compile seconds and persistent-cache traffic, from JAX's
    own monitoring events (cold vs warm cache shows up here)."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds, self.hits, self.writes = 0.0, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError("non-finite kernel output")
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def _check_grads(name, fn, ref, args, dtype, errs):
    """Forward and backward of ``fn`` against ``ref`` on ``args``; the
    worst relative error, or the failure, lands in ``errs[name]``. The
    cotangent weights every output element differently, so a wrong
    element anywhere shows in some gradient."""
    import jax
    import jax.numpy as jnp

    def fwd_bwd(f):
        def inner(*a):
            outs, vjp = jax.vjp(f, *a)
            cts = jax.tree_util.tree_map(
                lambda o: jnp.cos(jnp.arange(o.size, dtype=jnp.float32))
                .reshape(o.shape).astype(o.dtype), outs)
            return outs, vjp(cts)
        return jax.jit(inner)

    try:
        got = jax.tree_util.tree_leaves(fwd_bwd(fn)(*args))
        want = jax.tree_util.tree_leaves(fwd_bwd(ref)(*args))
        errs[name] = max(_rel_err(g, w) for g, w in zip(got, want))
        if errs[name] > TOL[dtype]:
            raise AssertionError(f"max relative error {errs[name]:.3e} > "
                                 f"{TOL[dtype]:.0e}")
    except Exception as e:     # report every kernel, then fail the phase
        traceback.print_exc()
        errs[name] = f"FAIL {type(e).__name__}: {str(e)[:200]}"


# ---- phases --------------------------------------------------------------

def build_model():
    from deeplearning4j_tpu.zoo.models import ResNet50
    return ResNet50(num_classes=200, height=64, width=64, channels=3,
                    compute_dtype="bfloat16", fused_blocks=True,
                    s2d_stem=True).init()


def _loss_listener(track_batch_shards=False):
    """Keeps each dispatch's loss on the device (no per-step sync that
    would serialize the feeder) and, on several chips, records which
    devices hold shards of the staged global batch."""
    import jax
    from deeplearning4j_tpu.optimize.listeners import TrainingListener

    class Losses(TrainingListener):
        def __init__(self):
            self.device_losses = []
            self.batch_shards = []      # per staged batch: {device: rows}

        def iteration_done(self, model, iteration, epoch, loss, etl_ms,
                           batch_size):
            self.device_losses.append(loss)
            if track_batch_shards:
                self.batch_shards += [
                    {s.device.id: s.data.shape[0]
                     for s in a.addressable_shards}
                    for a in jax.live_arrays()
                    if a.shape == (BATCH, 64, 64, 3)]

        def losses(self):
            return np.asarray(
                [np.asarray(x, np.float32) for x in self.device_losses])

    return Losses()


def _fit(model, steps, seed, listener, trainer=None, **kw):
    """``fit`` (the model's own, or a wrapper's) over ``steps`` synthetic
    TinyImageNet batches made from ``seed``; returns the loss of every
    dispatch the listener has seen so far."""
    from deeplearning4j_tpu.datasets.fetchers import (
        TinyImageNetDataSetIterator)
    model.set_listeners(listener)
    (trainer or model).fit(TinyImageNetDataSetIterator(
        batch_size=BATCH, subset=BATCH * steps, seed=seed), **kw)
    return listener.losses()


def phase_train(ctx):
    model = build_model()
    ctx["model"] = model
    lst = _loss_listener()
    fed = _fit(model, K1_STEPS, 7, lst)
    scanned = _fit(model, K_STEPS * K_DISPATCHES, 8, lst,
                   k_steps=K_STEPS)[K1_STEPS:]
    # the synchronous loop the feeder must replay bitwise: same seeds,
    # fresh model, no DeviceFeeder
    replay = build_model()
    sync = _fit(replay, K1_STEPS, 7, _loss_listener(), prefetch=0)
    losses = np.concatenate([fed, scanned])
    print("train losses k_steps=1 fed     :", fed.tolist())
    print("train losses k_steps=1 prefetch=0:", sync.tolist())
    print(f"train losses k_steps={K_STEPS} (last of each dispatch):",
          scanned.tolist())
    if len(fed) != K1_STEPS or len(scanned) != K_DISPATCHES:
        raise AssertionError(f"expected {K1_STEPS}+{K_DISPATCHES} "
                             f"dispatches, saw {len(fed)}+{len(scanned)}")
    if not np.isfinite(losses).all():
        raise AssertionError("non-finite training loss")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses[0]} -> "
                             f"{losses[-1]}")
    if not np.array_equal(fed, sync):
        raise AssertionError("fed losses differ from the prefetch=0 loop: "
                             "staged batches were corrupted")
    _assert_params_on_tpu(model)
    return {"first_loss": float(losses[0]), "last_loss": float(losses[-1]),
            "fed_equals_sync": True}


def _assert_params_on_tpu(model, n_devices=1):
    import jax
    for leaf in jax.tree_util.tree_leaves(model.train_state.params):
        devs = leaf.devices()
        if len(devs) != n_devices or any(d.platform != "tpu" for d in devs):
            raise AssertionError(f"param leaf on {devs}, expected "
                                 f"{n_devices} TPU device(s)")


def phase_train_4chips(ctx):
    """The train phase through ParallelWrapper on four chips: same seed,
    same global batch, per-step losses printed beside the one-chip run's."""
    import jax
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    model = build_model()
    ctx["model"] = model
    wrapper = ParallelWrapper.builder(model).workers(4).build()
    lst = _loss_listener(track_batch_shards=True)
    losses = _fit(model, K1_STEPS, 7, lst, trainer=wrapper)
    print("train losses 4 chips (ParallelWrapper):", losses.tolist())
    if len(losses) != K1_STEPS or not np.isfinite(losses).all():
        raise AssertionError(f"bad losses {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses[0]} -> "
                             f"{losses[-1]}")
    want = {d.id: BATCH // 4 for d in jax.devices()[:4]}
    if not lst.batch_shards or any(b != want for b in lst.batch_shards):
        raise AssertionError(f"staged batches held as {lst.batch_shards}, "
                             f"expected every one as {want}")
    _assert_params_on_tpu(model, n_devices=4)
    return {"first_loss": float(losses[0]), "last_loss": float(losses[-1]),
            "batch_rows_per_device": BATCH // 4}


def phase_serve(ctx):
    from deeplearning4j_tpu.parallel import ServingEngine
    model = ctx.get("model") or build_model()
    eng = ServingEngine(model, batch_limit=32, feature_shape=(64, 64, 3))
    try:
        rng = np.random.default_rng(0)
        worst = 0.0
        for n in SERVE_SIZES:
            x = rng.normal(size=(n, 64, 64, 3)).astype(np.float32)
            got = eng.output(x)
            want = np.asarray(model.output(x))
            if got.shape != (n, 200) or not np.isfinite(got).all():
                raise AssertionError(f"request of {n}: shape {got.shape} "
                                     "or non-finite")
            if not np.allclose(got, want, rtol=2e-2, atol=1e-3):
                raise AssertionError(
                    f"request of {n}: engine and model.output differ by "
                    f"{np.max(np.abs(got - want)):.3e}")
            worst = max(worst, float(np.max(np.abs(got - want))))
        eng.assert_warm()
    finally:
        eng.shutdown()
    return {"requests": len(SERVE_SIZES), "max_abs_diff": worst,
            "warmup_seconds": round(eng.warmup_seconds, 1)}


def _check_one_kernel_backward(arr, errs):
    """The flash backward as the SDAR cell takes it, one launch under
    ``BlockDiffusion(8192, 4)`` at 32 heads of 128 over 16,384 positions,
    bfloat16, against the two-launch path (dK/dV kernel, then the dQ
    kernel) on the same residuals: the same sums in the same order."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    from deeplearning4j_tpu.ops.visibility import BlockDiffusion
    name = "flash_bwd_one_kernel_block_diffusion"
    try:
        vis, (n, h, t, dh) = BlockDiffusion(8192, 4), (1, 32, 16384, 128)
        bq, bk = pk._default_blocks(dh, vis)
        q, k, v, do = (arr((n, h, t, dh), jnp.bfloat16) for _ in range(4))
        mask = jnp.ones((n, t), jnp.float32)
        out, lse = jax.jit(lambda q, k, v: pk._flash_forward(
            q, k, v, mask, vis, bq, bk, False))(q, k, v)
        args = (q, k, v, mask, out, lse, do, vis, bq, bk, False)
        launches = str(jax.make_jaxpr(
            lambda *a: pk._flash_backward_pallas(*a, *args[7:]))(
                *args[:7])).count("pallas_call")
        got = jax.jit(lambda *a: pk._flash_backward_pallas(*a, *args[7:]))(
            *args[:7])
        want = jax.jit(lambda *a: pk._flash_backward_kernels(
            *a, *args[7:], with_dq=False))(*args[:7])
        errs[name] = max(_rel_err(g, w) for g, w in zip(got, want))
        print(f"{name}: launches={launches} bitwise="
              f"{[bool(jnp.array_equal(g, w)) for g, w in zip(got, want)]}")
        if launches != 1:
            raise AssertionError(f"{launches} launches, not one")
        if errs[name] > TOL["bfloat16"]:
            raise AssertionError(f"max relative error {errs[name]:.3e}")
    except Exception as e:
        traceback.print_exc()
        errs[name] = f"FAIL {type(e).__name__}: {str(e)[:200]}"


def phase_kernels(ctx):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.layers.attention import (
        scaled_dot_product_attention)
    from deeplearning4j_tpu.ops.fused_conv import (
        _conv_reference, conv_bn_stats_xla)
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
    from deeplearning4j_tpu.ops.pallas_lstm import lstm_fused
    from deeplearning4j_tpu.ops.visibility import Causal

    errs = {}
    rng = np.random.default_rng(0)

    def arr(shape, dtype, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    # flash attention at BERT-base heads, T=1024 (the dispatcher's
    # crossover): causal, and a ragged key-validity mask
    n, t, h, dh = 4, 1024, 12, 64
    q, k, v = (arr((n, t, h, dh), jnp.bfloat16) for _ in range(3))
    mask = jnp.asarray(np.arange(t)[None, :]
                       < np.array([1024, 700, 333, 129])[:, None],
                       jnp.float32)
    _check_grads(
        "flash_causal",
        lambda q, k, v: flash_attention(q, k, v, visibility=Causal(),
                                        interpret=False),
        lambda q, k, v: scaled_dot_product_attention(q, k, v,
                                                     visibility=Causal()),
        (q, k, v), "bfloat16", errs)
    _check_grads(
        "flash_masked",
        lambda q, k, v: flash_attention(q, k, v, mask=mask,
                                        interpret=False),
        lambda q, k, v: scaled_dot_product_attention(q, k, v, mask=mask),
        (q, k, v), "bfloat16", errs)
    _check_one_kernel_backward(arr, errs)

    # Pallas LSTM recurrence at the 2xLSTM-512 geometry of
    # benchmarks/lstm_crossover.py --quick: batch 256, hidden 512, T=128
    nb, nh, nt = 256, 512, 128
    lens = rng.integers(1, nt + 1, nb)
    lmask = jnp.asarray(np.arange(nt)[:, None] < lens[None, :], jnp.float32)

    def lstm_ref(m):
        """Plain lax.scan cell in full-precision f32 whatever the input
        dtype (the kernel carries h/c in f32 too), so the error measured
        is the kernel's own and not a bf16 reference's."""
        def run(zx, h0, c0, wh):
            dt = zx.dtype
            zx, h0, c0, wh = (a.astype(jnp.float32)
                              for a in (zx, h0, c0, wh))

            def cell(carry, inp):
                zx_t, m_t = inp
                hp, cp = carry
                z = zx_t + jnp.dot(hp, wh)
                i, f, o = (jax.nn.sigmoid(z[:, j * nh:(j + 1) * nh])
                           for j in range(3))
                c = f * cp + i * jnp.tanh(z[:, 3 * nh:])
                hy = o * jnp.tanh(c)
                m_t = m_t[:, None]
                return (m_t * hy + (1 - m_t) * hp,
                        m_t * c + (1 - m_t) * cp), m_t * hy + (1 - m_t) * hp
            ms = jnp.ones((nt, nb), jnp.float32) if m is None else m
            with jax.default_matmul_precision("highest"):
                (hT, cT), ys = jax.lax.scan(cell, (h0, c0), (zx, ms))
            return ys.astype(dt), hT.astype(dt), cT.astype(dt)
        return run

    for dtype in ("bfloat16", "float32"):
        dt = jnp.dtype(dtype)
        args = (arr((nt, nb, 4 * nh), dt, 0.1), arr((nb, nh), dt, 0.1),
                arr((nb, nh), dt, 0.1), arr((nh, 4 * nh), dt, 0.05))
        for tag, m in (("nomask", None), ("mask", lmask)):
            _check_grads(
                f"lstm_{dtype}_{tag}",
                lambda zx, h0, c0, wh, m=m: lstm_fused(
                    zx, h0, c0, wh, m, interpret=False),
                lstm_ref(m), args, dtype, errs)

    # The fused block's conv + BN statistics against the plain reference:
    # one bottleneck's three convs at the stage-2 and stage-4 shapes of
    # the 64x64 model, batch 384. The stage-2 expand (128 -> 512) takes
    # the Gram statistics, the stage-4 expand (512 -> 2048) the direct
    # reduction.
    def conv_ref(x, w, s, b):
        """The reference on float32 copies of the bfloat16 operands: its
        own error stays out of the comparison, and a bfloat16 3x3 with
        float32 accumulation has no transpose rule."""
        y, sums = _conv_reference(x.astype(jnp.float32),
                                  w.astype(jnp.float32), s, b, True, True, 1)
        return y.astype(x.dtype), sums

    for stage, f, hw in (("s2", 128, 8), ("s4", 512, 2)):
        for tag, cin, wshape in (("1x1_reduce", 4 * f, (4 * f, f)),
                                 ("3x3", f, (3, 3, f, f)),
                                 ("1x1_expand", f, (f, 4 * f))):
            fan_in = int(np.prod(wshape[:-1]))
            args = (arr((BATCH, hw, hw, cin), jnp.bfloat16),
                    arr(wshape, jnp.bfloat16, fan_in ** -0.5),
                    1.0 + arr((cin,), jnp.float32, 0.1),
                    arr((cin,), jnp.float32, 0.1))
            _check_grads(f"conv_{stage}_{tag}", conv_bn_stats_xla, conv_ref,
                         args, "bfloat16", errs)
    # The held experts' loop over blocks of rows at the Qwen3-Next cell's
    # shapes (8,192 tokens, top-10 of 512 with 32 held: blocks of 8,192
    # rows) under routers that fill one block and several, against every
    # held expert applied to every token in float32 and masked by the
    # same routing. On the TPU the grouped products leave the rows past a
    # block's last group unwritten.
    from deeplearning4j_tpu.parallel.moe import (dispatch_block,
                                                 held_experts_ffn)
    t, d, f, e, g, k = 8192, 2048, 512, 512, 32, 10
    held = tuple(range(g))

    def experts_ref(x, router, w_gate, w_up, w_down):
        # the routing written out, not the program's: softmax over all
        # experts, the k largest kept and renormalised
        probs = jax.nn.softmax(jnp.dot(
            x, router, preferred_element_type=jnp.float32), -1)
        p = jnp.where(probs >= jnp.sort(probs, -1)[:, -k, None], probs, 0.0)
        p = p / jnp.sum(p, -1, keepdims=True)
        x32 = x.astype(jnp.float32)

        def one(y, expert):
            j, wg, wu, wd = expert
            pj = p[:, j]
            h = jax.nn.silu(x32 @ wg.astype(jnp.float32)) * (
                x32 @ wu.astype(jnp.float32))
            return y + pj[:, None] * (h @ wd.astype(jnp.float32)), None
        with jax.default_matmul_precision("highest"):
            y, _ = jax.lax.scan(one, jnp.zeros((t, d), jnp.float32),
                                (jnp.arange(g), w_gate, w_up, w_down))
        return y.astype(x.dtype)

    x = arr((t, d), jnp.bfloat16).at[:, 0].set(1.0)
    weights = (arr((g, d, f), jnp.bfloat16, 0.02),
               arr((g, d, f), jnp.bfloat16, 0.02),
               arr((g, f, d), jnp.bfloat16, 0.02))
    blocks_ran = {}
    for pushed in (0, 4, 10):
        # 2.5 on the logits of ``pushed`` held experts: most tokens pick
        # most of them (a push of 8 makes every token pick all, and the
        # gradient of the renormalised weights then cancels to nothing
        # but bfloat16's rounding, with the one-buffer program too)
        router = arr((d, e), jnp.bfloat16, 0.02).at[0, :pushed].add(2.5)
        name = f"held_experts_pushed_{pushed}"
        _check_grads(
            name, lambda *a: held_experts_ffn(*a, held, top_k=k)[0],
            experts_ref, (x, router) + weights, "bfloat16", errs)
        landed = int(jax.jit(lambda *a: held_experts_ffn(
            *a, held, top_k=k)[1][0])(x, router, *weights))
        blocks_ran[name] = -(-landed // dispatch_block(t, k, g, e))
        if (blocks_ran[name] > 1) != (pushed > 0) \
                and not isinstance(errs[name], str):
            errs[name] = (f"FAIL {landed} held assignments ran "
                          f"{blocks_ran[name]} blocks")
    print(f"held_experts blocks ran: {blocks_ran}")

    # the gated delta rule at the Qwen3-Next cell's shapes: one sequence of
    # 8,192 tokens, 32 value heads on 16 key heads of 128, chunks of 64.
    # ``gated_delta_rule`` takes the Pallas kernels here (it is handed a
    # TPU and heads of 128); the plain chunked form, its oracle, is XLA's
    from deeplearning4j_tpu.nn.layers.linear_attention import (
        chunk_gated_delta_rule, l2_normalize)
    from deeplearning4j_tpu.ops.pallas_delta_rule import (
        gated_delta_rule, kernels_take)
    t, hk, hv, dh = 8192, 16, 32, 128
    q = (l2_normalize(arr((1, t, hk, dh), jnp.float32))
         / np.sqrt(dh)).astype(jnp.bfloat16)
    k = l2_normalize(arr((1, t, hk, dh), jnp.float32)).astype(jnp.bfloat16)
    v = arr((1, t, hv, dh), jnp.bfloat16)
    log_decay = -jnp.asarray(rng.uniform(1e-3, 0.3, (1, t, hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, (1, t, hv)), jnp.float32)
    _check_grads(
        "gated_delta_rule",
        lambda *a: gated_delta_rule(*a, chunk_size=64),
        lambda q, k, *rest: chunk_gated_delta_rule(
            jnp.repeat(q, hv // hk, 2), jnp.repeat(k, hv // hk, 2), *rest,
            chunk_size=64),
        (q, k, v, log_decay, beta), "bfloat16", errs)
    if not kernels_take(q, v, 64):
        errs["gated_delta_rule"] = "FAIL the kernels did not take the call"
    # the selective scan at the Phi-4-mini-flash cell's shapes: one sequence
    # of 8,192 tokens, 5,120 channels of 16 states, chunks of 64, bfloat16
    # ``x``. ``selective_scan`` takes the Pallas kernels here; the plain
    # chunked form, its oracle, is XLA's
    from deeplearning4j_tpu.nn.layers.state_space import (
        selective_scan_chunked)
    from deeplearning4j_tpu.ops import pallas_selective_scan as ssm
    d, s = 5120, 16
    scan_args = (arr((1, t, d), jnp.bfloat16),
                 jnp.asarray(rng.uniform(1e-3, 0.1, (1, t, d)), jnp.float32),
                 -jnp.asarray(rng.uniform(1.0, 16.0, (d, s)), jnp.float32),
                 arr((1, t, s), jnp.float32), arr((1, t, s), jnp.float32))
    _check_grads("selective_scan", ssm.selective_scan,
                 selective_scan_chunked, scan_args, "float32", errs)
    if not ssm.kernels_take(scan_args[0], scan_args[2], 64):
        errs["selective_scan"] = "FAIL the kernels did not take the call"
    # the Mamba-2 scan with the mixer's skip, gate and grouped norm at the
    # Nemotron 3 Nano cell's shapes: one sequence of 8,192 tokens, 64 heads
    # of 64 in 8 groups, 128 states, chunks of 128, bfloat16 ``x``, ``B``,
    # ``C`` and ``z``. ``ssd_scan`` takes the Pallas kernels here; the
    # plain forms, its oracle, are XLA's
    from deeplearning4j_tpu.nn.layers.state_space import (
        gated_group_norm, ssd_chunked)
    from deeplearning4j_tpu.ops import pallas_ssd_scan as ssd
    h, p, g, s = 64, 64, 8, 128
    ssd_args = (arr((1, t, h, p), jnp.bfloat16),
                jnp.asarray(rng.uniform(1e-3, 0.1, (1, t, h)), jnp.float32),
                -jnp.arange(1, h + 1, dtype=jnp.float32),
                arr((1, t, g, s), jnp.bfloat16, 0.3),
                arr((1, t, g, s), jnp.bfloat16, 0.3),
                arr((1, t, h * p), jnp.bfloat16),
                jnp.asarray(rng.uniform(0.5, 1.5, (h,)), jnp.float32),
                jnp.asarray(rng.uniform(0.5, 1.5, (h * p,)), jnp.float32))
    _check_grads(
        "ssd_scan", lambda *a: ssd.ssd_scan(*a, 1e-5),
        lambda x, dt, a, b, c, z, skip, w: gated_group_norm(
            ssd_chunked(x, dt, a, b, c, chunk_size=128)[0], x, z, skip, w,
            g, 1e-5), ssd_args, "bfloat16", errs)
    if not ssd.kernels_take(ssd_args[0], ssd_args[3], 128):
        errs["ssd_scan"] = "FAIL the kernels did not take the call"
    for name, e in errs.items():
        print(f"kernel {name}: "
              + (e if isinstance(e, str) else f"max_rel_err={e:.3e}"))
    failed = [n for n, e in errs.items() if isinstance(e, str)]
    if failed:
        raise AssertionError(f"{len(failed)} of {len(errs)} kernel checks "
                             f"failed: {', '.join(failed)}")
    return {"checks": len(errs), "worst_rel_err": f"{max(errs.values()):.3e}"}


def _host_array_hold(put, rows):
    """Reference counts of one host array of ``rows`` ResNet images round
    ``put``: before, when ``put`` returns, when the staged array is ready,
    after the next call into the runtime, after the staged array goes.
    ``released_before_ready`` says whether the count was ever back at its
    first reading while the runtime could still be reading the array."""
    import jax
    held = [np.ones((rows, 64, 64, 3), np.float32)]
    # once before the reading: a shape's first ``put`` also builds programs
    jax.block_until_ready(put(np.zeros_like(held[0])))
    counts = {"before": sys.getrefcount(held[0])}
    t0 = time.perf_counter()
    staged = put(held[0])
    counts["put_returned"] = sys.getrefcount(held[0])
    released_at = ready_at = None
    while ready_at is None:
        # the count first: a release seen together with readiness is no
        # early release
        if released_at is None and (sys.getrefcount(held[0])
                                    == counts["before"]):
            released_at = time.perf_counter() - t0
        if staged.is_ready():
            ready_at = time.perf_counter() - t0
    counts["ready"] = sys.getrefcount(held[0])
    jax.device_put(np.zeros(3, np.float32)).block_until_ready()
    counts["after_next_call"] = sys.getrefcount(held[0])
    del staged
    counts["staged_array_gone"] = sys.getrefcount(held[0])
    return {"counts": counts, "ready_ms": round(ready_at * 1e3, 1),
            "released_before_ready": released_at is not None,
            "released_ms": (None if released_at is None
                            else round(released_at * 1e3, 1))}


def _tiny_wrapper():
    """A ParallelWrapper over four chips whose model is beside the point:
    the race check runs its feeder and its ``put``, never its step."""
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer
    from deeplearning4j_tpu.nn.layers.output import OutputLayer
    from deeplearning4j_tpu.ops.losses import LossFunction
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    conf = (NeuralNetConfiguration.Builder().seed(1).updater(Adam(1e-2))
            .list().layer(DenseLayer(n_out=8))
            .layer(OutputLayer(n_out=200, loss=LossFunction.MCXENT))
            .set_input_type(InputType.feed_forward(5)).build())
    wrapper = ParallelWrapper.builder(
        MultiLayerNetwork(conf).init()).workers(4).build()
    wrapper._step, wrapper._batch_sh = wrapper._build_sync_step()
    return wrapper


def _tally_class():
    from deeplearning4j_tpu.datasets.dataset import DataSetIterator

    class Tally(DataSetIterator):
        """Hands on the batches of ``base`` (a user's wrapper, as far as
        the pipeline can tell) and notes which of them say that their
        memory had been used before."""

        def __init__(self, base):
            self.base, self.reused = base, []

        def __iter__(self):
            for batch in self.base:
                self.reused.append(batch.reused_buffers)
                yield batch

        def reset(self):
            self.base.reset()

        @property
        def batch_size(self):
            return self.base.batch_size

    return Tally


def phase_feed_race(ctx):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from deeplearning4j_tpu.datasets.dataset import (
        ArrayDataSetIterator, DataSet)
    from deeplearning4j_tpu.datasets.feeder import DeviceFeeder
    from deeplearning4j_tpu.datasets.iterators import AsyncDataSetIterator

    chips = ctx["chips"]
    rows, batches = RACE[chips]
    rng = np.random.default_rng(RACE_SEED)
    data = DataSet(rng.random((RACE_SET_ROWS, 64, 64, 3), dtype=np.float32),
                   np.eye(200, dtype=np.float32)[
                       rng.integers(0, 200, RACE_SET_ROWS)])
    base = _tally_class()(ArrayDataSetIterator(data, rows, shuffle=True,
                                       seed=RACE_SEED, drop_last=True))
    if chips == 1:
        put, whole_sh = jax.device_put, None
        source = AsyncDataSetIterator(base)     # as fit() wraps it
        feeder = DeviceFeeder(source)
    else:
        wrapper = _tiny_wrapper()
        put = wrapper._put_batch
        whole_sh = NamedSharding(wrapper.mesh, PartitionSpec())
        feeder, source = wrapper._make_feeder(base)
    hold = _host_array_hold(put, rows)
    print("host array round put:", json.dumps(hold), flush=True)

    whole = (jax.device_put(data.features, whole_sh),
             jax.device_put(data.labels, whole_sh))
    same = jax.jit(lambda f, l, wf, wl, at: jnp.logical_and(
        jnp.array_equal(f, wf[at]), jnp.array_equal(l, wl[at])))
    per_pass = RACE_SET_ROWS // rows
    verdicts = []
    t0 = time.perf_counter()
    for epoch in range(batches // per_pass):
        # the order DataSet.shuffle(seed + epoch) draws, drawn here
        order = np.random.default_rng(RACE_SEED + epoch).permutation(
            RACE_SET_ROWS)
        for i, item in enumerate(feeder):
            verdicts.append(same(item.features, item.labels, *whole,
                                 order[i * rows:(i + 1) * rows]))
            if len(verdicts) > RACE_IN_FLIGHT:
                # as many unchecked batches on the device as a fit() keeps
                # staged, no more: the loop waits for transfers only
                verdicts[-1 - RACE_IN_FLIGHT].block_until_ready()
        source.reset()
    feeder.close()
    ok = np.asarray(jax.device_get(verdicts))
    seconds = time.perf_counter() - t0
    wrong = [int(i) for i in np.flatnonzero(~ok)]
    info = {"batches": len(ok), "rows": rows, "wrong": len(wrong),
            "reused": sum(base.reused), "gathered": len(base.reused),
            "pool_buffers": [len(p) for p in base.base._pools[:2]],
            "mbytes_per_s": round(len(ok) * rows * 64 * 64 * 3 * 4
                                  / seconds / 1e6, 1),
            "released_before_ready": hold["released_before_ready"]}
    if len(ok) != batches:
        raise AssertionError(f"{len(ok)} batches staged, expected {batches}")
    if wrong:
        raise AssertionError(f"staged batches {wrong[:20]} differ from an "
                             f"independent gather: {info}")
    return info


# ---- driver --------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--phases", default="",
                    help="comma-separated phases to run (default: all)")
    args = ap.parse_args()

    t0 = time.perf_counter()
    import importlib.metadata as md
    import jax
    import jaxlib
    import deeplearning4j_tpu  # noqa: F401  (applies the compile-cache rule)
    compiles = _Compiles()
    devs = jax.devices()
    dev = devs[0]
    print(f"phase=device {'ok' if dev.platform == 'tpu' else 'FAIL'} "
          f"seconds={time.perf_counter() - t0:.1f} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={md.version('libtpu')} platform={dev.platform} "
          f"device_kind={dev.device_kind!r} count={len(devs)} "
          f"compile_cache_dir={jax.config.jax_compilation_cache_dir}",
          flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found platform "
                 f"{dev.platform!r}. Not run.")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke.py --chips {args.chips}: JAX found "
                 f"{len(devs)} device(s). Not run.")

    phases = ([("train", phase_train), ("serve", phase_serve),
               ("kernels", phase_kernels)] if args.chips == 1
              else [("train", phase_train_4chips)])
    phases.append(("feed_race", phase_feed_race))
    if args.phases:
        asked = args.phases.split(",")
        unknown = set(asked) - {name for name, _ in phases}
        if unknown:
            sys.exit(f"chip_smoke.py --chips {args.chips} has no phase "
                     f"{sorted(unknown)}; it has {[n for n, _ in phases]}")
        phases = [(n, fn) for n, fn in phases if n in asked]
    ctx, failed = {"chips": args.chips}, []
    for name, fn in phases:
        t0, c0 = time.perf_counter(), compiles.seconds
        try:
            info = fn(ctx)
            status = "ok"
        except Exception as e:       # every phase reports; any FAIL exits 1
            traceback.print_exc()
            info = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            status = "FAIL"
            failed.append(name)
        extra = " ".join(f"{k}={v}" for k, v in info.items())
        print(f"phase={name} {status} "
              f"seconds={time.perf_counter() - t0:.1f} "
              f"compile_seconds={compiles.seconds - c0:.1f} {extra}",
              flush=True)

    from deeplearning4j_tpu.utils import native
    print(f"compile_seconds_total={compiles.seconds:.1f} "
          f"cache_hits={compiles.hits} cache_writes={compiles.writes} "
          f"native_lib_loaded={native.loaded()}", flush=True)
    if failed:
        sys.exit(f"chip_smoke.py: FAIL in {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
