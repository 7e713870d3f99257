"""ParallelWrapper — single-process multi-chip data-parallel training.

Analog of the reference's ``ParallelWrapper``
(deeplearning4j-scaleout-parallelwrapper/.../ParallelWrapper.java:58 —
TrainingMode AVERAGING / SHARED_GRADIENTS at :59, fit loop :217-310,
averaging via native ``Nd4j.averageAndPropagate`` :326) redesigned as SPMD:

- **SHARED_GRADIENTS** (default, the reference's EncodedGradientsAccumulator
  path): synchronous data parallelism. The global batch is sharded over the
  ``data`` mesh axis, parameters are replicated, and XLA inserts the
  gradient all-reduce over ICI during the backward pass. No threads, no
  queues, no 1-bit compression — the ICI allreduce IS the accumulator.
- **AVERAGING** (the reference's parameter-averaging mode): local-SGD.
  Each device runs ``averaging_frequency`` optimizer steps on its own batch
  shard with locally-diverged parameters inside a ``shard_map`` +
  ``lax.scan``, then parameters AND updater state are averaged with
  ``lax.pmean`` — exactly the reference's averaging semantics including
  updater-state averaging (ParallelWrapper.averageUpdatersState:338).

Both modes wrap an existing MultiLayerNetwork/ComputationGraph without
changing it: the wrapper builds its own jitted/shard_mapped step around the
model's pure loss function.
"""

from __future__ import annotations

import enum
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.datasets.dataset import DataSet, DataSetIterator
from deeplearning4j_tpu.observe.telemetry import has_buffer
from deeplearning4j_tpu.observe.tracer import get_tracer
from deeplearning4j_tpu.optimize.solver import TrainState
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, create_mesh


class TrainingMode(enum.Enum):
    SHARED_GRADIENTS = "shared_gradients"   # sync allreduce DP
    AVERAGING = "averaging"                 # local SGD + periodic averaging
    ASYNC_ELASTIC = "async_elastic"         # bounded-staleness PS rounds
    CUSTOM = "custom"


def _default_divergence_threshold() -> float:
    # mirrors observe/health.py: past this relative spread of per-replica
    # grad norms the replicas are considered diverging
    try:
        return float(os.environ.get("DL4J_DIVERGENCE_THRESHOLD", "2.0"))  # host-sync-ok: env knob read once at options construction
    except ValueError:
        return 2.0


@dataclass
class ElasticOptions:
    """Knobs for :attr:`TrainingMode.ASYNC_ELASTIC` — the
    parameter-server analog of the reference's Aeron-backed
    SharedTrainingMaster, recast as bounded-staleness rounds.

    Each round every worker runs ``averaging_frequency`` local steps
    from its last adopted server snapshot. Workers that report within
    ``round_deadline_ms`` are *members* of the round: their parameter
    deltas are merged into the server params, staleness-weighted by
    ``staleness_decay ** (age - 1)`` where ``age`` counts the rounds
    since the worker last adopted the server state. A contribution
    older than ``staleness_bound`` rounds is discarded outright (merged
    with weight 0 — the delta is against a hopelessly old base).
    Members adopt the merged server state and reset their age; dropped
    stragglers keep training on their divergent local params and age by
    one.

    The ``dl4j_replica_divergence`` gauge (relative spread of
    per-worker grad norms) guards the whole scheme: past
    ``divergence_threshold`` the next round is forced into a **hard
    sync** — every worker contributes with weight 1 and every worker
    adopts, collapsing the round to plain AVERAGING semantics.

    ``straggler_policy`` exists for tests/benchmarks: a deterministic
    ``(round_index, n_workers) -> per-worker delay in ms`` function
    simulating slow workers. It MUST be deterministic in its arguments
    — in multi-process runs every host evaluates it independently and
    they must agree on the round's membership. None means nobody lags.
    """
    round_deadline_ms: float = 250.0
    staleness_bound: int = 3
    staleness_decay: float = 0.5
    divergence_threshold: float = field(
        default_factory=_default_divergence_threshold)
    straggler_policy: Optional[
        Callable[[int, int], Sequence[float]]] = None


class ParallelWrapper:
    """Builder-style API mirroring the reference:

        wrapper = (ParallelWrapper.builder(model)
                   .training_mode(TrainingMode.SHARED_GRADIENTS)
                   .workers(8)
                   .averaging_frequency(5)
                   .build())
        wrapper.fit(iterator, epochs)
    """

    def __init__(self, model, mesh: Optional[Mesh] = None,
                 mode: TrainingMode = TrainingMode.SHARED_GRADIENTS,
                 averaging_frequency: int = 5,
                 average_updaters: bool = True,
                 tensor_parallel: bool = False,
                 elastic_options: Optional[ElasticOptions] = None,
                 watchdog=None):
        self.model = model
        self.mesh = mesh if mesh is not None else create_mesh()
        self.mode = mode
        self.averaging_frequency = averaging_frequency
        self.average_updaters = average_updaters
        self.tensor_parallel = tensor_parallel
        self.elastic_options = (elastic_options if elastic_options
                                is not None else ElasticOptions())
        self._watchdog = watchdog
        if tensor_parallel and mode is not TrainingMode.SHARED_GRADIENTS:
            # AVERAGING runs per-device replicas inside shard_map — params
            # cannot simultaneously be model-axis sharded; silently
            # ignoring the flag would fake TP at the user
            raise ValueError(
                f"tensor_parallel requires SHARED_GRADIENTS mode, not"
                f" {mode.name}")
        self._step = None
        self._elastic = None        # ASYNC_ELASTIC per-worker state
        self._feeder = None         # the running fit's DeviceFeeder
        if model.train_state is None:
            model.init()

    # ---- builder --------------------------------------------------------
    class Builder:
        def __init__(self, model):
            self._model = model
            self._mesh = None
            self._mode = TrainingMode.SHARED_GRADIENTS
            self._avg_freq = 5
            self._avg_updaters = True
            self._tp = False
            self._elastic_opts = None
            self._wd = None

        def workers(self, n: int):
            devs = jax.devices()
            if n > len(devs):
                raise ValueError(f"requested {n} workers but only"
                                 f" {len(devs)} devices present")
            self._mesh = create_mesh({DATA_AXIS: n}, devs[:n])
            return self

        def mesh(self, mesh: Mesh):
            self._mesh = mesh
            return self

        def training_mode(self, mode: TrainingMode):
            self._mode = mode
            return self

        def averaging_frequency(self, k: int):
            self._avg_freq = k
            return self

        def average_updaters(self, flag: bool):
            self._avg_updaters = flag
            return self

        def tensor_parallel(self, flag: bool = True):
            """Shard parameters over the mesh's ``model`` axis with the
            Megatron row/column pairing (parallel/tensor_parallel.py).
            Requires a mesh with a ``model`` axis (e.g.
            ``create_mesh({"data": 2, "model": 4})``)."""
            self._tp = flag
            return self

        def elastic_options(self, opts: "ElasticOptions"):
            """Bounded-staleness knobs for ASYNC_ELASTIC mode."""
            self._elastic_opts = opts
            return self

        def watchdog(self, wd):
            """Attach a CollectiveWatchdog (parallel/cluster.py): the
            wrapper marks every blocking collective wait in-flight via
            ``wd.guard()`` and routes collective exceptions through
            ``wd.on_collective_error`` so a dead peer produces an
            emergency checkpoint + ``peer_loss`` forensics instead of a
            hang or an unclassified crash."""
            self._wd = wd
            return self

        def build(self) -> "ParallelWrapper":
            return ParallelWrapper(self._model, self._mesh, self._mode,
                                   self._avg_freq, self._avg_updaters,
                                   tensor_parallel=self._tp,
                                   elastic_options=self._elastic_opts,
                                   watchdog=self._wd)

    @staticmethod
    def builder(model) -> "ParallelWrapper.Builder":
        return ParallelWrapper.Builder(model)

    # ---- internals ------------------------------------------------------
    @property
    def num_workers(self) -> int:
        return int(self.mesh.shape[DATA_AXIS])

    def _loss_adapter(self):
        """model-specific pure loss closure (masks threaded through)."""
        from deeplearning4j_tpu.models.multi_layer_network import (
            MultiLayerNetwork)
        m = self.model
        if isinstance(m, MultiLayerNetwork):
            def loss_fn(params, mstate, feats, labels, fmask, lmask, rng, it):
                return m._loss(params, mstate, feats, labels, fmask, lmask,
                               rng, it)
        else:
            def loss_fn(params, mstate, feats, labels, fmask, lmask, rng, it):
                return m._loss(params, mstate, (feats,), (labels,),
                               None if fmask is None else (fmask,),
                               None if lmask is None else (lmask,), rng, it)
        return loss_fn

    def _build_sync_step(self):
        """SHARED_GRADIENTS: jit with sharded batch + replicated (or, with
        ``tensor_parallel``, Megatron row/column-sharded) params. XLA emits
        the gradient psum over ICI in backward — the TPU-native
        EncodingHandler.broadcastUpdates."""
        loss_fn = self._loss_adapter()
        tx = self.model._tx
        mesh = self.mesh
        batch_sh = NamedSharding(mesh, P(DATA_AXIS))
        spec = self.model._telemetry_spec()
        self._built_spec = spec
        # grads here are globally reduced before any code sees them, so
        # the per-device observable is whether the REPLICAS still agree:
        # an L2 param fingerprint per device, gathered over the data axis
        # (desync / silent-data-corruption detector). TP params are
        # model-sharded — per-device norms would differ by construction.
        probe_replicas = (spec is not None and spec.replicas > 1
                          and not self.tensor_parallel)

        def _param_fingerprint(params):
            def l2(p):
                leaves = jax.tree_util.tree_leaves(p)
                sumsq = sum((jnp.sum(jnp.square(l.astype(jnp.float32)))
                             for l in leaves),
                            jnp.zeros((), jnp.float32))
                return jnp.sqrt(sumsq).reshape(1, 1)
            return jax.shard_map(
                l2, mesh=mesh, in_specs=(P(),),
                out_specs=P(DATA_AXIS), check_vma=False)(params)

        ts_sh = None
        if self.tensor_parallel:
            from deeplearning4j_tpu.parallel.mesh import MODEL_AXIS
            from deeplearning4j_tpu.parallel.tensor_parallel import (
                plan_tp, shard_train_state)
            if MODEL_AXIS not in mesh.shape:
                raise ValueError(
                    "tensor_parallel needs a mesh with a 'model' axis; got "
                    f"{dict(mesh.shape)}")
            plan = plan_tp(self.model, mesh)
            _, ts_sh = shard_train_state(self.model, plan)
            self.model._tp_plan = plan

        def step(ts: TrainState, feats, labels, fmask, lmask, rng):
            def lf(params):
                return loss_fn(params, ts.model_state, feats, labels, fmask,
                               lmask, rng, ts.iteration)
            (loss, new_ms), grads = jax.value_and_grad(lf, has_aux=True)(
                ts.params)
            updates, new_opt = tx.update(grads, ts.opt_state, ts.params)
            new_params = optax.apply_updates(ts.params, updates)
            buf = ts.telemetry
            if spec is not None and has_buffer(buf):
                # loss/grads are global here — the base row records the
                # same quantities as the single-device step
                buf = spec.record(buf, loss=loss, grads=grads,
                                  params=new_params,
                                  prev_params=ts.params,
                                  iteration=ts.iteration)
                if probe_replicas:
                    buf = spec.record_replica(
                        buf, values=_param_fingerprint(new_params),
                        iteration=ts.iteration)
            return TrainState(new_params, new_ms, new_opt,
                              ts.iteration + 1, buf), loss

        return jax.jit(
            step,
            in_shardings=(ts_sh, batch_sh, batch_sh, batch_sh, batch_sh,
                          None),
            out_shardings=(ts_sh, None),
            donate_argnums=(0,),
        ), batch_sh

    def _build_averaging_step(self):
        """AVERAGING: shard_map over the data axis; each worker runs
        ``averaging_frequency`` local steps (lax.scan over per-step batch
        slices), then params (+ updater state) are pmean'd — the
        Nd4j.averageAndPropagate analog (ParallelWrapper.java:326,338)."""
        loss_fn = self._loss_adapter()
        tx = self.model._tx
        mesh = self.mesh
        k = self.averaging_frequency
        avg_upd = self.average_updaters
        spec = self.model._telemetry_spec()
        self._built_spec = spec
        record_replicas = spec is not None and spec.replicas > 1

        def worker_steps(ts: TrainState, feats, labels, fmask, lmask, rng):
            # feats: (k, local_batch, ...) — k local steps for this worker
            widx = jax.lax.axis_index(DATA_AXIS)
            rng = jax.random.fold_in(rng, widx)

            def one(carry, xs):
                ts = carry
                f, l, fm, lm, i = xs
                key = jax.random.fold_in(rng, i)

                def lf(params):
                    return loss_fn(params, ts.model_state, f, l, fm, lm, key,
                                   ts.iteration)
                (loss, new_ms), grads = jax.value_and_grad(
                    lf, has_aux=True)(ts.params)
                updates, new_opt = tx.update(grads, ts.opt_state, ts.params)
                new_params = optax.apply_updates(ts.params, updates)
                # local grad-norm rides the scan ys: this worker's
                # gradients never leave the device otherwise, so this is
                # the ONLY place a genuine per-replica norm exists
                gnorm = jnp.sqrt(sum(
                    (jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for g in jax.tree_util.tree_leaves(grads)),
                    jnp.zeros((), jnp.float32)))
                return (TrainState(new_params, new_ms, new_opt,
                                   ts.iteration + 1, ts.telemetry),
                        (loss, gnorm))

            ts, (losses, gnorms) = jax.lax.scan(
                one, ts, (feats, labels, fmask, lmask, jnp.arange(k)))
            buf = ts.telemetry
            if record_replicas and has_buffer(buf):
                # per-worker means over the k local steps, gathered so
                # every device writes the identical [n_workers, 2] row —
                # the replicated layout the buffer lives in
                wl = jax.lax.all_gather(
                    jnp.mean(losses.astype(jnp.float32)), DATA_AXIS)
                wg = jax.lax.all_gather(jnp.mean(gnorms), DATA_AXIS)
                buf = spec.record_replica(
                    buf, values=jnp.stack([wl, wg], axis=-1),
                    iteration=ts.iteration - 1)
            # --- parameter averaging across the data axis (ICI psum) ---
            # integer leaves (Adam/updater step counts) are identical on
            # every replica and pmean would promote them to float,
            # corrupting the next round's tx.update — keep them verbatim
            avg = lambda t: (t if jnp.issubdtype(t.dtype, jnp.integer)
                             else jax.lax.pmean(t, DATA_AXIS))
            new_params = jax.tree_util.tree_map(avg, ts.params)
            new_ms = jax.tree_util.tree_map(avg, ts.model_state)
            new_opt = (jax.tree_util.tree_map(avg, ts.opt_state)
                       if avg_upd else ts.opt_state)
            return (TrainState(new_params, new_ms, new_opt, ts.iteration,
                               buf),
                    jax.lax.pmean(jnp.mean(losses), DATA_AXIS))

        # Everything replicated except the batch: (k, B, ...) sharded on B.
        pspec_batch = P(None, DATA_AXIS)
        wrapped = jax.shard_map(
            worker_steps, mesh=mesh,
            in_specs=(P(), pspec_batch, pspec_batch, pspec_batch,
                      pspec_batch, P()),
            out_specs=(P(), P()),
            check_vma=False)
        return jax.jit(wrapped, donate_argnums=(0,)), None

    def _build_async_step(self):
        """ASYNC_ELASTIC: bounded-staleness parameter-server rounds.

        Server params live replicated in ``model.train_state``; each
        worker additionally carries LOCAL params/updater-state plus the
        server snapshot it last adopted (``base``), all stacked with a
        leading worker dim sharded over the data axis. One round =
        ``averaging_frequency`` local steps per worker (same scan as
        AVERAGING), then a presence/staleness-weighted delta merge:

            theta' = theta + sum_i(w_i * (local_i - base_i)) / sum_i(w_i)
            w_i    = present_i * decay^(age_i)      (0 past the bound)

        Members (present_i=1) adopt theta' and reset base; dropped
        stragglers keep drifting on their local params. A hard-sync
        round (``hard=1``) ignores staleness entirely: every worker
        contributes with weight 1 and adopts — exactly an AVERAGING
        round. With no stragglers every round IS a hard round
        semantically (all ages 0, all weights 1), which is what makes
        straggler-free ASYNC_ELASTIC converge like AVERAGING.

        Presence/ages/hard are computed on the host (deterministic
        straggler policy — see ElasticOptions) and fed as tiny arrays;
        everything heavy stays on device.
        """
        loss_fn = self._loss_adapter()
        tx = self.model._tx
        mesh = self.mesh
        k = self.averaging_frequency
        avg_upd = self.average_updaters
        opts = self.elastic_options
        bound = float(opts.staleness_bound)  # host-sync-ok: trace-time config
        decay = float(opts.staleness_decay)  # host-sync-ok: trace-time config
        spec = self.model._telemetry_spec()
        self._built_spec = spec
        record_replicas = spec is not None and spec.replicas > 1

        def unstack(t):
            # inside shard_map each worker owns leading-dim slice [1, ...]
            return jax.tree_util.tree_map(lambda a: a[0], t)

        def restack(t):
            return jax.tree_util.tree_map(lambda a: a[None], t)

        def round_fn(ts: TrainState, local_p, local_o, base_p,
                     feats, labels, fmask, lmask, rng,
                     present, ages, hard):
            widx = jax.lax.axis_index(DATA_AXIS)
            lp, lo, bp = unstack(local_p), unstack(local_o), unstack(base_p)
            rng_w = jax.random.fold_in(rng, widx)

            def one(carry, xs):
                lp, lo, ms = carry
                f, l, fm, lm, i = xs
                key = jax.random.fold_in(rng_w, i)

                def lf(params):
                    return loss_fn(params, ms, f, l, fm, lm, key,
                                   ts.iteration + i)
                (loss, new_ms), grads = jax.value_and_grad(
                    lf, has_aux=True)(lp)
                updates, new_lo = tx.update(grads, lo, lp)
                new_lp = optax.apply_updates(lp, updates)
                gnorm = jnp.sqrt(sum(
                    (jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for g in jax.tree_util.tree_leaves(grads)),
                    jnp.zeros((), jnp.float32)))
                return (new_lp, new_lo, new_ms), (loss, gnorm)

            (lp, lo, ms), (losses, gnorms) = jax.lax.scan(
                one, (lp, lo, ts.model_state),
                (feats, labels, fmask, lmask, jnp.arange(k)))

            # ---- per-worker stats, gathered replicated ----------------
            wl = jax.lax.all_gather(
                jnp.mean(losses.astype(jnp.float32)), DATA_AXIS)
            wg = jax.lax.all_gather(jnp.mean(gnorms), DATA_AXIS)
            stats = jnp.stack([wl, wg], axis=-1)        # (n, 2)
            buf = ts.telemetry
            if record_replicas and has_buffer(buf):
                buf = spec.record_replica(buf, values=stats,
                                          iteration=ts.iteration + k - 1)

            # ---- staleness-weighted delta merge -----------------------
            pres = present[widx]
            age1 = ages[widx] + 1.0     # rounds of drift incl. this one
            w_soft = pres * jnp.where(age1 <= bound,
                                      decay ** (age1 - 1.0), 0.0)
            w = jnp.where(hard > 0, 1.0, w_soft)
            den = jax.lax.psum(w, DATA_AXIS)
            safe_den = jnp.maximum(den, 1e-12)

            def merge_params(srv, l, b):
                num = jax.lax.psum(w * (l - b), DATA_AXIS)
                return jnp.where(den > 0, srv + num / safe_den, srv)
            new_theta = jax.tree_util.tree_map(
                merge_params, ts.params, lp, bp)

            # model/opt state: adoption-weighted mean over members
            # (integer leaves — updater step counts — keep the server's
            # copy verbatim: a pmean would float-promote them)
            a = jnp.where(hard > 0, 1.0, pres)
            da = jax.lax.psum(a, DATA_AXIS)
            safe_da = jnp.maximum(da, 1e-12)

            def merge_state(srv, l):
                if jnp.issubdtype(srv.dtype, jnp.integer):
                    return srv
                num = jax.lax.psum(a * l, DATA_AXIS)
                return jnp.where(da > 0, num / safe_da, srv)
            new_ms = jax.tree_util.tree_map(merge_state, ts.model_state,
                                            ms)
            new_opt = (jax.tree_util.tree_map(merge_state, ts.opt_state,
                                              lo)
                       if avg_upd else ts.opt_state)

            # ---- worker adoption --------------------------------------
            adopt = jnp.where(hard > 0, 1.0, pres)

            def take(new, old):
                if jnp.issubdtype(old.dtype, jnp.integer):
                    return old          # counts advance locally
                return jnp.where(adopt > 0, new, old)
            lp2 = jax.tree_util.tree_map(take, new_theta, lp)
            bp2 = jax.tree_util.tree_map(take, new_theta, bp)
            lo2 = (jax.tree_util.tree_map(take, new_opt, lo)
                   if avg_upd else lo)

            new_ts = TrainState(new_theta, new_ms, new_opt,
                                ts.iteration + k, buf)
            loss_out = jax.lax.pmean(jnp.mean(losses), DATA_AXIS)
            return (new_ts, restack(lp2), restack(lo2), restack(bp2),
                    stats, loss_out)

        pspec_batch = P(None, DATA_AXIS)
        stacked = P(DATA_AXIS)          # leading worker dim
        wrapped = jax.shard_map(
            round_fn, mesh=mesh,
            in_specs=(P(), stacked, stacked, stacked,
                      pspec_batch, pspec_batch, pspec_batch, pspec_batch,
                      P(), P(), P(), P()),
            out_specs=(P(), stacked, stacked, stacked, P(), P()),
            check_vma=False)
        return jax.jit(wrapped, donate_argnums=(0, 1, 2, 3)), None

    # ---- fit ------------------------------------------------------------
    def fit(self, iterator: DataSetIterator, epochs: int = 1):
        """Train over the iterator.

        Multi-process contract: EVERY batch each host yields (not just
        the first) must be proportional to that host's share of the mesh
        devices — same rows-per-device everywhere. Hosts pad their tail
        batches independently (``_pad_batch`` pads to the local worker
        multiple), so an uneven final split that violates this builds
        inconsistent global shapes and hangs the first collective rather
        than raising; the cross-host equality check runs only once (see
        ``_global_batch_size`` for why repeating it would itself
        deadlock). A collective-free local monitor warns when a
        *non-final* batch's per-device count drifts from the checked
        value — the final batch legitimately may."""
        self._pending_uneven_per = None     # fresh fit: prior tail is fine
        if self.mode not in (TrainingMode.SHARED_GRADIENTS,
                             TrainingMode.AVERAGING,
                             TrainingMode.ASYNC_ELASTIC):
            raise ValueError(f"unsupported mode: {self.mode}")
        m = self.model
        m._enter_fit_call()
        self._arm_telemetry()
        try:
            if self.mode is TrainingMode.SHARED_GRADIENTS:
                self._fit_sync(iterator, epochs)
            elif self.mode is TrainingMode.ASYNC_ELASTIC:
                self._fit_async(iterator, epochs)
            else:
                self._fit_averaging(iterator, epochs)
            m._end_fit_call()
            return m
        except Exception as e:
            # a collective that RAISES on peer death (fail-fast
            # transports like gloo) goes through the watchdog's
            # classifier first: peer loss gets the emergency checkpoint
            # + peer_loss dump + resumable marker instead of a generic
            # crash dump
            wd = self._watchdog
            if wd is not None and wd.on_collective_error(e):
                raise
            # same crash-forensics contract as BaseModel.fit: dump, then
            # let the exception surface
            rec = m._recorder()
            if rec is not None:
                rec.record_crash(m, exc=e)
            raise
        finally:
            feeder, self._feeder = self._feeder, None
            if feeder is not None:
                # a traced fit's last ``resident`` spans; no-op untraced
                feeder.close()

    def _arm_telemetry(self):
        """Extend an attached TelemetryCollector with the per-device row
        ring: AVERAGING workers report genuine per-worker loss/grad-norm
        (local gradients exist per device there); synchronous DP reports
        an L2 param fingerprint per device, since its gradients are
        globally reduced before any code sees them. Enabling changes the
        buffer pytree, so the step is rebuilt and the buffer rebound —
        once, before the next dispatch. Also rebuilds the step when a
        collector was attached/detached after the step was compiled."""
        m = self.model
        tel = m.telemetry
        spec = m._telemetry_spec()
        if (self._step is not None
                and getattr(self, "_built_spec", None) is not spec):
            self._step = None
        if tel is None or self.num_workers <= 1 or self.tensor_parallel:
            return
        metrics = (("loss", "grad_norm")
                   if self.mode in (TrainingMode.AVERAGING,
                                    TrainingMode.ASYNC_ELASTIC)
                   else ("param_norm",))
        if tel.enable_replicas(self.num_workers, metrics):
            self._step = None
            if m.train_state is not None:
                m.train_state = tel.rebind_buffer(m.train_state)

    def _pad_batch(self, batch: DataSet, target: int | None = None) -> DataSet:
        """Pad to a multiple of num_workers (and optionally to ``target``
        examples) with zero-weight rows: padded examples carry
        labels_mask == 0, so the masked loss mean ignores them. Loss and
        gradients then match the unpadded single-device step; the one
        exception is BatchNormalization batch statistics, which see the
        duplicated rows (mask-free batch moments) — a bounded, usually
        negligible perturbation. (The reference rebalances queues across
        trainer threads instead — ParallelWrapper.java:225; static shapes
        make padding the XLA way.) Row duplication + mask synthesis live
        in datasets/feeder.pad_rows — one implementation for the fit loop
        and the wrapper."""
        from deeplearning4j_tpu.datasets.feeder import pad_rows
        n = batch.num_examples()
        w = self.num_workers
        pad = ((target - n) if target else 0) + ((-(target or n)) % w)
        return pad_rows(batch, pad)

    def _put_batch(self, a, sharding=None, batch_dim: int = 0):
        """Stage one batch tensor onto the data-sharded layout.

        Single process: device_put of the full array. Multi-process
        (real multi-host): ``a`` is THIS process's shard of the global
        batch (the standard jax data-loading contract — each host's
        iterator yields its share), assembled into the global array via
        make_array_from_process_local_data; XLA moves nothing between
        hosts. Processes may own UNEVEN device counts (round 3): each
        local batch must be proportional to this process's share of the
        mesh devices (checked once per shape — a wrong split would
        silently build inconsistent global shapes and hang the first
        collective)."""
        if a is None:
            return None
        sh = self._batch_sh if sharding is None else sharding
        if jax.process_count() == 1:
            return jax.device_put(jnp.asarray(a), sh)
        a = np.asarray(a)  # host-sync-ok: host-side batch split/pad before transfer
        total = self._global_batch_size(a.shape[batch_dim])
        gshape = list(a.shape)
        gshape[batch_dim] = total
        return jax.make_array_from_process_local_data(sh, a,
                                                      tuple(gshape))

    def _global_batch_size(self, n: int) -> int:
        """Global batch rows for a local shard of ``n`` rows: every
        device carries the same per-device batch, so the global size is
        (n / local_devices) · global_devices — valid when processes own
        UNEVEN device counts.

        The cross-process consistency check (a tiny device-sharded
        reduction) runs exactly ONCE, on the very first staged array —
        a point every process reaches together. It must NOT be repeated
        per shard size: processes can see different size sequences, and
        a check collective entered by only some of them would deadlock
        against the train-step collective of the rest."""
        loc = jax.local_device_count()
        if n % loc:
            raise ValueError(
                f"multi-host fit: this process's batch shard ({n} rows) "
                f"must divide evenly over its {loc} local devices — "
                "split each host's data by its device share.")
        per = n // loc
        if not getattr(self, "_batch_check_done", False):
            self._batch_check_done = True
            self._checked_per = per
            from deeplearning4j_tpu.parallel.mesh import (
                global_device_value_range)
            mn, mx = global_device_value_range(float(per))  # host-sync-ok: one-time per-device batch barrier
            if mn != mx:
                raise ValueError(
                    "multi-host fit needs the SAME per-device batch on "
                    f"every process; this process feeds {per} rows/"
                    f"device but the mesh sees between {int(mn)} and "
                    f"{int(mx)}. Split each host's data shard by its "
                    "device share.")
        return per * jax.device_count()

    def _monitor_uneven_batch(self, n: int):
        """Collective-free drift monitor (advisor r3), batch-level: a
        batch whose per-device count differs from the checked value is
        legal only as the FINAL batch of a fit. When ANOTHER batch
        follows an uneven one, the uneven one was mid-stream and the
        global shapes it built were inconsistent across hosts — warn
        loudly, once (we cannot raise retroactively, and a fresh
        collective check would deadlock; see ``_global_batch_size``)."""
        loc = jax.local_device_count()
        per = n // loc if n % loc == 0 else n / loc
        if (getattr(self, "_pending_uneven_per", None) is not None
                and not getattr(self, "_uneven_warned", False)):
            self._uneven_warned = True
            import warnings
            warnings.warn(
                "multi-host fit: a NON-final batch fed "
                f"{self._pending_uneven_per} rows/device where the "
                f"checked value is {getattr(self, '_checked_per', '?')} "
                "— each host must split every mid-stream batch "
                "proportionally to its device share; the preceding "
                "collective may have mixed inconsistent global shapes.",
                stacklevel=3)
        checked = getattr(self, "_checked_per", None)
        self._pending_uneven_per = per if (checked is not None
                                           and per != checked) else None

    def _sync_prepare(self, batch: DataSet) -> DataSet:
        """Host-side prep for one sync-mode batch: pad to the worker
        multiple, then run the multi-host drift monitor. Shared by the
        legacy per-batch staging and the DeviceFeeder ``prepare`` hook."""
        batch = self._pad_batch(batch)
        if jax.process_count() > 1:
            self._monitor_uneven_batch(batch.num_examples())
        return batch

    def _stage_batch(self, batch: DataSet):
        """Pad to the worker multiple and stage the four batch arrays on
        the mesh — the single home for sync-step argument staging."""
        batch = self._sync_prepare(batch)
        return (self._put_batch(batch.features),
                self._put_batch(batch.labels),
                self._put_batch(batch.features_mask),
                self._put_batch(batch.labels_mask))

    def _make_feeder(self, iterator):
        """Build the DeviceFeeder for this mode: per-replica shards are
        placed on the mesh (``_put_batch``) while the current round
        computes, and plain iterators get the AsyncDataSetIterator wrap —
        the same overlap fit() has, honoring AsyncShield. Returns
        (feeder, source); feeder is None when the iterator opted out."""
        from deeplearning4j_tpu.datasets.feeder import DeviceFeeder
        from deeplearning4j_tpu.datasets.iterators import (
            AsyncDataSetIterator)
        if not getattr(iterator, "async_supported", True):
            return None, iterator
        source = iterator
        if (isinstance(iterator, DataSetIterator)
                and not isinstance(iterator, AsyncDataSetIterator)):
            source = AsyncDataSetIterator(iterator)
        tracer = get_tracer(self.model)
        if isinstance(source, AsyncDataSetIterator):
            source.tracer = tracer      # its worker's ``produce`` spans
        if self.mode in (TrainingMode.AVERAGING,
                         TrainingMode.ASYNC_ELASTIC):
            feeder = DeviceFeeder(
                source, k_steps=self.averaging_frequency,
                pad_ragged=False,
                group_prepare=self._avg_group_prepare,
                group_remainder="pad",
                put=lambda a: self._put_batch(
                    a, sharding=self._avg_batch_sh, batch_dim=1),
                tracer=tracer, session_id="parallel")
        else:
            feeder = DeviceFeeder(source, prepare=self._sync_prepare,
                                  pad_ragged=False, put=self._put_batch,
                                  tracer=tracer, session_id="parallel")
        self._feeder = feeder
        return feeder, source

    def collective_census(self, batch: DataSet):
        """Compile the sync step for this batch's shapes and count its
        collective HLOs (the TP communication audit — e.g. the ResNet50
        conv pairing should show ~1 all-gather + 1 all-reduce per
        bottleneck plus the gradient all-reduce over the data axis).

        Note: this AOT-compiles a separate audit executable — jax's jit
        dispatch cache is not populated by ``lower().compile()``, so a
        following ``fit`` still compiles its own step."""
        from deeplearning4j_tpu.parallel.tensor_parallel import (
            count_collectives)
        if self.mode is not TrainingMode.SHARED_GRADIENTS:
            raise ValueError("collective_census audits the sync step")
        if self._step is None:
            self._step, self._batch_sh = self._build_sync_step()
        feats, labels, fmask, lmask = self._stage_batch(batch)
        compiled = self._step.lower(self.model.train_state, feats, labels,
                                    fmask, lmask,
                                    jax.random.PRNGKey(0)).compile()
        return count_collectives(compiled)

    def _fit_sync(self, iterator, epochs):
        if self._step is None:
            self._step, self._batch_sh = self._build_sync_step()
        m = self.model
        feeder, source = self._make_feeder(iterator)
        for _ in m._epochs(epochs, source):
            if feeder is not None:
                for item in feeder:
                    if item.k == 0:
                        # foreign object the feeder passed through:
                        # legacy staging (raises where it always did)
                        self._fit_sync_one(item.raw, item.queue_wait_ms)
                    else:
                        # the feeder already padded and placed the
                        # per-replica shards
                        self._send(item[:4], item.queue_wait_ms,
                                   item.n_examples, seq=item.seq)
            else:
                t0 = time.perf_counter()
                for batch in iterator:
                    etl_ms = (time.perf_counter() - t0) * 1000
                    self._fit_sync_one(batch, etl_ms)
                    t0 = time.perf_counter()
            # an epoch's final batch is "final" — a legal uneven tail
            # must not trip the drift monitor on the next epoch
            self._pending_uneven_per = None

    def _fit_sync_one(self, batch, etl_ms: float):
        """Legacy (unfed) sync-mode body: stage this batch now, then
        dispatch — used when the feeder is shielded off, and for foreign
        passthrough objects."""
        n_real = batch.num_examples()
        self._send(self._stage_batch(batch), etl_ms, n_real)

    def _send(self, arrays, wait_ms, n_real, seq=None, k=None):
        """One SHARED_GRADIENTS step, or an AVERAGING round of ``k`` local
        steps, through the model's dispatch protocol."""
        m = self.model
        steps = 1 if k is None else k
        m.train_state, loss = m._send_step(self._step, "parallel_step",
                                           arrays, seq=seq, k=k)
        self._guarded_wait(loss, steps)
        m._record_step(steps, loss, wait_ms, n_real)

    def _fit_averaging(self, iterator, epochs):
        if self._step is None:
            self._step, _ = self._build_averaging_step()
        self._fit_rounds(iterator, epochs, self._send)

    def _fit_async(self, iterator, epochs):
        if self._step is None:
            self._step, _ = self._build_async_step()
        if self._elastic is None:
            self._init_elastic_state()
        self._fit_rounds(iterator, epochs, self._send_async)

    def _fit_rounds(self, iterator, epochs, send):
        """Shared round loop for the k-local-steps modes (AVERAGING and
        ASYNC_ELASTIC): group k batches per round, fed or legacy, and
        ``send`` each round."""
        # (k, B, ...) rounds shard the batch dim over data; multi-host
        # staging assembles each process's slice (see _put_batch)
        self._avg_batch_sh = NamedSharding(self.mesh,
                                           P(None, DATA_AXIS))
        k = self.averaging_frequency
        feeder, source = self._make_feeder(iterator)
        for _ in self.model._epochs(epochs, source):
            if feeder is not None:
                # the feeder groups k batches per round (short tails
                # repeat the last batch — the old pending loop's
                # contract), runs _avg_group_prepare on the host thread,
                # and places the stacked (k, B, ...) round shards before
                # the previous round finishes
                for item in feeder:
                    if item.k == 0:
                        raise TypeError(
                            f"ParallelWrapper {self.mode.name} consumes "
                            "DataSet batches, got "
                            f"{type(item.raw).__name__}")
                    send(item[:4], item.queue_wait_ms, item.n_examples,
                         seq=item.seq, k=item.k)
            else:
                pending = []
                for batch in iterator:
                    pending.append(batch)
                    if len(pending) == k:
                        self._send_round(send, pending)
                        pending = []
                if pending:
                    # pad the round reusing batches (keeps shapes static)
                    while len(pending) < k:
                        pending.append(pending[-1])
                    self._send_round(send, pending)
            self._pending_uneven_per = None     # legal uneven tail round

    def _send_round(self, send, batches):
        """Legacy (unfed) round: stack the batches and place them now."""
        n_real = sum(b.num_examples() for b in batches)
        # multi-host: each process holds its slice of the (k, B) global
        # batch along the batch dim (dim 1)
        arrays = tuple(
            None if a is None else self._put_batch(
                a, sharding=self._avg_batch_sh, batch_dim=1)
            for a in self._avg_group_prepare(batches))
        send(arrays, 0.0, n_real, k=len(batches))

    def _avg_group_prepare(self, batches):
        """Host-side staging of one averaging round: equalize example
        counts with masked padding, harmonize labels masks, stack to
        (k, B, ...) host arrays. Shared by the legacy round path and the
        DeviceFeeder ``group_prepare`` hook."""
        from deeplearning4j_tpu.datasets.feeder import ones_labels_mask
        # equalize batch sizes (stacking needs it), padding w/ masked rows
        target = max(b.num_examples() for b in batches)
        batches = [self._pad_batch(b, target=target) for b in batches]
        if jax.process_count() > 1:
            # same drift contract as _stage_batch: every mid-stream
            # round's per-host rows must match the checked value
            self._monitor_uneven_batch(batches[0].num_examples())
        # padding gave short batches a labels_mask; full-size batches must
        # then get an all-ones mask, or stack() would drop every mask and
        # train on the padded rows as real examples
        if any(b.labels_mask is not None for b in batches):
            batches = [b if b.labels_mask is not None else DataSet(
                b.features, b.labels, b.features_mask, ones_labels_mask(b))
                for b in batches]

        def stack(get):
            vals = [get(b) for b in batches]
            if any(v is None for v in vals):
                return None
            return np.stack([np.asarray(v) for v in vals])  # host-sync-ok: host-side batch staging for averaging round

        return (stack(lambda b: b.features), stack(lambda b: b.labels),
                stack(lambda b: b.features_mask),
                stack(lambda b: b.labels_mask))

    # ---- ASYNC_ELASTIC --------------------------------------------------
    def _init_elastic_state(self):
        """Stack n copies of the server params/updater-state with a
        leading worker dim sharded over the data axis — each worker's
        local replica plus the base snapshot it diverges from."""
        m = self.model
        n = self.num_workers
        stacked_sh = NamedSharding(self.mesh, P(DATA_AXIS))

        stack_n = jax.jit(
            lambda tree: jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (n,) + a.shape),
                tree),
            out_shardings=stacked_sh)
        ts = m.train_state
        self._elastic = {
            "local_params": stack_n(ts.params),
            "local_opt": stack_n(ts.opt_state),
            "base_params": stack_n(ts.params),
            "ages": np.zeros(n, dtype=np.float32),
            "round": 0,
            "hard_next": False,
        }

    def _send_async(self, arrays, wait_ms, n_real, seq=None, k=1):
        """One bounded-staleness round of ``k`` local steps: host computes
        this round's membership (deterministic straggler policy) and
        staleness ages, the device step does the weighted merge, then the
        divergence guard decides whether the NEXT round is a hard sync."""
        m = self.model
        el = self._elastic
        opts = self.elastic_options
        n = self.num_workers
        round_idx = el["round"]
        hard = bool(el["hard_next"])
        if opts.straggler_policy is not None and not hard:
            delays = np.asarray(  # host-sync-ok: host-side policy output, not device data
                opts.straggler_policy(round_idx, n), dtype=np.float64)
            if delays.shape != (n,):
                raise ValueError(
                    "straggler_policy must return one delay per worker "
                    f"({n}), got shape {delays.shape}")
            present = (delays <= opts.round_deadline_ms
                       ).astype(np.float32)
        else:
            present = np.ones(n, dtype=np.float32)
        ages = el["ages"]

        (m.train_state, el["local_params"], el["local_opt"],
         el["base_params"], stats, loss) = m._send_step(
            self._step, "parallel_step", arrays,
            lead=(el["local_params"], el["local_opt"], el["base_params"]),
            after=(jnp.asarray(present), jnp.asarray(ages),
                   jnp.float32(1.0 if hard else 0.0)),
            seq=seq, k=k)
        self._guarded_wait(loss, k)

        # ---- host bookkeeping: ages, counters, divergence guard -------
        age1 = ages + 1.0
        adopted = np.ones(n, dtype=bool) if hard else present > 0
        merged_stale = int(np.sum(adopted & (age1 > 1)
                                  & (age1 <= opts.staleness_bound)))
        discarded_stale = 0 if hard else int(
            np.sum(adopted & (age1 > opts.staleness_bound)))
        dropped = int(np.sum(~adopted))
        el["ages"] = np.where(adopted, 0.0, age1).astype(np.float32)
        el["round"] = round_idx + 1

        # ONE small fetch per round (k steps amortize it) — the
        # divergence guard needs the per-worker grad norms on host
        with m._blocked("elastic_stats", k):
            arr = np.asarray(stats)  # host-sync-ok: per-round (k steps) fetch of the (n,2) stats row for the divergence guard
        gnorms = arr[:, 1]
        finite = gnorms[np.isfinite(gnorms)]
        if finite.size < gnorms.size:
            div = float("inf")      # host-sync-ok: a non-finite worker IS divergence
        elif finite.size >= 2:
            scale = float(np.mean(np.abs(finite)))  # host-sync-ok: np math on the already-fetched stats row
            div = float((finite.max() - finite.min()) / (scale + 1e-12))  # host-sync-ok: np math on the already-fetched stats row
        else:
            div = 0.0
        el["hard_next"] = div > opts.divergence_threshold
        self._publish_elastic(n - dropped, dropped, merged_stale,
                              discarded_stale, float(el["ages"].max()),  # host-sync-ok: host np bookkeeping
                              div, hard)

        m._record_step(k, loss, wait_ms, n_real)

    def _publish_elastic(self, members, dropped, merged_stale,
                         discarded_stale, max_age, div, was_hard):
        try:
            from deeplearning4j_tpu.observe.registry import (
                default_registry)
            r = default_registry()
        except Exception:
            return
        s = "elastic"
        r.gauge("dl4j_elastic_round_members", "workers whose delta was "
                "merged in the latest ASYNC_ELASTIC round").set(
            members, session=s)
        r.gauge("dl4j_elastic_staleness", "max rounds any worker has "
                "drifted without adopting the server params").set(
            max_age, session=s)
        if dropped:
            r.counter("dl4j_elastic_stragglers_dropped_total", "workers "
                      "dropped from a round for missing the deadline"
                      ).inc(dropped, session=s)
        if merged_stale:
            r.counter("dl4j_elastic_stale_merged_total", "late worker "
                      "contributions merged staleness-weighted").inc(
                merged_stale, session=s)
        if discarded_stale:
            r.counter("dl4j_elastic_stale_discarded_total", "late "
                      "contributions discarded past the staleness bound"
                      ).inc(discarded_stale, session=s)
        if was_hard:
            r.counter("dl4j_elastic_hard_syncs_total", "rounds forced "
                      "into full synchronous averaging by the "
                      "divergence guard").inc(session=s)
        r.gauge("dl4j_replica_divergence", "relative max pairwise "
                "spread of per-replica grad norms (0 = replicas in "
                "sync)").set(div, session=s)

    # ---- watchdog plumbing ----------------------------------------------
    def _guarded_wait(self, x, steps: int = 1):
        """Block on the output of the ``steps`` just dispatched under the
        collective watchdog's in-flight window, so a peer that died
        mid-collective turns into a peer_loss exit instead of an infinite
        hang (span ``blocked``, ``on="collective"``). No-op without a
        watchdog — the usual async dispatch pipelining is then
        preserved."""
        wd = self._watchdog
        if wd is None:
            return
        m = self.model
        it = getattr(m, "_host_iteration", None)
        with wd.guard(iteration=it if it is not None else 0), \
                m._blocked("collective", steps):
            jax.block_until_ready(x)
