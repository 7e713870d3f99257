"""Worker for the multihost chaos / uneven-device tests
(tests/test_multihost_chaos.py). Launched as

  python multihost_chaos_worker.py <rank> <nprocs> <port> <outdir> \
      <devices_csv> <die_rank> <die_step> <epochs> [mode]

``devices_csv`` lists EVERY rank's device count (e.g. "2,1,1"), so each
process can size its proportional slice of the global batch.

``mode`` is "dp" (default — the 1D data-parallel MLP job) or
"3d:DPxTPxPP" (e.g. "3d:2x2x1") — a composed dp×tp×pp
PipelinedTransformerLM job whose checkpoints restore across DIFFERENT
3D layouts via restore_sharded's explicit param_shardings path.

Each process owns ``local_devices`` virtual CPU devices (UNEVEN counts
across ranks are the point — a 2+1+1 layout is the honest simulation of
heterogeneous hosts). Training runs with frequent COMMITTED
checkpoints; rank ``die_rank`` (if >= 0) dies abruptly (os._exit) at
iteration ``die_step`` — mid-fit, after at least one checkpoint
committed. Survivors detect the broken collective through the
CollectiveWatchdog (heartbeat classification: dead peer vs straggler),
write the peer_loss forensics + resumable marker, and exit cleanly; the
relaunched (smaller/reshaped) job resumes from the last COMMITTED
checkpoint and reshards onto its new mesh — the reference's recovery
semantics (Spark recompute + driver-held params, SURVEY §5.3)
re-expressed as restore-and-reshard.
"""

import json
import os
import sys

rank, nprocs, port, outdir, devices_csv, die_rank, die_step, epochs = (
    int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4],
    sys.argv[5], int(sys.argv[6]), int(sys.argv[7]), int(sys.argv[8]))
mode = sys.argv[9] if len(sys.argv) > 9 else "dp"
counts = [int(c) for c in devices_csv.split(",")]
local_devices = counts[rank]

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={local_devices}")

import jax

jax.config.update("jax_platforms", "cpu")


def _make_watchdog(model, ckpt_dir):
    """Shared watchdog arming: heartbeats in outdir/hb, peer-loss
    markers + emergency checkpoint next to the training checkpoints.
    exit_on_loss covers the silent-hang path; the raise path goes
    through on_collective_error in the except handlers below."""
    from deeplearning4j_tpu.parallel.cluster import CollectiveWatchdog
    wd = CollectiveWatchdog(
        os.path.join(outdir, "hb"), rank=rank, n_ranks=nprocs,
        interval_s=0.25, deadline_s=20.0, dead_after_s=2.0,
        model=model, checkpoint_dir=ckpt_dir, exit_on_loss=True)
    return wd.start()


def _write_survivor(e, wd, iteration):
    with open(os.path.join(outdir, f"survivor_{rank}.json"), "w") as f:
        json.dump({"rank": rank, "detected": True,
                   "error": type(e).__name__,
                   "message": str(e)[:500],
                   "peer_loss": wd is not None
                   and wd.peer_loss_event is not None,
                   "iteration": iteration}, f)
    print(f"rank {rank}: peer failure detected ({type(e).__name__}: "
          f"{str(e)[:300]})", flush=True)


def main_dp():
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import (
        ArrayDataSetIterator, DataSet)
    from deeplearning4j_tpu.models.multi_layer_network import (
        MultiLayerNetwork)
    from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers.feedforward import DenseLayer
    from deeplearning4j_tpu.nn.layers.output import OutputLayer
    from deeplearning4j_tpu.ops.activations import Activation
    from deeplearning4j_tpu.optimize.listeners import TrainingListener
    from deeplearning4j_tpu.optimize.updaters import Sgd
    from deeplearning4j_tpu.parallel.checkpoint import ElasticTrainer
    from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, create_mesh
    from deeplearning4j_tpu.parallel.wrapper import (
        ParallelWrapper, TrainingMode)

    n_dev = jax.device_count()
    conf = (NeuralNetConfiguration.Builder().seed(1).updater(Sgd(0.05))
            .list()
            .layer(DenseLayer(n_out=16, activation=Activation.TANH))
            .layer(OutputLayer(n_out=3))
            .set_input_type(InputType.feed_forward(4)).build())
    model = MultiLayerNetwork(conf).init()
    mesh = create_mesh({DATA_AXIS: n_dev})

    # fixed GLOBAL batch of 64 rows; this process feeds the contiguous
    # slice proportional to its device share (uneven across ranks)
    rng = np.random.default_rng(0)
    gx = rng.normal(size=(64, 4)).astype(np.float32)
    gy = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 64)]
    per_row = 64 // n_dev
    sizes = [per_row * counts[r] for r in range(nprocs)]
    off = sum(sizes[:rank])
    lx = gx[off:off + sizes[rank]]
    ly = gy[off:off + sizes[rank]]

    ckpt_dir = os.path.join(outdir, "ckpt")
    wd = _make_watchdog(model, ckpt_dir)

    w = (ParallelWrapper.builder(model).mesh(mesh)
         .training_mode(TrainingMode.SHARED_GRADIENTS)
         .watchdog(wd).build())

    trainer = ElasticTrainer(model, ckpt_dir, checkpoint_every=2,
                             mesh=mesh)
    resumed = trainer.resume()
    start_iter = int(model.train_state.iteration)

    class _Killer(TrainingListener):
        def iteration_done(self, m, iteration, epoch, loss, etl_ms, n):
            if rank == die_rank and die_step >= 0 and \
                    iteration >= die_step:
                sys.stdout.flush()
                os._exit(17)   # abrupt death mid-fit, no cleanup

    if die_rank >= 0:
        model.add_listeners(_Killer())

    it = ArrayDataSetIterator(DataSet(lx, ly), batch_size=sizes[rank],
                              shuffle=False)

    # ElasticTrainer saves through the model fit loop; the wrapper owns
    # the distributed step, so attach the trainer's saver semantics by
    # checkpointing every N wrapper iterations via a listener
    class _Saver(TrainingListener):
        def __init__(self):
            self.last = start_iter

        def iteration_done(self, m, iteration, epoch, loss, etl_ms, n):
            if iteration - self.last >= trainer.checkpoint_every:
                from deeplearning4j_tpu.parallel.checkpoint import (
                    save_sharded)
                save_sharded(m.train_state, ckpt_dir)
                trainer._prune()
                self.last = int(iteration)

    model.add_listeners(_Saver())

    try:
        w.fit(it, epochs=epochs)
    except BaseException as e:     # a dead peer breaks the collective
        _write_survivor(e, wd, int(model.train_state.iteration))
        return
    finally:
        wd.stop()

    params = jax.tree_util.tree_map(np.asarray, model.params)
    flat = np.concatenate([l.ravel() for l in
                           jax.tree_util.tree_leaves(params)])
    with open(os.path.join(outdir, f"result_{rank}.json"), "w") as f:
        json.dump({"rank": rank, "loss": float(model._last_loss),  # host-sync-ok: end-of-run result dump
                   "param_sum": float(flat.sum()),  # host-sync-ok: end-of-run result dump
                   "resumed": bool(resumed),
                   "start_iteration": start_iter,
                   "final_iteration": int(model.train_state.iteration),
                   "n_devices": n_dev,
                   "local_batch": int(sizes[rank])}, f)
    print(f"rank {rank} done", flush=True)


def main_3d():
    """Composed dp×tp×pp chaos: a PipelinedTransformerLM trained with a
    manual jitted SGD step on a 3-axis mesh (GSPMD sequential path),
    sharded checkpoints every 2 steps, and
    resume onto whatever layout THIS launch specifies via
    restore_sharded's explicit param_shardings."""
    import numpy as np
    from types import SimpleNamespace

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.optimize.solver import TrainState
    from deeplearning4j_tpu.parallel.checkpoint import (
        latest_checkpoint, restore_sharded, save_sharded)
    from deeplearning4j_tpu.parallel.mesh import create_3d_mesh
    from deeplearning4j_tpu.parallel.pipeline import (
        PipelinedTransformerLM, restack_stages)

    dp, tp, pp = (int(x) for x in mode.split(":")[1].split("x"))
    n_dev = jax.device_count()
    assert n_dev == dp * tp * pp, (n_dev, dp, tp, pp)
    mesh = create_3d_mesh(dp, tp, pp)
    lm = PipelinedTransformerLM(vocab=16, width=8, n_heads=2,
                                n_layers=4, max_len=6, mesh=mesh,
                                remat=True)
    ckpt_dir = os.path.join(outdir, "ckpt")

    # deterministic init, materialized ALREADY SHARDED onto the 3D
    # layout (jit + out_shardings — every process runs the same SPMD
    # program, so this works multi-host where a host-side device_put
    # of non-addressable shards would not)
    key = jax.random.PRNGKey(7)
    tmpl = jax.eval_shape(lm.init, key)
    shardings = lm.param_shardings(tmpl)
    repl = NamedSharding(mesh, P())
    with mesh:
        params = jax.jit(lm.init, out_shardings=shardings)(key)
        it_dev = jax.jit(lambda: jnp.zeros((), jnp.int32),
                         out_shardings=repl)()

    # ---- resume from the last COMMITTED checkpoint, reshaped --------
    latest = latest_checkpoint(ckpt_dir)
    resumed = latest is not None
    prev_pp = None
    layout_file = os.path.join(ckpt_dir, "layout.json")
    if resumed:
        shim = SimpleNamespace(train_state=TrainState(
            tmpl, {}, {}, jnp.zeros((), jnp.int32)))
        restored = restore_sharded(shim, latest, mesh=mesh,
                                   param_shardings=shardings)
        params = dict(restored.params)
        it_dev = restored.iteration
        if os.path.exists(layout_file):
            with open(layout_file) as f:
                prev_pp = json.load(f).get("pp")
        if prev_pp and prev_pp != pp:
            # stage-dim order is device-major: a pp-layout change
            # permutes the stacked blocks (tests/test_3d_parallel.py)
            params["blocks"] = restack_stages(
                params["blocks"], from_devices=prev_pp, to_devices=pp)
    start_iter = int(it_dev)  # host-sync-ok: replicated scalar, once at startup

    wd = _make_watchdog(None, ckpt_dir)

    # fixed global batch, sharded over the data axis; this process owns
    # a contiguous dp-slice proportional to its device share
    rng = np.random.default_rng(0)
    g_toks = rng.integers(0, 16, (8, 6)).astype(np.int32)
    g_tgts = rng.integers(0, 16, (8, 6)).astype(np.int32)
    batch_sh = NamedSharding(mesh, P("data", None))
    # rows land on dp-groups: each process owns counts[rank] devices =
    # counts[rank]/(tp*pp) dp rows; 8 global rows split over dp rows
    dp_rows_owned = counts[rank] // (tp * pp)
    rows = 8 // dp * dp_rows_owned
    off = 8 // dp * sum(counts[r] // (tp * pp) for r in range(rank))
    l_toks = g_toks[off:off + rows]
    l_tgts = g_tgts[off:off + rows]
    toks = jax.make_array_from_process_local_data(batch_sh, l_toks,
                                                  (8, 6))
    tgts = jax.make_array_from_process_local_data(batch_sh, l_tgts,
                                                  (8, 6))

    @jax.jit
    def step(p, it, toks, tgts):
        loss, g = jax.value_and_grad(
            lambda p: lm.loss(p, toks, tgts, pipelined=False))(p)
        return (jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g),
                it + 1, loss)

    def save(p, it_arr, it_host):
        ts = TrainState(p, {}, {}, it_arr)
        save_sharded(ts, ckpt_dir)
        if jax.process_index() == 0:
            with open(layout_file, "w") as f:
                json.dump({"dp": dp, "tp": tp, "pp": pp,
                           "step": it_host}, f)

    loss_v = None
    it_host = start_iter
    try:
        with mesh:
            for _ in range(epochs):     # epochs == steps here
                params, it_dev, loss = step(params, it_dev, toks, tgts)
                with wd.guard(it_host + 1):
                    # the fetch IS the blocking collective wait the
                    # watchdog classifies on a dead peer
                    loss_v = float(loss)  # host-sync-ok: guarded per-step wait
                it_host = int(it_dev)  # host-sync-ok: replicated scalar after the guarded wait
                wd.iteration = it_host
                if rank == die_rank and die_step >= 0 \
                        and it_host >= die_step:
                    sys.stdout.flush()
                    os._exit(17)        # abrupt death mid-fit
                if it_host % 2 == 0:
                    save(params, it_dev, it_host)
    except BaseException as e:
        if not wd.on_collective_error(e):
            raise                       # our own bug — fail loudly
        _write_survivor(e, wd, wd.iteration)
        return
    finally:
        wd.stop()

    # cross-process param fingerprint: a replicated global reduction
    # (host-side np.asarray of non-addressable shards would throw)
    with mesh:
        fp = jax.jit(
            lambda p: sum(
                (jnp.sum(l.astype(jnp.float32))
                 for l in jax.tree_util.tree_leaves(p)),
                jnp.zeros((), jnp.float32)),
            out_shardings=repl)(params)
    with open(os.path.join(outdir, f"result_{rank}.json"), "w") as f:
        json.dump({"rank": rank, "loss": loss_v,
                   "param_sum": float(fp),  # host-sync-ok: end-of-run replicated fingerprint
                   "resumed": resumed,
                   "start_iteration": start_iter,
                   "final_iteration": int(it_dev),
                   "n_devices": n_dev,
                   "layout": [dp, tp, pp],
                   "prev_pp": prev_pp}, f)
    print(f"rank {rank} done", flush=True)


def main():
    from deeplearning4j_tpu.parallel.mesh import initialize_distributed
    initialize_distributed(f"127.0.0.1:{port}", num_processes=nprocs,
                           process_id=rank)
    assert jax.local_device_count() == local_devices
    if mode.startswith("3d:"):
        main_3d()
    else:
        main_dp()


if __name__ == "__main__":
    main()
