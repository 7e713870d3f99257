"""Device mesh construction.

The TPU-native replacement for the reference's device-affinity machinery
(JITA ``AffinityManager`` thread↔GPU pinning used by ParallelWrapper at
deeplearning4j-scaleout-parallelwrapper/.../ParallelWrapper.java:195 and the
Aeron ``VoidParameterServer`` mesh discovery — SURVEY §2.14): one
``jax.sharding.Mesh`` over all addressable devices, with named axes for
each parallelism strategy:

- ``data``  — data parallelism (ParallelWrapper / Spark masters analog)
- ``model`` — tensor parallelism (no reference analog; SURVEY §2.11 row 7)
- ``seq``   — sequence/context parallelism (ring attention)
- ``pipe``  — pipeline stages

Multi-host: ``jax.distributed.initialize`` + the same Mesh spanning all
processes; XLA routes collectives over ICI within a slice and DCN across
slices. No parameter server, no gradient compression — the interconnect is
the parameter server.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"


def create_mesh(axes: Optional[Dict[str, int]] = None,
                devices: Optional[Sequence] = None) -> Mesh:
    """Build a mesh. Default: all devices on the data axis.

    ``axes`` values may include one -1 entry meaning "everything left",
    e.g. {"data": -1, "model": 4}.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not axes:
        axes = {DATA_AXIS: n}
    names = list(axes.keys())
    sizes = list(axes.values())
    if sizes.count(-1) > 1:
        raise ValueError("at most one axis may be -1")
    fixed = math.prod(s for s in sizes if s != -1)
    if -1 in sizes:
        if n % fixed != 0:
            raise ValueError(f"{n} devices not divisible by fixed axes {fixed}")
        sizes[sizes.index(-1)] = n // fixed
    total = math.prod(sizes)
    if total != n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total}"
                         f" devices, have {n}")
    dev_array = np.asarray(devices).reshape(sizes)  # host-sync-ok: device objects, not device data
    return Mesh(dev_array, tuple(names))


def create_3d_mesh(dp: int, tp: int, pp: int,
                   devices: Optional[Sequence] = None) -> Mesh:
    """dp×tp×pp mesh with the canonical axis order
    ``(data, model, pipe)`` — the composed-parallelism layout the
    PipelinedTransformerLM's ``param_shardings`` expects. Device order
    is whatever ``devices`` (default: ``jax.devices()``) yields, so the
    pipe axis varies fastest — stage-major placement, matching the
    device-major stage stacking in ``restack_stages``."""
    return create_mesh({DATA_AXIS: dp, MODEL_AXIS: tp, PIPE_AXIS: pp},
                       devices)


def local_device_count() -> int:
    return jax.local_device_count()


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None):
    """Multi-host bring-up (replaces VoidParameterServer.init + Aeron mesh
    discovery, SharedTrainingWrapper.java:206-244). On TPU pods with the
    standard runtime, argumentless initialize() autodetects everything.

    On the CPU backend, multiprocess computations need an explicit
    collectives transport — without one every cross-process jit fails
    with "Multiprocess computations aren't implemented on the CPU
    backend". Select gloo before the backend client is created; the
    knob is CPU-only so it is harmless on TPU/GPU, and absent on jax
    versions where CPU collectives were on by default."""
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except (AttributeError, ValueError):
        pass
    if coordinator_address is None:
        jax.distributed.initialize()
    else:
        jax.distributed.initialize(coordinator_address, num_processes,
                                   process_id)


import functools


@functools.lru_cache(maxsize=4)
def _device_range_fn(devs):
    """Cached (jitted reduction, mesh) over one flat device tuple — a
    fresh jit per call would re-trace/compile on every barrier."""
    mesh = Mesh(np.array(devs), ("d",))
    repl = NamedSharding(mesh, P())
    fn = jax.jit(lambda a: (a.min(), a.max()),
                 out_shardings=(repl, repl))
    return fn, mesh


def global_device_value_range(value: float) -> tuple:
    """(min, max) of a per-process scalar across ALL devices of ALL
    processes, via a tiny device-sharded reduction. Safe when processes
    own UNEVEN device counts (multihost_utils.process_allgather stacks
    per-process then tiles per-device and crashes on uneven layouts).
    Every process must call this — it doubles as a barrier."""
    devs = tuple(jax.devices())
    fn, mesh = _device_range_fn(devs)
    sh = NamedSharding(mesh, P("d"))
    loc = jax.local_device_count()
    arr = jax.make_array_from_process_local_data(
        sh, np.full((loc,), value, np.float64), (len(devs),))
    mn, mx = fn(arr)
    return float(mn), float(mx)  # host-sync-ok: barrier helper: the sync is the point


def batch_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Leading-dim (batch) sharding for input batches."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
