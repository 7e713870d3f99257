"""Pipeline parallelism: microbatched stage execution over the ``pipe`` axis.

ABSENT in the reference (SURVEY §2.11 row 7 — no PP/TP/SP/EP anywhere);
designed fresh for TPU per SURVEY §7.2 stage 7 / §7.3 item 4. The design is
the canonical TPU pipelining recipe (scaling-book style): the ``pipe`` mesh
axis holds pipeline *stages*; activations move stage-to-stage with
``lax.ppermute`` hops over ICI neighbours; a ``lax.scan`` over ticks runs
the schedule. Everything is pure, differentiable jax: ``jax.grad`` through
this function IS the backward pipeline (the VJP of ``ppermute`` is the
reverse permute, so the cool-down schedule falls out of autodiff — no
hand-written backward machinery).

Two schedules:

- **GPipe** (``repeats=1``): M microbatches through S stages,
  ``M + S - 1`` ticks, bubble fraction ``(S-1)/(M+S-1)``.
- **Circular / interleaved** (``repeats=R > 1``): each device holds R
  *non-adjacent* stages (device d owns global stages d, S+d, 2S+d, …) and
  microbatches recirculate around the ring R times — the interleaved-1F1B
  layout (Megatron "virtual pipeline"). For a fixed per-device parameter
  budget this divides the bubble by R: ``R*S`` layers cost
  ``R*M + S - 1`` ticks instead of the ``M + R*S - 1`` a GPipe pipeline of
  ``R*S`` devices would need.

1F1B's *memory* motivation (don't hold every microbatch's activations) is
answered the XLA way: ``remat=True`` wraps the stage in ``jax.checkpoint``
so the scan saves one activation per tick instead of the stage's internal
residuals, and backward recomputes — the rematerialisation trade the
hardware guide prescribes for HBM-bound training.

Constraints (standard for SPMD pipelining):
- stages are homogeneous in *shape*: one ``stage_fn`` whose params are
  stacked with a leading ``num_stages`` dim. Heterogeneous first/last
  layers (embed/unembed) stay OUTSIDE the pipelined region —
  ``PipelinedTransformerLM`` below shows the composition.
- activation shape is identical at every stage boundary.
- per-microbatch side inputs (e.g. attention masks) ride along via
  ``consts`` (leading dim = num_microbatches), gathered per tick.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

PIPE_AXIS = "pipe"


def _device_major_order(n: int, num_devices: int) -> list:
    """The circular layout's stage storage order: position p holds
    global stage ``order[p]``, where device d's contiguous R-block
    carries global stages d, S+d, 2S+d, … (R = n // num_devices). The
    ONE definition both stacking and checkpoint restacking use."""
    if n % num_devices:
        raise ValueError(f"{n} stages not divisible over"
                         f" {num_devices} devices")
    r = n // num_devices
    return [rep * num_devices + d
            for d in range(num_devices) for rep in range(r)]


def stack_stage_params(params_per_stage: Sequence[Any],
                       num_devices: Optional[int] = None) -> Any:
    """Stack per-stage parameter pytrees (identical structure) into one
    pytree with a leading ``num_stages`` dim — the layout
    ``pipeline_apply`` expects (shard dim 0 over the pipe axis).

    With ``num_devices`` given and ``len(params_per_stage) == R *
    num_devices`` for R > 1, stages are re-ordered device-major for the
    circular schedule: device d's contiguous block holds global stages
    ``d, S+d, 2S+d, …`` (its R interleaved stages)."""
    n = len(params_per_stage)
    order = (_device_major_order(n, num_devices)
             if num_devices and n > num_devices else list(range(n)))
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack([leaves[i] for i in order], 0),
        *params_per_stage)


def restack_stages(stacked_params: Any, from_devices: int,
                   to_devices: int) -> Any:
    """Permute the leading stage dim of a stacked-params pytree from one
    circular layout's device-major order to another's — the fix-up when
    a sharded checkpoint saved at pipeline size S1 restores onto S2
    (e.g. a 2-stage×2-repeat layout resharded to 4 straight stages).
    Positions follow ``stack_stage_params``: device d's block holds
    global stages d, S+d, 2S+d, …"""
    leaves = jax.tree_util.tree_leaves(stacked_params)
    n = leaves[0].shape[0]
    src = _device_major_order(n, from_devices)  # src[p] = stage at pos p
    dst = _device_major_order(n, to_devices)
    pos_of = {g: p for p, g in enumerate(src)}
    perm = jnp.asarray([pos_of[g] for g in dst])
    return jax.tree_util.tree_map(lambda a: jnp.take(a, perm, axis=0),
                                  stacked_params)


def _pipeline_local(stacked_params, x_mb, consts_mb, stage_fn,
                    axis_name: str, num_microbatches: int, repeats: int,
                    remat: bool):
    """Per-device body under shard_map.

    stacked_params: this device's R stages, leading dim R.
    x_mb: (M, mb, ...) full microbatch stream (replicated; only ring
          position 0 ingests it).
    consts_mb: pytree with leading dim M of per-microbatch side inputs.
    """
    S = lax.psum(1, axis_name)
    d = lax.axis_index(axis_name)
    M, R = num_microbatches, repeats

    mb_shape = x_mb.shape[1:]
    n_ticks = M * R + S - 1

    # ring: stage i sends to i+1. For R == 1 the wraparound edge carries
    # garbage that position 0 never reads; for the circular schedule it is
    # the real recirculation path (repeat r -> r+1).
    perm = [(i, (i + 1) % S) for i in range(S)]

    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    out0 = jnp.zeros((M,) + mb_shape, x_mb.dtype)
    recv0 = jnp.zeros(mb_shape, x_mb.dtype)

    def tick(carry, t):
        recv, out = carry
        # device d at tick t works on repeat r of microbatch m, where the
        # wavefront gives t = m + r*S + d (garbage outside the window —
        # computed in lockstep anyway, never recorded)
        r = jnp.clip((t - d) // S, 0, R - 1)
        m = jnp.mod(t - d, M)
        my_params = jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, r, 0, keepdims=False),
            stacked_params)
        inp = lax.dynamic_index_in_dim(x_mb, m, 0, keepdims=False)
        cst = jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, m, 0, keepdims=False),
            consts_mb)
        # ring position 0 ingests fresh microbatches during the first
        # injection phase; afterwards it reads the recirculated stream
        x_in = jnp.where(jnp.logical_and(d == 0, t < M), inp, recv)
        y = fn(my_params, x_in, cst)
        # last ring position records once the final repeat's wave arrives
        mb_idx = jnp.mod(t - (S - 1), M)
        record = jnp.logical_and(d == S - 1, t >= (R - 1) * M + S - 1)
        cur = lax.dynamic_index_in_dim(out, mb_idx, 0, keepdims=False)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(record, y, cur), mb_idx, 0)
        recv = lax.ppermute(y, axis_name, perm)
        return (recv, out), None

    (_, out), _ = lax.scan(tick, (recv0, out0), jnp.arange(n_ticks))
    # Replicate the last position's output buffer to every device (psum of
    # a one-hot-selected buffer == broadcast from the last ring position).
    out = lax.psum(jnp.where(d == S - 1, out, jnp.zeros_like(out)),
                   axis_name)
    return out


def pipeline_apply(stage_fn: Callable,
                   stacked_params: Any,
                   x: jnp.ndarray,
                   mesh: Mesh,
                   *,
                   axis: str = PIPE_AXIS,
                   num_microbatches: Optional[int] = None,
                   consts: Any = None,
                   repeats: int = 1,
                   remat: bool = False) -> jnp.ndarray:
    """Run ``x`` through ``repeats * mesh[axis]`` stage applications
    pipelined over ``mesh[axis]``.

    stage_fn: ``(stage_params, activation(mb, ...)) -> activation`` or,
       when ``consts`` is given, ``(stage_params, activation, consts_mb)
       -> activation``.
    stacked_params: pytree, leaves with leading dim ``repeats *
       mesh.shape[axis]`` in the device-major order produced by
       ``stack_stage_params(..., num_devices=mesh.shape[axis])``.
    x: (batch, ...) global batch; split into ``num_microbatches`` equal
       microbatches along dim 0 (default: one per stage).
    consts: optional pytree of per-example side inputs with leading dim
       ``batch`` (split like ``x``).
    repeats: R > 1 selects the circular/interleaved schedule (requires
       ``num_microbatches == mesh.shape[axis]``).
    remat: checkpoint each stage application (recompute in backward).

    Returns the composed stages applied to x, shape (batch, ...),
    replicated over the pipe axis.
    """
    S = mesh.shape[axis]
    m = num_microbatches or S
    if x.shape[0] % m != 0:
        raise ValueError(f"batch {x.shape[0]} not divisible into {m}"
                         " microbatches")
    if repeats > 1 and m != S:
        raise ValueError(
            f"circular schedule needs num_microbatches == num_stages"
            f" ({S}); got {m} (injection would collide with"
            " recirculation)")
    x_mb = x.reshape((m, x.shape[0] // m) + x.shape[1:])
    takes_consts = consts is not None
    consts_mb = jax.tree_util.tree_map(
        lambda a: a.reshape((m, a.shape[0] // m) + a.shape[1:]),
        consts if takes_consts else ())

    def fn3(p, xm, cst):
        return stage_fn(p, xm, cst) if takes_consts else stage_fn(p, xm)

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)
    # Manual ONLY over the pipe axis: any other mesh axes (data, model)
    # stay GSPMD-automatic, so dp batch sharding and Megatron TP inside
    # the stage compose with the pipeline schedule in ONE mesh — the
    # standard 3D dp×tp×pp deployment (partial-auto shard_map).
    manual = (frozenset({axis}) if len(mesh.axis_names) > 1
              else frozenset())
    fn = jax.shard_map(
        lambda p, xm, cm: _pipeline_local(p, xm, cm, fn3, axis, m,
                                          repeats, remat),
        mesh=mesh, in_specs=(pspec, P(), P()), out_specs=P(),
        check_vma=False, axis_names=manual)
    out_mb = fn(stacked_params, x_mb, consts_mb)
    return out_mb.reshape((x.shape[0],) + out_mb.shape[2:])


class PipelinedTransformerLM:
    """Causal transformer LM with heterogeneous embed/unembed OUTSIDE the
    pipelined region and ``n_layers`` TransformerEncoderBlocks as the
    pipelined stages (the upgrade VERDICT asked over the tanh toy).

    Layout: token embedding + learned positions (replicated, every device
    computes them — they are tiny next to the blocks), then
    ``pipeline_apply`` over the block stack (GPipe or circular), then a
    final LayerNorm and a weight-tied-optional unembedding, also outside
    the region. ``loss()`` is pure and jit/grad-able; the golden test
    asserts it matches the sequential (non-pipelined) stack exactly.
    """

    def __init__(self, vocab: int, width: int, n_heads: int, n_layers: int,
                 max_len: int, mesh: Mesh, *, axis: str = PIPE_AXIS,
                 ffn_mult: int = 4, num_microbatches: Optional[int] = None,
                 remat: bool = True):
        from deeplearning4j_tpu.nn.layers.attention import (
            TransformerEncoderBlock)
        S = int(mesh.shape[axis])
        if n_layers % S:
            raise ValueError(f"n_layers={n_layers} not divisible by"
                             f" pipeline size {S}")
        self.vocab, self.width, self.max_len = vocab, width, max_len
        self.mesh, self.axis = mesh, axis
        self.repeats = n_layers // S
        self.num_microbatches = num_microbatches or S
        self.remat = remat
        self.n_layers = n_layers
        self.block = TransformerEncoderBlock(
            n_in=width, n_out=width, n_heads=n_heads, ffn_mult=ffn_mult,
            causal=True)
        from deeplearning4j_tpu.nn.layers.normalization import (
            LayerNormalization)
        self._ln_f = LayerNormalization()

    def init(self, key) -> dict:
        from deeplearning4j_tpu.nn.inputs import RecurrentType
        ke, kp, kh, kb, kl = jax.random.split(key, 5)
        rt = RecurrentType(self.width, None)
        per_stage = [self.block.initialize(jax.random.fold_in(kb, i), rt)
                     for i in range(self.n_layers)]
        S = int(self.mesh.shape[self.axis])
        return {
            "embed": 0.02 * jax.random.normal(ke, (self.vocab, self.width)),
            "pos": 0.02 * jax.random.normal(kp, (self.max_len, self.width)),
            "blocks": stack_stage_params(per_stage, num_devices=S),
            "ln_f": self._ln_f.initialize(kl, rt),
            "head": 0.02 * jax.random.normal(kh, (self.width, self.vocab)),
        }

    def _stage_fn(self):
        from deeplearning4j_tpu.nn.layers.base import LayerContext
        block = self.block

        def fn(p, h):
            y, _ = block.apply(p, {}, h, LayerContext(train=False))
            return y
        return fn

    def param_shardings(self, params, model_axis: str = "model"):
        """NamedShardings composing the pipeline stage dim with Megatron
        tensor parallelism over ``model_axis`` — the 3D dp×tp×pp layout
        (params are replicated over the data axis; the batch shards
        there). Column-parallel: Wqkv (head-major columns = whole
        heads) and FFN W1; row-parallel: Wo and W2 (GSPMD inserts the
        allreduce after the row-parallel contraction). When the mesh
        has no ``model_axis``, this degrades to stage-only sharding."""
        from jax.sharding import NamedSharding
        mesh = self.mesh
        has_tp = model_axis in mesh.axis_names
        ax = self.axis

        def ns(*spec):
            return NamedSharding(mesh, P(*spec))

        col3 = ns(ax, None, model_axis) if has_tp else ns(ax)
        row3 = ns(ax, model_axis, None) if has_tp else ns(ax)
        col2 = ns(ax, model_axis) if has_tp else ns(ax)
        by_name = {"Wqkv": col3, "W1": col3, "bqkv": col2, "b1": col2,
                   "Wo": row3, "W2": row3}

        def block_leaf(path, leaf):
            name = getattr(path[-1], "key", None) or str(path[-1])
            return by_name.get(name, ns(ax))

        return {
            "embed": ns(), "pos": ns(),
            "blocks": jax.tree_util.tree_map_with_path(
                block_leaf, params["blocks"]),
            "ln_f": jax.tree_util.tree_map(lambda _: ns(),
                                           params["ln_f"]),
            "head": ns(None, model_axis) if has_tp else ns(),
        }

    def shard_params(self, params, model_axis: str = "model"):
        """device_put ``params`` onto the composed 3D layout."""
        return jax.device_put(params,
                              self.param_shardings(params, model_axis))

    def _trunk(self, params, tokens, pipelined: bool):
        x = jnp.take(params["embed"], tokens, axis=0)
        x = x + params["pos"][: tokens.shape[1]][None]
        if pipelined:
            h = pipeline_apply(self._stage_fn(), params["blocks"], x,
                               self.mesh, axis=self.axis,
                               num_microbatches=self.num_microbatches,
                               repeats=self.repeats, remat=self.remat)
        else:
            fn = self._stage_fn()
            # device-major stack order: walk repeats-within-device —
            # global stage r*S + d sits at position d*R + r
            S = int(self.mesh.shape[self.axis])
            h = x
            for r in range(self.repeats):
                for d in range(S):
                    p = jax.tree_util.tree_map(
                        lambda a: a[d * self.repeats + r], params["blocks"])
                    h = fn(p, h)
        from deeplearning4j_tpu.nn.layers.base import LayerContext
        h, _ = self._ln_f.apply(params["ln_f"], {}, h,
                                LayerContext(train=False))
        return h

    def logits(self, params, tokens, *, pipelined: bool = True):
        return self._trunk(params, tokens, pipelined) @ params["head"]

    def loss(self, params, tokens, targets, *, pipelined: bool = True):
        """Mean next-token cross-entropy; ``pipelined=False`` runs the
        sequential reference path (golden-test oracle)."""
        lg = self.logits(params, tokens, pipelined=pipelined)
        ll = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(ll, targets[..., None], axis=-1)
        return nll.mean()
