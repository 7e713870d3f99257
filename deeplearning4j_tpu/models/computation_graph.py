"""ComputationGraph — arbitrary-DAG model (multi-input / multi-output).

Analog of the reference's ``ComputationGraph``
(deeplearning4j-nn/.../nn/graph/ComputationGraph.java:93 — init():377,
topologicalSortOrder():1216, calcBackpropGradients:1947). Execution walks
the topological order computed at config time; the whole DAG — every
branch, merge, and loss — compiles to one XLA executable. Backprop in
reverse topo order is replaced by ``jax.grad`` through the forward walk.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.models.base import BaseModel, cast_params, compute_cast
from deeplearning4j_tpu.nn.graph.config import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.optimize.solver import TrainState, build_optimizer


class ComputationGraph(BaseModel):
    def __init__(self, conf: ComputationGraphConfiguration):
        super().__init__()
        self.conf = conf
        conf.resolve()
        self._topo = conf.topological_order()
        self._nodes = {n.name: n for n in conf.nodes}
        self._layer_nodes = [n for n in conf.nodes if n.layer is not None]
        self.layer_names = tuple(n.name for n in self._layer_nodes)
        self._output_fn = None
        self._loss_eval_fn = None
        self._rnn_step_fn = None
        self._rnn_carries = None   # stored state for rnn_time_step
        # tensor-parallel activation specs (parallel/tensor_parallel.py);
        # set by ParallelWrapper when TP is enabled
        self._tp_plan = None

    @property
    def conf_global(self):
        return self.conf.global_config

    # ---- init -----------------------------------------------------------
    def init(self, seed: Optional[int] = None):
        g = self.conf.global_config
        root = jax.random.PRNGKey(g.seed if seed is None else seed)
        self._rng = jax.random.fold_in(root, 0x5eed)
        params: Dict[str, Any] = {}
        state: Dict[str, Any] = {}
        for i, node in enumerate(self._layer_nodes):
            it = self.conf.layer_input_type(node.name)
            k = jax.random.fold_in(root, i)
            layer = node.layer
            params[node.name] = (layer.initialize(k, it)
                                 if layer.has_params else {})
            state[node.name] = layer.init_state(it)
        tx = self._make_tx()
        opt_state = tx.init(params)
        self.train_state = TrainState(params, state, opt_state,
                                      jnp.zeros((), jnp.int32))
        self._tx = tx
        return self

    def _make_tx(self):
        g = self.conf.global_config
        return build_optimizer(
            self.layer_names,
            {n.name: n.layer.updater for n in self._layer_nodes},
            {n.name: n.layer.frozen for n in self._layer_nodes},
            g.updater,
            g.gradient_normalization,
        )

    # ---- functional forward --------------------------------------------
    def _walk(self, params, model_state, inputs: Dict[str, jnp.ndarray],
              fmasks: Dict[str, Optional[jnp.ndarray]], train: bool, rng,
              stop_before_loss: bool, carries: Optional[dict] = None):
        """Execute the DAG. Returns (activations dict, new_state).
        When ``stop_before_loss`` the output layers' pre-activations are
        stored for the fused-loss path. ``carries`` maps recurrent node
        name → initial hidden state (TBPTT chunk chaining + stateful
        rnn_time_step — reference: rnnActivateUsingStoredState,
        ComputationGraph.java:2753)."""
        g = self.conf.global_config
        acts: Dict[str, jnp.ndarray] = {}
        for k, v in inputs.items():
            acts[k] = compute_cast(jnp.asarray(v), g.compute_dtype)
        new_state = dict(model_state)
        for li, name in enumerate(self._topo):
            node = self._nodes[name]
            xs = [acts[s] for s in node.inputs]
            if node.layer is not None:
                x = xs[0]
                if node.preprocessor is not None:
                    x = node.preprocessor.apply(x)
                key = None if rng is None else jax.random.fold_in(rng, li)
                it = self.conf.layer_input_type(name)
                mask = None
                if isinstance(it, RecurrentType):
                    mask = fmasks.get(node.inputs[0])
                    if mask is None:
                        mask = fmasks.get("__default__")
                ctx = LayerContext(train=train, rng=key, mask=mask)
                lp = cast_params(params.get(name, {}), g.compute_dtype)
                lp = node.layer.apply_weight_noise(lp, ctx, key)
                if node.layer.extra_inputs:
                    x = (x, *xs[1:])
                is_output = name in self.conf.network_outputs
                if is_output and stop_before_loss and hasattr(
                        node.layer, "compute_loss"):
                    acts[name] = (x, lp, ctx)  # defer to loss
                    continue
                if carries is not None and name in carries:
                    y, s = node.layer.apply(lp, model_state.get(name, {}),
                                            x, ctx,
                                            initial_state=carries[name])
                else:
                    y, s = node.layer.apply(lp, model_state.get(name, {}),
                                            x, ctx)
                new_state[name] = s
                extras = node.layer.extra_output_types(it)
                if extras:
                    y, *emitted = y
                    for extra, value in zip(extras, emitted):
                        acts[f"{name}:{extra}"] = value
                if self._tp_plan is not None:
                    y = self._tp_plan.constrain(name, y)
                acts[name] = y
            else:
                from deeplearning4j_tpu.nn.graph.vertices import (
                    LastTimeStepVertex)
                if isinstance(node.vertex, LastTimeStepVertex):
                    m = fmasks.get(node.inputs[0])
                    if m is None:
                        m = fmasks.get("__default__")
                    acts[name] = node.vertex.apply(*xs, mask=m)
                else:
                    acts[name] = node.vertex.apply(*xs)
        return acts, new_state

    def _loss(self, params, model_state, features, labels, fmasks, lmasks,
              rng, iteration, carries: Optional[dict] = None):
        inputs = dict(zip(self.conf.network_inputs, features))
        fm = {"__default__": fmasks[0] if fmasks else None}
        for i, k in enumerate(self.conf.network_inputs):
            fm[k] = fmasks[i] if fmasks and i < len(fmasks) else None
        acts, new_state = self._walk(params, model_state, inputs, fm, True,
                                     rng, stop_before_loss=True,
                                     carries=carries)
        any_leaf = jax.tree_util.tree_leaves(params)
        acc = (jnp.promote_types(jnp.float32, any_leaf[0].dtype)
               if any_leaf else jnp.float32)
        total = jnp.zeros((), acc)
        for i, out_name in enumerate(self.conf.network_outputs):
            node = self._nodes[out_name]
            entry = acts[out_name]
            label = labels[i]
            lmask = lmasks[i] if lmasks and i < len(lmasks) else None
            if isinstance(entry, tuple) and hasattr(node.layer, "compute_loss"):
                x, lp, ctx = entry
                if lmask is not None:
                    ctx = dataclasses.replace(ctx, mask=lmask)
                loss = node.layer.compute_loss(
                    lp, model_state.get(out_name, {}), x, label, ctx)
                if isinstance(loss, tuple):     # a head that keeps state
                    loss, new_state[out_name] = loss
            else:
                raise TypeError(f"output node '{out_name}' is not a loss-"
                                "bearing layer")
            total = total + loss.astype(acc)
        for n in self._layer_nodes:
            total = total + n.layer.regularization_loss(params.get(n.name, {}))
        # auxiliary losses surfaced via layer state (MoE load balancing)
        for s in new_state.values():
            if isinstance(s, dict) and "moe_aux_loss" in s:
                total = total + s["moe_aux_loss"].astype(acc)
        return total, new_state

    def _constraint_layers(self):
        return [n.layer for n in self._layer_nodes]

    def _staged_step_args(self, features, labels, fmask, lmask):
        # the DeviceFeeder stages plain DataSets; this graph's step takes
        # input/output tuples (multi-input safe) like _host_step_args
        return ((features,), (labels,),
                None if fmask is None else (fmask,),
                None if lmask is None else (lmask,))

    def _host_step_args(self, batch):
        if not isinstance(batch, MultiDataSet):
            return super()._host_step_args(batch)

        def masks(ms):
            return tuple(None if m is None else jnp.asarray(m)
                         for m in (ms or [])) or None
        return (tuple(jnp.asarray(f) for f in batch.features),
                tuple(jnp.asarray(l) for l in batch.labels),
                masks(batch.features_masks), masks(batch.labels_masks))

    # ---- truncated BPTT (reference: ComputationGraph.java:955,1184) -----
    def _fit_batch_tbptt(self, batch, etl_ms: float = 0.0):
        """Chunked-time fit over a DAG (reference: doTruncatedBPTT path of
        ComputationGraph.fit, ComputationGraph.java:955). 3-D features and
        sequence labels are sliced along time; 2-D (static) inputs repeat
        whole into every chunk, exactly like the reference's handling of
        non-sequence graph inputs."""
        self._tbptt_ready()
        if isinstance(batch, MultiDataSet):
            feats = [np.asarray(f) for f in batch.features]  # host-sync-ok: eval host staging
            labels = [np.asarray(l) for l in batch.labels]  # host-sync-ok: eval host staging
            fmasks = [None if m is None else np.asarray(m)  # host-sync-ok: eval host staging
                      for m in (batch.features_masks
                                or [None] * len(feats))]
            lmasks = [None if m is None else np.asarray(m)  # host-sync-ok: eval host staging
                      for m in (batch.labels_masks
                                or [None] * len(labels))]
        else:
            feats = [np.asarray(batch.features)]  # host-sync-ok: eval host staging
            labels = [np.asarray(batch.labels)]  # host-sync-ok: eval host staging
            fmasks = [None if batch.features_mask is None
                      else np.asarray(batch.features_mask)]  # host-sync-ok: eval host staging
            lmasks = [None if batch.labels_mask is None
                      else np.asarray(batch.labels_mask)]  # host-sync-ok: eval host staging
        k = self.conf.tbptt_fwd_length
        seq_lens = {f.shape[1] for f in feats if f.ndim == 3}
        if len(seq_lens) > 1:
            raise ValueError(
                "TBPTT fit needs equal sequence lengths across all 3-D "
                f"inputs (got {sorted(seq_lens)}): chunking slices every "
                "sequence with the same time window. Pad the shorter "
                "streams (with a features mask) to a common length.")
        T = seq_lens.pop()
        n = feats[0].shape[0]
        carries = self._zero_carries(n)
        loss = None
        n_chunks = 0
        for lo in range(0, T, k):
            hi = min(lo + k, T)
            cf, cl, cfm, clm = [], [], [], []
            for f, fm in zip(feats, fmasks):
                if f.ndim == 3:
                    cf.append(f[:, lo:hi])
                    cfm.append(None if fm is None else fm[:, lo:hi])
                else:
                    cf.append(f)
                    cfm.append(fm)
            for l, lm in zip(labels, lmasks):
                if l.ndim == 3:
                    cl.append(l[:, lo:hi])
                    clm.append(None if lm is None else lm[:, lo:hi])
                else:
                    cl.append(l)
                    clm.append(lm)
            if hi - lo < k:
                # Ragged tail: pad every 3-D stream to length k, masking
                # padded steps out of the recurrent math and the loss —
                # the multi-stream generalization of _pad_tbptt_tail
                # (multi_layer_network.py), sharing its _pad_time
                from deeplearning4j_tpu.models.multi_layer_network import (
                    _pad_time)
                pad = k - (hi - lo)

                def padt(a):
                    return _pad_time(a, pad)

                for i in range(len(cf)):
                    if cf[i].ndim != 3:
                        continue
                    base = (cfm[i] if cfm[i] is not None
                            else np.ones((n, hi - lo), np.float32))
                    cf[i] = padt(cf[i])
                    cfm[i] = padt(base)
                # the loss falls back to the DEFAULT features mask (the
                # first input's) when an output has no labels mask; the
                # synthesized tail mask must inherit it, or the padding
                # would unmask fmask-excluded real steps (MLN contract)
                default_fm = next(
                    (m for f, m in zip(cf, cfm)
                     if f.ndim == 3 and m is not None and m.ndim == 2),
                    None)
                for i in range(len(cl)):
                    if cl[i].ndim != 3:
                        continue
                    if clm[i] is None:
                        clm[i] = (default_fm if default_fm is not None
                                  else padt(np.ones((n, hi - lo),
                                            np.float32)))
                    else:
                        clm[i] = padt(clm[i])
                    cl[i] = padt(cl[i])
            tj = lambda seq: tuple(None if a is None else jnp.asarray(a)
                                   for a in seq)
            self.train_state, loss, carries = self._send_step(
                self._tbptt_step, "tbptt_step",
                (tj(cf), tj(cl), tj(cfm), tj(clm)), after=(carries,),
                unnoted=n_chunks)
            n_chunks += 1
        self._record_step(n_chunks, loss, etl_ms, n)

    def _fit_batch(self, batch: Union[DataSet, MultiDataSet],
                   etl_ms: float = 0.0):
        if (self.conf.backprop_type == "tbptt"
                and self._recurrent_carry_nodes()
                and any(np.ndim(f) == 3 for f in
                        (batch.features if isinstance(batch, MultiDataSet)
                         else [batch.features]))):
            return self._fit_batch_tbptt(batch, etl_ms=etl_ms)
        return super()._fit_batch(batch, etl_ms=etl_ms)

    # ---- stateful rnn inference (reference: CG.rnnTimeStep:2720) --------
    def rnn_time_step(self, *features, mask=None):
        """Streaming inference with internally stored recurrent state —
        reference: ComputationGraph.rnnTimeStep (ComputationGraph.java:
        2720). 2-D inputs are treated as one timestep and the time axis
        is squeezed from the outputs; 3-D inputs run multiple steps.
        State persists across calls until ``rnn_clear_previous_state``;
        batch-size changes reset it (same contract as the reference)."""
        from deeplearning4j_tpu.nn.layers.recurrent import (
            first_bidirectional_name)
        # unwrap inside the helper: a wrapped core must not slip past
        bidi = first_bidirectional_name(
            (n.name, n.layer) for n in self._layer_nodes)
        if bidi is not None:
            raise ValueError(
                "rnn_time_step is not supported on graphs with "
                f"bidirectional layers ('{bidi}'): the backward "
                "pass needs future timesteps")
        if self.train_state is None:
            self.init()
        if len(features) == 1 and isinstance(features[0], (list, tuple)):
            features = tuple(features[0])
        squeeze = all(np.ndim(f) == 2 for f in features)
        feats = tuple(jnp.asarray(f)[:, None, :]
                      if np.ndim(f) == 2 else jnp.asarray(f)
                      for f in features)
        n = feats[0].shape[0]
        leaves = (None if self._rnn_carries is None
                  else jax.tree_util.tree_leaves(self._rnn_carries))
        if self._rnn_carries is None or (leaves
                                         and leaves[0].shape[0] != n):
            self._rnn_carries = self._zero_carries(n)
        if self._rnn_step_fn is None:
            carry_nodes = self._recurrent_carry_nodes()

            def stepf(params, model_state, feats, default_mask, carries):
                inputs = dict(zip(self.conf.network_inputs, feats))
                fm = {"__default__": default_mask}
                acts, new_state = self._walk(
                    params, model_state, inputs, fm, False, None,
                    stop_before_loss=False, carries=carries)
                new_carries = {}
                for name, _, is_lstm in carry_nodes:
                    s = new_state[name]
                    new_carries[name] = ((s["last_h"], s["last_c"])
                                         if is_lstm else s["last_h"])
                return ([acts[o] for o in self.conf.network_outputs],
                        new_carries)
            self._rnn_step_fn = jax.jit(stepf)
        outs, self._rnn_carries = self._rnn_step_fn(
            self.train_state.params, self.train_state.model_state, feats,
            None if mask is None else jnp.asarray(mask),
            self._rnn_carries)
        if squeeze:
            outs = [o[:, 0] if o.ndim >= 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def rnn_clear_previous_state(self):
        """Reference: ComputationGraph.rnnClearPreviousState():2828."""
        self._rnn_carries = None

    def rnn_get_previous_state(self) -> Optional[dict]:
        """node name → stored hidden state ((h, c) for LSTM, h for
        SimpleRnn) — reference: rnnGetPreviousState(layer)."""
        return self._rnn_carries

    def rnn_set_previous_state(self, carries: dict):
        self._rnn_carries = None if carries is None else dict(carries)

    # ---- inference ------------------------------------------------------
    def build_inference_fn(self):
        """Pure inference forward ``(params, model_state, x, fmask) ->
        y`` for single-input single-output graphs — the shape the
        serving engine (parallel/serving.py) batches over. Multi-input /
        multi-output graphs have no single batchable signature; serve
        those through ``output()`` directly."""
        if len(self.conf.network_inputs) != 1 or \
                len(self.conf.network_outputs) != 1:
            raise ValueError(
                "build_inference_fn requires a single-input single-output"
                f" graph; this one has inputs={self.conf.network_inputs}"
                f" outputs={self.conf.network_outputs}")
        if self.train_state is None:
            self.init()
        in_name = self.conf.network_inputs[0]
        out_name = self.conf.network_outputs[0]

        def fwd(params, model_state, x, fmask):
            inputs = {in_name: x}
            fm = {"__default__": fmask}
            acts, _ = self._walk(params, model_state, inputs, fm, False,
                                 None, stop_before_loss=False)
            return acts[out_name]
        return fwd

    def output(self, *features, train: bool = False, mask=None):
        """Forward pass; returns a single array for single-output graphs,
        else a list (reference: ComputationGraph.output(INDArray...)).
        ``mask`` is the default (N, T) sequence mask for recurrent inputs."""
        if self.train_state is None:
            self.init()
        if len(features) == 1 and isinstance(features[0], (list, tuple)):
            features = tuple(features[0])
        if self._output_fn is None:
            def fwd(params, model_state, feats, default_mask):
                inputs = dict(zip(self.conf.network_inputs, feats))
                fm = {"__default__": default_mask}
                acts, _ = self._walk(params, model_state, inputs, fm, False,
                                     None, stop_before_loss=False)
                return [acts[o] for o in self.conf.network_outputs]
            self._output_fn = jax.jit(fwd)
        outs = self._output_fn(self.train_state.params,
                               self.train_state.model_state,
                               tuple(jnp.asarray(f) for f in features),
                               None if mask is None else jnp.asarray(mask))
        return outs[0] if len(outs) == 1 else outs

    def compute_loss(self, dataset: Union[DataSet, MultiDataSet]):
        if isinstance(dataset, MultiDataSet):
            feats = tuple(jnp.asarray(f) for f in dataset.features)
            labels = tuple(jnp.asarray(l) for l in dataset.labels)
        else:
            feats = (jnp.asarray(dataset.features),)
            labels = (jnp.asarray(dataset.labels),)
        if self._loss_eval_fn is None:
            def lf(params, model_state, f, l):
                loss, _ = self._loss(params, model_state, f, l, None, None,
                                     None, jnp.zeros((), jnp.int32))
                return loss
            self._loss_eval_fn = jax.jit(lf)
        return self._loss_eval_fn(self.train_state.params,
                                  self.train_state.model_state, feats, labels)

    def summary(self) -> str:
        lines = [f"{'name':<24}{'type':<26}{'inputs':<30}{'params':>10}"]
        for name in self._topo:
            node = self._nodes[name]
            kind = (type(node.layer).__name__ if node.layer is not None
                    else type(node.vertex).__name__)
            nparams = 0
            if self.train_state is not None and node.layer is not None:
                nparams = sum(int(np.prod(a.shape)) for a in
                              jax.tree_util.tree_leaves(
                                  self.train_state.params.get(name, {})))
            lines.append(f"{name:<24}{kind:<26}"
                         f"{','.join(node.inputs):<30}{nparams:>10}")
        if self.train_state is not None:
            lines.append(f"total params: {self.num_params()}")
        return "\n".join(lines)

    def clone(self) -> "ComputationGraph":
        m = ComputationGraph(self.conf)
        if self.train_state is not None:
            # see MultiLayerNetwork.clone: no wasted init, real copies
            # (donation safety)
            m._tx = m._make_tx()
            m._rng = self._rng
            copy = lambda t: jax.tree_util.tree_map(jnp.array, t)
            m.train_state = TrainState(
                copy(self.train_state.params),
                copy(self.train_state.model_state),
                copy(self.train_state.opt_state),
                jnp.array(self.train_state.iteration))
            m.epoch_count = self.epoch_count
        return m
