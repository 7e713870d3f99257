"""MultiLayerNetwork — sequential-stack model.

Analog of the reference's ``MultiLayerNetwork``
(deeplearning4j-nn/.../nn/multilayer/MultiLayerNetwork.java:94 — init():549,
fit(DataSetIterator):1268, backprop():1363, output:2031,
computeGradientAndScore:2360), redesigned around a functional core:

- parameters/state are pytrees keyed by layer name,
- the full forward+loss is one pure function; ``jax.grad`` replaces
  ``calcBackpropGradients``, and the whole train step compiles to a single
  XLA executable with donated buffers (no workspaces needed),
- stochastic layers get per-layer fold_in keys from one step key,
- feature/label masks thread through like the reference's
  ``setLayerMaskArrays`` path (SURVEY §5.7).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.base import BaseModel, cast_params, compute_cast
from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
from deeplearning4j_tpu.nn.inputs import RecurrentType
from deeplearning4j_tpu.nn.layers.base import LayerContext
from deeplearning4j_tpu.optimize.solver import TrainState, build_optimizer




def _pad_time(a, pad):
    """Zero-pad ``pad`` steps onto the time axis (shared by the MLN and
    ComputationGraph TBPTT ragged-tail paths)."""
    return np.concatenate(
        [a, np.zeros((a.shape[0], pad) + a.shape[2:], a.dtype)], axis=1)


def _pad_tbptt_tail(f, l, fm, lm, k, seq_labels):
    """Pad a ragged final TBPTT chunk to length k along time, masking the
    padded steps out of both the recurrent math and the loss."""
    n, t = f.shape[0], f.shape[1]
    pad = k - t
    f = _pad_time(f, pad)
    base_fm = fm if fm is not None else np.ones((n, t), np.float32)
    fm = _pad_time(base_fm, pad)
    if seq_labels:
        l = _pad_time(l, pad)
        if lm is not None:
            lm = _pad_time(lm, pad)
        else:
            # _loss falls back to fmask when lmask is None; the padded fm
            # already carries per-example valid steps + zeroed padding, so
            # synthesizing an all-ones lmask here would UNmask steps the
            # features mask excludes
            lm = fm
    return f, l, fm, lm


class MultiLayerNetwork(BaseModel):
    def __init__(self, conf: MultiLayerConfiguration):
        super().__init__()
        self.conf = conf
        conf.resolve_shapes()
        self.layers = conf.layers
        self.layer_names = tuple(l.name for l in self.layers)
        self._preprocessors = conf.preprocessors()
        self._input_types = conf.layer_input_types()
        self._output_fn = None
        self._loss_eval_fn = None
        # tensor-parallel activation specs (parallel/tensor_parallel.py);
        # set by ParallelWrapper when TP is enabled
        self._tp_plan = None

    @property
    def conf_global(self):
        return self.conf.global_config

    # ---- init -----------------------------------------------------------
    def init(self, seed: Optional[int] = None):
        """Build params/state pytrees (reference: init():549 — flattened
        buffer + per-layer views; here: named pytree, flattening only needed
        for checkpoint/averaging utilities)."""
        g = self.conf.global_config
        root = jax.random.PRNGKey(g.seed if seed is None else seed)
        self._rng = jax.random.fold_in(root, 0x5eed)
        params: Dict[str, Any] = {}
        state: Dict[str, Any] = {}
        for i, layer in enumerate(self.layers):
            it = self._input_types[i]
            k = jax.random.fold_in(root, i)
            params[layer.name] = layer.initialize(k, it) if layer.has_params else {}
            state[layer.name] = layer.init_state(it)
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
            {l.name: l.updater for l in self.layers},
            {l.name: l.frozen for l in self.layers},
            g.updater,
            g.gradient_normalization,
        )

    # ---- functional forward --------------------------------------------
    def _forward(self, params, model_state, x, fmask, train: bool, rng,
                 upto: Optional[int] = None, collect: bool = False,
                 carries: Optional[dict] = None):
        """Pure forward through layers [0, upto). Returns (activation,
        new_state) or (list_of_activations, new_state) when collect
        (reference: feedForwardToLayer:955). ``carries`` maps recurrent
        layer name → initial hidden state (TBPTT chunk chaining,
        reference: rnnActivateUsingStoredState:2881)."""
        g = self.conf.global_config
        x = compute_cast(jnp.asarray(x), g.compute_dtype)
        n = len(self.layers) if upto is None else upto
        new_state = dict(model_state)
        acts = []
        for i in range(n):
            layer = self.layers[i]
            pp = self._preprocessors.get(i)
            if pp is not None:
                x = pp.apply(x)
            key = None if rng is None else jax.random.fold_in(rng, i)
            mask = fmask if isinstance(self._input_types[i], RecurrentType) else None
            ctx = LayerContext(train=train, rng=key, mask=mask)
            lp = cast_params(params.get(layer.name, {}), g.compute_dtype)
            lp = layer.apply_weight_noise(lp, ctx, key)
            if carries is not None and layer.name in carries:
                x, s = layer.apply(lp, model_state.get(layer.name, {}), x,
                                   ctx, initial_state=carries[layer.name])
            else:
                x, s = layer.apply(lp, model_state.get(layer.name, {}), x, ctx)
            new_state[layer.name] = s
            if self._tp_plan is not None:
                # pin the boundary activation layout (Megatron pairing) so
                # GSPMD places exactly one psum per row/column pair
                x = self._tp_plan.constrain(layer.name, x)
            if collect:
                acts.append(x)
        return (acts if collect else x), new_state

    def _loss(self, params, model_state, features, labels, fmask, lmask, rng,
              iteration, carries: Optional[dict] = None):
        """Full training loss: forward to the last hidden layer, output
        layer loss, plus L1/L2 (reference: computeGradientAndScore:2360 +
        outputLayer.computeScore)."""
        n = len(self.layers)
        x, new_state = self._forward(params, model_state, features, fmask,
                                     True, rng, upto=n - 1, carries=carries)
        out_layer = self.layers[-1]
        pp = self._preprocessors.get(n - 1)
        if pp is not None:
            x = pp.apply(x)
        key = None if rng is None else jax.random.fold_in(rng, n - 1)
        mask = lmask if lmask is not None else (
            fmask if isinstance(self._input_types[n - 1], RecurrentType) else None)
        ctx = LayerContext(train=True, rng=key, mask=mask)
        if not hasattr(out_layer, "compute_loss"):
            raise TypeError(f"last layer {type(out_layer).__name__} is not an"
                            " output/loss layer")
        # keep the loss matmul in the compute dtype; a mixed-dtype einsum
        # here leaks f32 cotangents into the bf16 backward pass
        out_lp = cast_params(params.get(out_layer.name, {}),
                             self.conf.global_config.compute_dtype)
        out_lp = out_layer.apply_weight_noise(out_lp, ctx, key)
        loss = out_layer.compute_loss(out_lp,
                                      model_state.get(out_layer.name, {}),
                                      x, labels, ctx)
        reg = sum((l.regularization_loss(params.get(l.name, {}))
                   for l in self.layers), jnp.zeros((), jnp.float32))
        # auxiliary losses surfaced via layer state (MoE load balancing)
        aux = sum((s["moe_aux_loss"] for s in new_state.values()
                   if isinstance(s, dict) and "moe_aux_loss" in s),
                  jnp.zeros((), jnp.float32))
        # promote (not truncate): float64 under gradient checks, else float32
        acc = jnp.promote_types(jnp.float32, loss.dtype)
        return loss.astype(acc) + reg.astype(acc) + aux.astype(acc), new_state

    def _constraint_layers(self):
        return self.layers

    def _fit_batch(self, batch, etl_ms: float = 0.0):
        conf = self.conf
        if (conf.backprop_type != "tbptt" or np.ndim(batch.features) != 3
                or not self._recurrent_carry_nodes()):
            return super()._fit_batch(batch, etl_ms=etl_ms)
        self._tbptt_ready()
        k = conf.tbptt_fwd_length
        feats = np.asarray(batch.features)  # host-sync-ok: TBPTT slices the batch along time on the host
        T = feats.shape[1]
        labels = np.asarray(batch.labels)  # host-sync-ok: TBPTT slices the batch along time on the host
        seq_labels = labels.ndim == 3
        fmask = (None if batch.features_mask is None
                 else np.asarray(batch.features_mask))  # host-sync-ok: TBPTT slices the batch along time on the host
        lmask = (None if batch.labels_mask is None
                 else np.asarray(batch.labels_mask))  # host-sync-ok: TBPTT slices the batch along time on the host
        carries = self._zero_carries(feats.shape[0])
        loss = None
        n_chunks = 0
        for lo in range(0, T, k):
            hi = min(lo + k, T)
            f = feats[:, lo:hi]
            l = labels[:, lo:hi] if seq_labels else labels
            fm = None if fmask is None else fmask[:, lo:hi]
            # a labels mask is per-timestep only for sequence labels; for
            # 2-D labels it is per-output and must not be time-sliced
            lm = (lmask if not seq_labels
                  else None if lmask is None else lmask[:, lo:hi])
            if hi - lo < k:
                # Ragged tail: pad to length k with a zeroed feature mask so
                # the final partial chunk still trains (reference:
                # doTruncatedBPTT processes it; costs one extra compiled
                # shape because fm/lm go from None to arrays).
                f, l, fm, lm = _pad_tbptt_tail(f, l, fm, lm, k, seq_labels)
            fm = None if fm is None else jnp.asarray(fm)
            lm = None if lm is None else jnp.asarray(lm)
            f, l = jnp.asarray(f), jnp.asarray(l)
            self.train_state, loss, carries = self._send_step(
                self._tbptt_step, "tbptt_step", (f, l, fm, lm),
                after=(carries,), unnoted=n_chunks)
            n_chunks += 1
        self._record_step(n_chunks, loss, etl_ms, batch.num_examples())

    # ---- inference ------------------------------------------------------
    def build_inference_fn(self):
        """The pure inference forward ``(params, model_state, x, fmask)
        -> y`` behind ``output()``. The serving engine
        (parallel/serving.py) compiles this against its OWN committed
        (optionally bf16) parameter copies — one executable per batch
        bucket — instead of going through ``output()``'s trace cache
        keyed on ``self.train_state``."""
        if self.train_state is None:
            self.init()

        def fwd(params, model_state, x, fmask):
            n = len(self.layers)
            h, _ = self._forward(params, model_state, x, fmask, False,
                                 None, upto=n - 1)
            out = self.layers[-1]
            pp = self._preprocessors.get(n - 1)
            if pp is not None:
                h = pp.apply(h)
            ctx = LayerContext(train=False, rng=None, mask=fmask)
            y, _ = out.apply(params.get(out.name, {}),
                             model_state.get(out.name, {}), h, ctx)
            if hasattr(out, "pre_output") and hasattr(out, "activation"):
                # OutputLayer.apply already applies activation
                pass
            return y
        return fwd

    def output(self, features, train: bool = False, mask=None):
        """Inference forward pass (reference: output:2031 /
        output(INDArray, ..., featuresMask)). Jit-cached; the final output
        layer applies its activation (e.g. softmax). ``mask`` is the
        (N, T) features mask for padded sequence batches."""
        if self.train_state is None:
            self.init()
        if self._output_fn is None:
            self._output_fn = jax.jit(self.build_inference_fn())
        return self._output_fn(self.train_state.params,
                               self.train_state.model_state,
                               jnp.asarray(features),
                               None if mask is None else jnp.asarray(mask))

    def feed_forward(self, features, train: bool = False) -> List[jnp.ndarray]:
        """All layer activations (reference: feedForward())."""
        acts, _ = self._forward(self.train_state.params,
                                self.train_state.model_state,
                                jnp.asarray(features), None, train,
                                None, collect=True)
        return acts

    def compute_loss(self, dataset: DataSet):
        if self._loss_eval_fn is None:
            def lf(params, model_state, f, l, fm, lm):
                loss, _ = self._loss(params, model_state, f, l, fm, lm, None,
                                     jnp.zeros((), jnp.int32))
                return loss
            self._loss_eval_fn = jax.jit(lf)
        return self._loss_eval_fn(
            self.train_state.params, self.train_state.model_state,
            jnp.asarray(dataset.features), jnp.asarray(dataset.labels),
            None if dataset.features_mask is None else jnp.asarray(dataset.features_mask),
            None if dataset.labels_mask is None else jnp.asarray(dataset.labels_mask))

    # ---- rnn streaming inference ---------------------------------------
    def rnn_time_step(self, features, carries: Optional[dict] = None):
        """Stateful single/multi-step inference for recurrent nets —
        reference: rnnTimeStep (MultiLayerNetwork.java:2806). ``carries``
        maps layer name → (h, c); returns (output, new_carries).
        Functional: the caller threads the state."""
        from deeplearning4j_tpu.nn.layers.recurrent import (
            LSTM, SimpleRnn, unwrap_recurrent)
        if self.train_state is None:
            self.init()
        x = jnp.asarray(features)
        if x.ndim == 2:
            x = x[:, None, :]  # single timestep
        carries = dict(carries or {})
        params = self.train_state.params
        n = len(self.layers)
        for i, layer in enumerate(self.layers):
            pp = self._preprocessors.get(i)
            if pp is not None:
                x = pp.apply(x)
            ctx = LayerContext(train=False)
            lp = params.get(layer.name, {})
            st = self.train_state.model_state.get(layer.name, {})
            core = unwrap_recurrent(layer)
            if isinstance(core, (LSTM, SimpleRnn)):
                init = carries.get(layer.name)
                x, s = layer.apply(lp, st, x, ctx, initial_state=init)
                if isinstance(core, LSTM):
                    carries[layer.name] = (s["last_h"], s["last_c"])
                else:
                    carries[layer.name] = s["last_h"]
            elif i == n - 1 and hasattr(layer, "pre_output"):
                x, _ = layer.apply(lp, st, x, ctx)
            else:
                x, _ = layer.apply(lp, st, x, ctx)
        return x, carries

    # ---- misc -----------------------------------------------------------
    def summary(self) -> str:
        lines = [f"{'idx':<4}{'name':<22}{'type':<26}{'params':>10}  out"]
        for i, l in enumerate(self.layers):
            nparams = 0
            if self.train_state is not None:
                nparams = sum(int(np.prod(a.shape)) for a in
                              jax.tree_util.tree_leaves(
                                  self.train_state.params.get(l.name, {})))
            out_t = l.output_type(self._input_types[i])
            lines.append(f"{i:<4}{l.name:<22}{type(l).__name__:<26}"
                         f"{nparams:>10}  {out_t.shape()}")
        lines.append(f"total params: {self.num_params() if self.train_state else '?'}")
        return "\n".join(lines)

    def clone(self) -> "MultiLayerNetwork":
        m = MultiLayerNetwork(self.conf)
        if self.train_state is not None:
            # no init(): build just the optimizer transform and DEEP-copy
            # the state (the train step donates its input buffers, so
            # sharing references would let future fit() calls invalidate
            # the clone's arrays on TPU)
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

    # ---- layerwise pretraining ------------------------------------------
    def pretrain(self, iterator, epochs: int = 1):
        """Greedy layerwise unsupervised pretraining of every layer that
        defines ``pretrain_loss`` (AutoEncoder, VariationalAutoencoder) —
        the reference's MultiLayerNetwork.pretrain(DataSetIterator)."""
        for i, layer in enumerate(self.layers):
            if getattr(layer, "supports_pretrain", False):
                self.pretrain_layer(i, iterator, epochs)
        return self

    def pretrain_layer(self, idx: int, iterator, epochs: int = 1):
        """Pretrain one layer on activations from the (frozen) layers below
        it (reference: pretrainLayer(int, DataSetIterator))."""
        import optax
        if self.train_state is None:
            self.init()
        layer = self.layers[idx]
        if not getattr(layer, "supports_pretrain", False):
            return self
        g = self.conf.global_config
        updater = layer.updater or g.updater
        tx = updater.to_optax()
        lp = self.train_state.params[layer.name]
        opt_state = tx.init(lp)
        all_params = self.train_state.params
        model_state = self.train_state.model_state
        pp = self._preprocessors.get(idx)

        def step(lp, opt_state, x, key):
            def lf(lp):
                h, _ = self._forward(all_params, model_state, x, None,
                                     False, None, upto=idx)
                if pp is not None:
                    h = pp.apply(h)
                return layer.pretrain_loss(lp, h, key)

            loss, grads = jax.value_and_grad(lf)(lp)
            updates, opt_state2 = tx.update(grads, opt_state, lp)
            return optax.apply_updates(lp, updates), opt_state2, loss

        jstep = jax.jit(step, donate_argnums=(0, 1))
        for _ in range(epochs):
            for ds in iterator:
                self._rng, k = jax.random.split(self._rng)
                lp, opt_state, loss = jstep(
                    lp, opt_state, jnp.asarray(ds.features), k)
            if hasattr(iterator, "reset"):
                iterator.reset()
        new_params = dict(self.train_state.params)
        new_params[layer.name] = lp
        self.train_state = self.train_state._replace(params=new_params)
        self._last_loss = float(loss)  # host-sync-ok: end-of-pretrain loss read, once per layer
        return self
