"""Layer API.

Analog of the reference's layer contract (deeplearning4j-nn/.../nn/api/
Layer.java:38 — ``activate``/``backpropGradient`` pairs) redesigned for a
functional autodiff core: a layer is a **serializable config** with

- ``output_type(input_type)``    shape inference (drives auto-preprocessors),
- ``initialize(key, input_type)``→ parameter pytree (dict of arrays),
- ``init_state(input_type)``     → non-trainable state (e.g. BN running stats),
- ``apply(params, state, x, ctx)``→ ``(y, new_state)`` — a pure function.

There is **no** backprop method anywhere: gradients come from ``jax.grad``
through ``apply``. Layers must therefore be trace-safe: no data-dependent
Python control flow, static shapes only.

``LayerContext`` carries train/eval mode, a PRNG key for stochastic layers
(dropout, VAE sampling), and optional input masks (SURVEY §5.7).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.ops.activations import Activation
from deeplearning4j_tpu.ops.initializers import WeightInit
from deeplearning4j_tpu.optimize.updaters import Updater

Params = Dict[str, Any]
State = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LayerContext:
    train: bool = False
    rng: Optional[jax.Array] = None
    mask: Optional[jnp.ndarray] = None    # (N, T) for sequence data

    def split_rng(self) -> Tuple["LayerContext", Optional[jax.Array]]:
        if self.rng is None:
            return self, None
        k1, k2 = jax.random.split(self.rng)
        return dataclasses.replace(self, rng=k1), k2


@dataclasses.dataclass(frozen=True)
class Layer:
    """Base config for all layers. Field defaults here mirror the knobs
    every DL4J layer config inherits from ``BaseLayer`` (activation, weight
    init, L1/L2, dropout, per-layer updater override, frozen flag)."""

    name: Optional[str] = None
    # float drop-probability, or an nn.dropout.IDropout instance
    # (Dropout/AlphaDropout/GaussianDropout/GaussianNoise)
    dropout: Any = 0.0            # applied to the layer INPUT during training
    l1: float = 0.0
    l2: float = 0.0
    updater: Optional[Updater] = None   # per-layer override; None = global
    frozen: bool = False
    dtype: Optional[str] = None   # param dtype override ("float32"/"bfloat16")
    weight_noise: Optional[Any] = None  # nn.weightnoise.IWeightNoise
    constraints: Tuple = ()             # nn.constraints.LayerConstraint s

    # ---- contract -------------------------------------------------------
    def output_type(self, input_type: InputType) -> InputType:
        raise NotImplementedError

    def initialize(self, key: jax.Array, input_type: InputType) -> Params:
        return {}

    def init_state(self, input_type: InputType) -> State:
        return {}

    def apply(self, params: Params, state: State, x: jnp.ndarray,
              ctx: LayerContext) -> Tuple[jnp.ndarray, State]:
        raise NotImplementedError

    # ---- more than one input or output (ComputationGraph nodes) ---------
    @property
    def extra_inputs(self) -> Tuple[str, ...]:
        """Names of the inputs this layer takes after its first. A layer
        that declares some is applied to the tuple ``(x, *extras)``: a
        graph node lists that many further sources, in this order."""
        return ()

    def extra_output_types(self, input_type: InputType
                           ) -> Dict[str, InputType]:
        """Name -> type of what this layer emits beside its output, in
        order. A layer that declares some returns the tuple ``(y,
        *extras)`` from ``apply``; a graph node ``b`` reads the extra
        ``name`` of node ``a`` as the source ``a:name``."""
        return {}

    def upgrade_state(self, saved: Dict[str, Any]) -> Dict[str, Any]:
        """``saved``: this layer's state arrays as a checkpoint holds them,
        by path inside the layer. A layer whose state changed form since
        older checkpoints were written returns them in today's form."""
        return saved

    # ---- helpers --------------------------------------------------------
    @property
    def has_params(self) -> bool:
        return True

    def regularization_loss(self, params: Params) -> jnp.ndarray:
        """L1/L2 penalty over this layer's weight-like params (DL4J applies
        l1/l2 to weights only, not biases — param key convention: keys
        starting with 'b' / 'beta' / 'mean' / 'var' are exempt)."""
        if (self.l1 == 0.0 and self.l2 == 0.0) or not params:
            return jnp.zeros((), jnp.float32)
        total = jnp.zeros((), jnp.float32)
        # Check the LEAF-level key (last path component), so nested wrapper
        # params ({"fwd": {...,"b":...}, "bwd": {...}}) are classified per
        # actual parameter, not per wrapper key.
        from deeplearning4j_tpu.nn.param_keys import is_weight_path
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            if not is_weight_path(path):
                continue
            if self.l1:
                total = total + self.l1 * jnp.sum(jnp.abs(leaf))
            if self.l2:
                total = total + 0.5 * self.l2 * jnp.sum(jnp.square(leaf))
        return total

    def maybe_dropout(self, x: jnp.ndarray, ctx: LayerContext,
                      key: Optional[jax.Array]) -> jnp.ndarray:
        """Input dropout (inverted scaling, matching the reference's
        ``Dropout`` with p = retain probability semantics inverted: here
        ``dropout`` is the DROP probability, the common modern convention).
        Also accepts any IDropout (Alpha/Gaussian...; nn/dropout.py)."""
        if not ctx.train or key is None:
            return x
        if isinstance(self.dropout, (int, float)):
            if self.dropout <= 0.0:
                return x
            from deeplearning4j_tpu.nn.dropout import Dropout
            return Dropout(float(self.dropout)).apply_dropout(x, key)
        return self.dropout.apply_dropout(x, key)

    def apply_weight_noise(self, params, ctx: LayerContext,
                           key: Optional[jax.Array]):
        """Perturb params for this forward pass when a weight-noise conf is
        set (reference: conf/weightnoise/, applied in BaseLayer
        .getParamWithNoise)."""
        if self.weight_noise is None or not ctx.train or key is None \
                or not params:
            return params
        return self.weight_noise.apply_noise(params, key)

    def param_dtype(self, default=jnp.float32):
        if self.dtype == "bfloat16":
            return jnp.bfloat16
        if self.dtype == "float32" or self.dtype is None:
            return default
        return jnp.dtype(self.dtype)


@dataclasses.dataclass(frozen=True)
class FeedForwardLayer(Layer):
    """Base for layers with explicit nIn/nOut, matching the reference's
    ``FeedForwardLayer`` config. ``n_in`` may be None — inferred from the
    incoming ``InputType`` like DL4J's ``setNIn`` override mechanism."""
    n_in: Optional[int] = None
    n_out: int = 0
    activation: Activation = Activation.IDENTITY
    weight_init: WeightInit = WeightInit.XAVIER
    has_bias: bool = True

    def resolved_n_in(self, input_type: InputType) -> int:
        if self.n_in is not None:
            return self.n_in
        shape = input_type.shape()
        return shape[-1]
