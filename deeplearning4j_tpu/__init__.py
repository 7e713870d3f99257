"""deeplearning4j_tpu — a TPU-native deep-learning framework.

A brand-new framework with the capabilities of Deeplearning4j (reference:
codeinvento/deeplearning4j), designed TPU-first on JAX/XLA/Pallas:

- configuration-driven sequential (``MultiLayerNetwork``) and DAG
  (``ComputationGraph``) models compiled to single XLA executables,
- pure-functional layers differentiated with ``jax.grad`` (no hand-written
  backward passes — the reference pairs ``activate``/``backpropGradient`` by
  hand, e.g. deeplearning4j-nn/.../nn/api/Layer.java:88),
- optimizers as pure update transforms over parameter pytrees,
- SPMD parallelism over ``jax.sharding.Mesh`` axes (data/model/pipeline)
  instead of the reference's threaded ParallelWrapper + Spark/Aeron stack.

Public API intentionally mirrors DL4J naming so a DL4J user can find their
way around: ``NeuralNetConfiguration``, ``MultiLayerConfiguration``,
``ComputationGraphConfiguration``, ``MultiLayerNetwork``, ``ComputationGraph``,
``ParallelWrapper``, ``Evaluation``, ``EarlyStoppingConfiguration``, etc.
"""

def _wire_compile_cache():
    """The one compile-cache rule. Where ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX reads it itself and nothing in this package names another
    directory. Where it is not, XLA's persistent cache goes to one fixed
    path inside the checkout (git-ignored): a directory built from a
    temp name, a pid or the time is never found again, so it never
    hits. JAX's own thresholds stay (entries only for
    compiles over 1 s), so the CPU test suite does not fill the checkout
    with thousands of tiny entries."""
    import os

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache"))


_wire_compile_cache()

from deeplearning4j_tpu.nn.config import (
    NeuralNetConfiguration,
    MultiLayerConfiguration,
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.models.computation_graph import ComputationGraph

__version__ = "0.1.0"

__all__ = [
    "NeuralNetConfiguration",
    "MultiLayerConfiguration",
    "ComputationGraphConfiguration",
    "MultiLayerNetwork",
    "ComputationGraph",
    "__version__",
]
