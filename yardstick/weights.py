"""Weights from the seed, on the device, in one jitted call.

The program's ``init()`` makes its parameters leaf by leaf, one small
device program per leaf (some hundreds for ResNet-50, none of them over
the compile cache's one-second threshold, so every run would build them
again). Traced under one ``jax.jit`` the same code is one program that
the persistent cache keeps, with the program's own initialisers and keys,
in the type it trains and serves in (float32 masters); the values are
those of ``init(seed)`` to the last bit or two (XLA fuses the scaling into
the draw). The seed is an argument of that program, not a
constant in it, so every ``--seed`` finds the same cache entry and XLA
folds no initialiser at compile time.
"""

from __future__ import annotations


def init_on_device(model, seed: int):
    """``model.init(seed)`` as a single device program. Returns the
    model, initialised."""
    import jax
    import numpy as np

    def make(traced_seed):
        model.init(traced_seed)
        return model.train_state, model._rng

    model.train_state, model._rng = jax.jit(make)(np.int32(seed))
    return model
