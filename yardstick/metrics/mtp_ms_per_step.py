"""Layer math and kernels. Device time per optimizer step, on the first
chip, of the multi-token-prediction module: every operation traced under
the named scope ``mtp`` (``MultiTokenPredictionBlock``: the shifted
embedding, its norms, ``W_eh``, the module's latent attention and expert
layer; and ``MultiTokenLMOutputLayer``'s second term: the module's logits
through the shared head and their cross-entropy), forward, recomputed and
backward. A program without the scope gives None."""

from yardstick import scopes
from yardstick.held_steps import held


def read(obs):
    return scopes.read_scope_ms(held(obs), ("mtp",))
