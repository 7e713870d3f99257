"""Layer math and kernels. Device time per optimizer step, on the first
chip, of the Pallas flash-attention kernels of the gated attention layers
that see a causal window (the ``pallas_call`` operations traced under the
named scope ``attn.window``; ``GatedAttention(window=...)``, the AFMoE
family's sliding layers): the forward kernel, once a layer (the block's
``jax.checkpoint`` keeps its result and logsumexp), and the two backward
kernels. A program without the scope gives None."""

from yardstick import scopes
from yardstick.held_steps import held


def read(obs):
    return scopes.read_scope_ms(held(obs), ("attn.window",),
                                containing="pallas_call")
