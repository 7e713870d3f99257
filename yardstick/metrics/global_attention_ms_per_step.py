"""Layer math and kernels. Device time per optimizer step, on the first
chip, of the Pallas flash-attention kernels of the gated attention layers
that see the whole past (the ``pallas_call`` operations traced under the
named scope ``attn.gated``; the AFMoE family's full layers, without
positions): the forward kernel, once a layer, and the two backward
kernels. A program without the scope gives None."""

from yardstick import scopes
from yardstick.held_steps import held


def read(obs):
    return scopes.read_scope_ms(held(obs), ("attn.gated",),
                                containing="pallas_call")
