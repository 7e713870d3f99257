"""Layer math and kernels. Device time per optimizer step, on the first
chip, of the Pallas flash-attention kernels of the layers that attend
over the whole sequence (the ``pallas_call`` operations traced under the
named scopes ``attn.full`` and ``attn.cross``): forward, recomputation
and the two backward kernels of each."""

from yardstick import scopes
from yardstick.held_steps import held


def read(obs):
    return scopes.read_scope_ms(held(obs), ("attn.full", "attn.cross"),
                                containing="pallas_call")
