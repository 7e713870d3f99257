"""Layer math and kernels. Device time per optimizer step, on the first
chip, of the Pallas flash-attention kernels of the window-attention
layers (the ``pallas_call`` operations traced under the named scope
``attn.window``): the forward kernel over both differential maps, its
recomputation and the two backward kernels."""

from yardstick import scopes
from yardstick.held_steps import held


def read(obs):
    return scopes.read_scope_ms(held(obs), ("attn.window",),
                                containing="pallas_call")
