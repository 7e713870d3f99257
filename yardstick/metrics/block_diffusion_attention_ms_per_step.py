"""Layer math and kernels. Device time per optimizer step, on the first
chip, of the Pallas flash-attention kernels of the block-diffusion
attention layers (the ``pallas_call`` operations traced under the named
scope ``attn.block_diffusion``): the forward kernel over the 16,384
positions ``[noisy | clean]``, its recomputation and the two backward
kernels, in every layer. A program without the scope gives None."""

from yardstick import scopes
from yardstick.held_steps import held


def read(obs):
    return scopes.read_scope_ms(held(obs), ("attn.block_diffusion",),
                                containing="pallas_call")
