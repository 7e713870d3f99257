"""Layer math and kernels. Device time per optimizer step, on the first
chip, of the latent attention layers (``LatentAttention``, the
GLM-4.7-Flash family's MLA): every operation traced under the named scope
``attn.latent``, the five projections, the two latent norms, the rotary,
the shared rotary key's broadcast to every head and the flash kernels,
forward, recomputed and backward, in every such layer (the
multi-token-prediction module's included). A program without the scope
gives None."""

from yardstick import scopes
from yardstick.held_steps import held


def read(obs):
    return scopes.read_scope_ms(held(obs), ("attn.latent",))
