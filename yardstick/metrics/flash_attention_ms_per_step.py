"""Layer math and kernels. Device time per optimizer step, on the first
chip, of the Pallas flash-attention kernels (the ``pallas_call``
operations traced under the named scope ``attn.gated``): the forward
kernel, its recomputation and the two backward kernels."""

from yardstick import scopes


def read(obs):
    return scopes.read_scope_ms(obs, ("attn.gated",),
                                containing="pallas_call")
