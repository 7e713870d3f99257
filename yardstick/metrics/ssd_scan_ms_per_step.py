"""Layer math and kernels. Device time per optimizer step, on the first
chip, of the Mamba-2 layers' short convolution and state-space duality
scan with its skip, gate and grouped norm (the program's named scopes
``ssd.conv`` and ``ssd.scan``), forward, recomputation and backward
together (xplane ``XLA Ops``, joined with the program's instruction ->
scope table). The projections before and after (``ssd.proj``,
``ssd.out``) are matrix products and not in it. A program without the
scopes gives None."""

from yardstick import scopes
from yardstick.held_steps import held

SCOPES = ("ssd.conv", "ssd.scan")


def read(obs):
    return scopes.read_scope_ms(held(obs), SCOPES)
