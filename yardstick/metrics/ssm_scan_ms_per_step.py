"""Layer math and kernels. Device time per optimizer step, on the first
chip, of the Mamba layers' short convolution and selective scan with its
skip and gate (the program's named scopes ``ssm.conv`` and ``ssm.scan``),
forward, recomputation and backward together (xplane ``XLA Ops``, joined
with the program's instruction -> scope table). The projections before
and after (``ssm.proj``, ``ssm.out``) are matrix products and not in it."""

from yardstick import scopes
from yardstick.held_steps import held

SCOPES = ("ssm.conv", "ssm.scan")


def read(obs):
    return scopes.read_scope_ms(held(obs), SCOPES)
