"""Layer math and kernels. Device time per optimizer step, on the first
chip, of the Gated DeltaNet layers' short convolution, chunked delta rule
and gated norm (the program's named scopes ``gdn.conv`` and ``gdn.scan``),
forward, recomputation and backward together (xplane ``XLA Ops``, joined
with the program's instruction -> scope table)."""

from yardstick import scopes

SCOPES = ("gdn.conv", "gdn.scan")


def read(obs):
    return scopes.read_scope_ms(obs, SCOPES)
