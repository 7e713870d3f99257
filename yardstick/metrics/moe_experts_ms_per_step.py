"""Layer math and kernels. Device time per optimizer step, on the first
chip, of the routed experts of every layer: router and top-k, the sort
and gather that dispatch tokens, the three grouped matrix products and the
weighted combine (named scopes ``moe.route``, ``moe.dispatch``,
``moe.experts``, ``moe.combine``; the shared expert is not in it),
forward, recomputation and backward together."""

from yardstick import scopes

SCOPES = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine")


def read(obs):
    return scopes.read_scope_ms(obs, SCOPES)
