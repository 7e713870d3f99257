"""Entry points / step. How far the fit loop runs ahead of the chip:
median, over the window's ``dispatch`` spans (cat ``step``), of their
``in_flight``: the optimizer steps dispatched before this one whose loss
was not ready as the call began (the program polls ``is_ready()`` on the
losses it keeps, which does not block; a ``k_steps`` group counts ``k``).
Nothing but the runtime bounds it. How to read it: 0 beside a high
``device_idle_share`` is a loop the chip waits for; 2-4 hides the host;
more buys no rate and is memory held (``.fit-dp4``'s staged batches on chip
0), a window that ends late by that many steps and, in the Phi cell, a
traced window the profiler cannot hold. The largest is printed to stderr.
None where the program's ``dispatch`` spans carry no ``in_flight`` (before
PR 35)."""

import sys

import numpy as np


def read(obs):
    depth = obs.spans.args("dispatch", "in_flight", cat="step")
    if not depth:
        return None
    print(f"steps_in_flight_p50: largest {max(depth)} over {len(depth)} "
          "dispatches", file=sys.stderr)
    return float(np.median(depth))
