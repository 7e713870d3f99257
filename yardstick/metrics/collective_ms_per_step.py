"""Collectives. Device time of collective operations (all-reduce and kin,
xplane line ``XLA Ops``) per optimizer step, averaged over the chips. A
one-chip cell has none and leaves this out."""

import numpy as np

from yardstick import xplane


def read(obs):
    steps = len(xplane.step_runs(obs.device).start)
    if obs.cell.chips < 2 or steps == 0:
        return None
    total_ns = np.mean([np.sum(c.end - c.start) for c in
                        (xplane.collectives(xplane.leaf_ops(line))
                         for line in obs.device.ops)])
    return float(total_ns) / 1e6 / steps
