"""Entry points / step. Median time the first chip sat between the end of
one train-step program and the start of the next (xplane, line
``XLA Modules``): what the host's loop, the feeder and the dispatch cost
the device per step."""

import numpy as np

from yardstick import xplane


def read(obs):
    runs = xplane.step_runs(obs.device)
    if len(runs.start) < 2:
        return None
    return float(np.median(runs.start[1:] - runs.end[:-1])) / 1e6
