"""Serving. Mean rows per dispatched batch (argument ``n`` of the program's
``batch_form`` span): with the mean bucket beside it, the ladder's
occupancy."""

import numpy as np


def read(obs):
    rows = obs.spans.args("batch_form", "n", cat="serve")
    return float(np.mean(rows)) if rows else None
