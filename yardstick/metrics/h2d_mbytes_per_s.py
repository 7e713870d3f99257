"""Input pipeline. The rate at which batches really become resident on the
chips while any is on its way: the ``bytes`` of the program's ``resident``
spans (cat ``data``; from the feeder's ``device_put`` until
``block_until_ready`` returns on the staged arrays) over the length of the
union of their intervals, in MB/s (10**6 bytes), all chips together.
``h2d_ms`` times only the issue of the copy. None where the program
records no ``resident`` span."""

import numpy as np

from yardstick.xplane import merge


def read(obs):
    rows = obs.spans.select("resident", cat="data")
    if not rows:
        return None
    start, end = merge(np.array([r[2] for r in rows]),
                       np.array([r[3] for r in rows]))
    in_flight_s = float(np.sum(end - start))
    if in_flight_s <= 0:
        return None
    return sum(r[4]["bytes"] for r in rows) / in_flight_s / 1e6
