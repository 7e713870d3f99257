"""Input pipeline. Share of the window in which at least one dispatched
step was waiting for its batch to arrive on the chip: the union, over
steps, of [end of the step's ``dispatch`` span (cat ``step``), end of its
batch's ``resident`` span (cat ``data``)) where the second is later, over
the window, in %. The program's own upper bound on the device's idle time
that the hand-off causes; 0 where every batch is resident before its step
is dispatched. A share of a union and no median: the steps run in bursts.
The two spans of one batch share ``seq``, which a feeder counts from 0 in
each ``fit()`` call: a step's batch is the latest ``resident`` span of its
``seq`` that began before the dispatch. A step without ``seq`` (the unfed
path) waits for nothing here. None where the program records no
``resident`` span (before PR 24, or a fit that bypasses the feeder)."""

import numpy as np

from yardstick.xplane import merge


def read(obs):
    resident = obs.spans.select("resident", cat="data")
    if not resident:
        return None
    waits = []
    for _, _, begun, dispatched, args in obs.spans.select("dispatch", "step"):
        if "seq" not in args:
            continue
        arrived = max((r for r in resident
                       if r[4].get("seq") == args["seq"] and r[2] <= begun),
                      key=lambda r: r[2], default=None)
        if arrived is not None and arrived[3] > dispatched:
            waits.append((dispatched, min(arrived[3], obs.spans.window[1])))
    start, end = merge(np.array([w[0] for w in waits]),
                       np.array([w[1] for w in waits]))
    return 100.0 * float(np.sum(end - start)) / obs.spans.window_s
