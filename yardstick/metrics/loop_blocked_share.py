"""Entry points / step. Share of the window in which the fit loop's own
thread waited for the chip outside a step's dispatch: the union of the
program's ``blocked`` spans (cat ``step``) that begin in the window, cut at
its end, over the window, in %. The program records one round each of the
loop's device reads: ``on="iteration"`` (each ``fit()`` call's first
``_post_step``: waits for the call's first step and every step before it),
``on="routing"`` (expert layers: one fetch as a call ends, which waits for
every step in flight), ``on="collective"`` (``ParallelWrapper`` under a
watchdog: every step). A union and no sum: two reads that touch count
once. The seconds of each ``on`` are printed to stderr beside it. High
beside a ``device_idle_share`` near 0 is a loop that waits while the chip
works (it ran ahead and is now held back, which costs nothing); high beside
a high ``device_idle_share`` is a read the chip waits behind. None where
the program records no such span (before PR 35)."""

import sys

import numpy as np

from yardstick.xplane import merge


def read(obs):
    rows = obs.spans.select("blocked", cat="step")
    if not rows:
        return None
    by_on = {}
    for _, _, start, end, args in rows:
        by_on[args.get("on")] = by_on.get(args.get("on"), 0.0) + end - start
    print("loop_blocked_share: " + ", ".join(
        f"on={on} {s:.3f} s" for on, s in sorted(by_on.items(), key=str)),
        file=sys.stderr)
    start, end = merge(np.array([r[2] for r in rows]),
                       np.array([min(r[3], obs.spans.window[1])
                                 for r in rows]))
    return 100.0 * float(np.sum(end - start)) / obs.spans.window_s
