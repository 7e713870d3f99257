"""Entry points / step. What one ``fit()`` / ``ParallelWrapper.fit()``
call costs before its second step can be dispatched: median, over the
program's ``blocked`` spans (cat ``step``) with ``on="iteration"`` that
begin in the window, of ``since_call_ms`` plus the span's own length. The
first is the call's lead-in on the host (the feeder's construction, the
prefetch thread's start, the refill, the first staging and the first
dispatch), the second the read of the device's iteration count in the
call's first ``_post_step``, which waits for that first step and for every
step of the call before that is still in flight: a call that follows
another straight away reads the run-ahead's drain here (``in_flight`` times
the step), during which the chip works. One span a call; the number of
calls and the medians of the two parts and of the span's ``in_flight`` are
printed to stderr. None where the program records no such span (before
PR 35)."""

import sys

import numpy as np


def read(obs):
    calls = [(args["since_call_ms"], (end - start) * 1e3, args["in_flight"])
             for _, _, start, end, args
             in obs.spans.select("blocked", cat="step")
             if args.get("on") == "iteration" and "since_call_ms" in args]
    if not calls:
        return None
    host, wait, depth = (float(np.median(col)) for col in zip(*calls))
    print(f"fit_call_lead_in_ms: {len(calls)} fit() calls in the window; "
          f"medians: {host:.1f} ms on the host before the read, the read "
          f"{wait:.1f} ms with {depth:g} steps in flight", file=sys.stderr)
    return float(np.median([h + w for h, w, _ in calls]))
