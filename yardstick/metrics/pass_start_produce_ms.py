"""Input pipeline. What the start of a pass costs the prefetch thread:
median duration of the program's ``produce`` spans (cat ``data``, one
around each ``next()`` of the iterator under ``AsyncDataSetIterator``)
with ``index == 0``, which hold whatever the iterator does when a pass
starts (``ArrayDataSetIterator(shuffle=True)`` copies the whole set). The
median of the other ``produce`` spans is printed to stderr beside it. None
where the program records no such span."""

import sys

import numpy as np


def read(obs):
    first, rest = [], []
    for _, _, start, end, args in obs.spans.select("produce", cat="data"):
        (first if args.get("index") == 0 else rest).append((end - start) * 1e3)
    if not first:
        return None
    if rest:
        print(f"pass_start_produce_ms: median of the other {len(rest)} "
              f"produce spans {float(np.median(rest)):.3f} ms",
              file=sys.stderr)
    return float(np.median(first))
