"""Input pipeline. Share of the window the step loop spent waiting for the
feeder with nothing staged (sum of the program's ``feed_stall`` spans over
the window, in %). Cross-checked against the program's own gauge
``dl4j_etl_stall_ms``, summed over the window's epochs: a disagreement is
printed, the spans are reported. A fit that bypasses the feeder (a
``MultiDataSet`` passes through it) has no such span and reads 0."""

import sys


def read(obs):
    from_spans = (obs.spans.total_s("feed_stall", cat="data") or 0.0)
    from_gauge = obs.facts.get("etl_stall_ms", 0.0) / 1e3
    if abs(from_spans - from_gauge) > max(0.05, 0.2 * from_spans):
        print(f"feed_stall_share: spans say {from_spans:.3f} s, the gauge "
              f"dl4j_etl_stall_ms says {from_gauge:.3f} s", file=sys.stderr)
    return 100.0 * from_spans / obs.spans.window_s
