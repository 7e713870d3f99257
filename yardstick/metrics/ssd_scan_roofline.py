"""Layer math and kernels. The least time one chip could take for a
step's Mamba-2 convolutions, recurrences, skips, gates and grouped norms
(the configuration's ``ssd_scan_work``: the token-by-token recurrence's
operations, the same whatever implements the scan, and the bytes of
reading the inputs and writing the result, forward and backward, no
recomputation; the larger of operations / 197 TFLOP/s and bytes / 819
GB/s) over ``ssd_scan_ms_per_step``, in %."""

from yardstick import cells, scopes


def read(obs):
    ms = cells.load_reader(obs.cell, "ssd_scan_ms_per_step").read(obs)
    work = getattr(cells.load_build(obs.cell), "ssd_scan_work", None)
    if ms is None or work is None:
        return None
    return scopes.roofline_share(obs, ms, *work(obs.cell.config))
