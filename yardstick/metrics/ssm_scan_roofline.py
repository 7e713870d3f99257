"""Layer math and kernels. The least time one chip could take for a
step's Mamba convolutions, selective scans, skips and gates (the
configuration's ``ssm_scan_work``: the recurrence's operations and the
bytes of reading the inputs and writing the result, forward and backward,
no recomputation; the larger of operations / 197 TFLOP/s and bytes / 819
GB/s, which here is the bytes) over ``ssm_scan_ms_per_step``, in %."""

from yardstick import cells, scopes


def read(obs):
    ms = cells.load_reader(obs.cell, "ssm_scan_ms_per_step").read(obs)
    work = getattr(cells.load_build(obs.cell), "ssm_scan_work", None)
    if ms is None or work is None:
        return None
    return scopes.roofline_share(obs, ms, *work(obs.cell.config))
