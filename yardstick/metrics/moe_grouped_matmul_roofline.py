"""Layer math and kernels. The least time one chip could take for a
step's routed experts (the configuration's ``moe_grouped_work``: router
and the expected held assignments' expert products, and the bytes of the
held experts' weights read forward, read and their gradients written
backward, plus the tokens; the larger of operations / 197 TFLOP/s and
bytes / 819 GB/s) over ``moe_experts_ms_per_step``, in %."""

from yardstick import cells, scopes


def read(obs):
    ms = cells.load_reader(obs.cell, "moe_experts_ms_per_step").read(obs)
    work = getattr(cells.load_build(obs.cell), "moe_grouped_work", None)
    if ms is None or work is None:
        return None
    return scopes.roofline_share(obs, ms, *work(obs.cell.config))
