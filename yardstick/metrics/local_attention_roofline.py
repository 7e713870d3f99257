"""Layer math and kernels. The least time one chip could take for a
step's windowed attention maps (the configuration's
``local_attention_work``: the operations of the pairs inside the window
and the bytes of q, k, v and the result, forward and backward, no
recomputation; the larger of operations / 197 TFLOP/s and bytes / 819
GB/s) over ``local_attention_ms_per_step``, in %. A kernel that computed
every causal block would read about 2.3 times lower at 8,192 positions
and a window of 2,048."""

from yardstick import cells, scopes


def read(obs):
    ms = cells.load_reader(obs.cell, "local_attention_ms_per_step").read(obs)
    work = getattr(cells.load_build(obs.cell), "local_attention_work", None)
    if ms is None or work is None:
        return None
    return scopes.roofline_share(obs, ms, *work(obs.cell.config))
