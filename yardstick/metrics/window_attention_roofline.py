"""Layer math and kernels. The least time one chip could take for a
step's window-attention maps (the configuration's
``window_attention_work``: the operations of the pairs inside the window
and the bytes of q, k, v and the result, forward and backward, no
recomputation; the larger of operations / 197 TFLOP/s and bytes / 819
GB/s) over ``window_attention_ms_per_step``, in %. A kernel that computes
every causal block reads about an eighth of one that skips those outside
the window."""

from yardstick import cells, scopes


def read(obs):
    ms = cells.load_reader(obs.cell, "window_attention_ms_per_step").read(obs)
    work = getattr(cells.load_build(obs.cell), "window_attention_work", None)
    if ms is None or work is None:
        return None
    return scopes.roofline_share(obs, ms, *work(obs.cell.config))
