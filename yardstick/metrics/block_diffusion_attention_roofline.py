"""Layer math and kernels. The least time one chip could take for a
step's block-diffusion attention maps (the configuration's
``block_diffusion_attention_work``: the operations of the visible pairs,
T (T + B) of the 4 T^2 a head, and the bytes of q, k, v and the result
over the 2 T positions, forward and backward, no recomputation; the
larger of operations / 197 TFLOP/s and bytes / 819 GB/s: the operations
bound it) over ``block_diffusion_attention_ms_per_step``, in %. A kernel
that computed every tile of the doubled sequence would read a quarter of
what one that computes the visible pairs alone reads."""

from yardstick import cells, scopes


def read(obs):
    ms = cells.load_reader(
        obs.cell, "block_diffusion_attention_ms_per_step").read(obs)
    work = getattr(cells.load_build(obs.cell),
                   "block_diffusion_attention_work", None)
    if ms is None or work is None:
        return None
    return scopes.roofline_share(obs, ms, *work(obs.cell.config))
