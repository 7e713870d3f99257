"""Layer math and kernels. The least time one chip could take for a
step's latent attention (the configuration's ``latent_attention_work``:
the operations of the five projections and of the causal pairs, and the
bytes of the weights, the latents, q, k, v and the result, forward and
backward, no recomputation; the larger of operations / 197 TFLOP/s and
bytes / 819 GB/s) over ``latent_attention_ms_per_step``, in %. The
projections computed again in the backward pass (the blocks keep only the
flash kernel's result) and the kernels' half-visible diagonal tiles are
work the share does not count."""

from yardstick import cells, scopes


def read(obs):
    ms = cells.load_reader(obs.cell, "latent_attention_ms_per_step").read(obs)
    work = getattr(cells.load_build(obs.cell), "latent_attention_work", None)
    if ms is None or work is None:
        return None
    return scopes.roofline_share(obs, ms, *work(obs.cell.config))
