"""Layer math and kernels. Pallas kernel launches of one optimizer step
under the attention layers' named scopes (the program's gauge
``dl4j_step_kernel_calls``, counted in the compiled step's text when its
``step_scopes`` span is recorded; summed over the scopes that start with
``attn.``): a layer on the flash kernels launches the forward kernel and
the two backward kernels, three a layer, and a fourth where the block's
``jax.checkpoint`` runs the forward kernel again to get its result back.
A program without the gauge, or with no such scope in it, gives None."""


def read(obs):
    from deeplearning4j_tpu.observe.registry import default_registry
    calls = default_registry().get_metric("dl4j_step_kernel_calls")
    if calls is None:
        return None
    values = [v for labels, v in calls.series().items()
              if dict(labels).get("scope", "").startswith("attn.")]
    return sum(values) if values else None
