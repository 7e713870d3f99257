"""Layer math and kernels. Pallas kernel launches of one optimizer step
under the scan layers' named scopes (the program's gauge
``dl4j_step_kernel_calls``, counted in the compiled step's text when its
``step_scopes`` span is recorded; summed over ``gdn.scan``, ``ssm.scan``
and ``ssd.scan``): a layer on the gated delta rule's, the selective
scan's or the Mamba-2 scan's kernels launches the forward kernel and the
backward kernel, two a layer, and a third where the block's
``jax.checkpoint`` runs the forward kernel again to get its results back.
A program without the gauge, or with no such scope in it, gives None."""

SCOPES = ("gdn.scan", "ssm.scan", "ssd.scan")


def read(obs):
    from deeplearning4j_tpu.observe.registry import default_registry
    calls = default_registry().get_metric("dl4j_step_kernel_calls")
    if calls is None:
        return None
    values = [v for labels, v in calls.series().items()
              if dict(labels).get("scope") in SCOPES]
    return sum(values) if values else None
