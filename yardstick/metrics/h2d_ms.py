"""Input pipeline. Median of the program's ``host_to_device`` span: the
host's time to stage one batch (the feeder's copy into its staging buffer
and the ``device_put`` call, or the unfed path's ``jnp.asarray``)."""


def read(obs):
    return obs.spans.median_ms("host_to_device", cat="data")
