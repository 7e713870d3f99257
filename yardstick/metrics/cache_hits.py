"""Compile cache. Programs this process found in the persistent cache
(``jax.monitoring``, ``/jax/compilation_cache/cache_hits``)."""


def read(obs):
    return obs.compiles.cache_hits
