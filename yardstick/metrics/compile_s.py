"""Compile cache. Seconds JAX spent building programs or loading them from
the persistent cache in this process (``jax.monitoring``,
``backend_compile_duration``): set-up a warm cache should shrink."""


def read(obs):
    return obs.compiles.seconds
