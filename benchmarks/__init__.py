import os


def cpu_only_children_env(name: str) -> dict:
    """For launchers that touch JAX in the parent and then start child
    processes that each want a device. A chip belongs to one process, so
    such a launcher cannot run on one: it is CPU-only. Refuses when asked
    for another platform, pins the parent to the CPU (call before the
    parent's first JAX use) and returns the children's environment with
    ``JAX_PLATFORMS=cpu`` set explicitly."""
    asked = os.environ.get("JAX_PLATFORMS", "")
    if asked not in ("", "cpu"):
        raise SystemExit(
            f"{name} is a CPU-only multi-process smoke (its parent and its "
            f"children would each need the chip); JAX_PLATFORMS={asked!r}. "
            "Run it with JAX_PLATFORMS=cpu.")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    return dict(os.environ)
