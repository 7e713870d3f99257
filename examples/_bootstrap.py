"""Shared example bootstrap: put the repo root on sys.path so every
walkthrough runs as ``python examples/<name>.py`` without installing
the package. Imported for its side effect (`import _bootstrap` — the
script's own directory is first on sys.path, so this resolves here)."""

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def pin_cpu_mesh(n_devices: int) -> None:
    """Pin the example to an ``n_devices``-wide virtual CPU mesh BEFORE
    jax initializes. An explicit user setting like ``JAX_PLATFORMS=tpu``
    is respected (the example then needs enough real devices or exits
    with a message)."""
    ambient = os.environ.get("JAX_PLATFORMS")
    if ambient not in (None, "", "cpu"):
        return                      # explicit user platform choice
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        # only fill in the device count the user did NOT choose
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{n_devices}").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")


def smoke() -> bool:
    """True when the DL4J_EXAMPLE_SMOKE env knob is set: examples
    shrink shapes/step counts to seconds-scale and skip interactive
    waits, so the test suite's smoke tier can assert each walkthrough
    still runs to rc=0 (see tests/test_examples.py,
    ``./runtests.sh --examples``)."""
    return os.environ.get("DL4J_EXAMPLE_SMOKE", "") not in ("", "0")


def sized(full, tiny):
    """Pick a tunable's full-size value, or the tiny smoke-tier value
    when DL4J_EXAMPLE_SMOKE is set."""
    return tiny if smoke() else full


def need_devices(n_devices: int) -> None:
    """Actionable exit when the backend came up too small (instead of an
    opaque mesh reshape error)."""
    import jax
    have = len(jax.devices())
    if have < n_devices:
        raise SystemExit(
            f"this example needs {n_devices} devices, found {have} — "
            "unset JAX_PLATFORMS to use the default virtual CPU mesh, "
            "or run on a host with enough chips")
