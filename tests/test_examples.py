"""Smoke tier for examples/ — every walkthrough must run to rc=0.

Each example is launched as a subprocess with DL4J_EXAMPLE_SMOKE=1
(examples shrink shapes/step counts and skip interactive waits — see
examples/_bootstrap.sized). Marked slow: excluded from the tier-1
``-m 'not slow'`` run; invoke via ``./runtests.sh --examples``.
"""

import os
import subprocess
import sys

import pytest

EXAMPLES_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples")

EXAMPLES = sorted(
    f for f in os.listdir(EXAMPLES_DIR)
    if f.endswith(".py") and not f.startswith("_"))


def _needs_keras(name: str) -> bool:
    return name in ("keras_import_finetune.py", "custom_keras_layer.py")


@pytest.mark.slow
@pytest.mark.parametrize("name", EXAMPLES)
def test_example_runs(name):
    if _needs_keras(name):
        pytest.importorskip("keras")
    env = dict(os.environ)
    env["DL4J_EXAMPLE_SMOKE"] = "1"
    # examples choose their own mesh via _bootstrap.pin_cpu_mesh; drop
    # the test session's 8-device XLA_FLAGS so they start clean
    env.pop("XLA_FLAGS", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES_DIR, name)],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, (
        f"{name} exited rc={proc.returncode}\n"
        f"--- stdout ---\n{proc.stdout[-4000:]}\n"
        f"--- stderr ---\n{proc.stderr[-4000:]}")
