"""The driver-visible contract of the root entry points.

The driver imports ``__graft_entry__`` and calls ``dryrun_multichip(8)``
directly — no env prep, no ``__main__`` block.  Round 1 failed exactly this
invocation (the mesh saw 1 device), so the regression test here replicates
it byte-for-byte in a fresh subprocess with the parent's env untouched.
``chip_smoke.py`` and ``bench.py`` measure a chip: where JAX finds none
they must refuse, not fall back to the CPU.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_entry_compiles_and_runs():
    import jax
    import __graft_entry__ as ge
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == args[-1].shape[0]


def test_dryrun_multichip_errors_clearly_when_mesh_too_small():
    # jax is already up with 8 CPU devices under pytest; asking for more
    # must raise the descriptive error, not the old bare mesh ValueError.
    import __graft_entry__ as ge
    with pytest.raises(RuntimeError, match="already"):
        ge.dryrun_multichip(64)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_chip_entry_points_refuse_the_cpu(script):
    """No accelerator: non-zero exit naming the platform found, before
    any model is built (seconds, not minutes), and no result line."""
    proc = subprocess.run(
        [sys.executable, script], cwd=REPO, capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert "phase=train" not in proc.stdout
    assert '"ok"' not in proc.stdout and '"metric"' not in proc.stdout


@pytest.mark.slow
def test_dryrun_multichip_driver_invocation():
    """Exactly what the driver runs: import + call, inherited env."""
    env = dict(os.environ)
    # Undo pytest's own pinning so the subprocess is as unprepared as the
    # driver's: no force_host_platform flag, no JAX_PLATFORMS.
    env.pop("JAX_PLATFORMS", None)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = " ".join(
        f for f in flags.split()
        if "xla_force_host_platform_device_count" not in f)
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ResNet50 train step OK" in proc.stdout
    assert "ring-attention + Ulysses a2a + MoE train step OK" in proc.stdout
    assert "circular pipeline" in proc.stdout
    assert "Megatron-paired transformer train step OK" in proc.stdout


@pytest.mark.parametrize("chips", [1, 4])
def test_feed_race_phase_at_a_tiny_size(chips, monkeypatch):
    """The smoke's race check, rehearsed here on virtual devices with the
    cell's image shape and a set of 320 rows: every staged batch equals an
    independent gather, through ``DeviceFeeder`` and through
    ``ParallelWrapper``'s feeder, and the phase reports what it counted."""
    import chip_smoke
    monkeypatch.setattr(chip_smoke, "RACE", {1: (16, 40), 4: (64, 10)})
    monkeypatch.setattr(chip_smoke, "RACE_SET_ROWS", 320)
    info = chip_smoke.phase_feed_race({"chips": chips})
    rows, batches = chip_smoke.RACE[chips]
    assert info["wrong"] == 0 and info["rows"] == rows
    assert info["batches"] == info["gathered"] == batches
    assert 0 < info["reused"] < batches
