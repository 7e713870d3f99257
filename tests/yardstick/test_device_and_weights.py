"""The peaks table, the refusal of anything but a TPU, and weights from the
seed in one device program."""

import subprocess
import sys

import jax
import numpy as np
import pytest

from yardstick import device
from yardstick.cells import ROOT
from yardstick.weights import init_on_device


def test_peaks_are_the_published_v5e_figures():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12 and p["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="no published peaks"):
        device.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        device.peaks("cpu")


def test_require_tpu_refuses_the_cpu():
    with pytest.raises(SystemExit) as e:
        device.require_tpu(1)
    assert e.value.code not in (0, None)


def test_the_command_exits_nonzero_and_prints_no_metric_without_a_tpu():
    done = subprocess.run(
        [sys.executable, "-m", "yardstick.run", "--workload",
         "resnet50-tiny64.fit", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert done.returncode != 0
    assert done.stdout == ""
    assert "Not run" in done.stderr


def test_weights_in_one_program_equal_the_programs_own_init(dummy_root):
    from yardstick import cells
    cell = cells.resolve_cell("dummy-mlp.fit", dummy_root)
    build = cells.load_build(cell)
    own = build.build(cell.config, 11).init(11)
    ours = init_on_device(build.build(cell.config, 11), 11)
    other = init_on_device(build.build(cell.config, 11), 12)
    a, b, c = (jax.tree_util.tree_leaves(m.train_state.params)
               for m in (own, ours, other))
    # fused into one program the scaling rounds differently in the last bit
    assert all(np.allclose(x, y, rtol=0, atol=1e-7) for x, y in zip(a, b))
    assert any(not np.allclose(x, y, atol=1e-3) for x, y in zip(a, c))
    assert np.array_equal(np.asarray(own._rng), np.asarray(ours._rng))
