"""Layer math and kernels. The least time one chip could take for one
optimizer step over the time it took, in %: the model's floating-point
operations per step on one chip (from shapes, by the configuration's
``train_flops_per_example``) over the chip's published bf16 peak, over the
median device time of the step program. The bound is compute: both
models' steps move far fewer bytes than 819 GB/s would carry in that
time. This is the whole step's share, not a kernel's."""

from yardstick import xplane
from yardstick.device import peaks


def read(obs):
    step_ms = xplane.step_ms(obs.device)
    flops = obs.facts.get("flops_per_step_per_chip")
    if step_ms is None or flops is None:
        return None
    least_ms = flops / peaks(obs.device_kind)["bf16_flops_per_s"] * 1e3
    return 100.0 * least_ms / step_ms
