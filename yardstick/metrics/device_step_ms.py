"""Layer math and kernels. Median duration on the first chip of the
program that took most device time (xplane, line ``XLA Modules``): in a
fit cell one optimizer step, forward, backward and update; in a serve cell
one bucket's forward."""

from yardstick import xplane


def read(obs):
    return xplane.step_ms(obs.device)
