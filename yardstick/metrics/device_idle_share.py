"""Device. Share of the traced window in which no operation ran on the
chip, averaged over the chips used (xplane; in %): how far the host holds
the device back."""

from yardstick import xplane


def read(obs):
    return 100.0 * (1.0 - xplane.busy_s(obs.device) / obs.device.window_s)
