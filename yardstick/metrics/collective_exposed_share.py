"""Collectives. Share of the window in which a collective ran on a chip and
no other operation did (xplane, per chip, averaged; in %): communication
the step did not hide behind compute."""

from yardstick import xplane


def read(obs):
    if obs.cell.chips < 2:
        return None
    return 100.0 * xplane.exposed_collective_s(obs.device) \
        / obs.device.window_s
