"""Compile cache. Programs built or loaded inside the measured window;
anything but 0 makes the run incorrect."""


def read(obs):
    return obs.compiles.in_window
