"""Entry points / step. Median of the program's ``dispatch`` span
(cat ``step``): the host's time inside one call of the jitted train step.
``ParallelWrapper`` records no such span, so its cells leave this out."""


def read(obs):
    return obs.spans.median_ms("dispatch", cat="step")
