"""Serving. The host's time per batch: the medians of the program's
``batch_form``, ``dispatch`` and ``fetch`` spans (cat ``serve``), added."""


def read(obs):
    parts = [obs.spans.median_ms(name, cat="serve")
             for name in ("batch_form", "dispatch", "fetch")]
    return None if None in parts else sum(parts)
