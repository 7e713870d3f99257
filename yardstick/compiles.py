"""Compilation seen through JAX's own monitoring events.

Copied from ``chip_smoke.py``'s ``_Compiles`` (PR 21) so that the
yardstick owns what it reads. ``backend_compile_duration`` fires for
every program the process builds, a persistent-cache hit included (the
retrieval is inside it), so an event inside the measured window is a
stall a request or a step paid, whichever it was.
"""

from __future__ import annotations


class Compiles:
    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0      # backend-compile seconds, cache loads included
        self.programs = 0       # programs built or loaded
        self.cache_hits = 0
        self.cache_misses = 0
        self._window_mark = None
        self.in_window = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def window_opens(self):
        self._window_mark = self.programs

    def window_closes(self):
        self.in_window = self.programs - self._window_mark
