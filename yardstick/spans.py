"""The program's host spans (``observe.tracer.SpanTracer`` events), cut to
the traced window and put on the host's ``perf_counter`` clock."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np


class Spans:
    def __init__(self, events: List[dict], tracer_t0: float,
                 window: Tuple[float, float]):
        """``events`` are Chrome-trace dicts with ``ts`` and ``dur`` in
        microseconds after ``tracer_t0`` (a ``perf_counter`` reading);
        only complete spans that begin inside ``window`` are kept."""
        self.window = window
        self._rows = []
        for ev in events:
            if ev.get("ph") != "X":
                continue
            start = tracer_t0 + ev["ts"] / 1e6
            if window[0] <= start < window[1]:
                self._rows.append((ev["name"], ev.get("cat"), start,
                                   start + ev["dur"] / 1e6,
                                   ev.get("args", {})))

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def select(self, name: str, cat: Optional[str] = None):
        return [r for r in self._rows
                if r[0] == name and (cat is None or r[1] == cat)]

    def durations_ms(self, name: str, cat: Optional[str] = None) -> np.ndarray:
        return np.array([(r[3] - r[2]) * 1e3 for r in self.select(name, cat)])

    def median_ms(self, name: str, cat: Optional[str] = None
                  ) -> Optional[float]:
        d = self.durations_ms(name, cat)
        return float(np.median(d)) if len(d) else None

    def total_s(self, name: str, cat: Optional[str] = None) -> Optional[float]:
        d = self.durations_ms(name, cat)
        return float(np.sum(d)) / 1e3 if len(d) else None

    def args(self, name: str, key: str, cat: Optional[str] = None) -> List:
        return [r[4][key] for r in self.select(name, cat) if key in r[4]]

    def intervals_by_name(self) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
        """Every span name with its (start, end) arrays, in seconds on
        the ``perf_counter`` clock. A category is part of the name where
        one name is used in two (``dispatch`` of a step and of a serving
        batch)."""
        out: Dict[str, List[Tuple[float, float]]] = {}
        for name, cat, start, end, _ in self._rows:
            out.setdefault(f"{cat}.{name}" if cat else name, []).append(
                (start, end))
        return {k: (np.array([s for s, _ in v]), np.array([e for _, e in v]))
                for k, v in out.items()}
