"""What one traced run saw: the single argument of every per-layer
metric's reader (``metrics/<name>.py``: ``read(obs) -> number or None``).
A reader that finds nothing to read returns None and its metric is left
out of the line."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from yardstick import xplane
from yardstick.cells import Cell
from yardstick.compiles import Compiles
from yardstick.spans import Spans
from yardstick.xplane import DeviceTrace


@dataclasses.dataclass
class Observed:
    cell: Cell
    spans: Spans                    # the program's spans inside the window
    device: DeviceTrace             # the xplane, reduced to intervals
    compiles: Compiles
    device_kind: str
    memory_peak_bytes: int
    # what the driver itself counted in the window: steps, examples,
    # flops_per_step, etl_stall_ms (the program's gauge, summed over
    # epochs), batch_rows, lags_ms ... a reader asks for what it needs
    facts: Dict[str, Any]

    def breakdown(self) -> Dict[str, List]:
        host = {name: (self.device.to_trace_ns(s), self.device.to_trace_ns(e))
                for name, (s, e) in self.spans.intervals_by_name().items()}
        return {"device_ops": xplane.top_ops(self.device),
                "idle_gaps": xplane.idle_by_host_span(self.device, host)}


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""
    correct: bool
    attempted: int                  # steps of a fit cell, requests of a serve cell
    failed: int
    end_to_end: Dict[str, float]    # host-clock metrics by name, setup_s among them
    notes: Dict[str, Any]           # printed on the line before the last
    observed: Optional[Observed] = None     # traced runs only
