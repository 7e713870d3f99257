"""Device time by the program's ``jax.named_scope`` names.

The profiler's ``XLA Ops`` line names an operation by its HLO instruction
and, as the window is traced (no HLO proto), carries nothing of the scope
it was traced under. The program says that of the step it trains with: a
zero-length span ``step_scopes`` (cat ``step``) in each ``fit()`` call
under a tracer, whose ``table`` maps instruction -> ``op_name`` path
(``deeplearning4j_tpu/observe/scopes.py``). The readers of per-scope
metrics join the two here. A program that records no such span gives
None, and the metric is left out of the line.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Sequence

import numpy as np

from yardstick import xplane
from yardstick.device import peaks

# copies and slices in flight: their events span the wait, not work
_ASYNC = re.compile(r"-(start|done)(\.\d+)?$")


def program_scopes(spans) -> Optional[Dict[str, str]]:
    """The newest ``step_scopes`` table among the window's spans."""
    tables = spans.args("step_scopes", "table", cat="step")
    return tables[-1] if tables else None


def in_scope(op_name: str, scopes: Sequence[str]) -> bool:
    """Whether one of ``scopes`` is a component of the ``op_name`` path,
    bare or wrapped by a transformation (``transpose(jvp(gdn.scan))``)."""
    return any(re.search(rf"(^|[/(]){re.escape(s)}($|[/)])", op_name)
               for s in scopes)


def scope_ms_per_step(trace: xplane.DeviceTrace, table: Dict[str, str],
                      scopes: Sequence[str],
                      containing: str = "") -> Optional[float]:
    """Milliseconds a whole execution of the main program spent, on the
    first chip, in operations traced under one of ``scopes`` (and, with
    ``containing``, whose ``op_name`` also holds that text): the union of
    their intervals inside the whole executions, over their number. None
    where there is no whole execution or no such operation."""
    runs = xplane.step_runs(trace)
    if len(runs.start) == 0:
        return None
    line = xplane.leaf_ops(trace.ops[0])
    inside = xplane.covered(runs.start, runs.end, line.start, line.end) \
        >= (line.end - line.start)
    mine = np.array([
        not _ASYNC.search(name) and containing in table.get(name, "")
        and in_scope(table.get(name, ""), scopes)
        for name in line.names], bool)
    keep = inside & mine
    if not keep.any():
        return None
    s, e = xplane.merge(line.start[keep], line.end[keep])
    return float(np.sum(e - s)) / 1e6 / len(runs.start)


def read_scope_ms(obs, scopes: Sequence[str],
                  containing: str = "") -> Optional[float]:
    table = program_scopes(obs.spans)
    if table is None:
        return None
    return scope_ms_per_step(obs.device, table, scopes, containing)


def roofline_share(obs, ms: Optional[float], flops: float,
                   nbytes: float) -> Optional[float]:
    """The least time the chip could take for ``flops`` operations and
    ``nbytes`` bytes (the larger of the two bounds at the published
    peaks) over the measured ``ms``, in %."""
    if ms is None or ms <= 0:
        return None
    peak = peaks(obs.device_kind)
    least_ms = max(flops / peak["bf16_flops_per_s"],
                   nbytes / peak["hbm_bytes_per_s"]) * 1e3
    return 100.0 * least_ms / ms
