"""A traced window without the step the profiler cut short.

Where a window puts more events on the device's lines than the profiler
keeps (the Phi-4-mini-flash cell: two selective scans of 8,192 token steps
put 522,005 events a step on ``XLA Ops``, and 32 steps are in flight), the
trace stops mid-step, and the step program running then ends on ``XLA
Modules`` where the trace does: an execution of 16 ms among twelve of 555
ms. ``scopes.scope_ms_per_step`` counts it as a whole step, so the time
per step reads 1/13 low (PERF.md section 5)."""

import dataclasses

import numpy as np

from yardstick import xplane


def held(obs):
    """``obs`` without the executions of the main program that last under
    half its median time."""
    trace = obs.device
    main = xplane.main_module(trace)
    modules = []
    for line in trace.modules:
        mine = np.array([n == main for n in line.names], bool)
        took = line.end - line.start
        if mine.any():
            mine &= took < 0.5 * np.median(took[mine])
        modules.append(line.pick(~mine))
    return dataclasses.replace(
        obs, device=dataclasses.replace(trace, modules=modules))
