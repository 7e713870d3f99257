"""The measured window of a run. With ``--trace 1`` it also holds the
profiler around the window, the two annotations that tie the profiler's
clock to the host's, and the program's span tracer, switched on from
outside the program."""

from __future__ import annotations

import shutil
import time
from pathlib import Path
from typing import Any, Dict, Optional

from yardstick import device, xplane
from yardstick.cells import Cell
from yardstick.compiles import Compiles
from yardstick.observed import Observed
from yardstick.spans import Spans


def _mark(name: str) -> float:
    import jax.profiler
    now = time.perf_counter()
    with jax.profiler.TraceAnnotation(name, perf_counter_ns=int(now * 1e9)):
        pass
    return now


class Window:
    """``with window: ...`` is the measured window: set-up ends where it
    opens, programs built inside it are counted, and a traced run profiles
    it. Make it before the warm-up: ``tracer`` (None in an untraced run) is
    the program's own ``SpanTracer`` for the driver to hand to the program,
    and is emptied as the window opens. A traced run's xplane stays under
    ``.yardstick_cache/trace/<cell>/`` of the checkout, the newest only."""

    def __init__(self, cell: Cell, trace: bool, compiles: Compiles):
        self.cell, self.trace, self.compiles = cell, trace, compiles
        self.dir = Path(cell.root) / ".yardstick_cache" / "trace" / cell.name
        self.opened_at = self.t0 = self.t1 = 0.0
        self.tracer = None
        if trace:
            from deeplearning4j_tpu.observe.tracer import SpanTracer
            # the tracer counts microseconds from its construction
            self._tracer_t0 = time.perf_counter()
            self.tracer = SpanTracer()

    def __enter__(self) -> "Window":
        if self.trace:
            self.tracer.clear()
        self.opened_at = time.perf_counter()        # set-up ends here
        self.compiles.window_opens()
        if not self.trace:
            self.t0 = time.perf_counter()
            return self
        import jax.profiler
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        # no Python call tracer, no HLO dump, and of the runtime's own host
        # events only the coarse ones: at the default level every chunk of
        # a host-side transpose is an event, 250 MB of them in five
        # seconds of ResNet, and writing them slows the threads measured
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=options)
        self.t0 = _mark(xplane.WINDOW_START)
        return self

    def __exit__(self, *exc):
        if self.trace:
            import jax.profiler
            self.t1 = _mark(xplane.WINDOW_END)
            jax.profiler.stop_trace()
        else:
            self.t1 = time.perf_counter()
        self.compiles.window_closes()
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def device_trace(self) -> xplane.DeviceTrace:
        return xplane.load(xplane.newest_xplane(self.dir), self.cell.chips)

    def observed(self, devices, facts: Dict[str, Any]) -> Optional[Observed]:
        """What the per-layer readers are handed; None of an untraced run."""
        if not self.trace:
            return None
        return Observed(
            cell=self.cell,
            spans=Spans(self.tracer.events, self._tracer_t0,
                        (self.t0, self.t1)),
            device=self.device_trace(), compiles=self.compiles,
            device_kind=devices[0].device_kind,
            memory_peak_bytes=max(device.peak_bytes(d) for d in devices),
            facts=facts)
