"""From the profiler's ``.xplane.pb`` to intervals, and from intervals to
what the per-layer readers report.

Read with ``jax.profiler.ProfileData`` and nothing else. A TPU appears
as a plane ``/device:TPU:<n>`` whose line ``XLA Modules`` holds one event
per executed program (``jit_step(<fingerprint>)``) and whose line
``XLA Ops`` holds one per HLO operation, named by its whole HLO text
(``%fusion.398 = (bf16[...]) fusion(...)``), the bodies of loops and
calls nested inside their callers. Names are cut to ``jit_step`` and
``fusion.398``. A third line, ``Async XLA Ops`` (copies and slices in
flight beside the core's own work), is not read: busy means the core ran
an operation. Times are nanoseconds on the profiler's clock; two annotations that the
driver writes around the traced window carry the host's
``perf_counter`` reading, which puts the program's spans on that clock.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW_START = "yardstick_window_start"
WINDOW_END = "yardstick_window_end"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
RUNTIME_EVENT_MIN_NS = 1e6

# operations that only hold other operations: counting them would count
# their bodies twice
_CONTAINER = re.compile(r"^(while|conditional|call)([.\d]|$)")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")


@dataclasses.dataclass
class Line:
    """Events of one line of one plane, by start time. ``kinds`` says
    what an operation is where its name does not (``fusion.398`` is a
    ``fusion bf16[128,128,3072]``); it defaults to the names."""
    names: List[str]
    start: np.ndarray           # ns
    end: np.ndarray             # ns
    kinds: Optional[List[str]] = None

    def __post_init__(self):
        if self.kinds is None:
            self.kinds = list(self.names)

    @classmethod
    def of(cls, events) -> "Line":
        rows = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                       e.name) for e in events)
        return cls([short_name(r[2]) for r in rows],
                   np.array([r[0] for r in rows], np.float64),
                   np.array([r[1] for r in rows], np.float64),
                   [kind_of(r[2]) for r in rows])

    def pick(self, keep: Sequence[bool]) -> "Line":
        keep = np.asarray(keep, bool)
        return Line([n for n, k in zip(self.names, keep) if k],
                    self.start[keep], self.end[keep],
                    [n for n, k in zip(self.kinds, keep) if k])

    def clip(self, lo: float, hi: float) -> "Line":
        """The events that overlap [lo, hi), cut to it."""
        line = self.pick((self.end > lo) & (self.start < hi))
        return Line(line.names, np.maximum(line.start, lo),
                    np.minimum(line.end, hi), line.kinds)


@dataclasses.dataclass
class DeviceTrace:
    """One traced window. ``ops`` and ``modules`` hold one ``Line`` per
    device, already cut to the window."""
    ops: List[Line]
    modules: List[Line]
    lo: float                   # window on the profiler's clock, ns
    hi: float
    perf_at_lo: float           # host perf_counter (s) at ``lo``
    # what the runtime's own host threads were doing, a millisecond or
    # more at a time (``XlaLinearize``, ``Execute`` ...): name -> intervals
    runtime: Dict[str, Tuple[np.ndarray, np.ndarray]] = dataclasses.field(
        default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def to_trace_ns(self, perf_s: float) -> float:
        return self.lo + (perf_s - self.perf_at_lo) * 1e9


_HLO_NAME = re.compile(r"^%([^\s=]+)\s*=")
_HLO_KIND = re.compile(r"^%([^\s=]+?)[.\d]*\s*=\s*\(?(\w+\[[\d,]*\])")
_MODULE_NAME = re.compile(r"^(jit_[^(]*)\(\d+\)?$")


def short_name(name: str) -> str:
    """``fusion.398`` of an operation's HLO text, ``jit_step`` of a
    program's name with its fingerprint; anything else as it is."""
    m = _HLO_NAME.match(name) or _MODULE_NAME.match(name)
    return m.group(1) if m else name


def kind_of(name: str) -> str:
    """What an operation is, from its HLO text: its name without the
    number, and the shape of its (first) result. The twelve layers'
    ``fusion.376`` ... ``fusion.398`` are one kind,
    ``fusion bf16[128,128,3072]``."""
    m = _HLO_KIND.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else short_name(name)


def newest_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"the profiler left no .xplane.pb under "
                                f"{trace_dir}")
    return found[-1]


def load(path: Path, chips: int) -> DeviceTrace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices: Dict[int, Dict[str, Line]] = {}
    marks: Dict[str, Tuple[float, float]] = {}
    runtime: Dict[str, List[Tuple[float, float]]] = {}
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            lines = {ln.name: Line.of(ln.events) for ln in plane.lines
                     if ln.name in (OPS_LINE, MODULES_LINE)}
            devices[int(m.group(1))] = lines
            continue
        for ln in plane.lines:
            for ev in ln.events:
                name = ev.name      # millions of these: ask once
                if name in (WINDOW_START, WINDOW_END):
                    stats = {k: v for k, v in ev.stats}
                    marks[name] = (float(ev.start_ns),
                                   float(stats["perf_counter_ns"]) / 1e9)
                elif ev.duration_ns >= RUNTIME_EVENT_MIN_NS:
                    runtime.setdefault(name, []).append(
                        (float(ev.start_ns),
                         float(ev.start_ns + ev.duration_ns)))
    if WINDOW_START not in marks or WINDOW_END not in marks:
        raise ValueError(f"{path} holds no {WINDOW_START}/{WINDOW_END} "
                         "annotations: was the window traced?")
    used = sorted(devices)[:chips]
    if len(used) < chips:
        raise ValueError(f"{path} holds {len(devices)} TPU plane(s), the "
                         f"cell used {chips}")
    lo, perf_at_lo = marks[WINDOW_START]
    hi = marks[WINDOW_END][0]
    empty = Line([], np.zeros(0), np.zeros(0))
    return DeviceTrace(
        ops=[devices[d].get(OPS_LINE, empty).clip(lo, hi) for d in used],
        modules=[devices[d].get(MODULES_LINE, empty).clip(lo, hi)
                 for d in used],
        lo=lo, hi=hi, perf_at_lo=perf_at_lo,
        runtime={k: (np.array([a for a, _ in v]), np.array([b for _, b in v]))
                 for k, v in runtime.items()})


# ---- interval arithmetic ---------------------------------------------------

def merge(start: np.ndarray, end: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """The union of intervals as disjoint intervals in order."""
    if len(start) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start, kind="stable")
    start, end = start[order], end[order]
    reach = np.maximum.accumulate(end)
    first = np.concatenate([[True], start[1:] > reach[:-1]])
    last = np.concatenate([first[1:], [True]])
    return start[first], reach[last]


def covered(start: np.ndarray, end: np.ndarray, a: np.ndarray,
            b: np.ndarray) -> np.ndarray:
    """For each query interval [a_i, b_i): how much of it the union of
    the given intervals covers."""
    s, e = merge(start, end)
    if len(s) == 0:
        return np.zeros(len(a))
    total = np.concatenate([[0.0], np.cumsum(e - s)])

    def upto(t):        # covered length in (-inf, t)
        i = np.searchsorted(s, t, side="right")      # intervals begun
        full = total[np.maximum(i - 1, 0)] * (i > 0)
        part = np.where(i > 0, np.minimum(t, e[np.maximum(i - 1, 0)])
                        - s[np.maximum(i - 1, 0)], 0.0)
        return full + part

    return upto(np.asarray(b, float)) - upto(np.asarray(a, float))


def gaps(line: Line, lo: float, hi: float) -> Tuple[np.ndarray, np.ndarray]:
    """The stretches of [lo, hi) in which nothing of ``line`` ran."""
    s, e = merge(line.start, line.end)
    a = np.concatenate([[lo], e])
    b = np.concatenate([s, [hi]])
    keep = b > a
    return a[keep], b[keep]


# ---- what the readers ask --------------------------------------------------

def leaf_ops(line: Line) -> Line:
    return line.pick([not _CONTAINER.match(n) for n in line.names])


def busy_s(trace: DeviceTrace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    per_chip = []
    for line in trace.ops:
        s, e = merge(line.start, line.end)
        per_chip.append(float(np.sum(e - s)) / 1e9)
    return float(np.mean(per_chip))


def top_ops(trace: DeviceTrace, n: int = 10) -> List[List]:
    """The kinds of operation that took most device time: seconds per
    chip, summed over a kind's operations and their executions."""
    total: Dict[str, float] = {}
    for line in trace.ops:
        leaves = leaf_ops(line)
        for kind, d in zip(leaves.kinds, leaves.end - leaves.start):
            total[kind] = total.get(kind, 0.0) + d / 1e9 / len(trace.ops)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def main_module(trace: DeviceTrace) -> Optional[str]:
    """The program that took most of the first chip's time: in a fit
    cell, the train step."""
    line = trace.modules[0]
    total: Dict[str, float] = {}
    for name, d in zip(line.names, line.end - line.start):
        total[name] = total.get(name, 0.0) + d
    return max(total, key=total.get) if total else None


def module_runs(trace: DeviceTrace, name: Optional[str] = None,
                chip: int = 0) -> Line:
    """Executions of one program (default: every program) on one chip,
    only those that lie whole inside the window."""
    line = trace.modules[chip]
    whole = (line.start > trace.lo) & (line.end < trace.hi)
    if name is not None:
        whole &= np.array([n == name for n in line.names], bool)
    return line.pick(whole)


def step_runs(trace: DeviceTrace) -> Line:
    """Whole executions of the main program on the first chip."""
    return module_runs(trace, main_module(trace))


def step_ms(trace: DeviceTrace) -> Optional[float]:
    """Median duration of the main program on the first chip."""
    runs = step_runs(trace)
    if len(runs.start) == 0:
        return None
    return float(np.median(runs.end - runs.start)) / 1e6


def collectives(line: Line) -> Line:
    return line.pick([bool(_COLLECTIVE.match(n)) for n in line.names])


def exposed_collective_s(trace: DeviceTrace) -> float:
    """Collective time during which no other operation ran on that chip,
    averaged over the chips."""
    per_chip = []
    for line in trace.ops:
        leaves = leaf_ops(line)
        is_coll = np.array([bool(_COLLECTIVE.match(n))
                            for n in leaves.names], bool)
        coll = leaves.pick(is_coll)
        rest = leaves.pick(~is_coll)
        cs, ce = merge(coll.start, coll.end)
        hidden = covered(rest.start, rest.end, cs, ce)
        per_chip.append(float(np.sum(ce - cs) - np.sum(hidden)) / 1e9)
    return float(np.mean(per_chip))


def _owners(a, b, spans: Dict[str, Tuple[np.ndarray, np.ndarray]]):
    """For each gap [a_i, b_i): the name whose intervals cover most of it,
    or None when none covers half."""
    names = sorted(spans)
    cover = np.zeros((len(names) + 1, len(a)))
    cover[0] = (b - a) / 2                      # the bar a span must clear
    for i, name in enumerate(names):
        cover[i + 1] = covered(*spans[name], a, b)
    owner = np.argmax(cover, axis=0)            # ties go to nobody
    return [None if i == 0 else names[i - 1] for i in owner]


def idle_by_host_span(trace: DeviceTrace,
                      host_spans: Dict[str, Tuple[np.ndarray, np.ndarray]],
                      n: int = 10) -> List[List]:
    """The first chip's idle seconds, by what the host was doing: each
    gap goes to the program's span that covers most of it (``host_spans``:
    name -> intervals on the profiler's clock); where none covers half,
    to the runtime's own host event that does, as ``runtime.<name>``;
    else to ``no_span``."""
    a, b = gaps(trace.ops[0], trace.lo, trace.hi)
    if len(a) == 0:
        return []
    owners = _owners(a, b, host_spans)
    orphan = np.array([o is None for o in owners], bool)
    by_runtime = iter(_owners(a[orphan], b[orphan], trace.runtime))
    total: Dict[str, float] = {}
    for length, owner in zip(b - a, owners):
        if owner is None:
            r = next(by_runtime)
            owner = "no_span" if r is None else f"runtime.{r}"
        total[owner] = total.get(owner, 0.0) + float(length) / 1e9
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
