"""How the recorded traces beside this file were cut from whole ones.

``python yardstick/testdata/trim_xplane.py <in.xplane.pb> <out.xplane.pb>
<steps> [<name length> [<chips>]]`` keeps, of a traced run's ``.xplane.pb``:
on every TPU plane (or the first ``chips``) the
lines ``XLA Ops`` and ``XLA Modules`` from a little before the first whole
run of the main program to the end of the ``steps``-th; on the host plane
the two window annotations, moved to enclose exactly that stretch, and
the runtime's events of a millisecond or more inside it; nothing else, and
no statistic but the annotations'. Operation names are cut to 100
characters, or the length given (the result shape stays from about 70). A tool for whoever records a new
trace, not part of the harness: it needs TensorFlow's copy of the XSpace
protobuf, which the harness does not.
"""

import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

WINDOW_START, WINDOW_END = "yardstick_window_start", "yardstick_window_end"
LEAD_PS = 5_000_000_000        # 5 ms of idle before the first step


def main(src, dst, steps, name_length=100, chips=None):
    space = xplane_pb2.XSpace()
    with open(src, "rb") as fh:
        space.ParseFromString(fh.read())
    out = xplane_pb2.XSpace()

    # the stretch: around the first `steps` whole runs of the program that
    # took most time on the first TPU plane
    tpu = sorted((p for p in space.planes if p.name.startswith("/device:TPU:")),
                 key=lambda p: p.name)[:chips]
    first = tpu[0]
    modules = next(ln for ln in first.lines if ln.name == "XLA Modules")
    names = {k: v.name for k, v in first.event_metadata.items()}
    total = {}
    for ev in modules.events:
        total[ev.metadata_id] = total.get(ev.metadata_id, 0) + ev.duration_ps
    main_id = max(total, key=total.get)
    runs = sorted((ev.offset_ps, ev.offset_ps + ev.duration_ps)
                  for ev in modules.events if ev.metadata_id == main_id)
    runs = runs[1:1 + steps]        # the first may be cut by the window
    base_ns = modules.timestamp_ns
    lo_ps, hi_ps = runs[0][0] - LEAD_PS, runs[-1][1] + LEAD_PS // 5
    print(f"main program {names[main_id]!r}: keeping {len(runs)} runs, "
          f"{(hi_ps - lo_ps) / 1e9:.1f} ms")

    def abs_ps(line, ev):
        return (line.timestamp_ns - base_ns) * 1000 + ev.offset_ps

    def copy_line(plane_out, meta_in, line, keep):
        new = plane_out.lines.add()
        new.id, new.name, new.timestamp_ns = line.id, line.name, line.timestamp_ns
        for ev in line.events:
            if not keep(line, ev):
                continue
            e = new.events.add()
            e.metadata_id, e.offset_ps, e.duration_ps = (
                ev.metadata_id, ev.offset_ps, ev.duration_ps)
            if ev.metadata_id not in plane_out.event_metadata:
                m = plane_out.event_metadata[ev.metadata_id]
                m.id = ev.metadata_id
                m.name = meta_in[ev.metadata_id].name[:name_length]
        return new

    def inside(line, ev):
        t = abs_ps(line, ev)
        return t >= lo_ps and t + ev.duration_ps <= hi_ps

    for plane in tpu:
        p = out.planes.add()
        p.id, p.name = plane.id, plane.name
        for line in plane.lines:
            if line.name in ("XLA Ops", "XLA Modules"):
                copy_line(p, plane.event_metadata, line, inside)

    host = next(p for p in space.planes if p.name == "/host:CPU")
    p = out.planes.add()
    p.id, p.name = host.id, host.name
    host_names = {k: v.name for k, v in host.event_metadata.items()}
    stat_names = {k: v.name for k, v in host.stat_metadata.items()}
    perf_id = next(k for k, v in stat_names.items() if v == "perf_counter_ns")
    p.stat_metadata[perf_id].id = perf_id
    p.stat_metadata[perf_id].name = "perf_counter_ns"
    for line in host.lines:
        marks = [ev for ev in line.events
                 if host_names[ev.metadata_id] in (WINDOW_START, WINDOW_END)]
        new = copy_line(
            p, host.event_metadata, line,
            lambda ln, ev: ev.duration_ps >= 1e9 and inside(ln, ev)
            and host_names[ev.metadata_id] not in (WINDOW_START, WINDOW_END))
        if marks:
            start = next(ev for ev in marks
                         if host_names[ev.metadata_id] == WINDOW_START)
            perf0 = next(s.int64_value or s.uint64_value for s in start.stats
                         if s.metadata_id == perf_id)
            t0_ps = abs_ps(line, start)
            for ev, at_ps in ((start, lo_ps), (marks[-1], hi_ps)):
                e = new.events.add()
                e.metadata_id, e.duration_ps = ev.metadata_id, ev.duration_ps
                e.offset_ps = at_ps - (line.timestamp_ns - base_ns) * 1000
                m = p.event_metadata[ev.metadata_id]
                m.id, m.name = ev.metadata_id, host_names[ev.metadata_id]
                s = e.stats.add()
                s.metadata_id = perf_id
                s.int64_value = perf0 + (at_ps - t0_ps) // 1000
        if not new.events:
            del p.lines[-1]
    with open(dst, "wb") as fh:
        fh.write(out.SerializeToString())
    print(f"{dst}: {len(out.SerializeToString())} bytes")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]),
         *(int(a) for a in sys.argv[4:6]))
