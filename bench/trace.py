"""Device trace: capture a span of the window and reduce it to numbers.

:func:`capture` records a profiler trace into a temporary directory, and
:func:`load` flattens its ``.xplane.pb`` into :class:`Event`\\ s.  The rest
is arithmetic on those events and is tested on a trace recorded on a v5e
(``tests/data``):

* busy time is the union of the intervals of the device's op events inside
  the traced span (the benchmark's own ``bench.traced`` host span);
* program time is the sum of the device's module (whole program) events;
* an idle gap is a stretch of the span with no device op; it is named by
  the innermost ``bench.*`` host span that covers its midpoint.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import tempfile

SPAN = "bench.traced"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@contextlib.contextmanager
def capture(parent: str | None = None):
    """Trace the enclosed block (no Python tracer) inside a ``bench.traced``
    host span; yields the trace directory, which the caller reads with
    :func:`load` and deletes."""
    import jax
    tmp = tempfile.mkdtemp(prefix="bench_trace_", dir=parent)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(SPAN):
            yield tmp
    finally:
        jax.profiler.stop_trace()


def xplane_files(directory: str) -> list[str]:
    return sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                            recursive=True))


def load(directory: str) -> list[Event]:
    """Every event of every ``.xplane.pb`` under ``directory``."""
    import jax
    events = []
    for path in xplane_files(directory):
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            for line in plane.lines:
                for e in line.events:
                    events.append(Event(plane.name, line.name, e.name,
                                        float(e.start_ns),
                                        float(e.duration_ns)))
    return events


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name


def host_spans(events) -> list[Event]:
    return [e for e in events
            if not is_device_plane(e.plane) and e.name.startswith("bench.")]


def traced_span(events) -> tuple[float, float] | None:
    spans = [e for e in host_spans(events) if e.name == SPAN]
    if not spans:
        return None
    return spans[0].start_ns, spans[0].end_ns


def _union(intervals):
    """Merge (start, end) intervals; returns sorted disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


@dataclasses.dataclass
class Reduced:
    devices: int
    window_ns: float
    busy_ns: float           # mean over devices
    program_ns: float        # module time summed over devices
    programs: dict           # module name -> ns, summed over devices
    ops: dict                # op name -> ns, summed over devices
    gaps: list               # (host span name, ns), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns


def reduce(events, span: tuple[float, float] | None = None) -> Reduced | None:
    """Reduce flattened events to busy, program and gap times inside
    ``span`` (default: the ``bench.traced`` host span).  None where the
    trace holds no span or no device op inside it."""
    span = span or traced_span(events)
    if span is None:
        return None
    lo, hi = span
    planes = sorted({e.plane for e in events if is_device_plane(e.plane)})
    busy, program_ns, programs, ops, idle = [], 0.0, {}, {}, []
    for plane in planes:
        op_iv = []
        for e in events:
            if e.plane != plane or e.end_ns <= lo or e.start_ns >= hi:
                continue
            inside = min(e.end_ns, hi) - max(e.start_ns, lo)
            if e.line == OPS_LINE:
                op_iv.append((e.start_ns, e.end_ns))
                ops[e.name] = ops.get(e.name, 0.0) + inside
            elif e.line == MODULES_LINE:
                programs[e.name] = programs.get(e.name, 0.0) + inside
                program_ns += inside
        merged = _union(_clip(op_iv, lo, hi))
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not planes or not any(busy):
        return None
    spans = [e for e in host_spans(events) if e.name != SPAN]
    gaps = []
    for s, e in idle:
        mid = (s + e) / 2
        cover = [h for h in spans if h.start_ns <= mid < h.end_ns]
        name = min(cover, key=lambda h: h.dur_ns).name if cover else SPAN
        gaps.append((name, e - s))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(len(planes), hi - lo, sum(busy) / len(planes), program_ns,
                   programs, ops, gaps)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most time
    and the longest idle gaps, in seconds."""
    ops = {}
    for name, ns in red.ops.items():
        # an op event is named by its whole HLO instruction: keep its name
        short = name.split(" = ", 1)[0]
        ops[short] = ops.get(short, 0.0) + ns
    ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9] for k, v in red.gaps[:top]]}
