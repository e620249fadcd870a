"""Device trace: capture a span of the window and reduce it to numbers.

:func:`capture` records a profiler trace into a temporary directory, and
:func:`load` flattens its ``.xplane.pb`` into :class:`Event`\\ s.  The rest
is arithmetic on those events and is tested on traces recorded on a v5e
(``tests/data``):

* busy time is the union of the intervals of the device's op events inside
  the traced span (the benchmark's own ``bench.traced`` host span);
* program time is the sum of the device's module (whole program) events;
* a host span is a ``bench.*`` span of the benchmark or a ``repro.*`` span
  of the program; its self time is its duration less what the host spans
  nested in it on the same thread cover;
* an idle gap is a stretch of the span with no device op; each idle
  nanosecond goes to the innermost host span covering it, so idle time by
  span is the spans' self time inside the gaps (``bench.traced`` where no
  other span covers it).
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import itertools
import os
import tempfile

SPAN = "bench.traced"
PREFIXES = ("bench.", "repro.")
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
    """The host spans of the benchmark and of the program."""
    return [e for e in events
            if not is_device_plane(e.plane) and e.name.startswith(PREFIXES)]


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


class _Cover:
    """The length of sorted disjoint ``(start, end)`` intervals inside any
    stretch, in log time: a prefix sum over the intervals."""

    def __init__(self, intervals):
        self.starts = [a for a, _ in intervals]
        self.ends = [b for _, b in intervals]
        self.before = list(itertools.accumulate(
            (b - a for a, b in intervals), initial=0.0))

    def _upto(self, x: float) -> float:
        k = bisect.bisect_right(self.starts, x)
        return self.before[k] - max(0.0, self.ends[k - 1] - x) if k else 0.0

    def within(self, e) -> float:
        """ns of event ``e`` inside the intervals."""
        return self._upto(e.end_ns) - self._upto(e.start_ns)


def device_planes(events) -> list[str]:
    return sorted({e.plane for e in events if is_device_plane(e.plane)})


def _busy(events, plane, lo, hi) -> list:
    """The disjoint intervals of ``[lo, hi)`` in which ``plane`` ran an
    op."""
    return _union(_clip([(e.start_ns, e.end_ns) for e in events
                         if e.plane == plane and e.line == OPS_LINE], lo, hi))


def _gaps(busy, lo, hi) -> list:
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_gaps(events, lo: float, hi: float) -> list:
    """``(start, end)`` of each stretch of ``[lo, hi)`` in which a device
    ran no op, over every device plane."""
    return [g for plane in device_planes(events)
            for g in _gaps(_busy(events, plane, lo, hi), lo, hi)]


def self_ns(events, intervals) -> dict:
    """Self time of each host span name inside the sorted disjoint
    ``(start, end)`` ``intervals``, in ns: the traced span, or one
    device's idle gaps.

    Spans of one thread nest, so the spans a span covers are its direct
    children plus theirs, and its children's clipped durations sum to the
    part of it they cover.  JAX's own trace events are no span's
    children."""
    out = {}
    threads = {}
    cover = _Cover(intervals)
    for e in host_spans(events):
        threads.setdefault((e.plane, e.line), []).append(e)
    for evs in threads.values():
        stack = []                       # [event, clipped child ns]
        evs.sort(key=lambda e: (e.start_ns, -e.end_ns))

        def close(top):
            e, kids = top
            own = cover.within(e)
            if own <= 0:
                return
            out[e.name] = out.get(e.name, 0.0) + own - kids
            if stack:
                stack[-1][1] += own

        for e in evs:
            while stack and stack[-1][0].end_ns <= e.start_ns:
                close(stack.pop())
            stack.append([e, 0.0])
        while stack:
            close(stack.pop())
    return out


@dataclasses.dataclass
class Reduced:
    devices: int
    window_ns: float
    busy_ns: float           # mean over devices
    program_ns: float        # module time summed over devices
    programs: dict           # module name -> ns, summed over devices
    ops: dict                # op name -> ns, summed over devices
    self_ns: dict            # host span name -> self ns in the span
    idle_ns: dict            # host span name -> idle ns, mean over devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns


def reduce(events, span: tuple[float, float] | None = None) -> Reduced | None:
    """Reduce flattened events to busy, program, self and idle times inside
    ``span`` (default: the ``bench.traced`` host span).  None where the
    trace holds no span or no device op inside it."""
    span = span or traced_span(events)
    if span is None:
        return None
    lo, hi = span
    planes = device_planes(events)
    busy, program_ns, programs, ops, idle_ns = [], 0.0, {}, {}, {}
    for plane in planes:
        for e in events:
            if e.plane != plane or e.end_ns <= lo or e.start_ns >= hi:
                continue
            inside = min(e.end_ns, hi) - max(e.start_ns, lo)
            if e.line == OPS_LINE:
                ops[e.name] = ops.get(e.name, 0.0) + inside
            elif e.line == MODULES_LINE:
                programs[e.name] = programs.get(e.name, 0.0) + inside
                program_ns += inside
        merged = _busy(events, plane, lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for name, ns in self_ns(events, _gaps(merged, lo, hi)).items():
            idle_ns[name] = idle_ns.get(name, 0.0) + ns / len(planes)
    if not planes or not any(busy):
        return None
    return Reduced(len(planes), hi - lo, sum(busy) / len(planes), program_ns,
                   programs, ops, self_ns(events, [(lo, hi)]),
                   {k: v for k, v in idle_ns.items() if v > 0})


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most time,
    and the host spans that hold the most idle time, in seconds."""
    ops = {}
    for name, ns in red.ops.items():
        # an op event is named by its whole HLO instruction: keep its name
        short = name.split(" = ", 1)[0]
        ops[short] = ops.get(short, 0.0) + ns
    ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9] for k, v in sorted(
                red.idle_ns.items(), key=lambda kv: -kv[1])[:top]]}
