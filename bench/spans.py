"""Where the host time of a traced window goes, by the program's spans.

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s>

Runs one cell as ``bench/run.py --trace 1`` does (the same set-up,
warm-up, traced span and check) and prints one JSON line: the run's
result, and from its trace

* ``self_ms_per_recon``: the self time of each ``bench.*``/``repro.*``
  span name inside the traced span, per reconciliation traced.  A span's
  self time is its duration, clipped to the traced span, less what its
  nested ``bench.*``/``repro.*`` spans on the same thread cover; JAX's
  own trace events are no span's children;
* ``idle_ms_by_span``: the device's idle time in the traced span, summed
  over every idle gap, by the innermost such span covering the gap's
  midpoint (``bench.traced`` where no other does);
* ``idle_ms_in_span``: the same idle time split exactly: each idle
  nanosecond to the innermost span covering it (the spans' self time
  inside the idle gaps);
* ``round_ms``: the median round time of the traced rounds and of the
  untraced rounds after them, which prices the tracing itself.

The program's spans are those of ``repro/trace.py``.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for _p in (str(BENCH.parent), str(BENCH.parent / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import trace as tr  # noqa: E402

PREFIXES = ("bench.", "repro.")


def spans(events) -> list:
    """The host spans of the benchmark and of the program."""
    return [e for e in events if not tr.is_device_plane(e.plane)
            and e.name.startswith(PREFIXES)]


def _within(e, intervals) -> float:
    """ns of event ``e`` inside the disjoint ``intervals``."""
    return sum(max(0.0, min(e.end_ns, b) - max(e.start_ns, a))
               for a, b in intervals)


def self_ns(events, intervals) -> dict:
    """Self time of each span name inside the disjoint ``(start, end)``
    ``intervals``, in ns: the traced span, or the device's idle gaps.

    Spans of one thread nest, so the spans a span covers are its direct
    children plus theirs, and its children's clipped durations sum to the
    part of it they cover."""
    out = {}
    threads = {}
    for e in spans(events):
        threads.setdefault((e.plane, e.line), []).append(e)
    for evs in threads.values():
        stack = []                       # [event, clipped child ns]
        evs.sort(key=lambda e: (e.start_ns, -e.end_ns))

        def close(top):
            e, kids = top
            own = _within(e, intervals)
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


def idle_gaps(events, lo: float, hi: float) -> list:
    """``(start, end)`` of each stretch of ``[lo, hi)`` in which a device
    ran no op, over every device plane."""
    idle = []
    for plane in sorted({e.plane for e in events
                         if tr.is_device_plane(e.plane)}):
        ops = [(e.start_ns, e.end_ns) for e in events
               if e.plane == plane and e.line == tr.OPS_LINE]
        merged = tr._union(tr._clip(ops, lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    return idle


def idle_by_span(events, lo: float, hi: float) -> dict:
    """Idle ns summed over every gap, each gap whole to the innermost span
    that covers its midpoint, as the benchmark's ``breakdown`` names gaps.
    ``self_ns(events, idle_gaps(...))`` splits each gap instead."""
    host = [e for e in spans(events) if e.name != tr.SPAN]
    out = {}
    for s, e in idle_gaps(events, lo, hi):
        mid = (s + e) / 2
        cover = [h for h in host if h.start_ns <= mid < h.end_ns]
        name = min(cover, key=lambda h: h.dur_ns).name if cover else tr.SPAN
        out[name] = out.get(name, 0.0) + e - s
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    from bench import run
    events, rounds = [], []

    def timed(entry):
        class Timed:
            @staticmethod
            def run_round(stream, locals_, session, span):
                traced = jax.profiler.TraceAnnotation.is_enabled()
                t = time.perf_counter()
                out = entry.run_round(stream, locals_, session, span)
                rounds.append((traced, len(locals_), time.perf_counter() - t))
                return out
        return Timed

    load = tr.load

    def keep(directory):
        # run_cell deletes its trace once it is reduced: keep the events
        events.extend(load(directory))
        return events
    tr.load = keep
    try:
        res = run.run_cell(args.workload, args.seed, args.seconds, True,
                           wrap_entry=timed,
                           log=lambda s: print(s, file=sys.stderr))
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    finally:
        tr.load = load
    window = rounds[[traced for traced, _, _ in rounds].index(True):]
    traced = [r for r in window if r[0]]
    untraced = [r for r in window if not r[0]]
    recons = sum(n for _, n, _ in traced)
    lo, hi = tr.traced_span(events)
    gaps = idle_gaps(events, lo, hi)
    out = {"workload": args.workload, "seed": args.seed, "result": res,
           "traced_recons": recons, "traced_span_ms": (hi - lo) / 1e6,
           "self_ms_per_recon": {k: v / 1e6 / recons for k, v in sorted(
               self_ns(events, [(lo, hi)]).items())},
           "idle_gaps": len(gaps),
           "idle_ms_by_span": {k: v / 1e6 for k, v in sorted(
               idle_by_span(events, lo, hi).items())},
           "idle_ms_in_span": {k: v / 1e6 for k, v in sorted(
               self_ns(events, gaps).items())},
           "round_ms": {
               "traced": [len(traced), 1e3 * statistics.median(
                   s for _, _, s in traced)],
               "untraced": [len(untraced), 1e3 * statistics.median(
                   s for _, _, s in untraced)] if untraced else None}}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
