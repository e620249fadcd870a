"""Where the host time of a traced window goes, by the program's spans.

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s>

Runs one cell as ``bench/run.py --trace 1`` does (the same set-up,
warm-up, traced span and check) and prints one JSON line: the run's
result, and from its trace

* ``self_ms_per_recon``: the self time of each ``bench.*``/``repro.*``
  span name inside the traced span (``bench/trace.py``'s ``self_ns``),
  per reconciliation traced;
* ``idle_ms_in_span``: the device's idle time in the traced span, split
  as the result line's ``breakdown`` splits it: each idle nanosecond to
  the innermost span covering it (``bench.traced`` where no other does);
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
    red = tr.reduce(events)
    out = {"workload": args.workload, "seed": args.seed, "result": res,
           "traced_recons": recons, "traced_span_ms": (hi - lo) / 1e6,
           "self_ms_per_recon": {k: v / 1e6 / recons for k, v in sorted(
               red.self_ns.items())},
           "idle_gaps": len(tr.idle_gaps(events, lo, hi)),
           "idle_ms_in_span": {k: v / 1e6 for k, v in sorted(
               red.idle_ns.items())},
           "round_ms": {
               "traced": [len(traced), 1e3 * statistics.median(
                   s for _, _, s in traced)],
               "untraced": [len(untraced), 1e3 * statistics.median(
                   s for _, _, s in untraced)] if untraced else None}}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
