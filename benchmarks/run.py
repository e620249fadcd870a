"""Benchmark runner — one module per paper figure/table.

``python -m benchmarks.run``            quick CI-scale sweep
``python -m benchmarks.run --full``     paper-scale sweep (slow)
``python -m benchmarks.run --only fig7``
``python -m benchmarks.run --roofline`` include roofline table rendering
                                        (requires dry-run artifacts)

Output: ``name,us_per_call,derived`` CSV on stdout.  These are CPU
timings of the paper's sweeps; the chip benchmark is ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="substring filter, e.g. fig7 / statesync / wire")
    ap.add_argument("--roofline", action="store_true",
                    help="render roofline table from dry-run artifacts")
    args = ap.parse_args()

    from repro.compile_cache import place_compile_cache
    place_compile_cache()
    from . import (alpha, itemsize, overhead, setsize, statesync, throughput,
                   wirebench)
    suites = [
        ("overhead", overhead),      # Figs 4, 6
        ("throughput", throughput),  # Figs 7, 8
        ("setsize", setsize),        # Fig 9
        ("itemsize", itemsize),      # Fig 10
        ("statesync", statesync),    # Figs 11, 12
        ("alpha", alpha),            # Fig 14
        ("wirebench", wirebench),    # §6 wire codec: vectorized vs loop
    ]
    failed = []
    for name, mod in suites:
        if args.only and args.only not in name:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        try:
            mod.main(quick=not args.full)
        except Exception as e:  # keep the suite going; report the failure
            print(f"{name},ERROR,{type(e).__name__}: {e}", flush=True)
            failed.append(name)
        print(f"# === {name} done in {time.time() - t0:.1f}s ===", flush=True)
    if args.roofline:  # independent of suite outcomes — render before exit
        from . import roofline
        roofline.main()

    if failed:  # exit nonzero so CI smoke steps actually catch breakage
        print(f"# FAILED suites: {', '.join(failed)}", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
