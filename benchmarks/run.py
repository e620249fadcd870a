"""Benchmark runner — one module per paper figure/table.

``python -m benchmarks.run``            quick CI-scale sweep
``python -m benchmarks.run --full``     paper-scale sweep (slow)
``python -m benchmarks.run --only fig7``
``python -m benchmarks.run --roofline`` include roofline table rendering
                                        (requires dry-run artifacts)

Output: ``name,us_per_call,derived`` CSV on stdout.  When the kernel or
shard suites run, their entries are additionally written to
``BENCH_kernels.json`` / ``BENCH_shards.json`` as machine-readable
``{name: µs}`` maps so CI can record the perf trajectory.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="substring filter, e.g. fig7 / statesync / kernel")
    ap.add_argument("--roofline", action="store_true",
                    help="render roofline table from dry-run artifacts")
    args = ap.parse_args()

    from repro.compile_cache import place_compile_cache
    place_compile_cache()
    from . import (alpha, enginebench, itemsize, kernelbench, overhead,
                   setsize, shardbench, statesync, throughput, wirebench)
    suites = [
        ("overhead", overhead),      # Figs 4, 6
        ("throughput", throughput),  # Figs 7, 8
        ("setsize", setsize),        # Fig 9
        ("itemsize", itemsize),      # Fig 10
        ("statesync", statesync),    # Figs 11, 12
        ("alpha", alpha),            # Fig 14
        ("kernelbench", kernelbench),  # device-encoder kernel (framework)
        ("wirebench", wirebench),    # §6 wire codec: vectorized vs loop
        ("shardbench", shardbench),  # sharded serving + batched decode
        ("enginebench", enginebench),  # N-peer engine vs serial sessions
    ]
    artifacts = {"kernelbench": "BENCH_kernels.json",
                 "shardbench": "BENCH_shards.json",
                 "enginebench": "BENCH_engine.json"}
    from .common import RESULTS
    failed = []
    for name, mod in suites:
        if args.only and args.only not in name:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        before = set(RESULTS)
        try:
            mod.main(quick=not args.full)
        except Exception as e:  # keep the suite going; report the failure
            print(f"{name},ERROR,{type(e).__name__}: {e}", flush=True)
            failed.append(name)
        print(f"# === {name} done in {time.time() - t0:.1f}s ===", flush=True)
        if name in artifacts and name not in failed:
            entries = {k: round(v, 2) for k, v in RESULTS.items()
                       if k not in before}
            with open(artifacts[name], "w") as f:
                json.dump(entries, f, indent=2, sort_keys=True)
            print(f"# wrote {artifacts[name]} ({len(entries)} entries)",
                  flush=True)
    if args.roofline:  # independent of suite outcomes — render before exit
        from . import roofline
        roofline.main()

    if failed:  # exit nonzero so CI smoke steps actually catch breakage
        print(f"# FAILED suites: {', '.join(failed)}", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
