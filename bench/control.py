"""The control and the planted faults that the check must catch.

    python3 bench/control.py --workload <name> --seconds <s> \\
        --seeds 1,2,3 [--control-seeds 4,5,6]

Runs, in one process on the chip, the cell as it is on ``--seeds`` and the
control on ``--control-seeds``, and prints each run's compared numbers.
The control breaks the configuration's first guarantee: it serves a
one-sided sync, the program's own answers with the replica-only side left
empty, as a pull-only client would take them.  The faults, planted under
the engine's batched device decode (:func:`plant`) or the lone session's
decode (:func:`plant_lone`), are for the tests
(``bench/tests/test_bench_faults.py``): a decode that returns its state
unchanged, one that decodes half of the batch and hands its answers to
the other half (a batch of one has no half), and one that alters an
answer where it is produced.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
for _p in (str(BENCH.parent), str(BENCH.parent / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def one_sided(entry):
    """The control: ``entry`` with every report's replica-only side
    dropped."""
    import dataclasses

    class OneSided:
        @staticmethod
        def run_round(stream, locals_, session, span):
            reports, counts = entry.run_round(stream, locals_, session, span)
            return [dataclasses.replace(r, only_local=r.only_local[:0])
                    for r in reports], counts
    return OneSided


def _unchanged(ops, units, nbytes):
    """Results that leave each unit's residual as it came."""
    return [ops.DeviceDecodeResult(
        np.zeros((0, u.L), np.uint32), np.zeros(0, np.uint64),
        np.zeros(0, np.int8), False, False, 1, u.copy()) for u in units]


def plant(fault: str):
    """A stand-in for ``ops.decode_device_batched_start`` with ``fault``
    planted: ``unchanged``, ``half`` or ``altered``."""
    from repro.kernels import ops
    real = ops.decode_device_batched_start

    def start(units, *, nbytes, **kw):
        units = list(units)
        if fault == "unchanged":
            res = _unchanged(ops, units, nbytes)
        elif fault == "half":
            # the first half is decoded and stands in for the second
            k = (len(units) + 1) // 2
            res = real(units[:k], nbytes=nbytes, **kw).wait()
            res = res + [res[i % k] for i in range(len(units) - k)]
        elif fault == "altered":
            res = [_altered(r) for r in real(units, nbytes=nbytes,
                                              **kw).wait()]
        else:
            raise ValueError(f"unknown fault {fault!r}")
        return ops.PendingBatchedDecode(None, None, [u.m for u in units],
                                        nbytes, results=res)
    return start


def _altered(r):
    """``r`` with its first recovered item's first word flipped."""
    if not r.items.shape[0]:
        return r
    items = r.items.copy()
    items[0, 0] ^= 1
    return r._replace(items=items)


def plant_lone(fault: str):
    """A stand-in for ``ops.decode_device``, the lone session's decode,
    with ``fault`` planted: ``unchanged`` or ``altered``."""
    from repro.kernels import ops
    real = ops.decode_device

    def decode(sums, checks, counts, *, nbytes, **kw):
        if fault == "unchanged":
            L = np.asarray(sums).shape[1]
            return ops.DeviceDecodeResult(
                np.zeros((0, L), np.uint32), np.zeros(0, np.uint64),
                np.zeros(0, np.int8), False, False, 1,
                ops.device_symbols_to_host(sums, checks, counts, nbytes))
        if fault == "altered":
            return _altered(real(sums, checks, counts, nbytes=nbytes, **kw))
        raise ValueError(f"unknown fault {fault!r}")
    return decode


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    from bench import run
    runs = [(int(s), None) for s in args.seeds.split(",") if s] + \
        [(int(s), one_sided) for s in args.control_seeds.split(",") if s]
    for seed, wrap in runs:
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           t0=time.perf_counter(), wrap_entry=wrap,
                           log=lambda s: print(s, file=sys.stderr))
        print(json.dumps({"seed": seed, "control": wrap is not None,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"], "check": res["check"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
