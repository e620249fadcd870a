"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine whose JAX sees a TPU with as
many chips as the cell asks for; anywhere else it exits nonzero and prints
no result.  The cell's parts are found by name (``bench/cell.py``).

Traffic is a closed loop in rounds: each round registers the round's
replicas from a pool built in set-up as fresh wire-mode sessions, drives
the cell's entry to completion and starts the next round.  A
reconciliation's completion time runs from the start of its round to the
return of the call that hands back its report, so every peer of a round
completes with the round.  Set-up (data, the server's stream cache, the
replica pool and the warm-up rounds that compile every program the window
runs) is timed as ``setup_s``; then rounds run until ``--seconds`` have
passed, and the last round is let finish.  Once the window has closed,
every reconciliation in it is compared with the plain reference
(``bench/reference.py``).  ``--trace 1`` also traces the device over the
first rounds of the window and reports the per-layer metrics.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TRACE_SECONDS = 4.0     # the traced span: whole rounds, at least this long


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Round:
    index: int
    start: float
    end: float
    replicas: list          # pool indices, in registration order
    reports: list | None    # None when the round raised
    counts: dict
    error: str | None

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Window:
    """What the metric readers see: the window's rounds and counts."""
    setup_s: float
    seconds: float
    rounds: list
    compiles: int
    traced_rounds: list
    trace: object           # trace.Reduced, or None

    @property
    def recons(self) -> int:
        return sum(len(r.replicas) for r in self.rounds)

    @property
    def completed(self) -> int:
        return sum(len(r.reports) for r in self.rounds if r.reports)

    def sync_ms(self) -> list:
        """One completion time per reconciliation, failed ones included."""
        return [1e3 * r.seconds for r in self.rounds for _ in r.replicas]

    def total(self, count: str) -> int:
        return sum(r.counts.get(count, 0) for r in self.rounds)

    def reports(self):
        return [rep for r in self.rounds if r.reports for rep in r.reports]

    def self_ms_per_recon(self, spans) -> float | None:
        """Self time of the host spans named ``spans`` in the traced span,
        in ms per reconciliation traced; None where the trace holds none
        of them."""
        recons = sum(len(r.replicas) for r in self.traced_rounds)
        found = [self.trace.self_ns[s] for s in spans
                 if self.trace and s in self.trace.self_ns]
        if not found or not recons:
            return None
        return sum(found) / 1e6 / recons


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def make_session_factory(config: dict):
    from bench import data
    from repro import protocol
    pacing = data.make_pacing(config)

    def session(local):
        return protocol.Session(local=local, backend="device",
                                max_diff=config["max_diff"], pacing=pacing,
                                max_m=config["max_m"])
    return session


def schedule(r: int, traffic: dict) -> list:
    """Pool indices of round ``r``'s replicas: the pool in turn."""
    p, pool = traffic["peers_per_round"], traffic["pool"]
    return [(r * p + i) % pool for i in range(p)]


def run_round(entry, stream, pool, session, r, idx) -> Round:
    t0 = time.perf_counter()
    try:
        reports, counts = entry.run_round(
            stream, [pool[i].encoder for i in idx], session, _span)
        error = None
    except Exception as e:  # a failed round fails its reconciliations
        reports, counts, error = None, {}, f"{type(e).__name__}: {e}"
    return Round(r, t0, time.perf_counter(), list(idx), reports, counts,
                 error)


def warmup_peer_counts(traffic: dict) -> list:
    """Batch sizes below a full round that the window can meet.  Where the
    pool's d values differ, the peers of a round settle at different ticks
    and the engine pads those still at work to a power of two: each power
    of two under ``peers_per_round``.  Where all share one d, they settle
    together and a full round is the only batch."""
    p = traffic["peers_per_round"]
    if len(set(traffic["d"])) < 2:
        return []
    return [1 << k for k in range(p.bit_length()) if 1 << k < p]


def warm_up(cell, stream, pool, session, log) -> int:
    """Run the cell's warm-up: ``warmup_rounds`` rounds of its own
    schedule, then one round of each of :func:`warmup_peer_counts`
    replicas (those of largest d), so that every padded batch size the
    window can meet is compiled.  A failed warm-up round is logged; the
    window's check then decides.  Returns the next round's index."""
    rounds = cell.traffic["warmup_rounds"]
    by_d = sorted((rep for rep in pool if rep.d > 0), key=lambda x: -x.d)
    plan = [(r, schedule(r, cell.traffic)) for r in range(rounds)] + \
        [(-1, [rep.index for rep in by_d[:p]])
         for p in warmup_peer_counts(cell.traffic)]
    for r, idx in plan:
        rd = run_round(cell.entry, stream, pool, session, r, idx)
        if rd.error:
            log(f"warm-up round of {len(idx)} peers failed: {rd.error}")
    return rounds


def check(rounds, server, pool, nbytes: int):
    """Compare every reconciliation of ``rounds`` with the reference.

    Returns each number compared, as ``[value, limit]``, and how many
    reconciliations failed: raised, differ from the reference on either
    side, overflowed ``max_diff`` or decoded on the host."""
    from bench import reference
    served = reference.RowSet(server)
    want = {}
    wrong = unfinished = overflows = host = failed = 0
    for rd in rounds:
        if rd.reports is None or len(rd.reports) != len(rd.replicas):
            unfinished += len(rd.replicas)
            failed += len(rd.replicas)
            continue
        for i, rep in zip(rd.replicas, rd.reports):
            if i not in want:
                want[i] = reference.expected_difference(
                    served, pool[i].drop, pool[i].own)
            only_server, only_replica = want[i]
            bad = not (reference.same_rows(rep.only_remote, only_server,
                                           nbytes) and
                       reference.same_rows(rep.only_local, only_replica,
                                           nbytes))
            wrong += bad
            overflows += rep.overflows
            host += rep.host_decodes
            failed += bad or rep.overflows > 0 or rep.host_decodes > 0
    numbers = {"wrong_recons": [wrong, 0], "unfinished_recons": [unfinished, 0],
               "overflows": [overflows, 0], "host_decodes": [host, 0]}
    return numbers, failed


def read_metrics(cell, metrics, window) -> dict:
    out = {}
    for m in metrics:
        value = cell.reader(m).read(window)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, n: int | None = None,
             t0: float | None = None, log=None, wrap_entry=None,
             spec: dict | None = None, traffic: dict | None = None) -> dict:
    """Set up, warm up, measure and check one cell; return the result.

    Rehearsals and controls only: ``n`` and ``traffic`` override the set
    size and entries of the traffic mix, ``spec`` stands for
    ``BENCHMARK.json``, and ``wrap_entry(entry)`` puts another entry in the
    program's place.  With ``require_tpu`` a missing or short TPU raises
    :class:`NoChip` before any data is made.
    """
    from bench import cell as cells, counters, data
    from bench import trace as tr

    t0 = _T0 if t0 is None else t0
    log = log or (lambda s: print(s, flush=True))
    c = cells.load_cell(workload, spec)
    c.traffic.update(traffic or {})
    from repro.compile_cache import place_compile_cache
    cache = place_compile_cache(ROOT)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu" or len(devs) < c.chips):
        raise NoChip(f"{workload} needs {c.chips} TPU chip(s); JAX found "
                     f"{len(devs)} {dev.platform!r} device(s)")
    log(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"devices={len(devs)} compile_cache={cache}")

    if wrap_entry:
        c.entry = wrap_entry(c.entry)
    entry = c.entry
    times = {"start_s": time.perf_counter() - t0}
    server, enc, pool = data.build(seed, c.config, c.traffic,
                                   n or c.config["n"], times)
    from repro.protocol import SymbolStream
    stream = SymbolStream(enc)
    session = make_session_factory(c.config)
    t = time.perf_counter()
    with counters.CompileCounter() as warm:
        r = warm_up(c, stream, pool, session, log)
    times["warmup_s"] = time.perf_counter() - t
    cache_m = enc.m
    setup_s = time.perf_counter() - t0
    log("setup " + " ".join(f"{k}={v}" for k, v in times.items()) +
        f" warmup_compiles={warm.compiles} warmup_compile_s={warm.seconds}"
        f" setup_s={setup_s}")

    rounds, traced, tmp = [], [], None
    try:
        with counters.CompileCounter() as cc:
            start = time.perf_counter()
            if trace:
                with tr.capture() as tmp:
                    while not rounds or \
                            time.perf_counter() - start < min(TRACE_SECONDS,
                                                              seconds):
                        rounds.append(run_round(entry, stream, pool, session,
                                                r, schedule(r, c.traffic)))
                        r += 1
                traced = list(rounds)
            while not rounds or rounds[-1].end - start < seconds:
                rounds.append(run_round(entry, stream, pool, session, r,
                                        schedule(r, c.traffic)))
                r += 1
            end = rounds[-1].end
        peak = counters.peak_bytes()
        reduced = None
        if trace:
            events = tr.load(tmp)
            reduced = tr.reduce(events)
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    grown = max([enc.m] + [rep.encoder.m for rep in pool])
    del stream, enc

    window = Window(setup_s, end - start, rounds, cc.compiles, traced,
                    reduced)
    sync = sorted(window.sync_ms())
    log(f"rounds={len(rounds)} recons={window.recons} "
        f"window_s={window.seconds} compiles_in_window={cc.compiles} "
        f"compile_s_in_window={cc.seconds} stream_cache_symbols={cache_m} "
        f"stream_cache_after={grown} "
        f"sync_p50_ms={_pct(sync, 50)} sync_p95_ms={_pct(sync, 95)} "
        f"sync_max_ms={sync[-1] if sync else None}")
    for rd in rounds:
        if rd.error:
            log(f"round {rd.index} failed: {rd.error}")
            break

    numbers, failed = check(rounds, server, pool, c.config["record"]["bytes"])
    correct = window.recons > 0 and \
        all(v <= lim for v, lim in numbers.values())
    metrics = read_metrics(c, c.per_layer if trace else c.end_to_end, window)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": window.recons,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["window_s"] = (reduced.window_ns / 1e9 if reduced else
                              sum(x.seconds for x in traced))
        device["busy_s"] = reduced.busy_ns / 1e9 if reduced else 0.0
        if reduced:
            result["breakdown"] = tr.breakdown(reduced)
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in numbers.items()}
    return result


def _pct(values, q):
    import numpy as np
    return float(np.percentile(values, q)) if values else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, v in result["check"].items():
        print(f"check {name}={v['value']} limit={v['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
