"""Run the served reconciliation path once on one TPU chip and check it.

    python3 chip_smoke.py [--seed 0]

Run from the root of a checkout on a machine with one TPU.  The server set
is n = 1,000,000 random 92-byte records (paper §7: a 20-B key and a 72-B
value); each replica drops d/2 of them and adds d/2 of its own.  Two phases
run through the entry points users call:

* ``single`` — one replica at d = 1,000 through ``run_session(..., wire=True)``
  with ``backend="device", max_diff=1024``: the lone-session decode.
* ``engine`` — one default (pipelined) ``ReconcileEngine`` serving 8 plain
  replicas at d ∈ {0, 1, 10, 100, 250, 500, 1000, 1000} and one 8-shard
  ``ShardedSession`` at d = 1,000, all on the device with ``max_diff=1024``.

Every report's ``only_remote``/``only_local`` is compared with the numpy
set difference of the byte rows; a phase fails on any mismatch, any
``max_diff`` overflow or any host decode.  Each phase prints one line of
counters; the last line of stdout is the JSON result.  Without a TPU the
script exits nonzero before it makes any data.  ``tests/test_chip_smoke.py``
rehearses the phases on CPU at a small n.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

NBYTES = 92
N_ITEMS = 1_000_000
D_SINGLE = 1000
D_ENGINE = (0, 1, 10, 100, 250, 500, 1000, 1000)
D_SHARDED = 1000
N_SHARDS = 8
MAX_DIFF = 1024


# ---------------------------------------------------------------------------
# Data and the plain reference.
# ---------------------------------------------------------------------------
def make_server(rng, n: int) -> np.ndarray:
    return rng.integers(0, 256, size=(n, NBYTES), dtype=np.uint8)


def make_replica(rng, server: np.ndarray, d: int) -> np.ndarray:
    """``server`` less d//2 of its rows, plus d - d//2 rows of its own."""
    drop = rng.choice(server.shape[0], d // 2, replace=False)
    own = rng.integers(0, 256, size=(d - d // 2, NBYTES), dtype=np.uint8)
    return np.concatenate([np.delete(server, drop, axis=0), own])


def _rows(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a, dtype=np.uint8)
    return a.view(np.dtype((np.void, a.shape[1]))).ravel()


def check_report(report, server: np.ndarray, local: np.ndarray) -> None:
    """Raise unless the recovered difference is the numpy set difference
    of the byte rows, with no overflow and no host decode."""
    for got, want in ((report.only_remote_bytes(),
                       np.setdiff1d(_rows(server), _rows(local))),
                      (report.only_local_bytes(),
                       np.setdiff1d(_rows(local), _rows(server)))):
        got = np.sort(_rows(got))
        if got.shape != want.shape or not np.array_equal(got, want):
            raise AssertionError(f"recovered {got.shape[0]} items, the "
                                 f"set difference has {want.shape[0]}")
    if report.overflows or report.host_decodes:
        raise AssertionError(f"{report.overflows} max_diff overflows, "
                             f"{report.host_decodes} host decodes")


# ---------------------------------------------------------------------------
# Counters.
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts backend compiles (and their seconds) while entered."""
    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0

    def _listen(self, event, duration, **_):
        if event == self._EVENT:
            self.compiles += 1
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _line(phase: str, **fields) -> str:
    return f"phase={phase} " + " ".join(f"{k}={v}" for k, v in fields.items())


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------
def phase_single(rng, server: np.ndarray, stream, d: int) -> str:
    """One replica through ``run_session`` on the device backend."""
    from repro.core.encoder import Encoder
    from repro.kernels.ops import peel_engine
    from repro.protocol import Session, run_session

    local = make_replica(rng, server, d)
    enc = Encoder(NBYTES)
    enc.add_items(local)
    with CompileCounter() as cc:
        t0 = time.perf_counter()
        report = run_session(stream, Session(local=enc, backend="device",
                                             max_diff=MAX_DIFF), wire=True)
        wall = time.perf_counter() - t0
    check_report(report, server, local)
    return _line("single", engine=peel_engine(), wall_s=wall,
                 compiles=cc.compiles, compile_s=cc.seconds,
                 ticks=report.grow_steps, dispatches=report.device_decodes,
                 overflows=report.overflows, host_decodes=report.host_decodes,
                 peers=f"[d={d}:used={report.symbols_used}:"
                       f"overhead={report.overhead(d)}]",
                 peak_bytes_in_use=peak_bytes())


def phase_engine(rng, server: np.ndarray, stream, sharded_stream, ds,
                 d_sharded: int) -> str:
    """Plain replicas and one sharded replica on one pipelined engine."""
    from repro.core.encoder import Encoder
    from repro.kernels.ops import peel_engine
    from repro.protocol import (ReconcileEngine, Session, ShardedSession,
                                ShardedStream)

    engine = ReconcileEngine()
    peers = []
    for d in ds:
        local = make_replica(rng, server, d)
        enc = Encoder(NBYTES)
        enc.add_items(local)
        engine.register(stream, Session(local=enc, backend="device",
                                        max_diff=MAX_DIFF), wire=True)
        peers.append((d, local))
    local = make_replica(rng, server, d_sharded)
    engine.register(sharded_stream, ShardedSession(
        local=ShardedStream.from_items(local, NBYTES,
                                       n_shards=sharded_stream.n_shards),
        backend="device", max_diff=MAX_DIFF), wire=True)
    peers.append((d_sharded, local))
    with CompileCounter() as cc:
        t0 = time.perf_counter()
        reports = engine.run()
        wall = time.perf_counter() - t0
    for report, (_, local) in zip(reports, peers):
        check_report(report, server, local)
    return _line("engine", engine=peel_engine(batched=True), wall_s=wall,
                 compiles=cc.compiles, compile_s=cc.seconds,
                 ticks=engine.ticks, dispatches=engine.dispatches,
                 overflows=sum(r.overflows for r in reports),
                 host_decodes=sum(r.host_decodes for r in reports),
                 peers="[" + ",".join(
                     f"d={d}:used={r.symbols_used}:overhead={r.overhead(d)}"
                     for r, (d, _) in zip(reports, peers)) + "]",
                 peak_bytes_in_use=peak_bytes())


def run_phases(seed: int, n: int = N_ITEMS, d_single: int = D_SINGLE,
               ds=D_ENGINE, d_sharded: int = D_SHARDED):
    """Make the data from ``seed`` and yield each phase's counter line."""
    from repro.protocol import ShardedStream, SymbolStream

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    server = make_server(rng, n)
    stream = SymbolStream.from_items(server, NBYTES)
    sharded = ShardedStream.from_items(server, NBYTES, n_shards=N_SHARDS)
    yield _line("setup", n=n, nbytes=NBYTES, seed=seed,
                wall_s=time.perf_counter() - t0)
    yield phase_single(rng, server, stream, d_single)
    yield phase_engine(rng, server, stream, sharded, ds, d_sharded)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import place_compile_cache
    cache = place_compile_cache(ROOT)
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {jax.default_backend()!r}",
              file=sys.stderr)
        return 1
    print(f"compile_cache={cache}", flush=True)
    for line in run_phases(args.seed):
        print(line, flush=True)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
