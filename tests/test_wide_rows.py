"""Rows wider than a word tile through the served path: two stale replicas
of 16,384-byte rows (4,096 words, sixteen tiles of the chain removal) that
differ by adds and drops reconcile on the device, in one engine round or
each alone through ``run_session``, both sides exact by the benchmark's
plain reference, and no row is hashed on the host once the round has
started.  Each replica's residual stays on the device from one decode to
the next, on the pipelined engine, the synchronous one and the lone
session."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import data, reference  # noqa: E402
from repro.core import hashing  # noqa: E402
from repro.protocol import (ReconcileEngine, Session, SymbolStream,  # noqa: E402
                            run_session)

NBYTES = 16384
CONFIG = {"record": {"bytes": NBYTES, "key_bytes": 32}, "change": "add_drop",
          "pacing": {"kind": "Exponential", "block": 8, "growth": 2.0},
          "key": list(hashing.DEFAULT_KEY)}


def _count_host_hashes(monkeypatch) -> list:
    """Count every call of the host SipHash, wherever it was imported."""
    calls = []
    real = hashing.siphash24

    def counted(*a, **k):
        calls.append(np.asarray(a[0]).shape)
        return real(*a, **k)
    for mod in list(sys.modules.values()):
        if getattr(mod, "__name__", "").startswith("repro") and \
                getattr(mod, "siphash24", None) is real:
            monkeypatch.setattr(mod, "siphash24", counted)
    return calls


def _build():
    traffic = {"d": [24], "pool": 2}
    server, enc, pool = data.build(4844, CONFIG, traffic, n=200)
    return server, SymbolStream(enc), pool


def _check(server, pool, reports):
    served = reference.RowSet(server)
    for rep, report in zip(pool, reports):
        only_server, only_replica = reference.expected_difference(
            served, rep.drop, rep.own)
        assert only_server.shape[0] == only_replica.shape[0] == 12
        assert reference.same_rows(report.only_remote, only_server, NBYTES)
        assert reference.same_rows(report.only_local, only_replica, NBYTES)
        assert report.host_decodes == report.overflows == 0
        assert report.device_decodes > 1
        # every decode but the first staged only the rows absorbed since
        assert report.resident_decodes == report.device_decodes - 1


def _sessions(pool):
    return [Session(local=rep.encoder, backend="device", max_diff=64,
                    pacing=data.make_pacing(CONFIG)) for rep in pool]


@pytest.mark.parametrize("pipeline", [True, False])
def test_engine_round_of_wide_rows(monkeypatch, pipeline):
    server, stream, pool = _build()
    engine = ReconcileEngine(pipeline=pipeline)
    for session in _sessions(pool):
        engine.register(stream, session)
    calls = _count_host_hashes(monkeypatch)
    reports = engine.run()
    assert calls == []
    _check(server, pool, reports)


def test_sessions_of_wide_rows(monkeypatch):
    """Each replica alone through ``run_session``: batches of one unit."""
    server, stream, pool = _build()
    sessions = _sessions(pool)
    calls = _count_host_hashes(monkeypatch)
    reports = [run_session(stream, session, wire=True)
               for session in sessions]
    assert calls == []
    _check(server, pool, reports)
