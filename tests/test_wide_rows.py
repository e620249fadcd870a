"""Rows wider than a word tile through the served path: two stale replicas
of 16,384-byte rows (4,096 words, sixteen tiles of the chain removal) that
differ by adds and drops reconcile in one engine round on the device,
both sides exact by the benchmark's plain reference, and no row is hashed
on the host once the round has started.  Each replica's residual stays on
the device from one decode to the next, on the pipelined engine and the
synchronous one."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import data, reference  # noqa: E402
from repro.core import hashing  # noqa: E402
from repro.protocol import ReconcileEngine, Session, SymbolStream  # noqa: E402

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


@pytest.mark.parametrize("pipeline", [True, False])
def test_engine_round_of_wide_rows(monkeypatch, pipeline):
    traffic = {"d": [24], "pool": 2}
    server, enc, pool = data.build(4844, CONFIG, traffic, n=200)
    stream = SymbolStream(enc)
    engine = ReconcileEngine(pipeline=pipeline)
    for rep in pool:
        engine.register(stream, Session(local=rep.encoder, backend="device",
                                        max_diff=64,
                                        pacing=data.make_pacing(CONFIG)))
    calls = _count_host_hashes(monkeypatch)
    reports = engine.run()
    assert calls == []
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
