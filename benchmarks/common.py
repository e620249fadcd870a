"""Shared benchmark helpers."""
from __future__ import annotations

import time

import numpy as np

RNG = np.random.default_rng(0xB0B)


def rand_items(n: int, nbytes: int, tag: int = 0) -> np.ndarray:
    out = RNG.integers(0, 256, size=(n, nbytes), dtype=np.uint8)
    if n:
        out[:, -1] = tag
    return out


def make_sets(n_common: int, da: int, db: int, nbytes: int):
    common = rand_items(n_common, nbytes, 0)
    ai = rand_items(da, nbytes, 1)
    bi = rand_items(db, nbytes, 2)
    return (np.concatenate([common, ai]), np.concatenate([common, bi]),
            ai, bi)


def timeit(fn, *args, repeat: int = 1, **kw):
    best = float("inf")
    out = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        best = min(best, time.perf_counter() - t0)
    return best, out


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.2f},{derived}", flush=True)


def riblt_symbols_to_decode(set_a, set_b, nbytes, key=None) -> int:
    """Exact minimal prefix length that decodes (one-symbol stream steps)."""
    from repro.core import Encoder
    from repro.core.hashing import DEFAULT_KEY
    from repro.protocol import FixedBlock, Session, SymbolStream, run_session
    key = key or DEFAULT_KEY
    A = Encoder(nbytes, key)
    B = Encoder(nbytes, key)
    if len(set_a):
        A.add_items(set_a)
    if len(set_b):
        B.add_items(set_b)
    rep = run_session(SymbolStream(A),
                      Session(local=B, pacing=FixedBlock(1)))
    return rep.symbols_used
