"""Sets made from ``--seed``: the served set and a pool of stale replicas.

A configuration's ``record`` fixes the row width and how many leading bytes
are the key; its ``change`` says how a replica differs from the served set:

* ``update`` — a changed record keeps its key and gets a new value, so the
  replica holds the old record and the server the new one: a difference of
  d rows is d/2 changed records.
* ``add_drop`` — the replica lacks d//2 of the server's rows and holds
  d - d//2 rows of its own that the server lacks.

Every seed gives the pool the same multiset of differences, in another
order, so seeds change which rows move and not how much work there is.
"""
from __future__ import annotations

import copy
import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Replica:
    """One stale replica: its encoder (stream cache kept up to date by
    linearity) and the rows that make it differ from the served set."""
    index: int
    d: int
    drop: np.ndarray     # indices into the served rows the replica lacks
    own: np.ndarray      # (k, nbytes) uint8 rows only the replica holds
    encoder: object


def _unique_keys(rng, count: int, key_bytes: int) -> np.ndarray:
    """``count`` keys of ``key_bytes`` bytes, as uint8 rows: distinct draws
    for keys of under 8 bytes, random bytes otherwise (the reference checks
    that the served rows form a set)."""
    if key_bytes >= 8:
        return rng.integers(0, 256, size=(count, key_bytes), dtype=np.uint8)
    ids = rng.choice(1 << (8 * key_bytes), size=count, replace=False)
    return ids.astype("<u8").view(np.uint8).reshape(count, 8)[:, :key_bytes]


def make_rows(rng, n: int, record: dict) -> np.ndarray:
    """``n`` rows of ``record['bytes']`` bytes with distinct keys."""
    nbytes, key_bytes = record["bytes"], record["key_bytes"]
    rows = np.empty((n, nbytes), np.uint8)
    rows[:, :key_bytes] = _unique_keys(rng, n, key_bytes)
    rows[:, key_bytes:] = rng.integers(0, 256, size=(n, nbytes - key_bytes),
                                       dtype=np.uint8)
    return rows


def pool_differences(rng, traffic: dict) -> list[int]:
    """The pool's d values: ``traffic['d']`` repeated to fill the pool,
    shuffled by the seed."""
    ds, pool = list(traffic["d"]), traffic["pool"]
    if pool % len(ds):
        raise ValueError(f"pool {pool} is not a multiple of {len(ds)} d values")
    return [int(d) for d in rng.permutation(ds * (pool // len(ds)))]


def make_replica_rows(rng, server: np.ndarray, d: int, config: dict,
                      fresh: np.ndarray):
    """(drop, own) for one replica at difference ``d``.  ``fresh`` holds
    rows whose keys the server does not have (``add_drop`` draws from it)."""
    n = server.shape[0]
    if config["change"] == "update":
        if d % 2:
            raise ValueError(f"update traffic needs an even d, got {d}")
        drop = np.sort(rng.choice(n, d // 2, replace=False))
        key_bytes = config["record"]["key_bytes"]
        own = server[drop].copy()
        own[:, key_bytes:] = rng.integers(0, 256, size=own[:, key_bytes:].shape,
                                          dtype=np.uint8)
        return drop, own
    if config["change"] == "add_drop":
        drop = np.sort(rng.choice(n, d // 2, replace=False))
        take = rng.choice(fresh.shape[0], d - d // 2, replace=False)
        return drop, fresh[take].copy()
    raise ValueError(f"unknown change {config['change']!r}")


def make_pacing(config: dict):
    """The configuration's pacing, as the program's ``protocol`` names it."""
    from repro import protocol
    p = dict(config["pacing"])
    return getattr(protocol, p.pop("kind"))(**p)


def cache_symbols(config: dict, traffic: dict) -> int:
    """Symbols of stream cache that the cell's longest reconciliation
    reaches.  A decode of d items takes about 1.35 d symbols and rarely
    more than 2 d: take the pacing's first prefix of 2 max(d) or more, one
    step further for a slow decode, and one more for the window the
    pipelined engine fetches while that decode is in flight."""
    pacing = make_pacing(config)
    m, need = 0, 2 * max(traffic["d"])
    while m < need:
        m += pacing.next_take(m)
    for _ in range(2):
        m += pacing.next_take(m)
    return m


def build(seed: int, config: dict, traffic: dict, n: int, times=None):
    """The served rows, the server's encoder with its stream cache grown
    past what the cell reaches (:func:`cache_symbols`), and the replica
    pool.

    Each replica is the server's encoder copied after the cache is grown,
    then updated by linearity (``remove_items`` / ``add_items``), as a
    deployed node keeps its own stream: O(d log m) per replica, not O(n).
    ``times``, where given, receives the seconds of each step.
    """
    times = {} if times is None else times
    t = time.perf_counter()
    from repro.core.encoder import Encoder

    rng = np.random.default_rng(seed)
    record = config["record"]
    ds = pool_differences(rng, traffic)
    n_fresh = sum(d - d // 2 for d in ds) if config["change"] == "add_drop" \
        else 0
    rows = make_rows(rng, n + n_fresh, record)
    server, fresh = rows[:n], rows[n:]
    times["rows_s"], t = time.perf_counter() - t, time.perf_counter()
    enc = Encoder(record["bytes"], tuple(config["key"]))
    enc.add_items(server)
    times["encoder_s"], t = time.perf_counter() - t, time.perf_counter()
    enc.extend(cache_symbols(config, traffic))
    times["cache_s"], t = time.perf_counter() - t, time.perf_counter()
    pool = []
    for i, d in enumerate(ds):
        drop, own = make_replica_rows(rng, server, d, config, fresh)
        local = copy.deepcopy(enc)
        if drop.size:
            local.remove_items(server[drop])
        if own.shape[0]:
            local.add_items(own)
        pool.append(Replica(i, d, drop, own, local))
    times["pool_s"] = time.perf_counter() - t
    return server, enc, pool
