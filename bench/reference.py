"""The plain reference: the exact set difference of byte rows, in numpy.

It imports nothing of the program.  The served rows must form a set (no
row twice), which :class:`RowSet` checks.  A replica holds the served rows
less ``drop``, plus ``own``; so the rows only the server holds are
``served[drop]`` less ``own``, and the rows only the replica holds are
``own`` less the served set.  Rows are compared whole, byte for byte: an
8-byte prefix only narrows the search.
"""
from __future__ import annotations

import numpy as np


def _prefix64(rows: np.ndarray) -> np.ndarray:
    """The first (up to) 8 bytes of each row as one little-endian uint64."""
    head = np.zeros((rows.shape[0], 8), np.uint8)
    k = min(8, rows.shape[1])
    head[:, :k] = rows[:, :k]
    return head.view("<u8").ravel()


def _keys(rows: np.ndarray) -> np.ndarray:
    """Each row as one ``np.void`` element of its width, which numpy
    orders and compares byte by byte: one pass over the rows, not one per
    byte column."""
    rows = np.ascontiguousarray(rows, np.uint8)
    return rows.view(np.dtype((np.void, rows.shape[1]))).ravel()


def _rows(keys: np.ndarray, nbytes: int) -> np.ndarray:
    return keys.view(np.uint8).reshape(keys.shape[0], nbytes)


def _sorted_rows(rows: np.ndarray) -> np.ndarray:
    """Rows in lexicographic byte order."""
    return _rows(np.sort(_keys(rows)), rows.shape[1])


def _distinct(rows: np.ndarray) -> np.ndarray:
    """Distinct rows in lexicographic byte order."""
    return _rows(np.unique(_keys(rows)), rows.shape[1])


class RowSet:
    """Exact membership over a set of equal-width byte rows."""

    def __init__(self, rows: np.ndarray):
        self.rows = np.ascontiguousarray(rows, np.uint8)
        keys = _prefix64(self.rows)
        self.order = np.argsort(keys, kind="stable")
        self.keys = keys[self.order]
        starts = np.flatnonzero(np.r_[True, self.keys[1:] != self.keys[:-1],
                                      True])
        for s, e in zip(starts[:-1], starts[1:]):
            if e - s > 1 and \
                    _distinct(self.rows[self.order[s:e]]).shape[0] < e - s:
                raise ValueError("a row is there twice: not a set")

    def contains(self, q: np.ndarray) -> np.ndarray:
        q = np.ascontiguousarray(q, np.uint8)
        keys = _prefix64(q)
        lo = np.searchsorted(self.keys, keys, side="left")
        hi = np.searchsorted(self.keys, keys, side="right")
        out = np.zeros(q.shape[0], bool)
        for j in np.flatnonzero(hi > lo):
            cand = self.rows[self.order[lo[j]:hi[j]]]
            out[j] = bool(np.any(np.all(cand == q[j], axis=1)))
        return out


def expected_difference(served: RowSet, drop: np.ndarray, own: np.ndarray):
    """(only_server, only_replica) rows, each distinct and sorted, for a
    replica that holds ``served`` less rows ``drop`` plus rows ``own``."""
    if np.unique(drop).size != drop.size:
        raise ValueError("drop lists a row twice")
    own = _distinct(own)
    only_replica = own[~served.contains(own)]
    gone = served.rows[drop]
    in_own = RowSet(own).contains(gone) if own.shape[0] else \
        np.zeros(gone.shape[0], bool)
    return _distinct(gone[~in_own]), only_replica


def words_to_rows(words: np.ndarray, nbytes: int) -> np.ndarray:
    """(r, L) little-endian uint32 words -> (r, nbytes) uint8 rows."""
    words = np.ascontiguousarray(words, dtype="<u4")
    raw = words.view(np.uint8).reshape(words.shape[0], 4 * words.shape[1])
    return raw[:, :nbytes]


def same_rows(got_words: np.ndarray, want_sorted: np.ndarray,
              nbytes: int) -> bool:
    """True when the recovered words hold exactly the rows ``want_sorted``
    (sorted and distinct), each once."""
    got = words_to_rows(got_words, nbytes)
    if got.shape[0] != want_sorted.shape[0]:
        return False
    return bool(np.array_equal(_sorted_rows(got), want_sorted))
