"""The plain reference orders and compares whole rows, at the record
widths the benchmark meets: 4-B ids, 92-B accounts, 128-KiB blobs."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference  # noqa: E402

WIDTHS = [4, 92, 131_072]


def _rows(nbytes, seed=11):
    """Rows with ties up to the last byte, a row twice and a row all 0xff
    (the last in byte order), in shuffled order."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 256, size=(24, nbytes), dtype=np.uint8)
    rows[1:6, :-1] = rows[0, :-1]
    rows[1:6, -1] = [200, 3, 255, 0, 17]
    rows[7] = rows[8]
    rows[9] = 0xFF
    rows[10, 0] = rows[11, 0]           # equal first bytes only
    return rows[rng.permutation(rows.shape[0])]


def _lexsorted(rows):
    return rows[np.lexsort(rows.T[::-1])]


@pytest.mark.parametrize("nbytes", WIDTHS)
def test_order_is_the_lexsort_order(nbytes):
    rows = _rows(nbytes)
    want = _lexsorted(rows)
    assert np.array_equal(reference._sorted_rows(rows), want)
    keep = np.r_[True, np.any(want[1:] != want[:-1], axis=1)]
    got = reference._distinct(rows)
    assert got.shape == (rows.shape[0] - 1, nbytes)
    assert np.array_equal(got, want[keep])
    assert reference._sorted_rows(rows[:0]).shape == (0, nbytes)


@pytest.mark.parametrize("nbytes", WIDTHS)
def test_same_rows_reads_the_last_byte(nbytes):
    rows = reference._distinct(_rows(nbytes))
    words = np.zeros((rows.shape[0], -(-nbytes // 4)), "<u4")
    words.view(np.uint8)[:, :nbytes] = rows[::-1]
    assert reference.same_rows(words, rows, nbytes)
    flipped = words.copy()
    flipped.view(np.uint8)[3, nbytes - 1] ^= 1
    assert not reference.same_rows(flipped, rows, nbytes)
    assert not reference.same_rows(words[1:], rows, nbytes)
    # a row recovered twice in place of another is not the set
    twice = words.copy()
    twice[0] = twice[1]
    assert not reference.same_rows(twice, rows, nbytes)
