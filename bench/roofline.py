"""The least the device peel must move, computed from shapes alone, and
the peak it is held to.

A batched peel wave of one unit reads each residual row of its tile
bucket once and writes it once: ``mp`` rows of ``L`` item words, two
checksum words and one count word.  Whatever implements the wave, it
cannot move less, so the count reads the same work before and after a
change to the program.
"""
from __future__ import annotations

# HBM bandwidth by ``device_kind`` (Google Cloud documentation, "TPU v5e":
# 16 GB of HBM at 819 GB/s).  A device not in the table is an error.
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}

# the batched decode's program in the trace's module events:
# ``jit_peel_batched(<fingerprint>)``
PEEL_PROGRAM = "jit_peel_batched"


def words(nbytes: int) -> int:
    """Item words of a row of ``nbytes`` bytes."""
    return (nbytes + 3) // 4


def wave_bytes(mp: float, L: int) -> float:
    """Bytes one wave of one unit moves at the least: its ``mp`` residual
    rows of ``4L + 12`` bytes, read once and written once."""
    return 2 * mp * (4 * L + 12)


def peel_bytes(reports) -> float | None:
    """The least bytes of every device decode in ``reports``: per report,
    its waves times :func:`wave_bytes` of its mean tile bucket
    (``device_symbols`` over ``device_decodes``).  Exact where a report's
    decodes share one bucket; where they do not, the larger buckets run
    more waves and the count reads low.  None where a report lacks the
    counters."""
    total = 0.0
    for r in reports:
        symbols = getattr(r, "device_symbols", None)
        waves = getattr(r, "device_waves", None)
        if symbols is None or waves is None:
            return None
        if r.device_decodes:
            total += waves * wave_bytes(symbols / r.device_decodes,
                                        words(r.nbytes))
    return total


def hbm_share(nbytes: float, seconds: float, device_kind: str) -> float:
    """``nbytes`` moved in ``seconds``, in % of the device's HBM peak."""
    return 100.0 * nbytes / seconds / HBM_BYTES_PER_S[device_kind]
