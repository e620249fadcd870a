"""Device wave-peeling decoder ≡ host peel (items, sides, success).

``decode_device`` is one unit of the batched decode; here it runs the
Python wave loop of the CPU, and its waves are held to the
:func:`repro.kernels.peel.peel_waves` oracle.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import Encoder, peel
from repro.core.decoder import resolve_backend
from repro.core.hashing import DEFAULT_KEY
from repro.core.stream import StreamDecoder
from repro.core.symbols import CodedSymbols, Residual
from repro.core.mapping import kmax, map_seeds
from repro.kernels.ops import (decode_device, decode_device_batched,
                               device_symbols_to_host, host_symbols_to_device)
from repro.kernels.peel import peel_waves

RNG = np.random.default_rng(2025)


def diff_symbols(d_a, d_b, L, m, n_common=40, rng=RNG):
    """Difference symbols of two sets with |A\\B| = d_a, |B\\A| = d_b."""
    nbytes = 4 * L
    pool = rng.integers(0, 2**32, size=(n_common + d_a + d_b, L),
                        dtype=np.uint32)
    pool[:, 0] = np.arange(pool.shape[0])   # force distinct items
    common, ai, bi = np.split(pool, [n_common, n_common + d_a])
    A, B = Encoder(nbytes), Encoder(nbytes)
    A.add_items(np.concatenate([common, ai]))
    B.add_items(np.concatenate([common, bi]))
    return A.symbols(m).subtract(B.symbols(m)), ai, bi


def as_sets(items, sides):
    return {(r.tobytes(), int(s)) for r, s in zip(items, sides)}


# ------------------------------------------------- host ≡ device sweep ----
@pytest.mark.parametrize("L", [1, 2, 8])
@pytest.mark.parametrize("d", [0, 1, 37, 500])
def test_decode_device_equals_host_peel(d, L):
    d_a = d // 2
    d_b = d - d_a
    m = max(16, int(2.2 * d))
    sym, _, _ = diff_symbols(d_a, d_b, L, m)
    host = peel(sym)
    res = decode_device(*host_symbols_to_device(sym), nbytes=4 * L)
    assert not res.overflow
    assert res.success == host.success
    assert as_sets(res.items, res.sides) == as_sets(host.items, host.sides)
    if host.success:
        assert res.residual.is_empty().all()


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 48), st.sampled_from([1, 2, 4]),
       st.floats(1.6, 3.0), st.integers(0, 2**31 - 1))
def test_decode_device_equals_host_peel_random(d, L, factor, seed):
    """Random cases, including under-provisioned prefixes (decode fails on
    both paths identically)."""
    rng = np.random.default_rng(seed)
    d_a = int(rng.integers(0, d + 1))
    m = max(8, int(factor * d))
    sym, _, _ = diff_symbols(d_a, d - d_a, L, m, n_common=20, rng=rng)
    host = peel(sym)
    res = decode_device(*host_symbols_to_device(sym), nbytes=4 * L)
    assert not res.overflow
    assert res.success == host.success
    assert as_sets(res.items, res.sides) == as_sets(host.items, host.sides)


def test_decode_device_empty_prefix():
    sym = CodedSymbols.zeros(0, 8)
    res = decode_device(*host_symbols_to_device(sym), nbytes=8)
    assert res.success and not res.overflow and res.items.shape == (0, 2)


@pytest.mark.parametrize("max_diff", [None, 5])
def test_decode_device_equals_the_oracle(max_diff):
    """One unit of the batched decode == the lone ref waves of
    ``peel_waves``, bit for bit: items, sides, waves, flags and the whole
    residual, on a full decode and on one that overflows ``max_diff``."""
    L, m, mp = 3, 150, 256
    sym, _, _ = diff_symbols(20, 17, L, m)
    sums, checks, counts = host_symbols_to_device(sym)
    res = decode_device(sums, checks, counts, nbytes=4 * L,
                        max_diff=max_diff)
    pad = ((0, mp - m), (0, 0))
    state, success = peel_waves(
        np.pad(sums, pad), np.pad(checks, pad), np.pad(counts[:, None], pad),
        m=m, nbytes=4 * L, key=DEFAULT_KEY, max_diff=max_diff or mp,
        K=kmax(mp))
    n = int(state.n_rec)
    assert res.items.shape[0] == n
    np.testing.assert_array_equal(res.items, state.rec_items[:n])
    np.testing.assert_array_equal(res.sides, state.rec_sides[:n])
    assert (res.rounds, res.success, res.overflow) == \
        (int(state.rounds), bool(success), bool(state.overflow))
    assert res.overflow == (max_diff is not None)
    assert res.success == (max_diff is None) == (n == 37)
    want = device_symbols_to_host(state.sums[:m], state.checks[:m],
                                  state.counts[:m, 0], 4 * L)
    np.testing.assert_array_equal(res.residual.sums, want.sums)
    np.testing.assert_array_equal(res.residual.checks, want.checks)
    np.testing.assert_array_equal(res.residual.counts, want.counts)


# ------------------------------------------- overflow -> host fallback ----
def test_decode_device_overflow_flag():
    sym, _, _ = diff_symbols(20, 17, 2, 128)
    res = decode_device(*host_symbols_to_device(sym), nbytes=8, max_diff=5)
    assert res.overflow and not res.success


def test_peel_backend_device_falls_back_on_overflow():
    sym, _, _ = diff_symbols(20, 17, 2, 128)
    host = peel(sym)
    dev = peel(sym, backend="device", max_diff=5)   # overflow -> host path
    assert dev.success and dev.success == host.success
    assert as_sets(dev.items, dev.sides) == as_sets(host.items, host.sides)


def test_stream_decoder_device_falls_back_on_overflow():
    nbytes = 8
    sym, ai, bi = diff_symbols(12, 9, 2, 96)
    dec = StreamDecoder(nbytes, backend="device", max_diff=4)
    dec.receive(sym)   # raw difference stream (local=None)
    only_a, only_b = dec.result()
    assert dec.decoded
    assert {r.tobytes() for r in only_a} == {r.tobytes() for r in ai}
    assert {r.tobytes() for r in only_b} == {r.tobytes() for r in bi}


# --------------------------------------------- backend plumbing bits ----
def test_resolve_backend():
    assert resolve_backend("host") == "host"
    assert resolve_backend("device") == "device"
    assert resolve_backend("auto") in ("host", "device")
    with pytest.raises(ValueError):
        resolve_backend("gpu")


def test_peel_backend_device_matches_host():
    sym, _, _ = diff_symbols(9, 6, 2, 64)
    host = peel(sym)
    dev = peel(sym, backend="device")
    assert dev.success == host.success
    assert as_sets(dev.items, dev.sides) == as_sets(host.items, host.sides)


def test_stream_decoder_device_incremental_windows():
    """Device-backed incremental decode across many windows == host."""
    nbytes = 8
    sym, ai, bi = diff_symbols(11, 7, 2, 128)
    host_dec = StreamDecoder(nbytes)
    dev_dec = StreamDecoder(nbytes, backend="device")
    for lo in range(0, 128, 16):
        win = sym.window(lo, lo + 16)
        host_done = host_dec.receive(win.copy())
        dev_done = dev_dec.receive(win.copy())
        assert host_done == dev_done
        assert host_dec.decoded == dev_dec.decoded
    assert dev_dec.decoded
    assert host_dec.decoded_at == dev_dec.decoded_at
    ha, hb = host_dec.result()
    da, db = dev_dec.result()
    assert {r.tobytes() for r in ha} == {r.tobytes() for r in da}
    assert {r.tobytes() for r in hb} == {r.tobytes() for r in db}


def test_stream_decoder_device_keeps_its_residual_on_the_device():
    """A device-backed decoder's peeled residual stays on the device from
    one window to the next: after its second window only the rows
    absorbed since are on the host, and it decodes what the host does."""
    nbytes = 8
    sym, ai, bi = diff_symbols(30, 25, 2, 160)
    host_dec = StreamDecoder(nbytes)
    dev_dec = StreamDecoder(nbytes, backend="device")
    for lo in range(0, 160, 16):
        win = sym.window(lo, lo + 16)
        assert host_dec.receive(win.copy()) == dev_dec.receive(win.copy())
        if lo == 16:
            assert not dev_dec.decoded
            assert dev_dec.work.base == 32 and dev_dec.work.host.m == 0
            assert dev_dec.work.device is not None
    assert dev_dec.decoded and dev_dec.decoded_at == host_dec.decoded_at
    for got, want in zip(dev_dec.result(), host_dec.result()):
        assert {r.tobytes() for r in got} == {r.tobytes() for r in want}
    assert {r.tobytes() for r in dev_dec.result()[0]} == \
        {r.tobytes() for r in ai}


@pytest.mark.parametrize("path", ["lone", "batched"])
def test_device_results_carry_the_map_seeds(path):
    """Every recovered row comes back with the mapping seed the wave
    derived its chain from, the one the host's ``map_seeds`` gives it, so
    merging a result hashes no row on the host."""
    L = 2
    sym, ai, bi = diff_symbols(9, 6, L, 64)
    if path == "batched":
        (res,) = decode_device_batched([Residual(sym)], nbytes=4 * L)
    else:
        res = decode_device(*host_symbols_to_device(sym), nbytes=4 * L)
    assert res.success and res.items.shape[0] == 15
    assert res.seeds.dtype == np.uint64
    np.testing.assert_array_equal(res.seeds,
                                  map_seeds(res.items, DEFAULT_KEY, 4 * L))


# --------------------------------------------------- layout round-trip ----
def test_symbols_device_roundtrip_uint64_checks():
    """host -> device -> host preserves the uint64 checksums bit-exactly,
    including values with all four 16-bit quarters populated."""
    rng = np.random.default_rng(3)
    m, L = 64, 3
    sym = CodedSymbols(
        rng.integers(0, 2**32, size=(m, L), dtype=np.uint32),
        rng.integers(0, 2**64, size=m, dtype=np.uint64),
        rng.integers(-3, 4, size=m).astype(np.int64), 4 * L)
    sym.checks[0] = np.uint64(0xFFFFFFFFFFFFFFFF)
    sym.checks[1] = np.uint64(0)
    sym.checks[2] = np.uint64(0x8000000000000001)
    back = device_symbols_to_host(*host_symbols_to_device(sym), 4 * L)
    np.testing.assert_array_equal(back.sums, sym.sums)
    np.testing.assert_array_equal(back.checks, sym.checks)
    np.testing.assert_array_equal(back.counts, sym.counts)
    assert back.sums.flags.writeable


# ------------------------------------------------ resident staging ----
def test_stage_batched_lays_out_units():
    """The bucket's input built on the device: each unit keeps rows
    [0, base) of its source (two sources of different buckets here), takes
    its own rows [base, m) from the staged tails, and reads zero past m;
    a unit with no source is staged whole, a padding unit is empty."""
    from repro.kernels.ops import stage_batched
    L, mp = 3, 32

    def rows(*shape):
        return RNG.integers(0, 2**32, size=shape + (L,), dtype=np.uint32)

    src_a, src_b = rows(2, 8), rows(1, 16)
    tails = rows(3 + 4 + 6)
    # (source, row, base, tail offset, m) per unit
    layout = np.array([[0, 1, 5, 0, 8], [1, 0, 10, 3, 14],
                       [-1, 0, 0, 7, 6], [-1, 0, 0, 13, 0]], np.int32)

    def fields(sums):
        # checks and counts ride along: derive them from the sums
        return (sums, sums[..., :2], sums[..., :1].astype(np.int32))

    out = stage_batched((fields(src_a), fields(src_b)), fields(tails),
                        layout, mp=mp)
    want = np.zeros((4, mp, L), np.uint32)
    want[0, :5], want[0, 5:8] = src_a[1, :5], tails[0:3]
    want[1, :10], want[1, 10:14] = src_b[0, :10], tails[3:7]
    want[2, :6] = tails[7:13]
    for got, w in zip(out[:3], fields(want)):
        np.testing.assert_array_equal(np.asarray(got), w)
    np.testing.assert_array_equal(np.asarray(out[3]), [8, 14, 6, 0])
