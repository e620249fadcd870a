"""Rateless IBLT encoder/decoder system invariants (paper §3–§4)."""
import zlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (CodedSymbols, Encoder, Sketch, StreamDecoder, encode,
                        peel, reconcile, reconcile_sets)


@pytest.fixture
def rng(request):
    """A generator of the test's own, seeded by its name and parameters,
    so no test's draws depend on which tests ran before it."""
    return np.random.default_rng(zlib.crc32(request.node.name.encode()))


def rand_items(rng, n, nbytes, tag=None):
    out = rng.integers(0, 256, size=(n, nbytes), dtype=np.uint8)
    if tag is not None:
        out[:, 0] = tag  # disjointness between groups
    return out


# ---------------------------------------------------------------- encode --
def test_symbol_zero_contains_every_item(rng):
    items = rand_items(rng, 50, 16)
    sym = encode(items, 16, 8)
    assert sym.counts[0] == 50  # rho(0) = 1


def test_encode_prefix_consistency(rng):
    """Rateless property: a longer prefix extends, never rewrites (Fig 3)."""
    items = rand_items(rng, 64, 24)
    s1 = encode(items, 24, 32)
    s2 = encode(items, 24, 512)
    np.testing.assert_array_equal(s1.sums, s2.sums[:32])
    np.testing.assert_array_equal(s1.checks, s2.checks[:32])
    np.testing.assert_array_equal(s1.counts, s2.counts[:32])


def test_incremental_extension_equals_oneshot(rng):
    items = rand_items(rng, 64, 8)
    enc = Encoder(8)
    enc.add_items(items)
    for m in (1, 2, 5, 17, 63, 200):
        enc.extend(m)
    a = enc.symbols(200)
    b = encode(items, 8, 200)
    np.testing.assert_array_equal(a.sums, b.sums)
    np.testing.assert_array_equal(a.checks, b.checks)
    np.testing.assert_array_equal(a.counts, b.counts)


def test_incremental_add_remove_equals_rebuild(rng):
    """Linearity (§4.1): updating the cached symbols in place == re-encoding
    the updated set from scratch."""
    base = rand_items(rng, 100, 16, tag=0)
    add = rand_items(rng, 10, 16, tag=1)
    rm = base[:7]
    enc = Encoder(16)
    enc.add_items(base)
    _ = enc.symbols(300)          # populate cache first
    enc.add_items(add)            # retro-encoded into the cached prefix
    enc.remove_items(rm)
    target = np.concatenate([base[7:], add])
    fresh = encode(target, 16, 300)
    got = enc.symbols(300)
    np.testing.assert_array_equal(got.sums, fresh.sums)
    np.testing.assert_array_equal(got.checks, fresh.checks)
    np.testing.assert_array_equal(got.counts, fresh.counts)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 40), st.integers(0, 40), st.integers(5, 33))
def test_linearity_subtraction(na, nb, nbytes):
    """IBLT(A) ⊖ IBLT(B) == IBLT(A △ B)  (the enabling identity, §3)."""
    rng = np.random.default_rng([na, nb, nbytes])
    common = rand_items(rng, 30, nbytes, tag=0)
    a_only = rand_items(rng, na, nbytes, tag=1)
    b_only = rand_items(rng, nb, nbytes, tag=2)
    m = 64
    sa = encode(np.concatenate([common, a_only]), nbytes, m)
    sb = encode(np.concatenate([common, b_only]), nbytes, m)
    diff = sa.subtract(sb)
    direct_a = encode(a_only, nbytes, m) if na else CodedSymbols.zeros(m, nbytes)
    direct_b = encode(b_only, nbytes, m) if nb else CodedSymbols.zeros(m, nbytes)
    direct = direct_a.subtract(direct_b)
    np.testing.assert_array_equal(diff.sums, direct.sums)
    np.testing.assert_array_equal(diff.checks, direct.checks)
    np.testing.assert_array_equal(diff.counts, direct.counts)


# ----------------------------------------------------------------- decode --
@pytest.mark.parametrize("d", [1, 2, 5, 40, 300])
def test_roundtrip_pure_set(d, rng):
    items = rand_items(rng, d, 16)
    m = max(8, int(2.2 * d))
    res = peel(encode(items, 16, m))
    assert res.success
    got = {r.tobytes() for r in res.items}
    want = {np.ascontiguousarray(w).tobytes()
            for w in encode(items, 16, 1).sums * 0 + 0}  # placeholder
    # compare against original items through the same word packing
    from repro.core import bytes_to_words
    want = {np.ascontiguousarray(w).tobytes() for w in bytes_to_words(items, 16)}
    assert got == want


@pytest.mark.parametrize("da,db", [(0, 5), (5, 0), (13, 7), (50, 50)])
def test_reconcile_directions(da, db, rng):
    common = rand_items(rng, 200, 32, tag=0)
    ai = rand_items(rng, da, 32, tag=1)
    bi = rand_items(rng, db, 32, tag=2)
    A = Sketch.from_items(np.concatenate([common, ai]), 32)
    B = Sketch.from_items(np.concatenate([common, bi]), 32)
    only_a, only_b, m_used = reconcile_sets(A, B)
    assert sorted(x.tobytes() for x in only_a) == sorted(x.tobytes() for x in ai)
    assert sorted(x.tobytes() for x in only_b) == sorted(x.tobytes() for x in bi)
    d = da + db
    assert m_used <= max(8, 8 * d)  # sane overhead even with block rounding


def test_identical_sets_decode_immediately(rng):
    items = rand_items(rng, 64, 16)
    A = Sketch.from_items(items, 16)
    B = Sketch.from_items(items.copy(), 16)
    only_a, only_b, m_used = reconcile_sets(A, B)
    assert len(only_a) == 0 and len(only_b) == 0
    assert m_used <= 8  # first block: all-zero symbols, symbol 0 empty


def test_undecodable_prefix_reports_failure(rng):
    """With m ≪ d the peeling decoder must stall, not hallucinate."""
    items = rand_items(rng, 500, 16)
    res = peel(encode(items, 16, 16))
    assert not res.success
    assert len(res.items) < 500


def test_symbol_zero_decodes_last(rng):
    """ρ(0)=1 ⇒ symbol 0 empties only when everything is recovered — the
    paper's termination signal."""
    items = rand_items(rng, 60, 16)
    sym = encode(items, 16, 200)
    res = peel(sym)
    assert res.success
    # prefix that fails: symbol 0 must still be non-empty after peeling
    short = sym.prefix(30)
    res2 = peel(short)
    if not res2.success:
        # re-run manually to inspect the worked buffer
        from repro.core.decoder import _remove_chains  # noqa: F401
        work = short.copy()
        assert not work.is_empty()[0] or res2.success


# ----------------------------------------------------------------- stream --
def test_stream_decoder_matches_batch(rng):
    common = rand_items(rng, 300, 16, tag=0)
    ai = rand_items(rng, 25, 16, tag=1)
    bi = rand_items(rng, 11, 16, tag=2)
    A = Sketch.from_items(np.concatenate([common, ai]), 16)
    B = Sketch.from_items(np.concatenate([common, bi]), 16)
    dec = StreamDecoder(16, local=B)
    m = 0
    while not dec.decoded:
        sym = A.symbols(m + 4)
        batch = CodedSymbols(sym.sums[m:], sym.checks[m:], sym.counts[m:], 16)
        dec.receive(batch)
        m += 4
        assert m < 4096
    only_a, only_b = dec.result()
    assert only_a.shape[0] == 25 and only_b.shape[0] == 11


def test_overhead_band_small_d(rng):
    """Paper Fig. 4: average overhead ≤ ~1.72 at the worst d (≈4), with
    slack for variance at small sample counts."""
    trials, d = 40, 8
    used = []
    for t in range(trials):
        items = rand_items(rng, d, 8)
        enc = Encoder(8)
        enc.add_items(items)
        m = d  # smallest prefix that could possibly decode has m >= d
        while True:
            if peel(enc.symbols(m)).success:
                used.append(m)
                break
            m += 1
    avg = np.mean(used) / d
    assert 1.0 <= avg < 2.3, f"overhead {avg}"


def test_wire_roundtrip(rng):
    from repro.core.wire import decode_stream, encode_stream
    items = rand_items(rng, 500, 20)
    sym = encode(items, 20, 128)
    blob = encode_stream(sym)
    back, n = decode_stream(blob)
    assert n == 500
    np.testing.assert_array_equal(back.sums, sym.sums)
    np.testing.assert_array_equal(back.checks, sym.checks)
    np.testing.assert_array_equal(back.counts, sym.counts)
    # §6 claim: count field ~1 byte amortized (we allow <= 2 here)
    per_sym = (len(blob) - 16) / 128 - (20 + 8)
    assert per_sym <= 2.0
