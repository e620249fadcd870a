"""The batched peel's dense chain removal against the bit-parity oracle,
and the batched wave loop against the lone ref-engine decode, unit by unit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.encoder import Encoder
from repro.core.hashing import DEFAULT_KEY
from repro.core.mapping import kmax
from repro.kernels import iblt_dense
from repro.kernels.iblt_dense import iblt_apply_dense
from repro.kernels.ops import host_symbols_to_device
from repro.kernels.peel import peel_waves, peel_waves_batched
from repro.kernels.ref import iblt_apply_ref, map_indices_ref

RNG = np.random.default_rng(9091)
MP = 256


def _rows(n, L, m_chain, sides):
    """n random rows, their chains within ``m_chain`` and checksums, and
    int32 sides drawn from ``sides``."""
    items = jnp.asarray(RNG.integers(0, 2**32, size=(n, L), dtype=np.uint32))
    idxs, chks = map_indices_ref(items, K=kmax(MP), m=m_chain, nbytes=4 * L,
                                 key=DEFAULT_KEY)
    side = jnp.asarray(RNG.choice(sides, size=n).astype(np.int32))
    return items, idxs, chks, side


def _assert_same(got, want):
    for g, w, name in zip(got, want, ("sums", "checks", "counts")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


# Each case: (m the apply sees, m the chains were drawn within, the sides
# drawn, whether a zero side also sends the row's chain to index m as the
# peel does).  Chains drawn within a larger prefix than the apply's ``m``
# hold indices in [m, MP); a chain drawn within ``m`` saturates at ``m``.
CASES = {
    "full": (MP, MP, [-1, 0, 1], True),
    "prefix_saturated": (MP - 37, MP - 37, [-1, 0, 1], True),
    "past_m": (MP // 2 + 5, MP, [-1, 1], True),
    "zero_side_keeps_chain": (MP - 9, MP - 9, [-1, 0, 1], False),
    "all_dropped": (MP - 3, MP - 3, [0], True),
}


def _check_dense(case, L, n=128):
    m, m_chain, sides, drop = CASES[case]
    items, idxs, chks, side = _rows(n, L, m_chain, sides)
    if drop:
        idxs = jnp.where(side[:, None] != 0, idxs, jnp.int32(m))
    # m is traced: one compiled program for every prefix in the bucket
    dense = jax.jit(lambda *a: iblt_apply_dense(*a[:4], m=a[4], m_out=MP))
    got = dense(items, idxs, chks, side, jnp.int32(m))
    want = iblt_apply_ref(items, idxs, chks, side, m=m, m_out=MP)
    _assert_same(got, want)
    if case == "all_dropped":
        assert not any(np.asarray(g).any() for g in got)
    else:
        assert np.asarray(got[2]).any()      # the case removes something
    assert not any(np.asarray(g)[m:].any() for g in got)


@pytest.mark.parametrize("L", [2, 23])
@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_apply_equals_oracle(case, L):
    _check_dense(case, L)


@pytest.mark.parametrize("L", [7, 8, 21])
@pytest.mark.parametrize("case", sorted(CASES))
def test_tiled_apply_equals_oracle(monkeypatch, case, L):
    """Rows wider than a tile go one word tile at a time: below one tile
    (one product), exactly one, and two whole tiles and a partial one."""
    monkeypatch.setattr(iblt_dense, "TILE_WORDS", 8)
    _check_dense(case, L)


def test_tiled_apply_equals_oracle_at_the_real_tile():
    """Four whole tiles of the real width and a partial fifth."""
    _check_dense("past_m", 4 * iblt_dense.TILE_WORDS + 5, n=32)


@pytest.mark.parametrize("L", [2, 23])
def test_dense_apply_vmaps_over_ragged_units(L):
    ms = [MP, MP - 100, 17, 0]
    rows = [_rows(64, L, m, [-1, 0, 1]) for m in ms]
    rows = [(it, jnp.where(sd[:, None] != 0, ix, jnp.int32(m)), ck, sd)
            for (it, ix, ck, sd), m in zip(rows, ms)]
    stacked = [jnp.stack(a) for a in zip(*rows)]
    got = jax.vmap(lambda it, ix, ck, sd, m: iblt_apply_dense(
        it, ix, ck, sd, m=m, m_out=MP))(*stacked, jnp.asarray(ms, jnp.int32))
    for u, (m, r) in enumerate(zip(ms, rows)):
        want = iblt_apply_ref(*r, m=m, m_out=MP)
        _assert_same([g[u] for g in got], want)


# ------------------------------------------------ batched ≡ lone decode --
def _unit(d, L, m, seed):
    """Padded difference symbols of two sets that differ by ``d`` items."""
    rng = np.random.default_rng(seed)
    pool = rng.integers(0, 2**32, size=(40 + d, L), dtype=np.uint32)
    pool[:, 0] = np.arange(pool.shape[0])
    A, B = Encoder(4 * L), Encoder(4 * L)
    A.add_items(pool[:40 + d - d // 3])
    B.add_items(np.concatenate([pool[:40], pool[40 + d - d // 3:]]))
    sums, checks, counts = host_symbols_to_device(
        A.symbols(m).subtract(B.symbols(m)))
    pad = ((0, MP - m), (0, 0))
    return (np.pad(sums, pad), np.pad(checks, pad),
            np.pad(counts[:, None], pad))


# (d, m) per unit: a full decode, a ragged shorter one, one whose prefix
# is too short to finish, a small one, and one that overflows max_diff in
# its second wave
UNITS = [(30, MP), (20, 90), (36, 40), (5, 200), (48, 160)]
MAX_DIFF = 40


@pytest.mark.parametrize("use_while_loop", [False, True],
                         ids=["python_loop", "while_loop"])
@pytest.mark.parametrize("L", [2, 23])
def test_batched_peel_equals_lone_peel(L, use_while_loop):
    units = [_unit(d, L, m, seed) for seed, (d, m) in enumerate(UNITS)]
    ms = [m for _, m in UNITS]
    kw = dict(nbytes=4 * L, key=DEFAULT_KEY, max_diff=MAX_DIFF, K=kmax(MP))
    state, success = peel_waves_batched(
        *(np.stack(a) for a in zip(*units)), m=np.asarray(ms, np.int32),
        use_while_loop=use_while_loop, **kw)
    lone = [peel_waves(*u, m=m, **kw) for u, m in zip(units, ms)]
    for u, (ls, lsucc) in enumerate(lone):
        n = int(ls.n_rec)
        assert int(state.n_rec[u]) == n
        np.testing.assert_array_equal(state.rec_items[u, :n],
                                      ls.rec_items[:n])
        np.testing.assert_array_equal(state.rec_sides[u, :n],
                                      ls.rec_sides[:n])
        np.testing.assert_array_equal(state.sums[u], ls.sums)
        np.testing.assert_array_equal(state.checks[u], ls.checks)
        np.testing.assert_array_equal(state.counts[u], ls.counts)
        assert bool(success[u]) == bool(lsucc)
        assert bool(state.overflow[u]) == bool(ls.overflow)
    # every unit steps with the batch, so each counts the batch's waves:
    # as many as the slowest lone decode took
    assert {int(r) for r in state.rounds} == {max(int(s.rounds)
                                                 for s, _ in lone)}
    outcome = [(bool(s), bool(ls.overflow)) for ls, s in lone]
    assert (True, False) in outcome and (False, False) in outcome
    assert (False, True) in outcome


@pytest.mark.parametrize("use_while_loop", [False, True],
                         ids=["python_loop", "while_loop"])
def test_batched_peel_of_one_unit_counts_its_own_waves(use_while_loop):
    d, m = UNITS[0]
    unit = _unit(d, 2, m, 0)
    kw = dict(nbytes=8, key=DEFAULT_KEY, max_diff=MAX_DIFF, K=kmax(MP))
    state, success = peel_waves_batched(
        *(a[None] for a in unit), m=np.asarray([m], np.int32),
        use_while_loop=use_while_loop, **kw)
    ls, lsucc = peel_waves(*unit, m=m, **kw)
    assert int(state.rounds[0]) == int(ls.rounds) > 1
    assert bool(success[0]) == bool(lsucc) is True
