"""Mapping + hashing invariants (paper §4.1–4.2)."""
import jax
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import siphash24, siphash24_pair, rho, kmax
from repro.core.hashing import DEFAULT_KEY
from repro.core.mapping import (_pow2, _recip_rn, _sqrt_rn, indices_matrix_j,
                                indices_matrix_np, item_indices_np, map_seeds,
                                map_seeds_pair)
from repro.kernels.ref import map_indices_ref

RNG = np.random.default_rng(1234)


def rand_words(n, L):
    return RNG.integers(0, 2**32, size=(n, L), dtype=np.uint32)


# ---------------------------------------------------------------- hashing --
@settings(max_examples=20, deadline=None)
@given(st.integers(1, 12), st.integers(1, 64),
       st.integers(0, 2**64 - 1), st.integers(0, 2**64 - 1))
def test_siphash_host_device_bitexact(L, n, k0, k1):
    w = rand_words(n, L)
    h = siphash24(w, (k0, k1), nbytes=4 * L)
    hi, lo = siphash24_pair(jnp.asarray(w), (k0, k1), nbytes=4 * L)
    h2 = (np.asarray(hi).astype(np.uint64) << np.uint64(32)) | \
        np.asarray(lo).astype(np.uint64)
    np.testing.assert_array_equal(h, h2)


def test_siphash_rfc_vector():
    """RFC/reference test vector: key 000102..0f, msg 000102..07."""
    key = (0x0706050403020100, 0x0F0E0D0C0B0A0908)
    words = np.array([[0x03020100, 0x07060504]], dtype=np.uint32)
    got = siphash24(words, key, nbytes=8)[0]
    assert got == np.uint64(0x93F5F5799A932462)


def test_siphash_keyed():
    w = rand_words(8, 4)
    assert not np.array_equal(siphash24(w, (1, 2)), siphash24(w, (1, 3)))


# ---------------------------------------------------------------- mapping --
def test_first_index_always_zero():
    seeds = map_seeds(rand_words(200, 8), (1, 2), 32)
    M = indices_matrix_np(seeds, 1 << 14)
    assert np.all(M[:, 0] == 0)  # rho(0) = 1


def test_chain_strictly_monotone():
    seeds = map_seeds(rand_words(100, 8), (7, 9), 32)
    M = indices_matrix_np(seeds, 4096)
    for row in M:
        live = row[row < 4096]
        assert np.all(np.diff(live) >= 1)


def test_mapping_probability_matches_rho():
    """Empirical inclusion probability tracks ρ(i) (within the paper's
    stated C⁻¹ approximation, which shifts small-i mass by ~4%)."""
    n = 40_000
    seeds = map_seeds(rand_words(n, 8), (3, 5), 32)
    m = 256
    M = indices_matrix_np(seeds, m)
    counts = np.bincount(M[M < m].ravel(), minlength=m) / n
    i = np.array([2, 4, 8, 16, 32, 64, 128])
    emp, theo = counts[i], rho(i)
    assert np.all(np.abs(emp - theo) / theo < 0.08)


def test_host_device_chains_identical():
    n, m = 512, 2048
    w = rand_words(n, 8)
    seeds = map_seeds(w, (11, 13), 32)
    Mh = indices_matrix_np(seeds, m)
    hi, lo = map_seeds_pair(jnp.asarray(w), (11, 13), 32)
    Md = np.asarray(indices_matrix_j(hi, lo, m, K=Mh.shape[1]))
    np.testing.assert_array_equal(Mh, Md.astype(np.int64))


def test_device_inverse_sqrt_is_float32_exact():
    """The device's t = 1/sqrt(1 - r) equals numpy's float32 for all 2**24
    values of r, compiled (XLA would otherwise fuse 1/sqrt into rsqrt)."""
    rbits = np.arange(1 << 24, dtype=np.uint32)
    r = rbits.astype(np.float32) * np.float32(2.0 ** -24)
    want = np.float32(1.0) / np.sqrt(np.float32(1.0) - r)

    @jax.jit
    def t(rbits):
        Q, fb = _sqrt_rn((1 << 24) - rbits.astype(jnp.int32))
        return _recip_rn(Q).astype(jnp.float32) * _pow2(fb - 35)

    np.testing.assert_array_equal(np.asarray(t(jnp.asarray(rbits))), want)


def test_jitted_device_chains_identical():
    """The compiled device chains equal the host's at a real prefix length
    and item width (with a float32 1/sqrt, one of these 4096 rows moved by
    one index)."""
    n, m = 4096, 2048
    w = np.random.default_rng(0).integers(0, 2**32, size=(n, 23),
                                          dtype=np.uint32)
    Mh = indices_matrix_np(map_seeds(w, DEFAULT_KEY, 92), m)
    Md, _ = jax.jit(lambda w: map_indices_ref(
        w, K=Mh.shape[1], m=m, nbytes=92, key=DEFAULT_KEY))(jnp.asarray(w))
    np.testing.assert_array_equal(Mh, np.asarray(Md).astype(np.int64))


def test_kmax_bounds_chain_length():
    """No item maps more than kmax(m) times within m (statistical)."""
    for m in (64, 1024, 1 << 16):
        seeds = map_seeds(rand_words(20_000, 4), (17, 19), 16)
        M = indices_matrix_np(seeds, m)  # K defaults to kmax(m)
        # last column must already be saturated (= m) for every item,
        # i.e. kmax was large enough to exhaust every chain.
        assert np.all(M[:, -1] == m), f"kmax({m}) too small"


def test_expected_density_is_logarithmic():
    """Each item maps to ~2·ln(m/2) of the first m symbols (§4.1.2)."""
    n, m = 5_000, 8192
    seeds = map_seeds(rand_words(n, 4), (23, 29), 16)
    M = indices_matrix_np(seeds, m)
    mean_deg = (M < m).sum() / n
    from repro.core import expected_degree
    assert abs(mean_deg - expected_degree(m)) / expected_degree(m) < 0.05


def test_universality_prefix_consistency():
    """Symbols for index i do not depend on how many symbols were asked
    for — the defining rateless property."""
    seeds = map_seeds(rand_words(64, 4), (31, 37), 16)
    M1 = indices_matrix_np(seeds, 128)
    M2 = indices_matrix_np(seeds, 4096)
    for r1, r2 in zip(M1, M2):
        a = r1[r1 < 128]
        b = r2[r2 < 128]
        np.testing.assert_array_equal(a, b)
