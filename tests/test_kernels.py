"""Pallas kernel validation (interpret=True) against pure-jnp oracles.

Interpret-mode executes the kernel body op-by-op on CPU with ~10 ms/op
overhead, so sweeps use small blocks/K; shape coverage is what matters.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.encoder import Encoder, encode
from repro.core.hashing import DEFAULT_KEY
from repro.core.mapping import indices_matrix_np, kmax, map_seeds
from repro.kernels.iblt_encode import iblt_encode
from repro.kernels.map_indices import map_indices
from repro.kernels.ops import (decode_device, device_symbols_to_host,
                               encode_device, host_symbols_to_device)
from repro.kernels.peel import _purity_body, iblt_apply, purity_scan
from repro.kernels.ref import iblt_apply_ref, iblt_encode_ref, map_indices_ref

RNG = np.random.default_rng(4242)


def rand_items(n, L):
    return RNG.integers(0, 2**32, size=(n, L), dtype=np.uint32)


# -------------------------------------------------------------- mapping --
# NOTE on coverage: the kernels run in the TPU interpreter here.  The
# generic interpreter took XLA-CPU minutes to compile once the SipHash
# kernel spanned more than one grid step, so these tests pin L=2 (8-byte
# items — the paper's §7.2 benchmark size) and a single grid step; wider L /
# multi-block coverage runs through the identical-math ref path
# (`map_indices_ref`, tested against the host chains in test_core_mapping),
# the `slow` cases below, and the compiles in test_tpu_compile.
@pytest.mark.parametrize("K", [4, 6, 8])
def test_map_indices_kernel_vs_ref(K):
    L, block_n = 2, 64
    items = jnp.asarray(rand_items(block_n, L))
    ki, kc = map_indices(items, K=K, m=256, nbytes=4 * L, key=DEFAULT_KEY,
                         block_n=block_n)
    ri, rc = map_indices_ref(items, K=K, m=256, nbytes=4 * L, key=DEFAULT_KEY)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(kc), np.asarray(rc))


@pytest.mark.slow
@pytest.mark.parametrize("L,block_n,K", [(3, 64, 8), (4, 64, 8), (8, 128, 6)])
def test_map_indices_kernel_vs_ref_wide(L, block_n, K):
    items = jnp.asarray(rand_items(block_n * 2, L))
    ki, kc = map_indices(items, K=K, m=256, nbytes=4 * L, key=DEFAULT_KEY,
                         block_n=block_n)
    ri, rc = map_indices_ref(items, K=K, m=256, nbytes=4 * L, key=DEFAULT_KEY)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(kc), np.asarray(rc))


def test_map_indices_kernel_vs_host_chain():
    """Kernel indices == exact host (numpy uint64) chains."""
    L, n, m, K = 2, 64, 64, 8
    items = rand_items(n, L)
    ki, _ = map_indices(jnp.asarray(items), K=K, m=m, nbytes=4 * L,
                        key=DEFAULT_KEY, block_n=64)
    seeds = map_seeds(items, DEFAULT_KEY, 4 * L)
    hm = indices_matrix_np(seeds, m, K=K)
    # host chains saturate at pad=m exactly like the kernel
    np.testing.assert_array_equal(
        np.minimum(np.asarray(ki).astype(np.int64), m), hm)


# --------------------------------------------------------------- encode --
@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3).map(lambda e: 64 * e),   # n
       st.sampled_from([1, 2, 4, 8]),             # L words
       st.sampled_from([64, 128, 192]))           # m
def test_iblt_encode_kernel_vs_ref_sweep(n, L, m):
    items = jnp.asarray(rand_items(n, L))
    idxs, chks = map_indices_ref(items, K=10, m=m, nbytes=4 * L,
                                 key=DEFAULT_KEY)
    ks, kc, kn = iblt_encode(items, idxs, chks, m=m, block_m=64, block_n=64)
    rs, rc, rn = iblt_encode_ref(items, idxs, chks, m=m)
    np.testing.assert_array_equal(np.asarray(ks)[:m], np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(kc)[:m], np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(kn)[:m], np.asarray(rn))


def test_iblt_encode_grid_accumulation():
    """Multi-block grids (m and n both tiled) accumulate correctly."""
    n, L, m = 256, 2, 256
    items = jnp.asarray(rand_items(n, L))
    idxs, chks = map_indices_ref(items, K=12, m=m, nbytes=8, key=DEFAULT_KEY)
    ks, kc, kn = iblt_encode(items, idxs, chks, m=m, block_m=64, block_n=64)
    rs, rc, rn = iblt_encode_ref(items, idxs, chks, m=m)
    np.testing.assert_array_equal(np.asarray(ks)[:m], np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(kn)[:m], np.asarray(rn))


def test_encode_device_equals_host_encoder():
    """Full device pipeline == host incremental encoder, bit for bit.
    (K = kmax(m): exact chains never truncate at this size.)"""
    n, L, m = 300, 4, 128
    items = rand_items(n, L)
    s, c, cnt = encode_device(jnp.asarray(items), m=m, nbytes=16,
                              block_n=128, block_m=128)
    dev = device_symbols_to_host(s, c, cnt, 16)
    host = encode(items, 16, m)
    np.testing.assert_array_equal(dev.sums, host.sums)
    np.testing.assert_array_equal(dev.checks, host.checks)
    np.testing.assert_array_equal(dev.counts, host.counts)


def test_encode_device_decodes():
    """Device-encoded symbols feed the host peeling decoder."""
    from repro.core import peel
    items = rand_items(40, 4)
    s, c, cnt = encode_device(jnp.asarray(items), m=128, nbytes=16,
                              block_n=64, block_m=64)
    res = peel(device_symbols_to_host(s, c, cnt, 16))
    assert res.success
    got = {r.tobytes() for r in res.items}
    assert got == {i.tobytes() for i in items}


def test_encode_device_ragged_n_padding():
    """n not a multiple of block_n: zero-padding must not leak."""
    items = rand_items(100, 2)
    s1, c1, n1 = encode_device(jnp.asarray(items), m=64, nbytes=8,
                               block_n=64, block_m=64)
    host = encode(items, 8, 64)
    dev = device_symbols_to_host(s1, c1, n1, 8)
    np.testing.assert_array_equal(dev.sums, host.sums)
    np.testing.assert_array_equal(dev.counts, host.counts)


@pytest.mark.parametrize("mapping", ["ref", "pallas"])
def test_encode_device_padded_equals_unpadded(mapping):
    """Regression: the same items encoded through a block size that needs
    zero-padding and one that doesn't produce bit-identical symbols.
    (K is truncated identically on both runs, so bit-equality holds even
    at a small K that keeps the interpret-mode kernel cheap.)"""
    items = jnp.asarray(rand_items(96, 2))
    kw = dict(m=64, nbytes=8, K=8, block_m=64, mapping=mapping)
    s_pad, c_pad, n_pad = encode_device(items, block_n=64, **kw)   # 96 -> 128
    s_raw, c_raw, n_raw = encode_device(items, block_n=32, **kw)   # no pad
    np.testing.assert_array_equal(np.asarray(s_pad), np.asarray(s_raw))
    np.testing.assert_array_equal(np.asarray(c_pad), np.asarray(c_raw))
    np.testing.assert_array_equal(np.asarray(n_pad), np.asarray(n_raw))


# ----------------------------------------------------------------- peel --
def _small_diff(d, L, m, seed=5):
    rng = np.random.default_rng(seed)
    pool = rng.integers(0, 2**32, size=(30 + d, L), dtype=np.uint32)
    pool[:, 0] = np.arange(pool.shape[0])
    A, B = Encoder(4 * L), Encoder(4 * L)
    A.add_items(pool)
    B.add_items(pool[:30])
    return A.symbols(m).subtract(B.symbols(m))


def test_purity_scan_kernel_vs_ref():
    """Pallas purity kernel == pure-jnp purity over a real difference
    (mix of pure, empty, and multi-item symbols, both signs)."""
    sym = _small_diff(6, 2, 64)
    sym.counts[:8] *= -1          # exercise negative sides too
    sums, checks, counts = host_symbols_to_device(sym)
    counts = counts[:, None]
    kern = purity_scan(sums, checks, counts, key=DEFAULT_KEY, nbytes=8,
                       block_m=64)
    ref = _purity_body(sums, checks, counts, key=DEFAULT_KEY, nbytes=8)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(ref))
    assert int(np.sum(np.asarray(ref) != 0)) > 0   # scenario has pure rows


def test_iblt_apply_kernel_vs_ref():
    """Signed-removal kernel == bit-parity oracle, mixed ±1/0 sides."""
    n, L, m, K = 64, 2, 64, 10
    items = jnp.asarray(rand_items(n, L))
    idxs, chks = map_indices_ref(items, K=K, m=m, nbytes=8, key=DEFAULT_KEY)
    sides = jnp.asarray(RNG.integers(-1, 2, size=n, dtype=np.int32))
    idxs = jnp.where(sides[:, None] != 0, idxs, jnp.int32(m))
    ks, kc, kn = iblt_apply(items, idxs, chks, sides, m=m, block_m=64,
                            block_n=64)
    rs, rc, rn = iblt_apply_ref(items, idxs, chks, sides, m=m, m_out=64)
    np.testing.assert_array_equal(np.asarray(ks)[:m], np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(kc)[:m], np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(kn)[:m], np.asarray(rn))


def test_decode_device_pallas_engine_equals_ref_engine():
    """Full wave loop through the Pallas kernels == the jnp ref engine,
    wave for wave (same K so chain truncation is identical)."""
    sym = _small_diff(3, 2, 64)
    dev = host_symbols_to_device(sym)
    kw = dict(nbytes=8, K=14, block_n=64, block_m=64)
    rp = decode_device(*dev, kernel="pallas", **kw)
    rr = decode_device(*dev, kernel="ref", **kw)
    assert rp.success == rr.success and rp.rounds == rr.rounds
    np.testing.assert_array_equal(rp.items, rr.items)
    np.testing.assert_array_equal(rp.sides, rr.sides)
    np.testing.assert_array_equal(rp.residual.sums, rr.residual.sums)
    np.testing.assert_array_equal(rp.residual.checks, rr.residual.checks)
    np.testing.assert_array_equal(rp.residual.counts, rr.residual.counts)
