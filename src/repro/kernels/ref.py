"""Pure-jnp oracles for the Pallas kernels (no Pallas, no tricks).

XOR-scatter has no native jnp primitive, so the oracle goes through bit
parity: unpack words to bits, segment-sum by target index, mod 2, repack.
Slow but obviously correct; every kernel test compares against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.mapping import _jump_j

from .common import checksum_and_seed


def map_indices_ref(items, *, K: int, m: int, nbytes: int, key):
    """The ``map_indices`` kernel's chains and checksums in plain jnp."""
    chk_hi, chk_lo, h, l = checksum_and_seed(items, key, nbytes)
    return chains_ref(h, l, K=K, m=m), jnp.stack([chk_hi, chk_lo], axis=1)


def chains_ref(h, l, *, K: int, m):
    """(n,) uint32 seed pairs -> (n, K) int32: the first K mapped indices
    of each chain, saturated at ``m`` (which may be traced)."""
    n = h.shape[0]
    msat = jnp.asarray(m, jnp.int32)     # m may be traced (peel stages)

    def step(k, carry):     # rolled: compiles in a fraction of the time
        idx, h, l, out = carry
        out = jax.lax.dynamic_update_slice(out, idx[:, None], (0, k))
        nidx, h, l = _jump_j(idx, h, l)
        return jnp.minimum(nidx, msat), h, l, out

    *_, idxs = jax.lax.fori_loop(
        0, K, step, (jnp.zeros(n, jnp.int32), h, l,
                     jnp.zeros((n, K), jnp.int32)))
    return idxs


def _unpack_bits(x):
    """(n, W) uint32 -> (n, W*32) uint8 of 0/1.

    uint8 segment sums wrap mod 256, which keeps their parity: a quarter
    of the memory of int32 bits for the same XOR.
    """
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (x[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(x.shape[0], -1).astype(jnp.uint8)


def _pack_bits(b, W):
    """(m, W*32) 0/1 -> (m, W) uint32."""
    b = b.reshape(b.shape[0], W, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(b << shifts, axis=-1, dtype=jnp.uint32)


def iblt_encode_ref(items, idxs, chks, *, m: int):
    """XOR-scatter via bit-parity segment sums."""
    n, L = items.shape
    K = idxs.shape[1]
    flat = idxs.reshape(-1)
    valid = (flat < m).astype(jnp.int32)
    rep_items = jnp.repeat(items, K, axis=0)
    rep_chks = jnp.repeat(chks, K, axis=0)
    tgt = jnp.where(flat < m, flat, m)
    # rows past m target the dropped segment
    bits_i = _unpack_bits(rep_items)
    bits_c = _unpack_bits(rep_chks)
    seg_i = jax.ops.segment_sum(bits_i, tgt, num_segments=m + 1)[:m]
    seg_c = jax.ops.segment_sum(bits_c, tgt, num_segments=m + 1)[:m]
    counts = jax.ops.segment_sum(valid, tgt, num_segments=m + 1)[:m]
    sums = _pack_bits(seg_i % 2, L)
    checks = _pack_bits(seg_c % 2, 2)
    return sums, checks, counts[:, None]


def iblt_apply_ref(items, idxs, chks, sides, *, m, m_out: int | None = None):
    """Signed XOR-scatter oracle for the peel kernel's chain removal.

    Like :func:`iblt_encode_ref` but counts accumulate ``sides`` (int32,
    0 disables a row) instead of +1, and the segment count ``m_out`` may
    exceed the true ``m`` (rows [m, m_out) stay zero) so the caller can keep
    tile-padded symbol state.  ``m`` may be a traced scalar.
    """
    n, L = items.shape
    K = idxs.shape[1]
    if m_out is None:
        m_out = int(m)
    flat = idxs.reshape(-1)
    valid = (flat < m).astype(jnp.int32)
    rep_items = jnp.repeat(items, K, axis=0)
    rep_chks = jnp.repeat(chks, K, axis=0)
    rep_sides = jnp.repeat(sides.astype(jnp.int32), K)
    tgt = jnp.where(flat < m, flat, m_out)
    # rows past m target the dropped segment
    bits_i = _unpack_bits(rep_items)
    bits_c = _unpack_bits(rep_chks)
    seg_i = jax.ops.segment_sum(bits_i, tgt, num_segments=m_out + 1)[:m_out]
    seg_c = jax.ops.segment_sum(bits_c, tgt, num_segments=m_out + 1)[:m_out]
    counts = jax.ops.segment_sum(valid * rep_sides, tgt,
                                 num_segments=m_out + 1)[:m_out]
    sums = _pack_bits(seg_i % 2, L)
    checks = _pack_bits(seg_c % 2, 2)
    return sums, checks, counts[:, None]
