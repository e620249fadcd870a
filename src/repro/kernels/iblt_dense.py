"""Chain removal as one matrix product: the batched peel's apply.

A peel wave removes at most ``cap`` recovered rows from the coded symbols.
Scattering each row's K chain slots (the bit-parity oracle
:func:`repro.kernels.ref.iblt_apply_ref`) unpacks ``n·K`` copies of every
row; here only the ``n`` rows are unpacked, and their chains become an
incidence matrix instead:

  H (n, m_out), ``H[j, s]`` = 1 where symbol ``s < m`` lies on row ``j``'s
  chain — the K chain columns compared against a symbol iota and OR-ed, as
  in the Pallas ``iblt_apply``: a chain is strictly increasing, so it hits
  a symbol at most once and the OR is exact.

One product on the MXU then gives every symbol's delta at once,

  Hᵀ · [item bits | checksum bits | side]    bf16 → float32,

the low bit of a bit column's sum being the XOR of the rows that hit the
symbol and the side column's sum the signed count delta.  Every operand is
0 or ±1 and a sum is at most ``n`` < 2^24, so bfloat16 operands with
float32 accumulation are exact and the result is bit-identical to the
oracle.  (On a v5e the compare that builds H, fused into the product,
takes nearly all of the time, so int8 operands would gain nothing there;
XLA's CPU backend multiplies bfloat16 several times faster than int8.)

Rows wider than :data:`TILE_WORDS` words are taken one word tile at a
time: a tile's bits are unpacked, multiplied and packed back before the
next, so the temporaries grow with ``n × TILE_WORDS`` and not with the
item width (a 128-KiB row unpacks to a million bit columns).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .ref import _pack_bits


def _bits(x):
    """(n, W) uint32 -> (n, W*32) bfloat16 of 0/1, bit b of word w at
    w*32+b."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (x[:, :, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(x.shape[0], -1).astype(jnp.bfloat16)


# item words per product; a row of at most this many takes one product
TILE_WORDS = 256


def _product(H, cols):
    """Hᵀ · cols, bf16 operands summed exactly in float32, as int32."""
    acc = jax.lax.dot_general(H, cols, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return acc.astype(jnp.int32)


def iblt_apply_dense(items, idxs, chks, sides, *, m, m_out: int):
    """Signed coded-symbol delta of ``items`` over their mapped chains.

    items (n, L) uint32, idxs (n, K) int32 (an index ≥ m maps nowhere),
    chks (n, 2) uint32, sides (n,) int32 in {-1, 0, 1} -> (sums (m_out, L)
    uint32, checks (m_out, 2) uint32, counts (m_out, 1) int32), rows
    [m, m_out) zero.  ``m`` may be traced.  The result of
    :func:`repro.kernels.ref.iblt_apply_ref` for index lists that hit a
    symbol at most once, as every mapped chain does.
    """
    L = items.shape[1]
    sym = jnp.arange(m_out, dtype=jnp.int32)
    # one elementwise pass over (n, m_out), no (n, K, m_out) compare
    hit = functools.reduce(jnp.logical_or, [idxs[:, k:k + 1] == sym
                                            for k in range(idxs.shape[1])])
    H = (hit & (sym < m)).astype(jnp.bfloat16)                # (n, m_out)
    side = sides.astype(jnp.bfloat16)[:, None]
    if L <= TILE_WORDS:
        acc = _product(H, jnp.concatenate([_bits(items), _bits(chks), side],
                                          axis=1))
        par = acc[:, :32 * (L + 2)] & 1
        sums = _pack_bits(par[:, :32 * L], L)
        checks = _pack_bits(par[:, 32 * L:], 2)
        return sums, checks, acc[:, 32 * (L + 2):]

    acc = _product(H, jnp.concatenate([_bits(chks), side], axis=1))
    checks, counts = _pack_bits(acc[:, :64] & 1, 2), acc[:, 64:]
    T = TILE_WORDS
    trips = L // T

    def tile(items_t):
        return _pack_bits(_product(H, _bits(items_t)) & 1, items_t.shape[1])

    def trip(t, sums):
        lo = t * T
        return jax.lax.dynamic_update_slice(
            sums, tile(jax.lax.dynamic_slice_in_dim(items, lo, T, axis=1)),
            (0, lo))

    sums = jax.lax.fori_loop(0, trips, trip,
                             jnp.zeros((m_out, L), jnp.uint32))
    if L % T:
        sums = sums.at[:, trips * T:].set(tile(items[:, trips * T:]))
    return sums, checks, counts
