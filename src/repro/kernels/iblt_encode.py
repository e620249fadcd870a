"""Pallas TPU kernel: coded-symbol accumulation (the XOR hot loop).

Part 2 of the encoder (paper §7.2 shows XOR-summing dominates compute).
TPUs have no scatter-XOR, so the Go design (heap + pointer-chased XOR into
one symbol at a time) is replaced by dense VPU work:

  grid (m_blocks, n_blocks) — n innermost so each (BM, L) output tile stays
  resident in VMEM while every item block streams past it once.  Within an
  item block, a loop over chunks of ``_CHUNK`` items builds the chunk's
  (CH, BM) mask — one compare of each mapped-index column against the
  tile's symbol iota — and XOR-reduces the masked items over the chunk with
  a log2(CH) halving tree.

One kernel serves the encoder and the peel decoder's chain removal: counts
accumulate a signed weight per item (+1 to encode, the item's side to
remove it).  The prefix length ``m`` is a scalar-prefetch operand, not a
compile-time constant, so one compiled kernel serves every prefix length
in a tile bucket.

VMEM working set (v5e scoped limit 16 MiB): double-buffered input blocks
items (BN·L) + idx (BN·K) + chks/sides (BN·3) words, the (BM, L+3) output
tiles, and per chunk the (CH, BM) mask plus the (CH, BM, L) masked product.
L sits on the 128-lane axis, so the product takes CH·BM·128·4 B = 1 MiB at
CH=8, BM=256 for any L ≤ 128.  Materialising the whole block's (BN, K, BM)
compare and (BN, BM, L) product instead needs 28 MiB at BN=BM=256, K=65.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import pallas_interpret

_CHUNK = 8   # items per XOR tree: one sublane tile


def _tree_xor(v):
    """XOR-reduce axis 0 of (B, ...) — B a power of two — in log2(B) steps."""
    b = v.shape[0]
    while b > 1:
        b //= 2
        v = v[:b] ^ v[b:2 * b]
    return v[0]


def _kernel(m_ref, items_ref, idx_ref, chk_ref, wt_ref, sums_ref, checks_ref,
            counts_ref, *, block_m: int):
    i = pl.program_id(0)   # symbol tile
    j = pl.program_id(1)   # item block (innermost: accumulation)

    @pl.when(j == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        checks_ref[...] = jnp.zeros_like(checks_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    bn, K = idx_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, block_m), 1) + \
        i * block_m
    live = lane < m_ref[0]          # pad index m (and rows past m) map nowhere

    def chunk(c, acc):
        sums, checks, counts = acc
        rows = pl.ds(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK)
        idxs = idx_ref[rows, :]                          # (CH, K) int32
        # a chain is strictly increasing, so an item hits a symbol at most
        # once among its K slots: OR-ing the K column compares is exact
        hit = idxs[:, 0:1] == lane
        for k in range(1, K):
            hit = hit | (idxs[:, k:k + 1] == lane)
        hit = hit & live                                 # (CH, BM)
        counts = counts + jnp.sum(hit.astype(jnp.int32) * wt_ref[rows, :],
                                  axis=0)[:, None]
        hit_u = hit.astype(jnp.uint32)[:, :, None]
        sums = sums ^ _tree_xor(hit_u * items_ref[rows, :][:, None, :])
        checks = checks ^ _tree_xor(hit_u * chk_ref[rows, :][:, None, :])
        return sums, checks, counts

    sums, checks, counts = jax.lax.fori_loop(
        0, bn // _CHUNK, chunk,
        (sums_ref[...], checks_ref[...], counts_ref[...]))
    sums_ref[...] = sums
    checks_ref[...] = checks
    counts_ref[...] = counts


def iblt_apply(items, idxs, chks, sides, *, m, m_out: int | None = None,
               block_m: int = 256, block_n: int = 256,
               interpret: bool = True):
    """Signed coded-symbol delta of ``items`` over their mapped chains.

    items (n, L) uint32, idxs (n, K) int32 (an index ≥ m maps nowhere),
    chks (n, 2) uint32, sides (n,) int32 -> (sums (m', L) uint32,
    checks (m', 2) uint32, counts (m', 1) int32) with m' = ``m_out``, by
    default m rounded up to block_m.  ``m`` may be traced when ``m_out``
    is given.  The peel decoder XORs the sums/checks delta into its symbol
    state and *subtracts* the counts delta (removal = encode with negated
    sign).
    """
    n, L = items.shape
    K = idxs.shape[1]
    assert n % block_n == 0 and block_n % _CHUNK == 0, (n, block_n)
    if m_out is None:
        m_out = -(-int(m) // block_m) * block_m
    assert m_out % block_m == 0, (m_out, block_m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m_out // block_m, n // block_n),
        in_specs=[pl.BlockSpec((block_n, L), lambda i, j, m: (j, 0)),
                  pl.BlockSpec((block_n, K), lambda i, j, m: (j, 0)),
                  pl.BlockSpec((block_n, 2), lambda i, j, m: (j, 0)),
                  pl.BlockSpec((block_n, 1), lambda i, j, m: (j, 0))],
        out_specs=[pl.BlockSpec((block_m, L), lambda i, j, m: (i, 0)),
                   pl.BlockSpec((block_m, 2), lambda i, j, m: (i, 0)),
                   pl.BlockSpec((block_m, 1), lambda i, j, m: (i, 0))])
    return pl.pallas_call(
        functools.partial(_kernel, block_m=block_m),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((m_out, L), jnp.uint32),
                   jax.ShapeDtypeStruct((m_out, 2), jnp.uint32),
                   jax.ShapeDtypeStruct((m_out, 1), jnp.int32)],
        interpret=pallas_interpret(interpret),
    )(jnp.asarray(m, jnp.int32).reshape(1), items, idxs, chks,
      sides.astype(jnp.int32)[:, None])


def iblt_encode(items, idxs, chks, *, m: int, block_m: int = 256,
                block_n: int = 256, interpret: bool = True):
    """Accumulate coded symbols: :func:`iblt_apply` with every weight +1.

    items (n, L) uint32, idxs (n, K) int32 (pad = m), chks (n, 2) uint32
    -> (sums (m', L) uint32, checks (m', 2) uint32, counts (m', 1) int32)
    with m' = m rounded up to block_m (ops.py trims).
    """
    return iblt_apply(items, idxs, chks, jnp.ones(items.shape[0], jnp.int32),
                      m=m, block_m=block_m, block_n=block_n,
                      interpret=interpret)
