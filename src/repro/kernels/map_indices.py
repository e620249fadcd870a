"""Pallas TPU kernel: per-item keyed hash + mapped-index chain.

The paper's encoder hot loop has two parts; this kernel is part 1: for a
block of items compute (a) the SipHash-2-4 checksum, (b) the mapping-PRNG
seed, and (c) the first K skip-sampled mapped indices (§4.2).  Everything is
elementwise over the item lane — shifts, u32 adds, one rsqrt per jump — pure
VPU work with zero cross-lane traffic, which is why the chain generator is a
lane-parallel kernel rather than the Go heap.

Layout: items (n, L) uint32 in VMEM blocks of (BN, L); outputs idx (n, K)
int32, checksum (n, 2) uint32 (hi, lo).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.mapping import _JUMP_CAP, _jump_j

from .common import checksum_and_seed, pallas_interpret

_IDX_SAT = int(_JUMP_CAP) - 1   # idx + clamped jump stays below 2**31


def _kernel(items_ref, idx_ref, chk_ref, *, K: int, nbytes: int, key):
    items = items_ref[...]                       # (BN, L) uint32
    chk_hi, chk_lo, h, l = checksum_and_seed(items, key, nbytes)
    chk_ref[...] = jnp.stack([chk_hi, chk_lo], axis=1)
    bn = items.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, K), 1)

    # a rolled loop: unrolled, the K-step chain alone takes the TPU
    # compiler ~45 s at K=65
    def step(k, carry):
        idx, h, l, out = carry
        out = jnp.where(col == k, idx[:, None], out)
        nidx, h, l = _jump_j(idx, h, l)
        # saturate past any prefix length (stops int32 overflow); the
        # wrapper then saturates at m, which gives the same chain
        return jnp.minimum(nidx, jnp.int32(_IDX_SAT)), h, l, out

    idx0 = jnp.zeros(bn, jnp.int32)
    *_, out = jax.lax.fori_loop(0, K, step,
                                (idx0, h, l, jnp.zeros((bn, K), jnp.int32)))
    idx_ref[...] = out


def map_indices(items, *, K: int, m, nbytes: int, key,
                block_n: int = 256, interpret: bool = True):
    """items (n, L) uint32 -> (idx (n, K) int32, checksum (n, 2) uint32).

    Indices saturate at ``m`` (the pad that maps nowhere).  ``m`` is
    applied outside the kernel, so it may be traced and one compiled
    kernel serves every prefix length.  n must be a multiple of block_n
    (ops.py pads).  ``interpret=True`` runs the kernel in the TPU
    interpreter on CPU; on TPU pass interpret=False and jit the caller.
    """
    n, L = items.shape
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    kernel = functools.partial(_kernel, K=K, nbytes=nbytes, key=key)
    idx, chk = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, L), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_n, K), lambda i: (i, 0)),
                   pl.BlockSpec((block_n, 2), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, K), jnp.int32),
                   jax.ShapeDtypeStruct((n, 2), jnp.uint32)],
        interpret=pallas_interpret(interpret),
    )(items)
    return jnp.minimum(idx, jnp.asarray(m, jnp.int32)), chk
