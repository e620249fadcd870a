"""Shared device-side hashing for the encode and peel kernels.

Both the mapping kernel (`map_indices`) and the wave-peeling decoder
(`peel`) need the same two keyed hashes of an item block: the SipHash-2-4
checksum (paper §4.3) and the mapping-PRNG seed derived from the tweaked
session key.  Factored here so the encoder and decoder kernels stay
bit-identical by construction.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import map_key, siphash24_pair


def pallas_interpret(interpret: bool):
    """``pallas_call``'s ``interpret`` argument for a kernel flag: the TPU
    interpreter, or compiled.  XLA compiles the generic interpreter's
    program for a multi-step grid of the SipHash kernels in minutes; the
    TPU interpreter's in seconds."""
    return pltpu.InterpretParams() if interpret else False


def checksum_pair(items, key, nbytes: int):
    """(hi, lo) uint32 checksum of an item block ``(..., L)``."""
    return siphash24_pair(items, key, nbytes)


def checksum_and_seed(items, key, nbytes: int):
    """Checksum + mapping-PRNG seed for a block of items.

    Returns ``(chk_hi, chk_lo, seed_hi, seed_lo)`` uint32 arrays; the seed's
    low word is forced odd so the xorshift64 state is never zero — exactly
    the host-side :func:`repro.core.mapping.map_seeds` contract.
    """
    chk_hi, chk_lo = siphash24_pair(items, key, nbytes)
    seed_hi, seed_lo = siphash24_pair(items, map_key(key), nbytes)
    return chk_hi, chk_lo, seed_hi, seed_lo | jnp.uint32(1)
