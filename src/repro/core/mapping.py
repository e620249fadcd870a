"""The Rateless IBLT mapping (paper §4.1–4.2).

A source symbol is mapped to coded-symbol index ``i`` with probability
``ρ(i) = 1/(1 + αi)``, α = 0.5.  Every symbol maps to index 0 (ρ(0)=1).
Subsequent mapped indices are produced by *skip sampling*: from index ``i``
jump ``g = max(1, ⌈C⁻¹(r)⌉)`` with ``C⁻¹(r) ≈ (1.5+i)·((1−r)^{−1/2} − 1)``
and ``r ∈ [0,1)`` drawn from an xorshift64 PRNG seeded by the symbol's keyed
hash.  Constant cost per mapped index, O(log m) mapped indices in the first
``m`` — the property that gives Rateless IBLT its O(ℓ·log d) costs.

Determinism contract: the host (numpy) and device (JAX uint32-pair) chains
produce *identical* index sequences.  The host's real arithmetic is IEEE-754
float32; the device reproduces its square root and reciprocal in exact
integer arithmetic and keeps only a subtract, a multiply and a ceil in
float32, which every backend rounds the same way.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .hashing import map_key, siphash24, siphash24_pair

ALPHA = 0.5

_U64 = np.uint64


def rho(i):
    """Mapping probability ρ(i) = 1/(1 + αi)."""
    return 1.0 / (1.0 + ALPHA * np.asarray(i, dtype=np.float64))


def expected_degree(m: int) -> float:
    """E[#mapped indices among the first m] = Σ_{i<m} ρ(i)."""
    i = np.arange(m, dtype=np.float64)
    return float(np.sum(1.0 / (1.0 + ALPHA * i)))


def kmax(m: int) -> int:
    """Static bound on mapped-index count within the first m coded symbols.

    The count is a sum of independent Bernoulli(ρ(i)) with mean μ ≈ 2·ln m;
    a Bernstein tail at μ + 8√μ + 10 is ≪ 1e-12.  Used by the fixed-shape
    device encoder; the host encoder walks exact chains and never truncates.
    """
    mu = 2.0 * math.log(m + 2.0)
    return int(math.ceil(mu + 8.0 * math.sqrt(mu) + 10.0))


# ---------------------------------------------------------------------------
# PRNG: xorshift64 seeded with the keyed 64-bit item hash (forced nonzero).
# ---------------------------------------------------------------------------
def _xs64_np(s: np.ndarray) -> np.ndarray:
    s = s ^ (s << _U64(13))
    s = s ^ (s >> _U64(7))
    s = s ^ (s << _U64(17))
    return s


def map_seeds(words: np.ndarray, key, nbytes: int | None = None) -> np.ndarray:
    """Per-item mapping-PRNG seed (uint64, nonzero) from the session key."""
    s = siphash24(words, map_key(key), nbytes)
    return s | _U64(1)


def _jump_np(idx: np.ndarray, state: np.ndarray):
    """One skip-sampling step (vectorized).  idx int64, state uint64."""
    state = _xs64_np(state)
    rbits = (state >> _U64(40)).astype(np.float32)        # top 24 bits
    r = rbits * np.float32(2.0 ** -24)                    # uniform [0,1)
    t = np.float32(1.0) / np.sqrt(np.float32(1.0) - r)    # (1-r)^(-1/2)
    u = t - np.float32(1.0)
    f = np.float32(1.5) + idx.astype(np.float32)
    g = np.ceil(f * u).astype(np.int64)
    g = np.maximum(g, 1)
    return idx + g, state


def advance_np(idx, state, limit):
    """Advance chains until every idx >= limit.  Yields (active_sel, idx)
    batches for the encoder.  idx/state are modified in place."""
    while True:
        active = np.flatnonzero(idx < limit)
        if active.size == 0:
            return
        yield active, idx[active]
        nidx, nstate = _jump_np(idx[active], state[active])
        idx[active] = nidx
        state[active] = nstate


def walk_chains(nxt, state, hi, visit=None):
    """Advance every chain position in place until ``nxt >= hi``.

    ``visit(live, idx)`` is called per round with the still-walking row
    selector and their current mapped indices (e.g. to XOR-accumulate a
    removal).  Returns the concatenation of all visited indices — the rows
    a decoder must re-test for purity.
    """
    touched = []
    while True:
        live = np.flatnonzero(nxt < hi)
        if live.size == 0:
            break
        idx = nxt[live]
        touched.append(idx.copy())
        if visit is not None:
            visit(live, idx)
        nn, ns = _jump_np(idx, state[live])
        nxt[live] = nn
        state[live] = ns
    return np.concatenate(touched) if touched else np.zeros(0, np.int64)


def item_indices_np(seed: int, m: int) -> np.ndarray:
    """All mapped indices < m for one item (exact chain).  int64 array."""
    out = []
    idx = np.zeros(1, dtype=np.int64)
    state = np.array([seed], dtype=np.uint64)
    while idx[0] < m:
        out.append(int(idx[0]))
        idx, state = _jump_np(idx, state)
    return np.asarray(out, dtype=np.int64)


def indices_matrix_np(seeds: np.ndarray, m: int, K: int | None = None) -> np.ndarray:
    """(n,) seeds -> (n, K) mapped indices < m, padded with m (vectorized)."""
    if K is None:
        K = kmax(m)
    n = seeds.shape[0]
    out = np.full((n, K), m, dtype=np.int64)
    idx = np.zeros(n, dtype=np.int64)
    state = seeds.astype(np.uint64).copy()
    for k in range(K):
        live = idx < m
        out[live, k] = idx[live]
        if not live.any():
            break
        idx, state = _jump_np(idx, state)
    return out


# ---------------------------------------------------------------------------
# Device path (JAX): identical chain on (hi, lo) uint32 pairs.
# ---------------------------------------------------------------------------
def _xs64_pair(h, l):
    # s ^= s << 13
    nh = h ^ ((h << jnp.uint32(13)) | (l >> jnp.uint32(19)))
    nl = l ^ (l << jnp.uint32(13))
    h, l = nh, nl
    # s ^= s >> 7
    nh = h ^ (h >> jnp.uint32(7))
    nl = l ^ ((l >> jnp.uint32(7)) | (h << jnp.uint32(25)))
    h, l = nh, nl
    # s ^= s << 17
    nh = h ^ ((h << jnp.uint32(17)) | (l >> jnp.uint32(15)))
    nl = l ^ (l << jnp.uint32(17))
    return nh, nl


def map_seeds_pair(words, key, nbytes: int | None = None):
    hi, lo = siphash24_pair(words, map_key(key), nbytes)
    return hi, lo | jnp.uint32(1)


_JUMP_CAP = 2.0 ** 30     # device jumps are clamped here; needs m < 2**30


def _sqrt_rn(J):
    """``(Q, fb)`` with ``Q·2**-fb`` = float32 round-to-nearest of
    ``sqrt(J)``, for int32 ``J`` in [1, 2**24].

    ``fb`` is the number of fraction bits float32 leaves beside the
    integer part of ``sqrt(J)``; ``Q = RN(sqrt(J·4**fb))`` comes from the
    binary digit-by-digit square root of the 48-bit ``J·4**fb``, fed two
    bits at a time.  Root and remainder stay below 2**27: exact int32.
    """
    e = (jax.lax.bitcast_convert_type(J.astype(jnp.float32), jnp.int32)
         >> 23) - 127                                  # floor(log2 J)
    fb = 23 - (e >> 1)

    def digit(i, carry):
        root, rem = carry
        sh = 2 * (23 - i) - 2 * fb                     # bit pair of J·4**fb
        pair = jnp.where(sh >= 0, (J >> jnp.maximum(sh, 0)) & 3, 0)
        rem = (rem << 2) | pair
        trial = (root << 2) | 1
        take = rem >= trial
        return ((root << 1) | take.astype(jnp.int32),
                jnp.where(take, rem - trial, rem))

    zero = jnp.zeros_like(J)
    root, rem = jax.lax.fori_loop(0, 24, digit, (zero, zero))
    # no ties: rem > root  <=>  J·4**fb >= root² + root + 1
    return root + (rem > root).astype(jnp.int32), fb


def _recip_rn(Q):
    """``RN(2**47 / Q)`` for int32 ``Q`` in [2**23, 2**24], by binary long
    division; the remainder stays below 2**25.  No ties: 2**48 is not an
    odd multiple of Q."""
    q = (Q <= (1 << 23)).astype(jnp.int32)
    rem = (1 << 23) - q * Q

    def bit(_, carry):
        q, rem = carry
        rem = rem << 1
        take = rem >= Q
        return ((q << 1) | take.astype(jnp.int32),
                jnp.where(take, rem - Q, rem))

    q, rem = jax.lax.fori_loop(0, 24, bit, (q, rem))
    return q + ((rem << 1) > Q).astype(jnp.int32)


def _pow2(e):
    """float32 ``2**e`` for int32 ``e`` in the normal range."""
    return jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)


def _jump_j(idx, h, l):
    """One skip-sampling step on device.  idx int32, (h, l) uint32 state.

    Equal to :func:`_jump_np` for every index below ``m < 2**30``.
    ``t = 1/sqrt(1 - r)`` is computed in exact integer arithmetic with the
    roundings of float32 (``_sqrt_rn``, ``_recip_rn``): compilers replace
    float32 ``1/sqrt`` by approximations (XLA's CPU ``rsqrt`` is 1 ulp off
    for 29% of the inputs), and one ulp moves a chain.  ``f·u`` reaches
    ~4096·m, past int32, and backends convert out-of-range floats
    differently, so the jump is clamped to 2**30 in float first: a clamped
    jump still lands at or past ``m``, where the callers saturate the chain.
    """
    h, l = _xs64_pair(h, l)
    # 1 - r = J·2**-24 with r = (top 24 state bits)·2**-24; then
    # t = 2**12 / RN(sqrt(J)) = 2**12 · RN(2**47/Q) · 2**(fb-47)
    J = (1 << 24) - (h >> jnp.uint32(8)).astype(jnp.int32)
    Q, fb = _sqrt_rn(J)
    t = _recip_rn(Q).astype(jnp.float32) * _pow2(fb - 35)
    u = t - jnp.float32(1.0)
    f = jnp.float32(1.5) + idx.astype(jnp.float32)
    g = jnp.minimum(jnp.ceil(f * u), jnp.float32(_JUMP_CAP)).astype(jnp.int32)
    g = jnp.maximum(g, 1)
    return idx + g, h, l


def indices_matrix_j(seed_hi, seed_lo, m: int, K: int | None = None):
    """Device chain: (n,) uint32 seeds -> (n, K) int32 indices, pad = m."""
    if K is None:
        K = kmax(m)
    n = seed_hi.shape[0]
    idx = jnp.zeros(n, dtype=jnp.int32)
    h, l = seed_hi, seed_lo
    cols = []
    for _ in range(K):
        cols.append(idx)
        nidx, h, l = _jump_j(idx, h, l)
        # saturate at m: stops the chain (and prevents int32 overflow of
        # the ever-growing jump sizes once past the window).
        idx = jnp.minimum(nidx, jnp.int32(m))
    return jnp.stack(cols, axis=1)
