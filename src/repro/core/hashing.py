"""Keyed 64-bit hashing for Rateless IBLT (paper §4.3).

The paper uses SipHash, a keyed short-input PRF, for the per-symbol
``checksum`` field and (here) to seed the deterministic mapping PRNG.  We
implement SipHash-2-4 twice:

* host path — vectorized numpy over ``uint64`` (CPUs have native u64);
* device path — JAX over ``(hi, lo)`` ``uint32`` lane pairs, because TPUs
  have no 64-bit integer lanes.  Bit-exact with the host path (tested).

Items are fixed-length bit strings stored as little-endian ``uint32`` word
arrays of shape ``(..., L)``; the true byte length feeds SipHash's length
block so different-ℓ reconciliations never alias.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Keys.  A reconciliation session is parameterized by a 128-bit key (paper
# §4.3: secret, coordinated out of band when adversarial workloads matter).
# The checksum PRF and the mapping PRNG must be independent, so we tweak the
# user key with distinct constants for each role.
# ---------------------------------------------------------------------------
DEFAULT_KEY = (0x0706050403020100, 0x0F0E0D0C0B0A0908)
_MAP_TWEAK = (0x9E3779B97F4A7C15, 0xD1B54A32D192ED03)

_U64 = np.uint64


def map_key(key=DEFAULT_KEY):
    """Derive the mapping-PRNG key from the session key."""
    return (key[0] ^ _MAP_TWEAK[0], key[1] ^ _MAP_TWEAK[1])


# ---------------------------------------------------------------------------
# Host path: numpy uint64, vectorized over leading axes.
# ---------------------------------------------------------------------------
def _rotl_np(x, r):
    r = _U64(r)
    return (x << r) | (x >> _U64(64 - int(r)))


def _sipround_np(v0, v1, v2, v3):
    v0 = v0 + v1
    v1 = _rotl_np(v1, 13)
    v1 ^= v0
    v0 = _rotl_np(v0, 32)
    v2 = v2 + v3
    v3 = _rotl_np(v3, 16)
    v3 ^= v2
    v0 = v0 + v3
    v3 = _rotl_np(v3, 21)
    v3 ^= v0
    v2 = v2 + v1
    v1 = _rotl_np(v1, 17)
    v1 ^= v2
    v2 = _rotl_np(v2, 32)
    return v0, v1, v2, v3


def siphash24(words: np.ndarray, key=DEFAULT_KEY, nbytes: int | None = None) -> np.ndarray:
    """SipHash-2-4 of uint32 word arrays ``(..., L)`` -> uint64 ``(...,)``.

    Message = the L little-endian 32-bit words; the final block carries
    ``nbytes & 0xff`` in the top byte per the SipHash spec.
    """
    words = np.asarray(words, dtype=np.uint32)
    if words.ndim == 1:
        words = words[None, :]
        squeeze = True
    else:
        squeeze = False
    lead = words.shape[:-1]
    L = words.shape[-1]
    if nbytes is None:
        nbytes = 4 * L

    k0 = _U64(key[0])
    k1 = _U64(key[1])
    v0 = np.full(lead, k0 ^ _U64(0x736F6D6570736575), dtype=np.uint64)
    v1 = np.full(lead, k1 ^ _U64(0x646F72616E646F6D), dtype=np.uint64)
    v2 = np.full(lead, k0 ^ _U64(0x6C7967656E657261), dtype=np.uint64)
    v3 = np.full(lead, k1 ^ _U64(0x7465646279746573), dtype=np.uint64)

    w64 = words.astype(np.uint64)
    full = L // 2
    for i in range(full):
        m = w64[..., 2 * i] | (w64[..., 2 * i + 1] << _U64(32))
        v3 ^= m
        v0, v1, v2, v3 = _sipround_np(v0, v1, v2, v3)
        v0, v1, v2, v3 = _sipround_np(v0, v1, v2, v3)
        v0 ^= m
    # final block: leftover word (if L odd) + length byte in the top byte.
    b = _U64((nbytes & 0xFF)) << _U64(56)
    if L % 2 == 1:
        b = b | w64[..., L - 1]
    v3 ^= b
    v0, v1, v2, v3 = _sipround_np(v0, v1, v2, v3)
    v0, v1, v2, v3 = _sipround_np(v0, v1, v2, v3)
    v0 ^= b
    v2 ^= _U64(0xFF)
    for _ in range(4):
        v0, v1, v2, v3 = _sipround_np(v0, v1, v2, v3)
    out = v0 ^ v1 ^ v2 ^ v3
    return out[0] if squeeze else out


# ---------------------------------------------------------------------------
# Device path: JAX (hi, lo) uint32 pairs.  TPU-native u64 emulation.
# ---------------------------------------------------------------------------
def _add64(ah, al, bh, bl):
    lo = al + bl
    carry = (lo < al).astype(jnp.uint32)
    hi = ah + bh + carry
    return hi, lo


def _rotl64(h, l, r):
    if r == 32:
        return l, h
    if r > 32:
        h, l = l, h
        r -= 32
    rr = jnp.uint32(r)
    ri = jnp.uint32(32 - r)
    nh = (h << rr) | (l >> ri)
    nl = (l << rr) | (h >> ri)
    return nh, nl


def _sipround_j(v):
    (v0h, v0l), (v1h, v1l), (v2h, v2l), (v3h, v3l) = v
    v0h, v0l = _add64(v0h, v0l, v1h, v1l)
    v1h, v1l = _rotl64(v1h, v1l, 13)
    v1h, v1l = v1h ^ v0h, v1l ^ v0l
    v0h, v0l = _rotl64(v0h, v0l, 32)
    v2h, v2l = _add64(v2h, v2l, v3h, v3l)
    v3h, v3l = _rotl64(v3h, v3l, 16)
    v3h, v3l = v3h ^ v2h, v3l ^ v2l
    v0h, v0l = _add64(v0h, v0l, v3h, v3l)
    v3h, v3l = _rotl64(v3h, v3l, 21)
    v3h, v3l = v3h ^ v0h, v3l ^ v0l
    v2h, v2l = _add64(v2h, v2l, v1h, v1l)
    v1h, v1l = _rotl64(v1h, v1l, 17)
    v1h, v1l = v1h ^ v2h, v1l ^ v2l
    v2h, v2l = _rotl64(v2h, v2l, 32)
    return (v0h, v0l), (v1h, v1l), (v2h, v2l), (v3h, v3l)


def _const_pair(x):
    return (jnp.uint32((x >> 32) & 0xFFFFFFFF), jnp.uint32(x & 0xFFFFFFFF))


def _word(words, j):
    """``words[..., j]`` for a traced ``j``, as a masked sum over the word
    axis (the TPU kernels have no dynamic lane index)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, words.shape, words.ndim - 1)
    w = jax.lax.bitcast_convert_type(words, jnp.int32)
    w = jnp.sum(jnp.where(lane == j, w, 0), axis=-1)
    return jax.lax.bitcast_convert_type(w, jnp.uint32)


# 8-byte blocks compressed per loop trip of the XLA hash: fewer trips, and
# one copy of a contiguous slab per trip
BLOCKS_PER_TRIP = 8


def _sip_init(key, lead):
    k0h, k0l = _const_pair(key[0])
    k1h, k1l = _const_pair(key[1])
    c0, c1, c2, c3 = (_const_pair(x) for x in (
        0x736F6D6570736575, 0x646F72616E646F6D, 0x6C7967656E657261, 0x7465646279746573))
    return tuple((jnp.broadcast_to(h, lead), jnp.broadcast_to(lo, lead))
                 for h, lo in ((k0h ^ c0[0], k0l ^ c0[1]),
                               (k1h ^ c1[0], k1l ^ c1[1]),
                               (k0h ^ c2[0], k0l ^ c2[1]),
                               (k1h ^ c3[0], k1l ^ c3[1])))


def _sip_block(v, mh, ml):
    """Compress one 8-byte block ``(mh, ml)`` into the state."""
    v = list(v)
    v[3] = (v[3][0] ^ mh, v[3][1] ^ ml)
    v = list(_sipround_j(tuple(v)))
    v = list(_sipround_j(tuple(v)))
    v[0] = (v[0][0] ^ mh, v[0][1] ^ ml)
    return tuple(v)


def _sip_final(v, odd, nbytes: int, lead):
    """The last block (the odd word, if any, and the length byte) and the
    four finalization rounds -> (hi, lo)."""
    bh = jnp.broadcast_to(jnp.uint32((nbytes & 0xFF) << 24), lead)
    bl = jnp.broadcast_to(jnp.uint32(0) if odd is None else odd, lead)
    v = list(_sip_block(v, bh, bl))
    v[2] = (v[2][0], v[2][1] ^ jnp.uint32(0xFF))
    for _ in range(4):
        v = list(_sipround_j(tuple(v)))
    hi = v[0][0] ^ v[1][0] ^ v[2][0] ^ v[3][0]
    lo = v[0][1] ^ v[1][1] ^ v[2][1] ^ v[3][1]
    return hi, lo


def siphash24_pair(words, key=DEFAULT_KEY, nbytes: int | None = None):
    """JAX SipHash-2-4 of uint32 words ``(..., L)`` -> (hi, lo) uint32 pair.

    Bit-exact with :func:`siphash24` (hi = result >> 32, lo = low word),
    O(L) per row: the 8-byte blocks are moved to a leading axis once and
    a rolled loop reads :data:`BLOCKS_PER_TRIP` of them per trip (rolled:
    unrolled over every block, the TPU compiler takes ~25 s per hash of a
    92-byte item).  For XLA; inside a Pallas kernel use
    :func:`siphash24_pair_kernel`.
    """
    words = jnp.asarray(words, dtype=jnp.uint32)
    L = words.shape[-1]
    if nbytes is None:
        nbytes = 4 * L
    lead = words.shape[:-1]
    v = _sip_init(key, lead)
    nb = L // 2
    # (..., nb, 2) -> (nb, 2, ...): block i is one contiguous slab
    blocks = jnp.moveaxis(words[..., :2 * nb].reshape(lead + (nb, 2)),
                          (-2, -1), (0, 1))
    if nb:
        # a trip reads the largest whole share of the blocks up to
        # BLOCKS_PER_TRIP, and no block is compressed outside the loop
        # (XLA's CPU compiler takes minutes over a few blocks unrolled
        # beside the mapping chain's loop)
        k = max(j for j in range(1, BLOCKS_PER_TRIP + 1) if nb % j == 0)

        def trip(v, slab):          # slab (k, 2, ...)
            for j in range(k):
                v = _sip_block(v, slab[j, 1], slab[j, 0])
            return v, None

        v, _ = jax.lax.scan(trip, v, blocks.reshape(
            (nb // k, k) + blocks.shape[1:]))
    return _sip_final(v, words[..., L - 1] if L % 2 else None, nbytes, lead)


def siphash24_pair_kernel(words, key=DEFAULT_KEY, nbytes: int | None = None):
    """:func:`siphash24_pair` for the body of a Pallas TPU kernel, which has
    no dynamic lane index: each block is read as a masked sum over the
    word axis, O(L²) per row, fit only for the narrow rows those kernels
    load whole."""
    words = jnp.asarray(words, dtype=jnp.uint32)
    L = words.shape[-1]
    if nbytes is None:
        nbytes = 4 * L
    lead = words.shape[:-1]

    def block(i, v):
        return _sip_block(v, _word(words, 2 * i + 1), _word(words, 2 * i))

    # rolled, as in siphash24_pair
    v = jax.lax.fori_loop(0, L // 2, block, _sip_init(key, lead))
    return _sip_final(v, words[..., L - 1] if L % 2 else None, nbytes, lead)


# ---------------------------------------------------------------------------
# Byte <-> word helpers.
# ---------------------------------------------------------------------------
def words_per_item(nbytes: int) -> int:
    return (nbytes + 3) // 4


def bytes_to_words(items, nbytes: int) -> np.ndarray:
    """(n, nbytes) uint8 (or list[bytes]) -> (n, L) uint32 little-endian."""
    if isinstance(items, (list, tuple)):
        items = np.frombuffer(b"".join(items), dtype=np.uint8).reshape(len(items), nbytes)
    items = np.asarray(items, dtype=np.uint8)
    n = items.shape[0]
    L = words_per_item(nbytes)
    pad = 4 * L - nbytes
    if pad:
        items = np.concatenate([items, np.zeros((n, pad), dtype=np.uint8)], axis=1)
    return items.reshape(n, L, 4).view(np.uint32).reshape(n, L).copy()


def words_to_bytes(words: np.ndarray, nbytes: int) -> np.ndarray:
    words = np.ascontiguousarray(np.asarray(words, dtype=np.uint32))
    n = words.shape[0]
    if n == 0:
        return np.zeros((0, nbytes), dtype=np.uint8)
    raw = words.view(np.uint8).reshape(n, -1)
    return raw[:, :nbytes].copy()
