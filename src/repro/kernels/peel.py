"""Device-side wave-peeling decoder (paper §3 peeling as dense XLA work).

Host peeling walks a sparse graph one pure symbol at a time; on TPU we
restate each belief-propagation round as three dense, fixed-shape stages:

1. **purity scan** — every coded symbol's sum is re-keyed (SipHash-2-4,
   O(L) a row) and compared with the stored checksum: ``±1`` where the
   symbol holds exactly one source symbol, ``0`` elsewhere.
2. **compaction + dedupe** — pure rows are gathered into a fixed ``cap``-row
   buffer (``jnp.nonzero(..., size=cap)``), deduped pairwise by checksum
   within the wave and against the already-recovered buffer.  The same item
   being pure at several indices at once is the common case near the end of
   a decode.
3. **chain removal** — recovered items re-derive their mapped-index chains
   from their map seeds and are XOR-ed out of every position, with a signed
   count update (``counts -= Σ mask·side``).

Every device decode runs :func:`peel_waves_batched`: the wave ``vmap``-ed
over a leading **unit axis** — U ≥ 1 independent decodes, ragged prefix
lengths as data, one compiled program per shape bucket (see
``ops.decode_device_batched``).  Its chain removal is one matrix product
over the wave's candidate rows
(:func:`repro.kernels.iblt_dense.iblt_apply_dense`).  The waves iterate to
a fixed point — ``jax.lax.while_loop`` when the whole program is jitted
for TPU, a plain Python loop elsewhere.  Every shape is static: symbols
are padded to a tile bucket, per-wave compaction holds ``cap`` rows, and
the recovered-item buffer holds ``max_diff`` rows — a wave that would
overflow it leaves the unit's state untouched and raises its ``overflow``
flag so the caller can fall back to the exact host decoder.

:func:`peel_waves` is the oracle the tests hold it to: one unit, a Python
loop over jitted stages, and chain removal as the bit-parity scatter of
:func:`repro.kernels.ref.iblt_apply_ref`; each unit's waves are its waves,
bit for bit.  A unit was originally one shard of a sharded session;
through ``repro.protocol.engine`` it is any (peer, shard) pair in a shape
bucket, so N concurrent peers cost one dispatch per tick, not N.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.hashing import siphash24_pair
from repro.core.mapping import map_seeds_pair
from repro.trace import APPLY_SCOPE, HASH_SCOPE

from .iblt_dense import iblt_apply_dense
from .ref import chains_ref, iblt_apply_ref


# ---------------------------------------------------------------------------
# Stage 1: purity scan.
# ---------------------------------------------------------------------------
def _purity_body(sums, checks, counts, *, key, nbytes: int):
    """(mp, L) sums, (mp, 2) checks, (mp, 1) counts -> (mp,) int32 side.

    ``+1`` / ``-1`` where the symbol is pure (checksum matches the keyed
    hash of its sum and it is non-empty), ``0`` otherwise.
    """
    with jax.named_scope(HASH_SCOPE):
        h_hi, h_lo = siphash24_pair(sums, key, nbytes)
    cnt = counts[:, 0]
    pure = (h_hi == checks[:, 0]) & (h_lo == checks[:, 1]) & (cnt != 0)
    side = jnp.where(cnt > 0, jnp.int32(1), jnp.int32(-1))
    return jnp.where(pure, side, jnp.int32(0))


# ---------------------------------------------------------------------------
# The wave loop.
# ---------------------------------------------------------------------------
class PeelState(NamedTuple):
    sums: jax.Array        # (mp, L) uint32 — residual symbol sums
    checks: jax.Array      # (mp, 2) uint32 — residual checksums (hi, lo)
    counts: jax.Array      # (mp, 1) int32  — residual signed counts
    rec_items: jax.Array   # (D, L) uint32  — recovered source symbols
    rec_checks: jax.Array  # (D, 2) uint32  — their checksums
    rec_seeds: jax.Array   # (D, 2) uint32  — their mapping seeds (hi, lo)
    rec_sides: jax.Array   # (D,) int32     — +1 remote-only, -1 local-only
    n_rec: jax.Array       # () int32
    changed: jax.Array     # () bool — last wave recovered something
    overflow: jax.Array    # () bool — a wave would exceed max_diff
    rounds: jax.Array      # () int32


def _stage1(sums, checks, counts, rec_checks, n_rec, m, *, mp: int, cap: int,
            max_diff: int, purity_fn):
    """Purity scan + pure-row compaction + dedupe.

    Returns ``(p_items, p_chk, p_side, keep, n_new, overflow)`` — the
    wave's recovery candidates in ``cap`` fixed slots.  Pure rows beyond
    ``cap`` simply wait for the next wave (the scan is dense, nothing is
    lost).  ``m`` may be traced; every shape is static.
    """
    side = purity_fn(sums, checks, counts)                     # (mp,) i32
    pidx = jnp.nonzero(side != 0, size=cap, fill_value=mp)[0]
    valid = pidx < mp
    g = jnp.minimum(pidx, mp - 1)
    p_items = jnp.where(valid[:, None], sums[g], jnp.uint32(0))
    p_chk = jnp.where(valid[:, None], checks[g], jnp.uint32(0))
    p_side = jnp.where(valid, side[g], jnp.int32(0))

    # dedupe by checksum: within the wave (first occurrence wins — the same
    # item is often pure at several indices at once) ...
    eq = (p_chk[:, 0][:, None] == p_chk[:, 0][None, :]) & \
         (p_chk[:, 1][:, None] == p_chk[:, 1][None, :]) & \
         valid[:, None] & valid[None, :]
    dup = (jnp.tril(eq.astype(jnp.int32), k=-1) > 0).any(axis=1)
    # ... and against everything recovered in earlier waves
    live = jnp.arange(rec_checks.shape[0]) < n_rec
    seen = ((p_chk[:, 0][:, None] == rec_checks[:, 0][None, :]) &
            (p_chk[:, 1][:, None] == rec_checks[:, 1][None, :]) &
            live[None, :]).any(axis=1)
    keep = valid & ~dup & ~seen
    n_new = jnp.sum(keep.astype(jnp.int32))
    overflow = n_rec + n_new > max_diff
    return p_items, p_chk, p_side, keep, n_new, overflow


def _stage2(state: PeelState, p_items, p_chk, p_side, keep, m, *, mp: int,
            max_diff: int, map_fn, apply_fn) -> PeelState:
    """Chain re-derivation + signed dense removal + recovered-buffer append.

    Flags (``changed``/``overflow``/``rounds``) are managed by the caller.
    """
    n_new = jnp.sum(keep.astype(jnp.int32))
    idxs, seeds = map_fn(p_items, m)
    idxs = jnp.where(keep[:, None], idxs, jnp.asarray(m, jnp.int32))
    with jax.named_scope(APPLY_SCOPE):
        d_sums, d_checks, d_counts = apply_fn(
            p_items, idxs, p_chk, jnp.where(keep, p_side, jnp.int32(0)), m)

    pos = state.n_rec + jnp.cumsum(keep.astype(jnp.int32)) - 1
    dest = jnp.where(keep, pos, max_diff)          # index max_diff = dropped
    return state._replace(
        sums=state.sums ^ d_sums[:mp],
        checks=state.checks ^ d_checks[:mp],
        counts=state.counts - d_counts[:mp],
        rec_items=state.rec_items.at[dest].set(p_items, mode="drop"),
        rec_checks=state.rec_checks.at[dest].set(p_chk, mode="drop"),
        rec_seeds=state.rec_seeds.at[dest].set(seeds, mode="drop"),
        rec_sides=state.rec_sides.at[dest].set(p_side, mode="drop"),
        n_rec=state.n_rec + n_new,
    )


def _wave(state: PeelState, m, *, mp: int, cap: int, max_diff: int,
          purity_fn, map_fn, apply_fn) -> PeelState:
    """One traced peel wave (the ``lax.while_loop`` body).  On overflow the
    symbol/recovered state is preserved (only the flag changes) so a host
    fallback can redecode from scratch."""
    p_items, p_chk, p_side, keep, n_new, overflow = _stage1(
        state.sums, state.checks, state.counts, state.rec_checks,
        state.n_rec, m, mp=mp, cap=cap, max_diff=max_diff,
        purity_fn=purity_fn)
    out = _stage2(state, p_items, p_chk, p_side, keep, m, mp=mp,
                  max_diff=max_diff, map_fn=map_fn, apply_fn=apply_fn)
    out = out._replace(changed=n_new > 0, overflow=overflow,
                       rounds=state.rounds + 1)
    frozen = state._replace(changed=jnp.array(False), overflow=overflow,
                            rounds=state.rounds + 1)
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(overflow, a, b), frozen, out)


def _ref_stages(*, nbytes: int, key, K: int, mp: int):
    """Build the ref engine's (purity_fn, map_fn(items, m) -> (idxs,
    seeds), apply_fn(items, idxs, chks, sides, m)).  ``m`` is data, so one
    compiled program serves every prefix length within a tile bucket."""
    def purity_fn(sums, checks, counts):
        return _purity_body(sums, checks, counts, key=key, nbytes=nbytes)

    def map_fn(items, m):
        # the candidates' checksums are known: hash for the seed only
        with jax.named_scope(HASH_SCOPE):
            h, l = map_seeds_pair(items, key, nbytes)
        return chains_ref(h, l, K=K, m=m), jnp.stack([h, l], axis=1)

    def apply_fn(items, idxs, chks, sides, m):
        return iblt_apply_ref(items, idxs, chks, sides, m=m, m_out=mp)
    return purity_fn, map_fn, apply_fn


def _cap(D: int, mp: int, block_n: int) -> int:
    """Per-wave compaction slots: 2·max_diff, at most mp, whole blocks."""
    cap = min(2 * max(D, 1), mp)
    return max(-(-cap // block_n) * block_n, block_n)


def _init_state(sums, checks, counts, D: int) -> PeelState:
    """Fresh wave state over padded symbols; a leading unit axis, if the
    symbols have one, carries through every leaf."""
    lead, L = sums.shape[:-2], sums.shape[-1]
    return PeelState(
        sums=jnp.asarray(sums, jnp.uint32),
        checks=jnp.asarray(checks, jnp.uint32),
        counts=jnp.asarray(counts, jnp.int32),
        rec_items=jnp.zeros(lead + (D, L), jnp.uint32),
        rec_checks=jnp.zeros(lead + (D, 2), jnp.uint32),
        rec_seeds=jnp.zeros(lead + (D, 2), jnp.uint32),
        rec_sides=jnp.zeros(lead + (D,), jnp.int32),
        n_rec=jnp.zeros(lead, jnp.int32),
        changed=jnp.ones(lead, bool),
        overflow=jnp.zeros(lead, bool),
        rounds=jnp.zeros(lead, jnp.int32),
    )


def _success(state: PeelState):
    """All symbols empty (the ρ(0)=1 termination signal) and no overflow."""
    empty = (state.counts[..., 0] == 0) & (state.checks[..., 0] == 0) & \
        (state.checks[..., 1] == 0) & jnp.all(state.sums == 0, axis=-1)
    return jnp.all(empty, axis=-1) & ~state.overflow


@functools.lru_cache(maxsize=128)
def _ref_stages_jit(mp: int, cap: int, max_diff: int, K: int, L: int,
                    nbytes: int, key):
    """Jitted ref-engine wave stages, cached per static-shape bucket.

    ``m`` enters both stages as a traced scalar, so a growing stream prefix
    re-uses one compiled program until it crosses a tile boundary.
    """
    purity_fn, map_fn, apply_fn = _ref_stages(nbytes=nbytes, key=key, K=K,
                                              mp=mp)
    s1 = jax.jit(functools.partial(_stage1, mp=mp, cap=cap,
                                   max_diff=max_diff, purity_fn=purity_fn))
    s2 = jax.jit(functools.partial(_stage2, mp=mp, max_diff=max_diff,
                                   map_fn=map_fn, apply_fn=apply_fn))
    return s1, s2


def peel_waves(sums, checks, counts, *, m: int, nbytes: int, key,
               max_diff: int, K: int, max_rounds: int = 10_000,
               block_n: int = 256):
    """The oracle: iterate purity → compact/dedupe → remove to a fixed
    point over one unit.

    Inputs are the *padded* difference symbols: sums (mp, L) uint32, checks
    (mp, 2) uint32, counts (mp, 1) int32 with rows [m, mp) zero.  Returns
    the final :class:`PeelState` plus a ``success`` scalar (all symbols
    empty — the ρ(0)=1 termination signal holds: symbol 0 empties last).

    The loop runs in Python over the ref engine's stages, jitted per shape
    bucket (with ``m`` as data); the wave that recovers nothing ends it
    before its removal stage.  Tests hold :func:`peel_waves_batched`, the
    decode every device path runs, to it wave for wave.
    """
    mp, L = sums.shape
    D = max_diff
    cap = _cap(D, mp, block_n)
    state = _init_state(sums, checks, counts, D)
    s1, s2 = _ref_stages_jit(mp, cap, D, K, L, nbytes, tuple(key))
    rounds = 0
    while rounds < max_rounds:
        p_items, p_chk, p_side, keep, n_new, overflow = s1(
            state.sums, state.checks, state.counts, state.rec_checks,
            state.n_rec, m)
        rounds += 1
        if bool(overflow) or int(n_new) == 0:
            state = state._replace(changed=jnp.array(False),
                                   overflow=jnp.asarray(overflow),
                                   rounds=jnp.int32(rounds))
            break
        state = s2(state, p_items, p_chk, p_side, keep, m)
        state = state._replace(changed=jnp.array(True),
                               rounds=jnp.int32(rounds))
    return state, _success(state)


# ---------------------------------------------------------------------------
# Batched wave loop: S independent shard decodes as ONE device program.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def _batched_wave_jit(mp: int, cap: int, max_diff: int, K: int, nbytes: int,
                      key):
    """One jitted, ``vmap``-ed peel wave over the unit axis.

    Cached per static-shape bucket ``(mp, cap, max_diff, K)``; the
    per-unit prefix lengths ``m`` enter as a traced ``(S,)`` vector, so a
    set of growing unit prefixes re-uses one compiled program until the
    *longest* unit crosses a tile boundary.  The same stages on every
    backend: dense jnp stages vmap cleanly and compile for both CPU and
    TPU.
    """
    return jax.jit(_batched_wave(mp, cap, max_diff, K, nbytes, key))


def _batched_wave(mp, cap, max_diff, K, nbytes, key):
    """The vmapped wave: the ref engine's purity scan and chains, and chain
    removal as one product over the ``cap`` candidate rows."""
    purity_fn, map_fn, _ = _ref_stages(nbytes=nbytes, key=key, K=K, mp=mp)

    def apply_fn(items, idxs, chks, sides, m):
        return iblt_apply_dense(items, idxs, chks, sides, m=m, m_out=mp)

    wave = functools.partial(_wave, mp=mp, cap=cap, max_diff=max_diff,
                             purity_fn=purity_fn, map_fn=map_fn,
                             apply_fn=apply_fn)
    return jax.vmap(wave, in_axes=(0, 0))


@functools.lru_cache(maxsize=64)
def _batched_program(mp: int, cap: int, max_diff: int, K: int, nbytes: int,
                     key, max_rounds: int):
    """The whole batched decode of one bucket as one jitted program
    (state set-up, ``lax.while_loop`` over the vmapped wave, per-unit
    success); the ``(S,)`` prefix lengths are traced."""
    wave = _batched_wave(mp, cap, max_diff, K, nbytes, key)

    # the function's name is the program's name in a profiler trace
    def peel_batched(sums, checks, counts, m):
        state = jax.lax.while_loop(
            lambda s: jnp.any(s.changed & ~s.overflow) &
            jnp.all(s.rounds < max_rounds),
            lambda s: wave(s, m), _init_state(sums, checks, counts, max_diff))
        return state, _success(state)

    return jax.jit(peel_batched)


def peel_waves_batched(sums, checks, counts, *, m, nbytes: int, key,
                       max_diff: int, K: int, max_rounds: int = 10_000,
                       block_n: int = 256, use_while_loop: bool = False):
    """Wave-peel ``S`` decode units' difference symbols in lockstep.

    Every device decode, of one unit or many, runs here: the
    inputs carry a leading **unit axis** — sums ``(S, mp, L)`` uint32,
    checks ``(S, mp, 2)`` uint32, counts ``(S, mp, 1)`` int32 — where
    ``mp`` is the *shared* tile bucket (every unit padded to the longest
    unit's bucket; rows ``[m[s], mp)`` of unit ``s`` must be zero).  A unit
    is one independent residual prefix: one shard of a sharded session,
    or, through the protocol engine's cross-peer batching, any ragged
    peer×shard pair that landed in this shape bucket.  ``m`` is a ``(S,)``
    int32 vector of true per-unit prefix lengths and is traced data, not a
    static shape, so ragged unit progress batches into one program.

    Every wave is one vmapped dispatch over the unit axis
    (:func:`_batched_wave_jit`): the ref engine's purity scan and chains,
    and chain removal as one matrix product over the wave's candidate rows
    (:func:`repro.kernels.iblt_dense.iblt_apply_dense`), so each unit's
    waves are the :func:`peel_waves` oracle's, bit for bit.  A unit whose wave
    recovers nothing simply no-ops while hotter units keep peeling, and a
    unit that trips ``max_diff`` freezes its own state and raises only its
    own ``overflow`` flag — the other units are unaffected (per-unit host
    fallback, not all-unit).  Every unit counts the batch's waves in
    ``rounds``.

    Returns ``(state, success)``: a :class:`PeelState` whose every leaf has
    the leading unit axis, and a ``(S,)`` bool of per-unit success (all
    of the unit's symbols emptied and no overflow).

    ``use_while_loop=True`` runs the cached per-bucket program of
    :func:`_batched_program` (one device dispatch total — the TPU serving
    path); the default Python loop issues one batched dispatch per wave,
    which is the right trade on CPU where each jitted wave is cheap but
    staging thousands of waves is not.
    """
    mp = sums.shape[1]
    D = max_diff
    cap = _cap(D, mp, block_n)
    key = tuple(key)
    m = jnp.asarray(m, jnp.int32)
    if use_while_loop:
        program = _batched_program(mp, cap, D, K, nbytes, key, max_rounds)
        return program(sums, checks, counts, m)
    state = _init_state(sums, checks, counts, D)
    wave = _batched_wave_jit(mp, cap, D, K, nbytes, key)
    while True:
        state = wave(state, m)
        if not bool(jnp.any(state.changed & ~state.overflow)) or \
                int(state.rounds.max()) >= max_rounds:
            break
    return state, _success(state)
