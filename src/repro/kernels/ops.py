"""Public device API: the encoder and decoder pipelines around the kernels.

``encode_device`` (pad → map_indices → iblt_encode) is the TPU-native
counterpart of ``repro.core.encode`` and produces bit-identical coded
symbols; ``decode_device`` (pad → wave peeling, :mod:`kernels.peel`) is the
counterpart of ``repro.core.peel`` and recovers the identical difference.
:func:`peel_engine` is the one place the platform picks the engine: the
compiled Pallas kernels on TPU, the pure-jnp "ref" engine elsewhere (the
Pallas interpreter pays ~10 ms/op; the kernels themselves are validated in
interpret mode in tests at small sizes).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hashing import DEFAULT_KEY
from repro.core.mapping import kmax
from repro.trace import STAGE, UNSTAGE, WAIT, span

from .iblt_encode import iblt_encode
from .map_indices import map_indices
from .peel import peel_waves, peel_waves_batched


def peel_engine(batched: bool = False) -> str:
    """The kernel engine the device path runs here: ``"pallas"`` (compiled
    kernels) or ``"ref"`` (pure jnp).

    A lone decode and the encoder take the Pallas kernels on a TPU and the
    ref engine elsewhere.  The batched decode takes ``"ref"`` everywhere:
    the ref engine's purity scan and chains, whose dense stages ``vmap``
    over the unit axis and compile for any backend, with chain removal as
    one matrix product over the wave's candidate rows
    (:func:`repro.kernels.iblt_dense.iblt_apply_dense`).
    """
    if batched or jax.default_backend() != "tpu":
        return "ref"
    return "pallas"


def _engine(kernel, interpret):
    """Fill unset ``kernel``/``interpret`` from :func:`peel_engine`: Pallas
    kernels run compiled where the ref engine is not the default, in
    interpret mode otherwise."""
    auto = peel_engine()
    return (auto if kernel is None else kernel,
            auto == "ref" if interpret is None else interpret)


def _pad_items(items, block_n):
    n = items.shape[0]
    np_ = ((n + block_n - 1) // block_n) * block_n
    if np_ == n:
        return items, n
    pad = jnp.zeros((np_ - n, items.shape[1]), dtype=items.dtype)
    return jnp.concatenate([items, pad], axis=0), n


def encode_device(items, *, m: int, nbytes: int | None = None,
                  key=DEFAULT_KEY, K: int | None = None,
                  block_n: int = 256, block_m: int = 256,
                  interpret: bool | None = None,
                  mapping: str | None = None):
    """items (n, L) uint32 -> (sums (m, L) u32, checks (m, 2) u32,
    counts (m,) i32).  Fixed-shape device encoder (chains truncated at
    kmax(m); truncation probability < 1e-12).

    ``mapping``: "pallas" (map_indices kernel) or "ref" (pure-jnp chain);
    the default is :func:`peel_engine`'s.  Both produce identical
    indices."""
    mapping, interpret = _engine(mapping, interpret)
    items = jnp.asarray(items, dtype=jnp.uint32)
    n0 = items.shape[0]
    L = items.shape[1]
    if nbytes is None:
        nbytes = 4 * L
    if K is None:
        K = kmax(m)

    def run(items_padded):
        # mask first, map second: pad rows are zero items whose mappings
        # must never be computed into the symbols (idx := m kills a row).
        n_pad = items_padded.shape[0] - n0
        if mapping == "pallas":
            # the kernel needs whole blocks — map everything, mask the pads
            idxs, chks, _ = map_indices(items_padded, K=K, m=m,
                                        nbytes=nbytes, key=key,
                                        block_n=block_n, interpret=interpret)
            if n_pad:
                pad_rows = jnp.arange(items_padded.shape[0]) >= n0
                idxs = jnp.where(pad_rows[:, None], jnp.int32(m), idxs)
        else:
            # the jnp chain has no block constraint — skip pad rows entirely
            from .ref import map_indices_ref
            idxs, chks = map_indices_ref(items_padded[:n0], K=K, m=m,
                                         nbytes=nbytes, key=key)
            if n_pad:
                idxs = jnp.concatenate(
                    [idxs, jnp.full((n_pad, K), m, jnp.int32)])
                chks = jnp.concatenate(
                    [chks, jnp.zeros((n_pad, 2), jnp.uint32)])
        sums, checks, counts = iblt_encode(items_padded, idxs, chks, m=m,
                                           block_m=block_m, block_n=block_n,
                                           interpret=interpret)
        return sums[:m], checks[:m], counts[:m, 0]

    padded, n0 = _pad_items(items, block_n)
    if not interpret:
        # real-TPU path: one fused jit program around both kernels
        run = jax.jit(run)
    return run(padded)


def _pairs64(pairs) -> np.ndarray:
    """(r, 2) uint32 (hi, lo) pairs -> (r,) uint64."""
    return (pairs[:, 0].astype(np.uint64) << np.uint64(32)) | \
        pairs[:, 1].astype(np.uint64)


def device_symbols_to_host(sums, checks, counts, nbytes: int):
    """Convert device output to a host CodedSymbols (checks -> uint64)."""
    from repro.core.symbols import CodedSymbols
    # np.array (not asarray): jax arrays convert to read-only views, but
    # CodedSymbols buffers are mutated in place by the host decoders.
    sums = np.array(sums, dtype=np.uint32)
    checks = np.asarray(checks, dtype=np.uint32)
    counts = np.asarray(counts)
    return CodedSymbols(sums, _pairs64(checks), counts.astype(np.int64),
                        nbytes)


def host_symbols_to_device(sym):
    """CodedSymbols -> (sums (m, L) u32, checks (m, 2) u32, counts (m,) i32),
    the device layout (uint64 checksums split into (hi, lo) word pairs) in
    host arrays; :func:`decode_device` pads and stages them.  Inverse of
    :func:`device_symbols_to_host` (tested round-trip)."""
    checks = np.empty((sym.m, 2), np.uint32)
    checks[:, 0] = (sym.checks >> np.uint64(32)).astype(np.uint32)
    checks[:, 1] = (sym.checks & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return (np.asarray(sym.sums, np.uint32), checks,
            sym.counts.astype(np.int32))


class DeviceDecodeResult(NamedTuple):
    """Host-materialized outcome of :func:`decode_device`."""
    items: np.ndarray     # (r, L) uint32 — recovered source symbols
    hashes: np.ndarray    # (r,) uint64   — their checksums
    sides: np.ndarray     # (r,) int8     — +1 remote-only, -1 local-only
    success: bool         # all symbols emptied (difference fully recovered)
    overflow: bool        # max_diff exceeded — decode stopped mid-peel
    rounds: int           # peel waves executed
    residual: object      # CodedSymbols — symbols after all removals
    transfer_bytes: int = 0   # host→device staged + device→host fetched
    # (r,) uint64 — the recovered items' mapping seeds, as
    # :func:`repro.core.mapping.map_seeds` gives them
    seeds: np.ndarray = np.zeros(0, np.uint64)


def _stage(*arrays):
    """Copy host arrays to the device; returns them and the bytes sent."""
    return jax.device_put(arrays), sum(a.nbytes for a in arrays)


def _fetch(tree):
    """Copy a pytree of device arrays to the host (one blocking read of
    every leaf); returns it and the bytes received."""
    host = jax.device_get(tree)
    return host, sum(np.asarray(a).nbytes for a in jax.tree.leaves(host))


def decode_device(sums, checks, counts, *, nbytes: int, key=DEFAULT_KEY,
                  max_diff: int | None = None, max_rounds: int = 10_000,
                  K: int | None = None, block_n: int = 256,
                  block_m: int = 256, interpret: bool | None = None,
                  kernel: str | None = None) -> DeviceDecodeResult:
    """Wave-peel difference symbols on device (paper §3 decode).

    Inputs are device-layout difference symbols — sums (m, L) uint32,
    checks (m, 2) uint32, counts (m,) int32, e.g. from
    :func:`host_symbols_to_device` or an ``encode_device`` subtraction.

    ``max_diff`` bounds the fixed-shape recovered-item buffers; it defaults
    to the tile-padded prefix length (≥ m), which can never overflow:
    recovering an item permanently empties the symbol it was pure at (the
    item was that symbol's whole content), so even a partial decode
    recovers at most m items.  A tighter bound trades buffer size for a possible
    ``overflow=True`` outcome — the decode stops with the overflowing wave
    unapplied (items/residual cover only the completed waves) and the
    caller should fall back to the host decoder.

    ``kernel``: "pallas" (purity/map/apply kernels) or "ref" (pure jnp);
    the default is :func:`peel_engine`'s.  Off the interpreter the whole
    wave loop is one jit program per tile bucket under
    ``jax.lax.while_loop``, with ``m`` traced, so a growing prefix
    recompiles only when it crosses a tile boundary.  Chains are truncated
    at ``kmax(mp)`` like the device encoder (< 1e-12 probability).
    """
    kernel, interpret = _engine(kernel, interpret)
    sums = np.asarray(sums, np.uint32)
    m, L = sums.shape
    if nbytes is None:
        nbytes = 4 * L
    if m == 0:
        from repro.core.symbols import CodedSymbols
        return DeviceDecodeResult(
            np.zeros((0, L), np.uint32), np.zeros(0, np.uint64),
            np.zeros(0, np.int8), True, False, 0,
            CodedSymbols.zeros(0, nbytes))
    mp = ((m + block_m - 1) // block_m) * block_m
    # defaults quantize to the tile bucket (mp ≥ m) so a growing stream
    # prefix re-uses one compiled program per bucket
    if K is None:
        K = kmax(mp)
    D = mp if max_diff is None else max(int(max_diff), 1)
    with span(STAGE, units=1, mp=mp):
        # pad on the host: the padded shapes are the bucket's, whatever m
        sums_p = np.zeros((mp, L), np.uint32)
        checks_p = np.zeros((mp, 2), np.uint32)
        counts_p = np.zeros((mp, 1), np.int32)
        sums_p[:m] = sums
        checks_p[:m] = np.asarray(checks, np.uint32)
        counts_p[:m, 0] = np.asarray(counts, np.int32)
        staged, sent = _stage(sums_p, checks_p, counts_p)
        out = peel_waves(
            *staged, m=m, nbytes=nbytes, key=key, max_diff=D, K=K,
            max_rounds=max_rounds, kernel=kernel, block_m=block_m,
            block_n=block_n, interpret=interpret,
            use_while_loop=not interpret)
    with span(WAIT):
        (state, success), got = _fetch(out)
    with span(UNSTAGE):
        n_rec = int(state.n_rec)
        residual = device_symbols_to_host(
            state.sums[:m], state.checks[:m], state.counts[:m, 0], nbytes)
        return DeviceDecodeResult(
            state.rec_items[:n_rec], _pairs64(state.rec_checks[:n_rec]),
            state.rec_sides[:n_rec].astype(np.int8), bool(success),
            bool(state.overflow), int(state.rounds), residual, sent + got,
            _pairs64(state.rec_seeds[:n_rec]))


class PendingBatchedDecode:
    """An in-flight :func:`decode_device_batched_start` dispatch.

    Holds the device-resident :class:`~repro.kernels.peel.PeelState` (with
    its leading unit axis) before host materialization.  ``ready()`` polls
    the underlying JAX arrays non-blockingly — on TPU the whole wave loop
    is one async dispatch, so a caller can overlap host work (e.g. frame
    ingest for the next round) with the decode and only then ``wait()``.
    On CPU the Python wave loop has already run by construction and
    ``ready()`` is immediately True.
    """

    __slots__ = ("_state", "_success", "_ms", "_nbytes", "_results",
                 "_sent")

    def __init__(self, state, success, ms, nbytes, results=None, sent=0):
        self._state = state
        self._success = success
        self._ms = ms
        self._nbytes = nbytes
        self._results = results
        self._sent = sent

    def ready(self) -> bool:
        """Non-blocking: True once the device results can be read without
        stalling (always True for trivially-empty or materialized work)."""
        if self._results is not None:
            return True
        is_ready = getattr(self._success, "is_ready", None)
        return bool(is_ready()) if callable(is_ready) else True

    def wait(self) -> list[DeviceDecodeResult]:
        """Materialize (blocking) — one result per input unit, in order.

        The bucket's bytes, staged and fetched (padding units included),
        are split evenly over its units' ``transfer_bytes``, so they sum
        to the bucket's total."""
        if self._results is not None:
            return self._results
        ms, nbytes = self._ms, self._nbytes
        with span(WAIT):
            (state, success), got = _fetch((self._state, self._success))
        with span(UNSTAGE):
            total = self._sent + got
            out = []
            for s, m_s in enumerate(ms):
                n_rec = int(state.n_rec[s])
                residual = device_symbols_to_host(
                    state.sums[s, :m_s], state.checks[s, :m_s],
                    state.counts[s, :m_s, 0], nbytes)
                out.append(DeviceDecodeResult(
                    state.rec_items[s, :n_rec].copy(),
                    _pairs64(state.rec_checks[s, :n_rec]),
                    state.rec_sides[s, :n_rec].astype(np.int8),
                    bool(success[s]), bool(state.overflow[s]),
                    int(state.rounds[s]), residual,
                    total // len(ms) + (s < total % len(ms)),
                    _pairs64(state.rec_seeds[s, :n_rec])))
        self._results = out
        self._state = self._success = None   # free device references
        return out


def decode_device_batched_start(units, *, nbytes: int, key=DEFAULT_KEY,
                                max_diff: int | None = None,
                                max_rounds: int = 10_000, K: int | None = None,
                                block_m: int = 256, pad_units: int | None = None,
                                interpret: bool | None = None
                                ) -> PendingBatchedDecode:
    """Dispatch the batched wave decode of U units without materializing.

    ``units`` is a sequence of host :class:`~repro.core.symbols.CodedSymbols`
    — one ragged residual prefix per unit (the ``work`` buffers of U
    decoders; a unit is a shard of one session or, through the protocol
    engine, any peer×shard pair sharing this shape bucket).  Every unit is
    padded to a single shared tile bucket
    ``mp = ceil(max_u m_u / block_m) · block_m`` and the per-unit true
    prefix lengths travel as a traced ``(U,)`` data vector into
    :func:`repro.kernels.peel.peel_waves_batched`, which ``vmap``s the wave
    engine over the unit axis: one compiled program, one dispatch per wave
    (or one total under ``lax.while_loop`` on TPU), regardless of U.

    ``max_diff`` bounds each unit's fixed recovered-item buffer
    *individually*; a unit that trips it freezes only itself and comes
    back with ``overflow=True`` while its neighbours finish — the caller
    falls back to the host decoder for exactly those units.  The default
    (``mp``) can never overflow, same argument as :func:`decode_device`.

    ``pad_units`` pads the unit axis to a fixed batch size with empty
    (m=0) dummy units, which no-op after their first wave.  The unit
    count is a static shape in the per-bucket jit cache, so a caller
    whose batch shrinks as units settle (the protocol engine, as peers
    terminate) quantizes U to e.g. the next power of two and re-uses one
    compiled program instead of recompiling per departure.

    Returns a :class:`PendingBatchedDecode`; ``wait()`` yields one
    :class:`DeviceDecodeResult` per unit, in input order.
    """
    # the batched stages are the same everywhere; ``interpret``
    # only picks one staged program (compiled platforms) or a Python loop
    _, interpret = _engine(None, interpret)
    from repro.core.symbols import CodedSymbols
    U = len(units)
    if U == 0:
        return PendingBatchedDecode(None, None, (), nbytes, results=[])
    ms = [sym.m for sym in units]
    m_hi = max(ms)
    if m_hi == 0:
        L = units[0].L
        empty = DeviceDecodeResult(
            np.zeros((0, L), np.uint32), np.zeros(0, np.uint64),
            np.zeros(0, np.int8), True, False, 0,
            CodedSymbols.zeros(0, nbytes))
        return PendingBatchedDecode(None, None, ms, nbytes,
                                    results=[empty] * U)
    L = units[0].L
    assert all(sym.L == L and sym.nbytes == units[0].nbytes
               for sym in units), "units must share one item geometry"
    Up = max(U, pad_units) if pad_units else U
    mp = ((m_hi + block_m - 1) // block_m) * block_m
    if K is None:
        K = kmax(mp)
    D = mp if max_diff is None else max(int(max_diff), 1)

    with span(STAGE, units=U, mp=mp):
        sums = np.zeros((Up, mp, L), np.uint32)
        checks = np.zeros((Up, mp, 2), np.uint32)
        counts = np.zeros((Up, mp, 1), np.int32)
        for s, sym in enumerate(units):
            sums[s, : sym.m] = sym.sums
            checks[s, : sym.m, 0] = \
                (sym.checks >> np.uint64(32)).astype(np.uint32)
            checks[s, : sym.m, 1] = (sym.checks &
                                     np.uint64(0xFFFFFFFF)).astype(np.uint32)
            counts[s, : sym.m, 0] = sym.counts.astype(np.int32)
        (sums, checks, counts, m), sent = _stage(
            sums, checks, counts, np.asarray(ms + [0] * (Up - U), np.int32))
        state, success = peel_waves_batched(
            sums, checks, counts, m=m, nbytes=nbytes, key=key, max_diff=D,
            K=K, max_rounds=max_rounds, use_while_loop=not interpret)
    # wait() materializes per entry of ms (length U): dummy pad units past
    # U are simply never read back
    return PendingBatchedDecode(state, success, ms, nbytes, sent=sent)


def decode_device_batched(units, *, nbytes: int, key=DEFAULT_KEY,
                          max_diff: int | None = None,
                          max_rounds: int = 10_000, K: int | None = None,
                          block_m: int = 256, pad_units: int | None = None,
                          interpret: bool | None = None
                          ) -> list[DeviceDecodeResult]:
    """Wave-peel U units' difference symbols in ONE batched device call.

    The synchronous convenience over :func:`decode_device_batched_start` —
    dispatch and immediately materialize.  Callers that can overlap host
    work with the device decode (the protocol engine's double-buffered
    tick loop) use start/``wait`` directly.
    """
    return decode_device_batched_start(
        units, nbytes=nbytes, key=key, max_diff=max_diff,
        max_rounds=max_rounds, K=K, block_m=block_m, pad_units=pad_units,
        interpret=interpret).wait()
