"""Public device API: the encoder and decoder pipelines around the kernels.

``encode_device`` (pad → map_indices → iblt_encode) is the TPU-native
counterpart of ``repro.core.encode`` and produces bit-identical coded
symbols.  Every device decode is :func:`decode_device_batched_start` over
U ≥ 1 units: their residual rows staged in one array, the batched wave
peel (:func:`repro.kernels.peel.peel_waves_batched`), and what the host
reads packed into flags and chunks of recovered rows; each unit's peeled
residual stays on the device.  :func:`decode_device` is one unit of it
over host arrays, with the residual fetched — the counterpart of
``repro.core.peel``, recovering the identical difference.
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
from .peel import peel_waves_batched


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
    both produce identical indices.  By default a TPU runs the compiled
    kernels, any other platform the ref chain and the Pallas interpreter
    (~10 ms/op; the kernels are validated there at small sizes)."""
    on_tpu = jax.default_backend() == "tpu"
    if mapping is None:
        mapping = "pallas" if on_tpu else "ref"
    if interpret is None:
        interpret = not on_tpu
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
    host arrays, as :func:`decode_device` takes them.  Inverse of
    :func:`device_symbols_to_host` (tested round-trip)."""
    checks = np.empty((sym.m, 2), np.uint32)
    checks[:, 0] = (sym.checks >> np.uint64(32)).astype(np.uint32)
    checks[:, 1] = (sym.checks & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return (np.asarray(sym.sums, np.uint32), checks,
            sym.counts.astype(np.int32))


class DeviceDecodeResult(NamedTuple):
    """Host-materialized outcome of one unit's device decode."""
    items: np.ndarray     # (r, L) uint32 — recovered source symbols
    hashes: np.ndarray    # (r,) uint64   — their checksums
    sides: np.ndarray     # (r,) int8     — +1 remote-only, -1 local-only
    success: bool         # all symbols emptied (difference fully recovered)
    overflow: bool        # max_diff exceeded — decode stopped mid-peel
    rounds: int           # peel waves executed
    # symbols after all removals: host CodedSymbols from decode_device, a
    # Residual on the device from the batched decode
    residual: object
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
                  K: int | None = None,
                  block_m: int = 256) -> DeviceDecodeResult:
    """Wave-peel difference symbols on device (paper §3 decode).

    Inputs are device-layout difference symbols — sums (m, L) uint32,
    checks (m, 2) uint32, counts (m,) int32, e.g. from
    :func:`host_symbols_to_device` or an ``encode_device`` subtraction.
    They decode as one unit of :func:`decode_device_batched`, and the
    peeled residual is fetched: ``residual`` is host
    :class:`~repro.core.symbols.CodedSymbols`, its bytes counted in
    ``transfer_bytes``.

    ``max_diff`` bounds the fixed-shape recovered-item buffers; it defaults
    to the tile-padded prefix length (≥ m), which can never overflow:
    recovering an item permanently empties the symbol it was pure at (the
    item was that symbol's whole content), so even a partial decode
    recovers at most m items.  A tighter bound trades buffer size for a possible
    ``overflow=True`` outcome — the decode stops with the overflowing wave
    unapplied (items/residual cover only the completed waves) and the
    caller should fall back to the host decoder.  Chains are truncated
    at ``kmax(mp)`` like the device encoder (< 1e-12 probability).
    """
    from repro.core.symbols import Residual
    unit = Residual(device_symbols_to_host(sums, checks, counts, nbytes))
    (res,) = decode_device_batched(
        [unit], nbytes=nbytes, key=key, max_diff=max_diff,
        max_rounds=max_rounds, K=K, block_m=block_m)
    got = res.residual
    rows, fetched = got.device.fetch() if got.base else (got.host, 0)
    return res._replace(residual=rows,
                        transfer_bytes=res.transfer_bytes + fetched)


# Bytes of recovered rows per chunk that crosses device→host (about 2 MiB).
CHUNK_BYTES = 2 << 20
# uint32 columns a packed recovered row carries past the item's L words:
# its checksum (hi, lo), its map seed (hi, lo) and its side
PACKED_EXTRA = 5


class DeviceRows(NamedTuple):
    """Rows [0, m) of one unit's residual, left on the device by a batched
    decode: unit ``row`` of the bucket's output ``arrays`` (sums, checks,
    counts, each with the leading unit axis), shared by the bucket's
    units and freed when the last of them lets go."""
    arrays: tuple
    row: int
    m: int
    nbytes: int

    def fetch(self):
        """Copy the rows to the host: ``(CodedSymbols, bytes fetched)``."""
        (sums, checks, counts), got = _fetch(
            tuple(a[self.row, :self.m] for a in self.arrays))
        return device_symbols_to_host(sums, checks, counts[:, 0],
                                      self.nbytes), got


def chunk_rows(L: int, rows: int, chunk_bytes: int) -> int:
    """Recovered rows per fetched chunk: about ``chunk_bytes`` of packed
    rows, at most ``rows`` (one unit's buffer)."""
    return max(1, min(rows, -(-chunk_bytes // (4 * (L + PACKED_EXTRA)))))


def stage_batched(sources, tails, layout, *, mp: int):
    """Build a bucket's decode input on the device.

    ``layout`` is ``(Up, 5)`` int32, one row per unit: the index of its
    source in ``sources`` (-1: none), its row there, ``base`` (the rows
    [0, base) it keeps from that source), the offset of its own rows in
    ``tails`` and its prefix length ``m``.  Each source is a previous
    bucket's (sums, checks, counts); ``tails`` holds every unit's rows
    [base, m), concatenated.  Rows at and past ``m`` are zero.  Returns
    (sums (Up, mp, L), checks (Up, mp, 2), counts (Up, mp, 1), m (Up,)).
    """
    src, row, base, start, m = (layout[:, j] for j in range(5))
    i = jnp.arange(mp)[None, :]
    held = i < base[:, None]
    new = ~held & (i < m[:, None])
    R = tails[0].shape[0]
    if R:
        at = jnp.where(new, start[:, None] + i - base[:, None], R)
        out = [jnp.take(t, at, axis=0, mode="fill", fill_value=0)
               for t in tails]
    else:
        out = [jnp.zeros((layout.shape[0], mp) + t.shape[1:], t.dtype)
               for t in tails]
    for k, arrays in enumerate(sources):
        keep = (held & (src == k)[:, None])[..., None]
        r = jnp.clip(row, 0, arrays[0].shape[0] - 1)
        for f, a in enumerate(arrays):
            a = a[r, :mp]
            if a.shape[1] < mp:
                a = jnp.pad(a, ((0, 0), (0, mp - a.shape[1]), (0, 0)))
            out[f] = jnp.where(keep, a, out[f])
    return (*out, m)


def unstage_batched(state, success, *, chunk_bytes: int):
    """What the host reads of a batched decode: ``(flags, chunks)``.

    ``flags`` is ``(Up, 5)`` int32 per unit: ``n_rec``, overflow, success,
    waves, and whether residual row 0 is empty.  ``chunks`` splits the
    units' recovered rows, packed (:data:`PACKED_EXTRA`) and laid end to
    end in unit order, into :func:`chunk_rows`-row arrays, each at most
    one unit's buffer: the host reads the first with the flags and the
    others only where they hold rows.
    """
    Up, D, L = state.rec_items.shape
    n = state.n_rec
    row0 = (state.counts[:, 0, 0] == 0) & (state.checks[:, 0, 0] == 0) & \
        (state.checks[:, 0, 1] == 0) & jnp.all(state.sums[:, 0] == 0, axis=1)
    flags = jnp.stack([n, state.overflow, success, state.rounds, row0],
                      axis=1).astype(jnp.int32)
    packed = jnp.concatenate([
        state.rec_items, state.rec_checks, state.rec_seeds,
        jax.lax.bitcast_convert_type(state.rec_sides, jnp.uint32)[..., None]],
        axis=-1)
    C = chunk_rows(L, D, chunk_bytes)
    n_chunks = -(-(Up * D) // C)
    ends = jnp.cumsum(n)
    r = jnp.arange(n_chunks * C)
    unit = jnp.searchsorted(ends, r, side="right")
    u = jnp.minimum(unit, Up - 1)
    j = jnp.clip(r - ends[u] + n[u], 0, D - 1)
    rows = jnp.where((unit < Up)[:, None], packed[u, j], jnp.uint32(0))
    return flags, tuple(rows[c * C:(c + 1) * C] for c in range(n_chunks))


_stage_program = jax.jit(stage_batched, static_argnames="mp")
_unstage_program = jax.jit(unstage_batched, static_argnames="chunk_bytes")


class PendingBatchedDecode:
    """An in-flight :func:`decode_device_batched_start` dispatch.

    Holds the device's per-unit flags, the chunks of recovered rows and
    the bucket's output residual (the arrays each unit's
    :class:`DeviceRows` keeps) before host materialization.  ``ready()``
    polls the underlying JAX arrays non-blockingly — on TPU the whole wave
    loop is one async dispatch, so a caller can overlap host work (e.g.
    frame ingest for the next round) with the decode and only then
    ``wait()``.  On CPU the Python wave loop has already run by
    construction and ``ready()`` is immediately True.
    """

    __slots__ = ("_flags", "_chunks", "_ms", "_nbytes", "_results",
                 "_sent", "_arrays")

    def __init__(self, flags, chunks, ms, nbytes, results=None, sent=0,
                 arrays=None):
        self._flags = flags
        self._chunks = chunks
        self._ms = ms
        self._nbytes = nbytes
        self._results = results
        self._sent = sent
        self._arrays = arrays

    def ready(self) -> bool:
        """Non-blocking: True once the device results can be read without
        stalling (always True for trivially-empty or materialized work)."""
        if self._results is not None:
            return True
        is_ready = getattr(self._flags, "is_ready", None)
        return bool(is_ready()) if callable(is_ready) else True

    def wait(self) -> list[DeviceDecodeResult]:
        """Materialize (blocking) — one result per input unit, in order.

        Copies back the flags and the first chunk of recovered rows in one
        read, and the chunks after it, in one more, only where the units
        recovered more rows than a chunk holds; the residual stays on the
        device, each unit's as the :class:`DeviceRows` of its result's
        ``residual``.
        The bucket's bytes, staged and fetched (padding units included),
        are split evenly over its units' ``transfer_bytes``, so they sum
        to the bucket's total."""
        if self._results is not None:
            return self._results
        from repro.core.symbols import CodedSymbols, Residual
        ms, nbytes = self._ms, self._nbytes
        with span(WAIT):
            (flags, first), got = _fetch((self._flags, self._chunks[0]))
            n = flags[:len(ms), 0]
            k = -(-int(n.sum()) // first.shape[0])
            rest, got_rest = _fetch(self._chunks[1:k]) if k > 1 else ((), 0)
        with span(UNSTAGE):
            total = self._sent + got + got_rest
            L = first.shape[1] - PACKED_EXTRA
            rows = np.concatenate((first, *rest)) if rest else first
            out, at = [], 0
            for s, m_s in enumerate(ms):
                r = rows[at:at + n[s]]
                at += n[s]
                residual = Residual(CodedSymbols.zeros(0, nbytes), m_s,
                                    DeviceRows(self._arrays, s, m_s, nbytes),
                                    bool(flags[s, 4]))
                out.append(DeviceDecodeResult(
                    r[:, :L], _pairs64(r[:, L:L + 2]),
                    r[:, L + 4].view(np.int32).astype(np.int8),
                    bool(flags[s, 2]), bool(flags[s, 1]), int(flags[s, 3]),
                    residual, total // len(ms) + (s < total % len(ms)),
                    _pairs64(r[:, L + 2:L + 4])))
        self._results = out
        # free the device references but the residual the results keep
        self._flags = self._chunks = self._arrays = None
        return out


def decode_device_batched_start(units, *, nbytes: int, key=DEFAULT_KEY,
                                max_diff: int | None = None,
                                max_rounds: int = 10_000, K: int | None = None,
                                block_m: int = 256, pad_units: int | None = None
                                ) -> PendingBatchedDecode:
    """Dispatch the batched wave decode of U units without materializing.

    ``units`` is a sequence of :class:`~repro.core.symbols.Residual` — one
    ragged residual prefix per unit (the ``work`` of U decoders; a unit is a shard of one session
    or, through the protocol engine, any peer×shard pair sharing this
    shape bucket).  Only the rows the host holds are copied up, in one
    array for the bucket; the rows an earlier decode left on the device
    are taken where they are, and one small program
    (:func:`stage_batched`) lays out every unit in a single shared tile
    bucket ``mp = ceil(max_u m_u / block_m) · block_m``.  The per-unit
    true prefix lengths travel as a traced ``(U,)`` data vector into
    :func:`repro.kernels.peel.peel_waves_batched`, which ``vmap``s the wave
    engine over the unit axis: one compiled program, one dispatch in all
    under ``lax.while_loop`` on a TPU (one per wave of a Python loop
    elsewhere), regardless of U.
    Another small program (:func:`unstage_batched`) packs what the host
    reads back.

    ``max_diff`` bounds each unit's fixed recovered-item buffer
    *individually*; a unit that trips it freezes only itself and comes
    back with ``overflow=True`` while its neighbours finish — the caller
    falls back to the host decoder for exactly those units.  The default
    (``mp``) can never overflow: a unit recovers at most one item per
    symbol (see :func:`decode_device`).

    ``pad_units`` pads the unit axis to a fixed batch size with empty
    (m=0) dummy units, which no-op after their first wave.  The unit
    count is a static shape in the per-bucket jit cache, so a caller
    whose batch shrinks as units settle (the protocol engine, as peers
    terminate) quantizes U to e.g. the next power of two and re-uses one
    compiled program instead of recompiling per departure.

    Returns a :class:`PendingBatchedDecode`; ``wait()`` yields one
    :class:`DeviceDecodeResult` per unit, in input order, whose
    ``residual`` is a :class:`~repro.core.symbols.Residual` held on the
    device.
    """
    from repro.core.symbols import CodedSymbols, Residual
    U = len(units)
    if U == 0:
        return PendingBatchedDecode(None, None, (), nbytes, results=[])
    ms = [u.m for u in units]
    m_hi = max(ms)
    if m_hi == 0:
        L = units[0].L
        empty = DeviceDecodeResult(
            np.zeros((0, L), np.uint32), np.zeros(0, np.uint64),
            np.zeros(0, np.int8), True, False, 0,
            Residual(CodedSymbols.zeros(0, nbytes)))
        return PendingBatchedDecode(None, None, ms, nbytes,
                                    results=[empty] * U)
    L = units[0].L
    assert all(u.L == L and u.nbytes == units[0].nbytes
               for u in units), "units must share one item geometry"
    Up = max(U, pad_units) if pad_units else U
    mp = ((m_hi + block_m - 1) // block_m) * block_m
    if K is None:
        K = kmax(mp)
    D = mp if max_diff is None else max(int(max_diff), 1)

    with span(STAGE, units=U, mp=mp):
        # every unit's host rows, end to end in one fresh array a field,
        # padded to a power of two rows: peers settling at different ticks
        # then meet the few staging programs the first rounds compiled
        R = sum(u.host.m for u in units)
        Rp = 1 << (R - 1).bit_length() if R else 0
        sums = np.empty((Rp, L), np.uint32)
        checks = np.empty((Rp, 2), np.uint32)
        counts = np.empty((Rp, 1), np.int32)
        for a in (sums, checks, counts):
            a[R:] = 0
        sources, where = [], {}
        layout = np.zeros((Up, 5), np.int32)
        layout[:, 0] = -1
        at = 0
        for s, u in enumerate(units):
            if u.base:
                if u.device is None:
                    raise ValueError("a settled unit's residual was released")
                k = where.setdefault(id(u.device.arrays), len(sources))
                if k == len(sources):
                    sources.append(u.device.arrays)
                layout[s, :3] = k, u.device.row, u.base
            layout[s, 3:] = at, u.m
            h, end = u.host, at + u.host.m
            sums[at:end] = h.sums
            checks[at:end, 0] = h.checks >> np.uint64(32)
            checks[at:end, 1] = h.checks & np.uint64(0xFFFFFFFF)
            counts[at:end, 0] = h.counts
            at = end
        (*tails, layout), sent = _stage(sums, checks, counts, layout)
        *arrays, m = _stage_program(tuple(sources), tuple(tails), layout,
                                    mp=mp)
        state, success = peel_waves_batched(
            *arrays, m=m, nbytes=nbytes, key=key, max_diff=D, K=K,
            max_rounds=max_rounds,
            use_while_loop=jax.default_backend() == "tpu")
        flags, chunks = _unstage_program(state, success,
                                         chunk_bytes=CHUNK_BYTES)
    # wait() materializes per entry of ms (length U): dummy pad units past
    # U are simply never read back
    return PendingBatchedDecode(
        flags, chunks, ms, nbytes, sent=sent,
        arrays=(state.sums, state.checks, state.counts))


def decode_device_batched(units, *, nbytes: int, key=DEFAULT_KEY,
                          max_diff: int | None = None,
                          max_rounds: int = 10_000, K: int | None = None,
                          block_m: int = 256, pad_units: int | None = None
                          ) -> list[DeviceDecodeResult]:
    """Wave-peel U units' difference symbols in ONE batched device call.

    The synchronous convenience over :func:`decode_device_batched_start` —
    dispatch and immediately materialize.  Callers that can overlap host
    work with the device decode (the protocol engine's double-buffered
    tick loop) use start/``wait`` directly.
    """
    return decode_device_batched_start(
        units, nbytes=nbytes, key=key, max_diff=max_diff,
        max_rounds=max_rounds, K=K, block_m=block_m,
        pad_units=pad_units).wait()
