"""Incremental stream decoding (paper §4.1 protocol).

Alice streams coded symbols; Bob subtracts his own (locally generated)
symbols index-wise and peels as symbols arrive, terminating as soon as
symbol 0 empties (ρ(0)=1 ⇒ it is decoded last).  Already-recovered items are
XOR-ed out of newly arriving symbols by extending their mapping chains — the
decoder mirror of the encoder's incrementality.

With ``backend="device"`` the per-window peel runs through the batched
device decode (:func:`repro.kernels.ops.decode_device_batched`, here of one
unit) instead of the numpy loop: recovered items come back, and the host
keeps only the chain bookkeeping that extends recovered items into future
windows.  Both engines maintain the identical ``work``/recovered state, so
the backend can be switched between windows.

The peeled residual stays on the device between decodes, this decoder's
own or the protocol engine's: ``work`` is a :class:`~repro.core.symbols.
Residual` whose leading rows are the device's and whose tail, the rows
absorbed since, is the host's; a host peel first copies the device's rows
back (:meth:`StreamDecoder.to_host`).
"""
from __future__ import annotations

import numpy as np

from .decoder import resolve_backend
from .encoder import Encoder, _xor_accumulate
from .hashing import DEFAULT_KEY, siphash24
from .mapping import map_seeds, walk_chains
from .symbols import CodedSymbols, Residual


class StreamDecoder:
    """Decodes A △ B from an incrementally received prefix of A's stream.

    ``local`` is Bob's encoder for his set B (its prefix is extended in lock
    step and subtracted).  Pass ``local=None`` to decode a raw set stream.
    ``backend``: "host" | "device" | "auto" peel engine; ``max_diff`` bounds
    the device decoder's fixed recovered-item buffers (the default — the
    prefix length — cannot overflow, since a peel recovers at most one
    item per symbol; see :func:`repro.kernels.ops.decode_device`).
    """

    def __init__(self, nbytes: int, local: Encoder | None = None,
                 key=DEFAULT_KEY, backend: str = "host",
                 max_diff: int | None = None):
        self.nbytes = nbytes
        self.key = key
        self.local = local
        self.backend = resolve_backend(backend)
        self.max_diff = max_diff
        self.work = Residual(CodedSymbols.zeros(0, nbytes))
        self.rec_items = np.zeros((0, (nbytes + 3) // 4), np.uint32)
        self.rec_hashes = np.zeros(0, np.uint64)
        self.rec_sides = np.zeros(0, np.int8)
        # chain positions of recovered items at index == self.work.m
        self._rnext = np.zeros(0, np.int64)
        self._rstate = np.zeros(0, np.uint64)
        self.symbols_received = 0
        self.decoded_at: int | None = None  # symbols used at first decode

    # ------------------------------------------------------------------
    @property
    def decoded(self) -> bool:
        return bool(self.work.decoded())

    def receive(self, sym: CodedSymbols) -> bool:
        """Feed symbols [m, m+sym.m) of A's stream.  Returns `decoded`."""
        old, m = self.absorb(sym)
        if self.backend == "device":
            self._peel_device(old, m)
        else:
            self.peel_window(old, m)
        return self.mark_decoded()

    def absorb(self, sym: CodedSymbols) -> tuple[int, int]:
        """Ingest a window without peeling: subtract the local symbols,
        append to the residual ``work`` prefix, and extend every already-
        recovered item's chain through the new rows.

        Returns ``(old, new)`` — the prefix length before and after —
        for a later :meth:`peel_window` / batched device decode.  Splitting
        ingest from peel is what lets a sharded session absorb every
        shard's frame first and then decode all shards in one batched
        device call; plain sessions use :meth:`receive`, which is
        ``absorb`` + peel + :meth:`mark_decoded`.
        """
        old = self.work.m
        if self.local is not None:
            sym = sym.subtract(self.local.window(old, old + sym.m))
        else:
            sym = sym.copy()
        host = self.work.host
        self.work.host = host.concat(sym) if host.m else sym
        self.symbols_received = self.work.m
        m = self.work.m
        # extend recovered items' chains through the new rows
        self._walk(self.rec_items, self.rec_hashes, self.rec_sides,
                   self._rnext, self._rstate, m)
        return old, m

    def peel_window(self, old: int, m: int) -> None:
        """Host-peel rows [old, m) of the residual (plus whatever their
        removals touch) — the exact engine, also the per-shard overflow
        fallback of the batched device path."""
        self.to_host()
        self._peel(np.arange(old, m, dtype=np.int64))

    def to_host(self) -> int:
        """Copy the residual's device rows back, so that the host holds the
        whole prefix again; returns the bytes fetched (0 if it did)."""
        w = self.work
        if not w.base:
            return 0
        if w.device is None:
            raise RuntimeError("the residual's device rows were released "
                               "when the decoder settled")
        rows, fetched = w.device.fetch()
        self.work = Residual(rows.concat(w.host))
        return fetched

    def mark_decoded(self, at: int | None = None) -> bool:
        """Record the ρ(0)=1 termination point once; returns ``decoded``.

        ``at`` pins the recorded prefix length to the decode that actually
        produced the signal — a pipelined engine absorbs the next window
        *before* the previous decode's result lands, so at that moment
        ``symbols_received`` already includes speculative overshoot that
        the termination did not need.
        """
        done = self.decoded
        if done and self.decoded_at is None:
            self.decoded_at = self.symbols_received if at is None \
                else min(at, self.symbols_received)
        return done

    # ------------------------------------------------------------------
    def _walk(self, items, hashes, sides, nxt, state, hi):
        # chains reach only rows at or past the host's first row
        host, base = self.work.host, self.work.base

        def remove(live, idx):
            _xor_accumulate(host.sums, host.checks, host.counts, idx,
                            items[live], hashes[live],
                            -sides[live].astype(np.int64), base)

        return walk_chains(nxt, state, hi, remove)

    def _peel(self, cand: np.ndarray) -> None:
        work = self.work.host       # the whole prefix (``to_host``)
        m = work.m
        while cand.size:
            cand = np.unique(cand)
            h = siphash24(work.sums[cand], self.key, self.nbytes)
            pure = cand[(h == work.checks[cand]) & (work.counts[cand] != 0)]
            if pure.size == 0:
                return
            items = work.sums[pure]
            hashes = work.checks[pure]
            sides = np.sign(work.counts[pure]).astype(np.int8)
            _, first = np.unique(hashes, return_index=True)
            items, hashes, sides = items[first], hashes[first], sides[first]
            fresh = ~np.isin(hashes, self.rec_hashes)
            items, hashes, sides = items[fresh], hashes[fresh], sides[fresh]
            if items.shape[0] == 0:
                return
            n = items.shape[0]
            nxt = np.zeros(n, np.int64)
            state = map_seeds(items, self.key, self.nbytes).copy()
            cand = self._walk(items, hashes, sides, nxt, state, m)
            self.rec_items = np.concatenate([self.rec_items, items])
            self.rec_hashes = np.concatenate([self.rec_hashes, hashes])
            self.rec_sides = np.concatenate([self.rec_sides, sides])
            self._rnext = np.concatenate([self._rnext, nxt])
            self._rstate = np.concatenate([self._rstate, state])

    def _peel_device(self, old: int, m: int) -> None:
        """Wave-peel the residual prefix on device and merge.

        ``self.work`` already has previously recovered items removed, so
        the device decoder starts from a clean residual; only its host rows
        are staged.  It returns the newly recovered items, the peeled
        residual stays on the device, and the host walks each new item's
        chain to its first index ≥ m so later windows keep extending it
        (`_walk`).  A ``max_diff`` overflow falls back to the exact host
        peel for this window.
        """
        from repro.kernels.ops import decode_device_batched
        (res,) = decode_device_batched([self.work], nbytes=self.nbytes,
                                       key=self.key, max_diff=self.max_diff)
        if res.overflow:
            self.peel_window(old, m)
            return
        self.merge_device_result(res)

    def merge_device_result(self, res) -> None:
        """Fold one unit's successful
        :func:`repro.kernels.ops.decode_device_batched` outcome into host
        state: adopt
        the peeled residual as ``work`` and register each newly recovered
        item with its chain, started from the seed the device returned,
        advanced to the first index ≥ the prefix length (so later windows
        keep extending it).  ``res.overflow`` must be
        False — overflowed decodes leave state untouched and the caller
        falls back to :meth:`peel_window`.

        ``res.residual`` is a :class:`~repro.core.symbols.Residual`,
        normally the rows the decode left on the device, in which case the
        host drops the rows it had staged.

        Tail-aware: the decode may cover only a *prefix* of the current
        ``work`` (``res.residual.m ≤ work.m``) — a pipelined engine absorbs
        the next window while the device result is still in flight.  The
        rows absorbed after the dispatch are kept and each newly recovered
        item is removed from them by walking its chain through the tail,
        exactly as :meth:`absorb` does for previously recovered items.
        """
        assert not res.overflow
        got = res.residual
        m0 = got.m
        assert self.work.base <= m0 <= self.work.m
        tail = self.work.host.window(m0 - self.work.base)
        host = got.host.concat(tail) if got.host.m and tail.m else \
            got.host if got.host.m else tail
        self.work = Residual(host, got.base, got.device, got.row0_empty)
        if res.items.shape[0] == 0:
            return
        nxt = np.zeros(res.items.shape[0], np.int64)
        state = res.seeds.copy()   # the device hashed them as it peeled
        walk_chains(nxt, state, m0)  # position each chain at first idx >= m0
        # remove the new items from any tail rows and leave every chain
        # parked at the first index >= work.m for future windows
        self._walk(res.items, res.hashes, res.sides, nxt, state, self.work.m)
        self.rec_items = np.concatenate([self.rec_items, res.items])
        self.rec_hashes = np.concatenate([self.rec_hashes, res.hashes])
        self.rec_sides = np.concatenate([self.rec_sides, res.sides])
        self._rnext = np.concatenate([self._rnext, nxt])
        self._rstate = np.concatenate([self._rstate, state])

    # ------------------------------------------------------------------
    def result(self):
        """(items_exclusive_to_A, items_exclusive_to_B) as uint32 words."""
        a = self.rec_items[self.rec_sides > 0]
        b = self.rec_items[self.rec_sides < 0]
        return a, b
