"""Reconciliation engine — one event-driven core serving N concurrent peers.

The paper's north-star deployment (§7, Ethereum state sync) is a node
reconciling against *many* peers at once.  Before this module each
``Session``/``ShardedSession`` owned its own grow loop, so N concurrent
peers meant N separate device dispatches per round and a fully serial
ingest → decode → request cycle.  The engine restates reconciliation as an
event loop with an explicit **plan/execute split**:

* **plan** — each tick, pending work from every registered peer is
  collected into a :class:`DecodePlan` of ``(peer, shard, window)``
  :class:`DecodeUnit`\\ s and coalesced by *shape bucket* (tile-padded
  prefix length, item geometry, session key, ``max_diff`` bound);
* **execute** — each bucket becomes ONE
  :func:`repro.kernels.ops.decode_device_batched` dispatch: the peel wave
  ``vmap``-ed over a ragged peer×shard unit axis with per-unit prefix
  lengths as traced data.  This generalizes the sharded session's
  cross-*shard* batching to cross-*peer* batching — 8 peers × 4 shards at
  the same pacing is still one device program per tick;
* **double-buffering** — with ``pipeline=True`` the device peels tick t's
  buckets as a JAX async dispatch (:class:`PendingRound`, polled
  non-blockingly) while the host absorbs tick t+1's frames and computes
  the next window requests from the stateless pacing policies.  Decode
  results merge *behind* the newly absorbed symbols
  (:meth:`repro.core.stream.StreamDecoder.merge_device_result` is
  tail-aware), and ``decoded_at`` is pinned to the prefix length the
  successful decode actually covered, so pipelining never inflates the
  reported overhead.

``Session`` and ``ShardedSession`` are thin single-peer wrappers over this
module: their ``offer``/``offer_windows`` paths delegate to
:func:`absorb_round` + :func:`execute_round`, so the grow-loop, overflow
fallback, termination and accounting logic live exactly once.  A unit
whose device decode overflows ``max_diff`` falls back to the exact host
peel and is **pinned to the host** from then on — re-dispatching a known
oversized residual to the device (e.g. after a mid-session
``set_backend``) would only buy another overflow.

Pull protocol, multi-peer::

    engine = ReconcileEngine()
    for stream, session in peers:
        engine.register(stream, session, wire=True)
    reports = engine.run()

:func:`run_session` / :func:`run_sharded_session` delegate their single
pair to a non-pipelined engine, which reproduces the legacy lockstep
trajectory exactly.
"""
from __future__ import annotations

from typing import NamedTuple

from repro.core.decoder import resolve_backend
from repro.core.stream import StreamDecoder
from repro.core.wire import decode_frames, decode_shard_frames
from repro.trace import (ABSORB, HOST_PEEL, MERGE, PLAN, REPORT, SERVE, TICK,
                         WIRE_DECODE, span)


class ProtocolError(RuntimeError):
    """A window arrived out of order / with inconsistent geometry."""


# ---------------------------------------------------------------------------
# Peer state: decode units + pacing + accounting, shared by every wrapper.
# ---------------------------------------------------------------------------
class UnitState:
    """One (peer, shard) decode unit: an incremental decoder plus its
    protocol bookkeeping.  ``pinned_host`` is set the first time a device
    decode of this unit overflows ``max_diff`` — from then on the unit
    peels on the host even if the peer's backend is (re)set to device."""

    __slots__ = ("shard", "decoder", "remote_items", "pinned_host")

    def __init__(self, shard: int, decoder: StreamDecoder):
        self.shard = shard
        self.decoder = decoder
        self.remote_items: int | None = None
        self.pinned_host = False


class PeerState:
    """Everything the engine knows about one registered peer.

    Owns the per-shard :class:`UnitState`\\ s (a plain session is the
    S=1 special case), the pacing policy, the backend/``max_diff`` decode
    configuration, and the wire accounting.  Wrappers keep a ``PeerState``
    as their single source of truth; a :class:`ReconcileEngine` drives any
    number of them through one shared plan/execute loop.  ``index`` is the
    peer's registration index on its engine, the ``peer`` argument of its
    trace spans (:mod:`repro.trace`).
    """

    def __init__(self, *, nbytes: int, key, locals_, pacing, max_m: int,
                 backend: str, max_diff: int | None, sharded: bool):
        self.nbytes = nbytes
        self.key = tuple(key)
        self.pacing = pacing
        self.max_m = max_m
        self.backend = resolve_backend(backend)
        self.max_diff = max_diff
        self.sharded = sharded
        self.index = 0
        self.bytes_received = 0
        self.grow_steps = 0
        # decode accounting: where each unit decode ran, and how many
        # device decodes overflowed max_diff (each then re-ran on the host)
        self.device_decodes = 0
        self.host_decodes = 0
        self.overflows = 0
        # device work of those decodes: peel waves run, tile-padded symbols
        # (each decode's mp), and host<->device bytes staged and fetched
        # (a batched bucket's split over its units); resident_decodes
        # counts the device decodes that found the unit's residual still
        # on the device from its last decode and staged only the new rows
        self.resident_decodes = 0
        self.device_waves = 0
        self.device_symbols = 0
        self.transfer_bytes = 0
        # the ENGINE owns decode dispatch (plan/execute), so the decoders
        # never self-dispatch here; their backend/max_diff are still kept
        # in sync so a decoder used directly (decoder.receive) behaves
        # like the session that owns it
        self.units = [
            UnitState(s, StreamDecoder(nbytes, local=loc, key=key,
                                       backend=self.backend,
                                       max_diff=max_diff))
            for s, loc in enumerate(locals_)]

    @property
    def n_units(self) -> int:
        return len(self.units)

    @property
    def decoded(self) -> bool:
        """True once every unit hit its ρ(0)=1 termination signal."""
        return all(u.decoder.decoded for u in self.units)

    @property
    def symbols_received(self) -> int:
        return sum(u.decoder.symbols_received for u in self.units)

    def set_backend(self, backend: str) -> None:
        self.backend = resolve_backend(backend)
        for u in self.units:
            u.decoder.backend = self.backend

    def requests(self, strict: bool = True) -> list[tuple[int, int, int]]:
        """Next window ``(shard, lo, hi)`` per still-undecoded unit.

        Window sizes come from the stateless pacing policy applied to each
        unit's own progress, clamped to ``max_m``.  A unit at ``max_m``
        without a decode signal raises ``RuntimeError`` (diverging
        reconciliation) — unless ``strict=False``, where it is skipped so
        a pipelined engine can defer the verdict until the unit's
        in-flight decode result lands.
        """
        reqs = []
        for u in self.units:
            if u.decoder.decoded:
                continue
            lo = u.decoder.symbols_received
            if lo >= self.max_m:
                if not strict:
                    continue
                what = f"shard {u.shard}" if self.sharded else \
                    "reconciliation"
                raise RuntimeError(f"{what} did not converge within "
                                   f"{self.max_m} symbols")
            reqs.append((u.shard, *self.pacing.next_window(lo, self.max_m)))
        return reqs


class DecodeUnit(NamedTuple):
    """One tick's pending work for one (peer, shard): the unit absorbed a
    window and rows ``[old, m)`` of its residual await peeling."""
    peer: PeerState
    unit: UnitState
    old: int
    m: int


# ---------------------------------------------------------------------------
# Ingest: validate + absorb (no peeling — that is the execute phase's job).
# ---------------------------------------------------------------------------
def validate_round(peer: PeerState, windows) -> list:
    """Check one round of ``(shard, symbols, start)`` windows against the
    peer's positions without mutating anything.

    Validation is all-or-nothing: every window is checked (shard id,
    order, geometry) before ANY state mutates, so a rejected round can be
    corrected and retried without losing symbols.  Overlap with already-
    consumed symbols is trimmed, wholly stale windows are dropped; a round
    may carry several windows for one unit, each validated against the
    position the previous ones will leave behind.  Returns the accepted
    ``(unit, symbols)`` list in arrival order.
    """
    have = {}
    accepted = []
    for shard_id, sym, start in windows:
        if not 0 <= shard_id < peer.n_units:
            raise ProtocolError(f"shard_id {shard_id} outside "
                                f"[0, {peer.n_units})")
        unit = peer.units[shard_id]
        pos = have.setdefault(shard_id, unit.decoder.symbols_received)
        if start > pos:
            where = f"shard {shard_id} gap" if peer.sharded else "gap"
            raise ProtocolError(f"{where}: expected window at {pos}, "
                                f"got {start}")
        if sym.nbytes != peer.nbytes:
            raise ProtocolError(f"geometry mismatch: ℓ={sym.nbytes}, "
                                f"session ℓ={peer.nbytes}")
        if start < pos:
            if start + sym.m <= pos:
                continue                      # wholly stale window
            sym = sym.window(pos - start)
        have[shard_id] = pos + sym.m
        accepted.append((unit, sym))
    return accepted


def absorb_round(peer: PeerState, windows) -> list[DecodeUnit]:
    """Validate and ingest one round of windows; return the decode units.

    Each touched unit absorbs all of its windows (local-symbol
    subtraction, chain extension of already-recovered items — see
    :meth:`repro.core.stream.StreamDecoder.absorb`) and contributes ONE
    :class:`DecodeUnit` covering everything it absorbed this round.  Units
    that terminate on absorb alone (a d=0 unit subtracts to an all-empty
    residual) are marked decoded immediately and excluded, so an identical
    peer never occupies a decode slot or stalls its neighbours.
    """
    accepted = validate_round(peer, windows)
    if not accepted:
        return []
    touched: dict[int, DecodeUnit] = {}
    with span(ABSORB, peer=peer.index):
        for unit, sym in accepted:
            old, m = unit.decoder.absorb(sym)
            prev = touched.get(unit.shard)
            touched[unit.shard] = DecodeUnit(peer, unit,
                                             prev.old if prev else old, m)
        peer.grow_steps += 1
        out = []
        for du in touched.values():
            if du.unit.decoder.mark_decoded(at=du.m):
                continue                      # settled on absorb alone
            out.append(du)
    return out


def ingest_frames(peer: PeerState, data: bytes) -> list[DecodeUnit]:
    """Absorb one self-describing wire frame (plain, single-unit peers)."""
    with span(WIRE_DECODE, peer=peer.index):
        sym, n_items, start = decode_frames(data)
    peer.bytes_received += len(data)
    peer.units[0].remote_items = n_items
    return absorb_round(peer, [(0, sym, start)])


def ingest_payload(peer: PeerState, data: bytes) -> list[DecodeUnit]:
    """Absorb one merged shard payload (sharded peers)."""
    with span(WIRE_DECODE, peer=peer.index):
        n_shards, frames = decode_shard_frames(data)
    if n_shards != peer.n_units:
        raise ProtocolError(f"partition mismatch: payload has {n_shards} "
                            f"shards, session {peer.n_units}")
    peer.bytes_received += len(data)
    windows = []
    for shard_id, sym, n_items, start in frames:
        if 0 <= shard_id < peer.n_units:
            peer.units[shard_id].remote_items = n_items
        windows.append((shard_id, sym, start))
    return absorb_round(peer, windows)


# ---------------------------------------------------------------------------
# Plan: bucket pending units by shape; Execute: one dispatch per bucket.
# ---------------------------------------------------------------------------
class DecodePlan:
    """One tick's decode work, split by engine and shape.

    ``host`` units peel on the exact numpy engine; ``buckets`` maps a
    shape key — ``(mp, L, nbytes, key, max_diff)`` with ``mp`` the
    tile-padded prefix length — to the units that batch into one
    :func:`repro.kernels.ops.decode_device_batched` dispatch.  Units of
    different peers land in the same bucket whenever their shapes agree
    (the common case for peers on the same pacing schedule), which is what
    makes the engine's device cost per tick O(#buckets), not O(#peers).
    """

    def __init__(self, host: list[DecodeUnit],
                 buckets: dict[tuple, list[DecodeUnit]]):
        self.host = host
        self.buckets = buckets


def build_plan(units: list[DecodeUnit], block_m: int = 256) -> DecodePlan:
    """Split pending units into host work and per-shape device buckets."""
    host, buckets = [], {}
    with span(PLAN):
        for du in units:
            if du.peer.backend != "device" or du.unit.pinned_host:
                host.append(du)
                continue
            mp = ((du.m + block_m - 1) // block_m) * block_m
            D = mp if du.peer.max_diff is None else \
                max(int(du.peer.max_diff), 1)
            key = (mp, du.unit.decoder.work.L, du.peer.nbytes, du.peer.key,
                   D)
            buckets.setdefault(key, []).append(du)
    return DecodePlan(host, buckets)


class PendingRound:
    """In-flight device work for one tick: one pending batched decode per
    shape bucket.  ``poll()`` is non-blocking; :meth:`finish` materializes
    results, merges them into the decoders (tail-aware, so symbols
    absorbed *after* dispatch survive), applies the per-unit host fallback
    on overflow — pinning the unit to the host — and records each unit's
    termination signal at the prefix length the decode covered."""

    def __init__(self, dispatches: list):
        self._dispatches = dispatches      # [(units, PendingBatchedDecode)]
        self.n_dispatches = len(dispatches)

    def poll(self) -> bool:
        """True once every bucket's device result is ready (non-blocking)."""
        return all(pending.ready() for _, pending in self._dispatches)

    def finish(self) -> None:
        for units, pending in self._dispatches:
            for du, res in zip(units, pending.wait()):
                du.peer.device_waves += res.rounds
                du.peer.transfer_bytes += res.transfer_bytes
                if res.overflow:
                    du.peer.overflows += 1
                    du.unit.pinned_host = True
                    _host_peel(du)
                    continue
                with span(MERGE, peer=du.peer.index):
                    dec = du.unit.decoder
                    dec.merge_device_result(res)
                    if dec.mark_decoded(at=du.m):
                        dec.work.device = None   # settled: free its rows


def _host_peel(du: DecodeUnit) -> None:
    """Peel one unit's new rows on the exact host engine, the residual's
    device rows copied back first."""
    du.peer.host_decodes += 1
    with span(HOST_PEEL, peer=du.peer.index):
        du.peer.transfer_bytes += du.unit.decoder.to_host()
        du.unit.decoder.peel_window(du.old, du.m)
        du.unit.decoder.mark_decoded(at=du.m)


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def execute_round(units: list[DecodeUnit], block_m: int = 256,
                  pipeline: bool = False) -> PendingRound:
    """Decode one tick's absorbed units: host units peel immediately, each
    device bucket becomes one batched dispatch.  With ``pipeline=False``
    each bucket is decoded synchronously
    (:func:`repro.kernels.ops.decode_device_batched`) and the round is
    finished before returning; with ``pipeline=True`` each bucket is an
    async :func:`~repro.kernels.ops.decode_device_batched_start` dispatch
    and the returned :class:`PendingRound` is still in flight — the caller
    overlaps host ingest with it before calling ``finish()``.

    The unit axis is padded to the next power of two (``pad_units``): the
    unit count is a static shape in the per-bucket jit cache, so peers
    settling one by one re-use one compiled program instead of
    recompiling per departure.  A unit's residual stays on the device
    from one batched decode to the next, and only the rows absorbed since
    are staged.  A bucket of one unit, a lone plain session's, is a batch
    like any other."""
    from repro.kernels import ops
    plan = build_plan(units, block_m)
    for du in plan.host:
        _host_peel(du)
    dispatches = []
    for (mp, L, nbytes, key, D), us in plan.buckets.items():
        works = [du.unit.decoder.work for du in us]
        for du, work in zip(us, works):
            du.peer.device_decodes += 1
            du.peer.resident_decodes += work.base > 0
            du.peer.device_symbols += mp
        if pipeline:
            pending = ops.decode_device_batched_start(
                works, nbytes=nbytes, key=key, max_diff=D, block_m=block_m,
                pad_units=_next_pow2(len(us)))
        else:
            pending = ops.PendingBatchedDecode(
                None, None, (), nbytes, results=ops.decode_device_batched(
                    works, nbytes=nbytes, key=key, max_diff=D,
                    block_m=block_m, pad_units=_next_pow2(len(us))))
        dispatches.append((us, pending))
    round_ = PendingRound(dispatches)
    if not pipeline:
        round_.finish()
    return round_


def offer_round(peer: PeerState, windows) -> bool:
    """The wrappers' push-style entry: absorb one round of in-process
    windows and decode it synchronously.  Returns ``decoded``."""
    execute_round(absorb_round(peer, windows))
    return peer.decoded


# ---------------------------------------------------------------------------
# The engine: N peers, one tick loop.
# ---------------------------------------------------------------------------
class _Registered(NamedTuple):
    stream: object      # SymbolStream | ShardedStream
    session: object     # Session | ShardedSession
    peer: PeerState
    wire: bool


class ReconcileEngine:
    """Drive any number of (stream, session) pairs through one shared
    plan/execute loop.

    Parameters
    ----------
    pipeline: overlap device decode with host ingest (double-buffering).
        While tick t's buckets peel on the device, the engine already
        fetches and absorbs tick t+1's frames — speculatively, from the
        stateless pacing policies — and only then blocks on tick t's
        results.  Peers whose decode lands keep their speculative window
        as ordinary pacing overshoot (``symbols_received`` grows,
        ``symbols_used`` does not — the termination point is pinned to the
        decoded prefix).  ``False`` reproduces the serial lockstep
        request → offer → decode trajectory of the legacy per-session
        loops exactly; :func:`~repro.protocol.session.run_session` uses
        that mode.
    block_m: device tile size — the shape-bucket quantum.

    ``ticks`` counts plan/execute rounds, ``dispatches`` the batched
    device programs issued; with N peers on one pacing schedule
    ``dispatches == ticks`` regardless of N.
    """

    def __init__(self, *, pipeline: bool = True, block_m: int = 256):
        self.pipeline = pipeline
        self.block_m = block_m
        self.ticks = 0
        self.dispatches = 0
        self._peers: list[_Registered] = []

    # -- registration -------------------------------------------------------
    def register(self, stream, session, *, wire: bool = True) -> int:
        """Attach one (stream, session) pair; returns its index.

        ``session`` is an ordinary :class:`~repro.protocol.session.Session`
        or :class:`~repro.protocol.sharded.ShardedSession` — the engine
        adopts its :class:`PeerState`, so a session driven to completion
        here reports through its own ``report()`` exactly as if it had
        been driven by its own wrapper loop.  Sharded pairs must agree on
        the partition up front (mixed shard counts would silently
        mis-reconcile in-process).
        """
        peer = session._peer
        n_shards = getattr(stream, "n_shards", None)
        if peer.sharded:
            if n_shards != peer.n_units:
                raise ProtocolError(
                    f"partition mismatch: stream has {n_shards} shards, "
                    f"session {peer.n_units}")
        elif n_shards is not None:
            raise ProtocolError("plain Session registered against a "
                                "ShardedStream; use ShardedSession")
        peer.index = len(self._peers)
        self._peers.append(_Registered(stream, session, peer, wire))
        return peer.index

    # -- ingest (request + fetch + absorb, no decode) -----------------------
    def _gather_one(self, entry: _Registered,
                    strict: bool = True) -> list[DecodeUnit]:
        peer, stream = entry.peer, entry.stream
        reqs = peer.requests(strict=strict)
        if not reqs:
            return []
        with span(SERVE, peer=peer.index):
            if peer.sharded:
                got = stream.payload(reqs) if entry.wire else \
                    [(s, stream.window(s, lo, hi), lo) for s, lo, hi in reqs]
            else:
                ((_, lo, hi),) = reqs
                got = stream.frames(lo, hi) if entry.wire else \
                    [(0, stream.window(lo, hi), lo)]
        if not entry.wire:
            return absorb_round(peer, got)
        return (ingest_payload if peer.sharded else ingest_frames)(peer, got)

    def _gather(self, strict: bool = True) -> list[DecodeUnit]:
        units = []
        for entry in self._peers:
            if not entry.peer.decoded:
                units += self._gather_one(entry, strict=strict)
        return units

    # -- the loop -----------------------------------------------------------
    def tick(self) -> bool:
        """One synchronous plan/execute round over all live peers.
        Returns True while any peer still has work (event-driven callers
        loop on it; :meth:`run` adds the double-buffered fast path)."""
        with span(TICK, tick=self.ticks + 1) as s:
            units = self._gather()
            if units:
                self.ticks += 1
                n = execute_round(units, self.block_m).n_dispatches
                self.dispatches += n
                s.set_metadata(units=len(units), buckets=n)
        return any(not e.peer.decoded for e in self._peers)

    def run(self) -> list:
        """Drive every registered peer to termination; returns reports in
        registration order.  A settled unit lets go of its residual's
        device rows; should the run raise, every unit still at work gets
        its rows back on the host, so the device keeps none."""
        try:
            return self._run()
        finally:
            for entry in self._peers:
                for u in entry.peer.units:
                    if not u.decoder.decoded:
                        entry.peer.transfer_bytes += u.decoder.to_host()

    def _run(self) -> list:
        if not self.pipeline:
            while self.tick():
                pass
            return self.reports()
        staged = self._gather()
        while staged:
            self.ticks += 1
            with span(TICK, tick=self.ticks, units=len(staged)) as s:
                round_ = execute_round(staged, self.block_m, pipeline=True)
                self.dispatches += round_.n_dispatches
                s.set_metadata(buckets=round_.n_dispatches)
                # device busy → absorb the next tick's frames now.
                # Speculative: decodes in flight count as "not decoded",
                # and a unit already at max_m defers its non-convergence
                # verdict.
                staged = self._gather(strict=False)
                round_.finish()
                # units that deferred (skipped by the speculative gather,
                # still undecoded after their results landed) get an
                # authoritative verdict now — this is where a genuinely
                # diverging reconciliation raises, at most one tick later
                # than serial.
                speculated = {id(du.unit) for du in staged}
                for entry in self._peers:
                    peer = entry.peer
                    if peer.decoded:
                        continue
                    pending = [u for u in peer.units
                               if not u.decoder.decoded]
                    unstaged = [u for u in pending
                                if id(u) not in speculated]
                    for u in unstaged:
                        if u.decoder.symbols_received >= peer.max_m:
                            what = f"shard {u.shard}" if peer.sharded else \
                                "reconciliation"
                            raise RuntimeError(
                                f"{what} did not converge within "
                                f"{peer.max_m} symbols")
                    if unstaged:
                        # defensive: an undecoded unit below max_m is
                        # always staged by the speculative gather today —
                        # regather authoritatively rather than exit with
                        # it stalled
                        staged += self._gather_one(entry, strict=True)
                # drop speculative units whose peer terminated meanwhile —
                # the absorbed window stays as accounted pacing overshoot.
                staged = [du for du in staged
                          if not du.unit.decoder.decoded]
        return self.reports()

    # -- outcome ------------------------------------------------------------
    def reports(self) -> list:
        """Current reports for every registered peer, in registration
        order (valid mid-run: undecoded peers report partial recovery)."""
        with span(REPORT):
            return [entry.session.report() for entry in self._peers]


def serve(pairs, *, wire: bool = True, backend: str | None = None,
          pipeline: bool = True) -> list:
    """Drive ``(stream, session)`` pairs to completion on one engine.

    The multi-peer counterpart of :func:`~repro.protocol.session.run_session`:
    all sessions advance in shared ticks, decode work batches across peers
    per shape bucket, and (with ``pipeline=True``) device decode overlaps
    host ingest.  Returns the reports in input order.
    """
    engine = ReconcileEngine(pipeline=pipeline)
    for stream, session in pairs:
        if backend is not None:
            session.set_backend(backend)
        engine.register(stream, session, wire=wire)
    return engine.run()
