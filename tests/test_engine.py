"""ReconcileEngine: N concurrent peers on one plan/execute loop —
cross-peer batched decode, double-buffered pipeline, overflow pinning."""
import numpy as np
import pytest

from repro.core import Sketch
from repro.protocol import (FixedBlock, ProtocolError, ReconcileEngine,
                            Session, ShardedSession, ShardedStream,
                            SymbolStream, run_session, serve)

RNG = np.random.default_rng(1618)


def rand_items(n, nbytes, tag=None):
    out = RNG.integers(0, 256, size=(n, nbytes), dtype=np.uint8)
    if tag is not None:
        out[:, 0] = tag
    return out


def as_sorted_bytes(rows):
    return sorted(x.tobytes() for x in rows)


def stale_replica(state, lost, added, nbytes):
    """A replica missing the last ``lost`` rows plus ``added`` extras;
    returns (items, remote_only_truth, local_only_truth)."""
    extra = rand_items(added, nbytes, tag=9)
    items = np.concatenate([state[:-lost], extra]) if lost else \
        np.concatenate([state, extra])
    return items, state[-lost:] if lost else state[:0], extra


# ------------------------------------------------- N peers x S shards ----
@pytest.mark.parametrize("n_peers", [1, 3, 8])
@pytest.mark.parametrize("n_shards", [1, 4])
def test_engine_peers_within_overhead_band(n_peers, n_shards):
    """N concurrent peers x S shards on ONE engine: every peer recovers
    its exact difference and stays inside the paper's 1.35-2x overhead
    band (Fig. 4; d large enough for the measured regime)."""
    nbytes = 16
    state = rand_items(1500, nbytes, tag=0)
    lost, added = (40, 8) if n_shards == 1 else (140, 20)
    d = lost + added
    if n_shards == 1:
        stream = SymbolStream.from_items(state, nbytes)
    else:
        stream = ShardedStream.from_items(state, nbytes, n_shards=n_shards)
    engine = ReconcileEngine()
    truths = []
    for _ in range(n_peers):
        items, only_remote, only_local = stale_replica(
            state, lost, added, nbytes)
        if n_shards == 1:
            session = Session(local=Sketch.from_items(items, nbytes),
                              pacing=FixedBlock(8))
        else:
            session = stream.session(
                local=ShardedStream.from_items(items, nbytes,
                                               n_shards=n_shards),
                pacing=FixedBlock(8))
        engine.register(stream, session, wire=True)
        truths.append((only_remote, only_local))
    reports = engine.run()
    assert len(reports) == n_peers
    for rep, (only_remote, only_local) in zip(reports, truths):
        assert as_sorted_bytes(rep.only_remote_bytes()) == \
            as_sorted_bytes(only_remote)
        assert as_sorted_bytes(rep.only_local_bytes()) == \
            as_sorted_bytes(only_local)
        assert 1.0 <= rep.overhead(d) <= 2.0, \
            f"N={n_peers} S={n_shards}: overhead {rep.overhead(d):.2f}"
        assert rep.bytes_received > 0
    assert engine.ticks > 0


# ------------------------------------- one dispatch per shape bucket ----
def test_one_batched_dispatch_per_tick_with_8_peers(monkeypatch):
    """8 concurrent device-backend peers at the same pacing land in ONE
    shape bucket: every engine tick issues exactly one batched device
    dispatch regardless of peer count, and the per-unit decode_device
    path is never taken."""
    from repro.kernels import ops
    calls = {"start": 0}
    real = ops.decode_device_batched_start
    monkeypatch.setattr(
        ops, "decode_device_batched_start",
        lambda *a, **k: (calls.__setitem__("start", calls["start"] + 1)
                         or real(*a, **k)))
    monkeypatch.setattr(ops, "decode_device",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("per-unit decode_device called")))
    nbytes = 16
    state = rand_items(800, nbytes, tag=0)
    stream = SymbolStream.from_items(state, nbytes)
    engine = ReconcileEngine()          # pipeline=True -> async dispatches
    for _ in range(8):
        items, *_ = stale_replica(state, 24, 4, nbytes)
        engine.register(stream, Session(local=Sketch.from_items(items, nbytes),
                                        pacing=FixedBlock(8),
                                        backend="device"), wire=True)
    reports = engine.run()
    assert all(r.only_remote.shape[0] == 24 for r in reports)
    # same staleness + same pacing => identical per-tick shapes => exactly
    # one bucket, one batched dispatch per tick, for all 8 peers together
    assert calls["start"] == engine.dispatches == engine.ticks > 0


def test_mixed_progress_buckets_by_shape():
    """Peers at different stream depths split into (few) shape buckets,
    never into per-peer dispatches: dispatches <= buckets-per-tick sum,
    and the engine still recovers every difference."""
    nbytes = 16
    state = rand_items(1200, nbytes, tag=0)
    stream = SymbolStream.from_items(state, nbytes)
    engine = ReconcileEngine()
    for lost in (8, 8, 300):            # two cool peers + one deep peer
        items, *_ = stale_replica(state, lost, 2, nbytes)
        engine.register(stream, Session(local=Sketch.from_items(items, nbytes),
                                        pacing=FixedBlock(16),
                                        backend="device"), wire=True)
    reports = engine.run()
    assert [r.only_remote.shape[0] for r in reports] == [8, 8, 300]
    # 3 peers never cost 3 dispatches/tick: equal progress shares a bucket
    assert engine.dispatches < 3 * engine.ticks


# --------------------------------------------------- d=0 termination ----
def test_d0_peer_terminates_immediately_without_stalling_others():
    """An identical replica (d=0) settles on its very first absorb — no
    decode slot, no further requests — while stale peers keep going."""
    nbytes = 16
    state = rand_items(1000, nbytes, tag=0)
    stream = SymbolStream.from_items(state, nbytes)
    engine = ReconcileEngine()
    same = Session(local=Sketch.from_items(state.copy(), nbytes),
                   pacing=FixedBlock(8))
    stale = Session(local=Sketch.from_items(state[:-64], nbytes),
                    pacing=FixedBlock(8))
    engine.register(stream, same, wire=True)
    engine.register(stream, stale, wire=True)
    rep_same, rep_stale = engine.run()
    assert rep_same.only_remote.shape[0] == rep_same.only_local.shape[0] == 0
    assert rep_same.symbols_used <= 8          # first window was enough
    assert rep_same.symbols_received <= 8      # ... and it never re-pulled
    assert rep_stale.only_remote.shape[0] == 64
    assert rep_stale.symbols_used > 64         # kept running to completion


# ------------------------------------------------- pipeline semantics ----
@pytest.mark.parametrize("backend", ["host", "device"])
def test_pipeline_matches_serial_symbols_used(backend):
    """Double-buffering absorbs tick t+1 while tick t decodes; the
    termination point is pinned to the decoded prefix, so symbols_used
    (and therefore the reported overhead) matches the serial lockstep
    loop exactly — speculation only ever shows up in symbols_received."""
    nbytes = 16
    state = rand_items(1500, nbytes, tag=0)
    stream = SymbolStream.from_items(state, nbytes)
    mk = lambda: Session(local=Sketch.from_items(state[:-48], nbytes),
                         pacing=FixedBlock(8), backend=backend)
    rep_serial = run_session(stream, mk(), wire=True)
    rep_pipe = serve([(stream, mk())], wire=True, pipeline=True)[0]
    assert rep_pipe.symbols_used == rep_serial.symbols_used
    assert rep_pipe.symbols_received >= rep_serial.symbols_received
    assert as_sorted_bytes(rep_pipe.only_remote_bytes()) == \
        as_sorted_bytes(rep_serial.only_remote_bytes())


def test_pipeline_nonconvergence_still_raises():
    """A diverging peer raises through the pipelined loop too (the
    verdict is deferred past the in-flight decode, never dropped)."""
    nbytes = 16
    a = rand_items(40, nbytes, tag=1)
    b = rand_items(40, nbytes, tag=2)
    engine = ReconcileEngine()
    engine.register(SymbolStream.from_items(a, nbytes),
                    Session(local=Sketch.from_items(b, nbytes),
                            pacing=FixedBlock(4), max_m=8), wire=True)
    with pytest.raises(RuntimeError, match="did not converge"):
        engine.run()


# ----------------------------------------------- overflow host pinning ----
def test_overflowed_shards_stay_pinned_to_host(monkeypatch):
    """Satellite fix: once a shard overflows max_diff and falls back to
    the host peel, later grow steps keep it on the host — even across a
    mid-session set_backend("device") — instead of re-dispatching a
    residual already known to exceed the device buffers."""
    from repro.kernels import ops
    nbytes = 16
    state = rand_items(600, nbytes, tag=0)
    stream = ShardedStream.from_items(state, nbytes, n_shards=2)
    session = stream.session(
        local=ShardedStream.from_items(state[:-80], nbytes, n_shards=2),
        pacing=FixedBlock(8), backend="device", max_diff=2)
    # grow until every shard has tripped max_diff (d/S >> 2, so a device
    # decode can never finish a shard — the completing wave overflows)
    for _ in range(64):
        if all(u.pinned_host for u in session._shards):
            break
        reqs = session.requests()
        session.offer_windows([(s, stream.window(s, lo, hi), lo)
                               for s, lo, hi in reqs])
    assert all(u.pinned_host for u in session._shards)
    # mid-session backend churn must not unpin
    session.set_backend("host")
    session.set_backend("device")
    assert all(u.pinned_host for u in session._shards)
    # later rounds: no device dispatch at all — everything is pinned
    monkeypatch.setattr(ops, "decode_device_batched",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("pinned shard re-dispatched")))
    monkeypatch.setattr(ops, "decode_device_batched_start",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("pinned shard re-dispatched")))
    from repro.protocol import run_sharded_session
    rep = run_sharded_session(stream, session)
    assert rep.only_remote.shape[0] == 80
    assert session.grow_steps > 1


# ------------------------------------------------------- registration ----
def test_register_rejects_mismatched_pairs():
    nbytes = 16
    items = rand_items(100, nbytes)
    engine = ReconcileEngine()
    with pytest.raises(ProtocolError, match="partition"):
        engine.register(ShardedStream.from_items(items, nbytes, n_shards=4),
                        ShardedSession(n_shards=2, nbytes=nbytes))
    with pytest.raises(ProtocolError, match="ShardedSession"):
        engine.register(ShardedStream.from_items(items, nbytes, n_shards=4),
                        Session(nbytes=nbytes))


def test_engine_mixes_plain_and_sharded_peers():
    """One engine can serve a plain peer and a sharded peer side by side;
    each reports through its own flavour."""
    nbytes = 16
    state = rand_items(900, nbytes, tag=0)
    plain_stream = SymbolStream.from_items(state, nbytes)
    shard_stream = ShardedStream.from_items(state, nbytes, n_shards=4)
    engine = ReconcileEngine()
    engine.register(plain_stream,
                    Session(local=Sketch.from_items(state[:-40], nbytes),
                            pacing=FixedBlock(8)), wire=True)
    engine.register(shard_stream, shard_stream.session(
        local=ShardedStream.from_items(state[:-70], nbytes, n_shards=4),
        pacing=FixedBlock(8)), wire=True)
    rep_plain, rep_shard = engine.run()
    assert rep_plain.only_remote.shape[0] == 40
    assert rep_shard.only_remote.shape[0] == 70
    assert len(rep_shard.shards) == 4


# ------------------------------------------------ device work counters ----
@pytest.mark.parametrize("entry", ["engine", "session"])
def test_device_counters_add_up(monkeypatch, entry):
    """The reports' ``transfer_bytes`` sum to the bytes of every array
    staged to and fetched from the device (a batched bucket's padding
    unit included), their ``device_waves`` to the waves of the decode
    results, and their ``device_symbols`` to each decode's tile-padded
    prefix: three pipelined peers in one padded bucket, or one lone
    session on the non-pipelined path.  Every batched decode after a
    unit's first finds its residual on the device (``resident_decodes``),
    and the peers move fewer bytes than staging and fetching each
    bucket's whole residual alone would."""
    import jax
    from repro.kernels import ops
    moved = [0]
    results = {}
    restaged = [0]
    reads, waits = [0], [0]
    put, get = jax.device_put, jax.device_get
    wait = ops.PendingBatchedDecode.wait
    start = ops.decode_device_batched_start

    def spy_start(units, **k):
        # what staging and fetching the whole padded residual would move
        up = max(len(units), k.get("pad_units") or 0)
        mp = -(-max(u.m for u in units) // 256) * 256
        restaged[0] += 2 * up * mp * (4 * units[0].L + 12)
        return start(units, **k)

    def nbytes(tree):
        return sum(np.asarray(a).nbytes for a in jax.tree.leaves(tree))

    def spy_put(x, *a, **k):
        moved[0] += nbytes(x)
        return put(x, *a, **k)

    def spy_get(x):
        out = get(x)
        moved[0] += nbytes(out)
        reads[0] += 1
        return out

    def spy_wait(self):
        waits[0] += self._results is None
        out = wait(self)
        results.update((id(r), r) for r in out)
        return out
    monkeypatch.setattr(jax, "device_put", spy_put)
    monkeypatch.setattr(jax, "device_get", spy_get)
    monkeypatch.setattr(ops.PendingBatchedDecode, "wait", spy_wait)
    monkeypatch.setattr(ops, "decode_device_batched_start", spy_start)

    nbytes_ = 16
    state = rand_items(900, nbytes_, tag=0)
    stream = SymbolStream.from_items(state, nbytes_)
    mk = lambda lost: Session(local=Sketch.from_items(state[:-lost], nbytes_),
                              pacing=FixedBlock(16), backend="device")
    if entry == "engine":
        reports = serve([(stream, mk(lost)) for lost in (20, 30, 40)])
    else:
        reports = [run_session(stream, mk(30), wire=True)]
    assert [r.overflows + r.host_decodes for r in reports] == \
        [0] * len(reports)
    assert sum(r.transfer_bytes for r in reports) == moved[0] > 0
    assert sum(r.device_waves for r in reports) == \
        sum(r.rounds for r in results.values()) > 0
    for r in reports:
        assert r.device_waves >= r.device_decodes > 0
        # every decode here lands in the first tile bucket, mp = 256
        assert r.device_symbols == 256 * r.device_decodes
        assert r.resident_decodes == r.device_decodes - 1
    assert moved[0] < restaged[0]
    # each bucket's recovered rows fit one chunk: one read a dispatch
    assert reads[0] == waits[0] > 0


def test_lone_session_decodes_through_the_batched_program(monkeypatch):
    """``run_session`` on the device backend decodes each window as a
    batch of one unit, its residual kept on the device, and never through
    ``decode_device``; it recovers the difference exactly."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "decode_device",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("decode_device called")))
    nbytes = 16
    state = rand_items(900, nbytes, tag=0)
    items, only_remote, only_local = stale_replica(state, 40, 6, nbytes)
    stream = SymbolStream.from_items(state, nbytes)
    rep = run_session(stream, Session(local=Sketch.from_items(items, nbytes),
                                      pacing=FixedBlock(16),
                                      backend="device"), wire=True)
    assert as_sorted_bytes(rep.only_remote_bytes()) == \
        as_sorted_bytes(only_remote)
    assert as_sorted_bytes(rep.only_local_bytes()) == \
        as_sorted_bytes(only_local)
    assert rep.host_decodes == rep.overflows == 0
    assert rep.resident_decodes == rep.device_decodes - 1 > 0


# ------------------------------------------- the residual stays resident ----
# case: (pipelined engine, shards, each peer's (lost, added), max_diff)
RESIDENT_CASES = {
    # a d = 0 peer settles on absorb, the others at different ticks: the
    # batch shrinks through the padded unit counts 4, 2 and 1
    "pipelined": (True, 1, [(0, 0), (8, 2), (60, 4), (200, 8)], None),
    "sync": (False, 1, [(0, 0), (8, 2), (60, 4), (200, 8)], None),
    "sharded": (False, 4, [(140, 20)], None),
    # recovered rows cross in chunks of 16 rows, not one
    "chunked": (True, 1, [(0, 0), (8, 2), (60, 4), (200, 8)], None),
    # the deep peer recovers past max_diff in one decode mid-run: it takes
    # its residual back from the device and peels on the host
    "overflow": (True, 1, [(8, 2), (12, 2), (80, 8)], 32),
}


@pytest.mark.parametrize("case", sorted(RESIDENT_CASES))
def test_resident_residual_decodes_exactly(monkeypatch, case):
    """With each unit's residual kept on the device between decodes, every
    peer recovers the numpy set difference exactly, and the same sets at
    the same prefix as the exact host peel."""
    from repro.kernels import ops
    pipeline, n_shards, peers, max_diff = RESIDENT_CASES[case]
    nbytes = 16
    state = rand_items(1500, nbytes, tag=0)
    replicas = [stale_replica(state, lost, added, nbytes)
                for lost, added in peers]
    reads = []
    if case == "chunked":
        import jax
        monkeypatch.setattr(ops, "CHUNK_BYTES", 16 * 4 * (nbytes // 4 + 5))
        get = jax.device_get
        monkeypatch.setattr(jax, "device_get", lambda tree: reads.append(
            len(jax.tree.leaves(tree))) or get(tree))
    fetched = []
    fetch = ops.DeviceRows.fetch
    monkeypatch.setattr(ops.DeviceRows, "fetch",
                        lambda rows: fetched.append(rows.m) or fetch(rows))

    def run(backend):
        if n_shards == 1:
            stream = SymbolStream.from_items(state, nbytes)
        else:
            stream = ShardedStream.from_items(state, nbytes,
                                              n_shards=n_shards)
        engine = ReconcileEngine(pipeline=pipeline)
        for items, *_ in replicas:
            if n_shards == 1:
                session = Session(local=Sketch.from_items(items, nbytes),
                                  pacing=FixedBlock(16), backend=backend,
                                  max_diff=max_diff, max_m=4096)
            else:
                session = stream.session(
                    local=ShardedStream.from_items(items, nbytes,
                                                   n_shards=n_shards),
                    pacing=FixedBlock(8), backend=backend,
                    max_diff=max_diff, max_m=4096)
            engine.register(stream, session, wire=True)
        return engine.run()

    device, host = run("device"), run("host")
    for dev, ref, (_, only_remote, only_local) in zip(device, host,
                                                      replicas):
        assert as_sorted_bytes(dev.only_remote_bytes()) == \
            as_sorted_bytes(ref.only_remote_bytes()) == \
            as_sorted_bytes(only_remote)
        assert as_sorted_bytes(dev.only_local_bytes()) == \
            as_sorted_bytes(ref.only_local_bytes()) == \
            as_sorted_bytes(only_local)
        assert dev.symbols_used == ref.symbols_used
    assert 0 < sum(r.resident_decodes for r in device) < \
        sum(r.device_decodes for r in device)
    if case == "chunked":
        # the flags and first chunk in one read, several chunks in another
        assert max(reads) > 2
    if case == "overflow":
        assert [r.overflows > 0 for r in device] == [False, False, True]
        assert device[2].resident_decodes > 0 and fetched
    else:
        assert sum(r.overflows + r.host_decodes for r in device) == 0
        assert fetched == []


@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_planted_faults_reach_the_check(monkeypatch, fault):
    """The benchmark's planted faults stand in for the batched decode
    with resident residuals and show as what they are, not as a failing
    stand-in: results that leave each residual as it came never let a
    peer settle, so the round runs out of symbols; results of half the
    batch handed to the other half settle every peer, some on another
    peer's difference."""
    import sys
    from pathlib import Path
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import control
    from repro.kernels import ops
    monkeypatch.setattr(ops, "decode_device_batched_start",
                        control.plant(fault))
    nbytes = 16
    state = rand_items(900, nbytes, tag=0)
    stream = SymbolStream.from_items(state, nbytes)
    # same staleness, different extras: one bucket, different answers
    replicas = [stale_replica(state, 24, 4, nbytes) for _ in range(4)]
    engine = ReconcileEngine()
    for items, *_ in replicas:
        engine.register(stream, Session(local=Sketch.from_items(items, nbytes),
                                        pacing=FixedBlock(16),
                                        backend="device", max_m=1024),
                        wire=True)
    if fault == "unchanged":
        with pytest.raises(RuntimeError, match="did not converge"):
            engine.run()
        return
    reports = engine.run()
    wrong = [as_sorted_bytes(r.only_local_bytes()) != as_sorted_bytes(extra)
             for r, (_, _, extra) in zip(reports, replicas)]
    assert wrong == [False, False, True, True]


def test_second_round_compiles_nothing_and_keeps_no_buffer():
    """A second engine round on the same shapes triggers no backend
    compile, and once ``run()`` returns no device buffer of the round is
    left alive: every settled unit let go of its residual."""
    import gc

    import jax
    nbytes = 16
    state = rand_items(900, nbytes, tag=0)
    stream = SymbolStream.from_items(state, nbytes)

    def round_():
        # the sessions outlive the round, as a caller's would
        sessions = [Session(local=Sketch.from_items(state[:-lost], nbytes),
                            pacing=FixedBlock(16), backend="device")
                    for lost in (20, 30, 40)]
        return sessions, serve([(stream, s) for s in sessions])

    first = round_()
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)
    gc.collect()
    before = len(jax.live_arrays())
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        sessions, reports = round_()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    gc.collect()
    assert [r.only_remote.shape[0] for r in reports] == [20, 30, 40]
    assert all(r.resident_decodes for r in reports) and first and sessions
    assert compiles == []
    assert len(jax.live_arrays()) == before
