"""Sharded serving: stable partition, merged payloads, shard invariance of
the recovered difference, and the one-batched-device-call decode path."""
import numpy as np
import pytest

from repro.core import Encoder, Sketch
from repro.core.symbols import Residual
from repro.core.wire import (decode_shard_frames, encode_frames,
                             encode_shard_frames)
from repro.protocol import (FixedBlock, ProtocolError, ShardedReport,
                            ShardedSession, ShardedStream, run_session,
                            run_sharded_session, shard_of)

RNG = np.random.default_rng(2718)


def rand_items(n, nbytes, tag=None):
    out = RNG.integers(0, 256, size=(n, nbytes), dtype=np.uint8)
    if tag is not None:
        out[:, 0] = tag
    return out


def two_sets(n_common, da, db, nbytes):
    common = rand_items(n_common, nbytes, tag=0)
    ai = rand_items(da, nbytes, tag=1)
    bi = rand_items(db, nbytes, tag=2)
    return (np.concatenate([common, ai]), np.concatenate([common, bi]),
            ai, bi)


def as_sorted_bytes(rows):
    return sorted(x.tobytes() for x in rows)


# --------------------------------------------------------- partitioning ----
def test_shard_of_is_a_stable_partition():
    items = rand_items(5000, 16)
    ids = shard_of(items, 8, nbytes=16)
    assert ids.shape == (5000,) and ids.min() >= 0 and ids.max() < 8
    # deterministic, order-independent, and identical for byte/word input
    np.testing.assert_array_equal(ids, shard_of(items, 8, nbytes=16))
    perm = RNG.permutation(5000)
    np.testing.assert_array_equal(ids[perm], shard_of(items[perm], 8,
                                                      nbytes=16))
    from repro.core.hashing import bytes_to_words
    np.testing.assert_array_equal(
        ids, shard_of(bytes_to_words(items, 16), 8, nbytes=16))
    # S=1 degenerates to the unsharded stream
    assert (shard_of(items, 1, nbytes=16) == 0).all()
    # no empty-by-construction shard: every id appears on a 5000-item set
    assert set(np.unique(ids)) == set(range(8))
    # a different session key yields a different partition
    other = shard_of(items, 8, key=(123, 456), nbytes=16)
    assert (ids != other).any()
    with pytest.raises(ValueError):
        shard_of(items, 0, nbytes=16)


def test_sharded_stream_routes_mutations():
    nbytes = 16
    items = rand_items(400, nbytes)
    stream = ShardedStream.from_items(items, nbytes, n_shards=4)
    assert stream.n_items == 400
    ids = shard_of(items, 4, nbytes=nbytes)
    per_shard = [int((ids == s).sum()) for s in range(4)]
    assert [st.n_items for st in stream.shards] == per_shard
    extra = rand_items(40, nbytes, tag=7)
    stream.add_items(extra)
    assert stream.n_items == 440
    stream.remove_items(items[:100])
    assert stream.n_items == 340
    ids2 = shard_of(np.concatenate([items[100:], extra]), 4, nbytes=nbytes)
    assert [st.n_items for st in stream.shards] == \
        [int((ids2 == s).sum()) for s in range(4)]


# --------------------------------------------------------- wire payload ----
def test_shard_frames_roundtrip():
    nbytes = 8
    enc = Encoder(nbytes)
    enc.add_items(rand_items(200, nbytes))
    frames = [(0, encode_frames(enc.window(0, 16), start=0, n_items=200)),
              (3, encode_frames(enc.window(16, 50), start=16, n_items=200))]
    payload = encode_shard_frames(frames, n_shards=4)
    n_shards, out = decode_shard_frames(payload)
    assert n_shards == 4 and len(out) == 2
    sid, sym, n_items, start = out[0]
    assert (sid, n_items, start, sym.m) == (0, 200, 0, 16)
    np.testing.assert_array_equal(sym.sums, enc.window(0, 16).sums)
    sid, sym, n_items, start = out[1]
    assert (sid, n_items, start, sym.m) == (3, 200, 16, 34)
    np.testing.assert_array_equal(sym.counts, enc.window(16, 50).counts)
    # empty payloads are legal (every shard settled)
    assert decode_shard_frames(encode_shard_frames([], 4)) == (4, [])


def test_shard_frames_rejects_garbage():
    nbytes = 8
    frame = encode_frames(Encoder(nbytes).window(0, 4), n_items=0)
    with pytest.raises(ValueError, match="magic"):
        decode_shard_frames(b"XXXX" + b"\x00" * 16)
    with pytest.raises(ValueError, match="truncated"):
        decode_shard_frames(b"")
    with pytest.raises(ValueError, match="truncated"):
        decode_shard_frames(encode_shard_frames([(0, frame)], 2)[:-5])
    with pytest.raises(ValueError, match="shard_id"):
        encode_shard_frames([(2, frame)], 2)
    with pytest.raises(ValueError):
        encode_shard_frames([(0, frame)], 0)
    # shard id beyond the declared partition on the decode side
    bad = bytearray(encode_shard_frames([(1, frame)], 2))
    bad[8:10] = (9).to_bytes(2, "little")      # patch the ext shard_id
    with pytest.raises(ValueError, match="shard_id"):
        decode_shard_frames(bytes(bad))


# ----------------------------------------------------- shard invariance ----
@pytest.mark.parametrize("backend", ["host", "device"])
@pytest.mark.parametrize("n_shards", [1, 2, 8])
def test_shard_invariance_property(n_shards, backend):
    """Reconciling a random diff sharded S ∈ {1, 2, 8} ways recovers the
    IDENTICAL symmetric difference, and total coded symbols stay within
    the paper's 1.35–2x overhead band (Fig. 4; d/S ≥ 32 per shard so each
    shard decodes inside the measured regime)."""
    nbytes = 16
    a_items, b_items, ai, bi = two_sets(3000, 320, 80, nbytes)
    d = 400
    stream = ShardedStream.from_items(a_items, nbytes, n_shards=n_shards)
    local = ShardedStream.from_items(b_items, nbytes, n_shards=n_shards)
    session = stream.session(local=local, pacing=FixedBlock(8),
                             backend=backend,
                             max_diff=128 if backend == "device" else None)
    rep = run_session(stream, session, wire=True)   # dispatches on type
    assert isinstance(rep, ShardedReport)
    assert len(rep.shards) == n_shards
    # the union over shards IS the unsharded symmetric difference
    assert as_sorted_bytes(rep.only_remote_bytes()) == as_sorted_bytes(ai)
    assert as_sorted_bytes(rep.only_local_bytes()) == as_sorted_bytes(bi)
    # paper overhead band on TOTAL symbols at decode (2x hard ceiling)
    assert 1.0 <= rep.overhead(d) <= 2.0, \
        f"S={n_shards}: overhead {rep.overhead(d):.2f}"
    assert rep.bytes_received > 0
    assert rep.remote_items == len(a_items)
    # per-shard decode signals: every shard terminated on its own ρ(0)=1
    assert sum(sr.symbols_used for sr in rep.shards) == rep.symbols_used
    assert all(sr.symbols_used >= 1 for sr in rep.shards)


def test_sharded_in_process_equals_wire():
    nbytes = 16
    a_items, b_items, ai, bi = two_sets(800, 40, 10, nbytes)
    mk = lambda: ShardedSession(
        local=ShardedStream.from_items(b_items, nbytes, n_shards=4),
        pacing=FixedBlock(8))
    stream = ShardedStream.from_items(a_items, nbytes, n_shards=4)
    rep_wire = run_sharded_session(stream, mk(), wire=True)
    rep_mem = run_sharded_session(stream, mk(), wire=False)
    assert rep_wire.symbols_used == rep_mem.symbols_used
    assert rep_wire.bytes_received > 0 and rep_mem.bytes_received == 0
    assert as_sorted_bytes(rep_wire.only_remote_bytes()) == \
        as_sorted_bytes(rep_mem.only_remote_bytes()) == as_sorted_bytes(ai)


# ------------------------------------------------- batched device decode ----
def test_device_grow_step_is_one_batched_dispatch(monkeypatch):
    """S=8 device decode issues exactly ONE decode_device_batched call per
    grow step and never falls into the per-shard decode_device path."""
    from repro.kernels import ops
    calls = {"batched": 0, "single": 0}
    real = ops.decode_device_batched
    monkeypatch.setattr(ops, "decode_device_batched",
                        lambda *a, **k: (calls.__setitem__(
                            "batched", calls["batched"] + 1) or real(*a, **k)))
    monkeypatch.setattr(ops, "decode_device",
                        lambda *a, **k: (_ for _ in ()).throw(
                            AssertionError("per-shard decode_device called")))
    nbytes = 16
    a_items, b_items, ai, bi = two_sets(600, 30, 10, nbytes)
    stream = ShardedStream.from_items(a_items, nbytes, n_shards=8)
    session = stream.session(
        local=ShardedStream.from_items(b_items, nbytes, n_shards=8),
        pacing=FixedBlock(8), backend="device", max_diff=64)
    rep = run_sharded_session(stream, session)
    assert calls["batched"] == rep.grow_steps > 0
    assert as_sorted_bytes(rep.only_remote_bytes()) == as_sorted_bytes(ai)
    assert as_sorted_bytes(rep.only_local_bytes()) == as_sorted_bytes(bi)


def test_decode_device_batched_overflow_is_per_shard():
    """One hot shard tripping max_diff flags ONLY itself; its neighbours
    in the same batched call decode to completion."""
    from repro.kernels.ops import decode_device_batched
    nbytes = 8
    m = 96
    shards = []
    for d in (2, 30):              # cool shard, hot shard
        items = RNG.integers(0, 2**32, size=(300, 2), dtype=np.uint32)
        A, B = Encoder(nbytes), Encoder(nbytes)
        A.add_items(items)
        B.add_items(items[:-d])
        shards.append(A.symbols(m).subtract(B.symbols(m)))
    res = decode_device_batched([Residual(s) for s in shards], nbytes=nbytes,
                                max_diff=8)
    assert res[0].success and not res[0].overflow
    assert res[0].items.shape[0] == 2
    assert res[1].overflow and not res[1].success
    # frozen state: the hot shard recovered nothing it can hand back
    assert res[1].items.shape[0] <= 8


def test_session_overflow_falls_back_per_shard():
    """A tiny per-shard max_diff overflows the device buffers; every shard
    falls back to the exact host peel individually and the reconciliation
    still recovers the exact difference."""
    nbytes = 16
    a_items, b_items, ai, bi = two_sets(500, 36, 6, nbytes)
    stream = ShardedStream.from_items(a_items, nbytes, n_shards=4)
    session = stream.session(
        local=ShardedStream.from_items(b_items, nbytes, n_shards=4),
        pacing=FixedBlock(8), backend="device", max_diff=2)
    rep = run_sharded_session(stream, session)
    assert as_sorted_bytes(rep.only_remote_bytes()) == as_sorted_bytes(ai)
    assert as_sorted_bytes(rep.only_local_bytes()) == as_sorted_bytes(bi)


# ----------------------------------------------------------- protocol ----
def test_sharded_session_protocol_errors():
    nbytes = 16
    items = rand_items(200, nbytes)
    stream = ShardedStream.from_items(items, nbytes, n_shards=4)
    sess = ShardedSession(n_shards=8, nbytes=nbytes)     # wrong partition
    with pytest.raises(ProtocolError, match="partition"):
        sess.offer_payload(stream.payload([(0, 0, 8)]))
    sess = ShardedSession(n_shards=4, nbytes=nbytes)
    with pytest.raises(ProtocolError, match="gap"):
        sess.offer_payload(stream.payload([(1, 8, 16)]))
    # overlap is trimmed, stale windows are no-ops
    sess.offer_payload(stream.payload([(1, 0, 8)]))
    sess.offer_payload(stream.payload([(1, 4, 12), (1, 0, 4)]))
    assert sess._shards[1].decoder.symbols_received == 12
    with pytest.raises(ValueError):
        ShardedSession(nbytes=nbytes)                    # no n_shards
    with pytest.raises(ValueError):
        ShardedSession(local=ShardedStream.from_items(items, nbytes, 4),
                       n_shards=8)                       # mismatched local


def test_offer_windows_validates_before_absorbing():
    """A bad window anywhere in a round rejects the WHOLE round: no shard
    absorbs anything, so a corrected retry is not treated as stale."""
    nbytes = 16
    items = rand_items(300, nbytes)
    stream = ShardedStream.from_items(items, nbytes, n_shards=2)
    sess = ShardedSession(n_shards=2, nbytes=nbytes)
    with pytest.raises(ProtocolError, match="gap"):
        sess.offer_windows([(0, stream.window(0, 0, 16), 0),
                            (1, stream.window(1, 8, 16), 8)])
    assert sess._shards[0].decoder.symbols_received == 0   # nothing absorbed
    with pytest.raises(ProtocolError, match="shard_id"):
        sess.offer_windows([(0, stream.window(0, 0, 16), 0),
                            (5, stream.window(1, 0, 8), 0)])
    assert sess._shards[0].decoder.symbols_received == 0
    # the corrected retry of the same round is consumed in full
    sess.offer_windows([(0, stream.window(0, 0, 16), 0),
                        (1, stream.window(1, 0, 16), 0)])
    assert all(st.decoder.symbols_received == 16 for st in sess._shards)
    # several windows for ONE shard in one round validate against the
    # simulated position, not the stale pre-round one
    sess.offer_windows([(0, stream.window(0, 16, 24), 16),
                        (0, stream.window(0, 24, 32), 24)])
    assert sess._shards[0].decoder.symbols_received == 32


def test_run_sharded_rejects_partition_mismatch():
    """Driving mismatched partitions must raise, not silently
    mis-reconcile (in-process windows carry no n_shards header)."""
    nbytes = 16
    items = rand_items(200, nbytes)
    stream = ShardedStream.from_items(items, nbytes, n_shards=4)
    sess = ShardedSession(
        local=ShardedStream.from_items(items[:-5], nbytes, n_shards=2))
    with pytest.raises(ProtocolError, match="partition"):
        run_sharded_session(stream, sess, wire=False)
    with pytest.raises(ProtocolError, match="partition"):
        run_sharded_session(stream, sess, wire=True)


def test_raw_stream_sharded_decode():
    """local=None recovers the remote shard sets themselves."""
    nbytes = 16
    items = rand_items(48, nbytes)
    stream = ShardedStream.from_items(items, nbytes, n_shards=2)
    sess = ShardedSession(n_shards=2, nbytes=nbytes, pacing=FixedBlock(16))
    rep = run_sharded_session(stream, sess)
    assert as_sorted_bytes(rep.only_remote_bytes()) == as_sorted_bytes(items)
    assert rep.only_local.shape[0] == 0
    assert rep.remote_items == 48


def test_sharded_stream_update_then_sync():
    """Linearity per shard: after add/remove the same sharded stream
    serves correct syncs to a fresh session."""
    nbytes = 16
    state = rand_items(1000, nbytes, tag=0)
    stream = ShardedStream.from_items(state, nbytes, n_shards=4)
    _ = stream.payload([(s, 0, 16) for s in range(4)])   # materialize caches
    new = rand_items(5, nbytes, tag=5)
    stream.add_items(new)
    stream.remove_items(state[:3])
    truth = np.concatenate([state[3:], new])
    local = np.concatenate([truth[:-7], rand_items(2, nbytes, tag=7)])
    sess = stream.session(
        local=ShardedStream.from_items(local, nbytes, n_shards=4),
        pacing=FixedBlock(8))
    rep = run_sharded_session(stream, sess)
    assert as_sorted_bytes(rep.only_remote_bytes()) == \
        as_sorted_bytes(truth[-7:])
    assert as_sorted_bytes(rep.only_local_bytes()) == \
        as_sorted_bytes(local[-2:])
