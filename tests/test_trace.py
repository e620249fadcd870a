"""The reconciliation path's host spans (``repro.trace``), read back from a
profiler capture: every layer's span appears, each carries its request's
identifier, and the spans nest as the layers call each other."""
import glob
import os
from typing import NamedTuple

import jax
import numpy as np
import pytest

from repro import trace
from repro.core import Sketch
from repro.protocol import (FixedBlock, ReconcileEngine, Session,
                            SymbolStream, run_session)

RNG = np.random.default_rng(8)
NBYTES = 16
PER_PEER = {trace.SERVE, trace.WIRE_DECODE, trace.ABSORB, trace.MERGE,
            trace.HOST_PEEL}


class Span(NamedTuple):
    thread: tuple
    name: str
    start: float
    end: float
    args: dict


def captured(directory, fn):
    """Run ``fn`` under a profiler session; return its result and the
    ``repro.*`` spans of the capture."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(directory), profiler_options=opts):
        out = fn()
    spans = []
    for path in glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                          recursive=True):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        spans.append(Span(
                            (plane.name, line.name), e.name, e.start_ns,
                            e.start_ns + e.duration_ns, dict(e.stats)))
    return out, spans


def inside(child: Span, parent: Span) -> bool:
    return child.thread == parent.thread and \
        parent.start <= child.start and child.end <= parent.end


def nested(spans, child: str, parent: str) -> bool:
    """Every ``child`` span lies inside some ``parent`` span."""
    outer = [s for s in spans if s.name == parent]
    kids = [s for s in spans if s.name == child]
    return bool(kids) and all(any(inside(k, p) for p in outer) for k in kids)


@pytest.fixture(scope="module")
def state():
    return RNG.integers(0, 256, size=(600, NBYTES), dtype=np.uint8)


@pytest.fixture(scope="module")
def engine_run(state, tmp_path_factory):
    """Three device peers on one pipelined engine; the last one's
    ``max_diff`` is too small, so it overflows and peels on the host."""
    stream = SymbolStream.from_items(state, NBYTES)
    engine = ReconcileEngine()
    for lost, max_diff in ((20, None), (30, None), (40, 2)):
        engine.register(stream, Session(
            local=Sketch.from_items(state[:-lost], NBYTES),
            pacing=FixedBlock(16), backend="device", max_diff=max_diff))
    reports, spans = captured(tmp_path_factory.mktemp("engine"), engine.run)
    assert [r.only_remote.shape[0] for r in reports] == [20, 30, 40]
    assert reports[2].overflows > 0
    return engine, spans


@pytest.fixture(scope="module")
def session_run(state, tmp_path_factory):
    """One lone session through ``run_session`` (non-pipelined)."""
    stream = SymbolStream.from_items(state, NBYTES)
    session = Session(local=Sketch.from_items(state[:-25], NBYTES),
                      pacing=FixedBlock(16), backend="device")
    report, spans = captured(tmp_path_factory.mktemp("session"),
                             lambda: run_session(stream, session, wire=True))
    assert report.only_remote.shape[0] == 25
    return spans


def test_every_span_appears(engine_run, session_run):
    _, spans = engine_run
    assert {s.name for s in spans + session_run} == set(trace.NAMES)
    assert all(s.name.startswith("repro.") for s in spans)


def test_per_peer_spans_carry_the_peer(engine_run, session_run):
    _, spans = engine_run
    for s in spans + session_run:
        if s.name in PER_PEER:
            assert s.args["peer"] in (0, 1, 2), s
    by_peer = {s.args["peer"] for s in spans if s.name == trace.SERVE}
    assert by_peer == {0, 1, 2}
    assert {s.args["peer"] for s in spans if s.name == trace.HOST_PEEL} == \
        {2}
    assert {s.args["peer"] for s in session_run
            if s.name in PER_PEER} == {0}


def test_ticks_and_staging_carry_their_sizes(engine_run):
    engine, spans = engine_run
    ticks = [s for s in spans if s.name == trace.TICK]
    assert sorted(s.args["tick"] for s in ticks) == \
        list(range(1, engine.ticks + 1))
    assert all(s.args["units"] >= 1 and s.args["buckets"] >= 0
               for s in ticks)
    assert sum(s.args["buckets"] for s in ticks) == engine.dispatches
    for s in spans:
        if s.name == trace.STAGE:
            assert s.args["units"] >= 1 and s.args["mp"] % 256 == 0


def test_layers_nest(engine_run, session_run):
    _, spans = engine_run
    for run in (spans, session_run):
        assert nested(run, trace.WIRE_ENCODE, trace.SERVE)
    # non-pipelined: a tick absorbs its window, then decodes it
    for name in (trace.ABSORB, trace.STAGE, trace.WAIT, trace.MERGE):
        assert nested(session_run, name, trace.TICK), name
    # pipelined: the speculative gather and the finish run inside a tick
    for name in (trace.STAGE, trace.WAIT, trace.UNSTAGE, trace.MERGE):
        assert nested(spans, name, trace.TICK), name
