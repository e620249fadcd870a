"""The reduction from a device trace to busy, idle and program time, and
to self and idle time by host span."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cell, run  # noqa: E402
from bench import trace as tr  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, line, name, start, dur):
    return tr.Event(plane, line, name, float(start), float(dur))


def test_reduce_by_hand():
    events = [
        ev(HOST, "python", tr.SPAN, 100, 1000),          # span [100, 1100)
        ev(HOST, "python", "bench.register", 100, 200),  # [100, 300)
        ev(HOST, "python", "bench.run", 300, 800),       # [300, 1100)
        ev(DEV, tr.MODULES_LINE, "jit_run", 350, 300),   # [350, 650)
        ev(DEV, tr.OPS_LINE, "fusion.1", 350, 100),      # [350, 450)
        ev(DEV, tr.OPS_LINE, "fusion.2", 400, 150),      # [400, 550) overlaps
        ev(DEV, tr.OPS_LINE, "copy.3", 600, 50),         # [600, 650)
        ev(DEV, tr.OPS_LINE, "fusion.1", 1050, 100),     # [1050, 1150) clipped
        ev(DEV, tr.OPS_LINE, "early", 0, 50),            # before the span
    ]
    red = tr.reduce(events)
    # busy: [350, 550) + [600, 650) + [1050, 1100) = 200 + 50 + 50
    assert red.busy_ns == 300 and red.window_ns == 1000
    assert red.idle_share == pytest.approx(0.7)
    assert red.program_ns == 300 and red.programs == {"jit_run": 300}
    assert red.ops == {"fusion.1": 150, "fusion.2": 150, "copy.3": 50}
    # gaps [100, 350), [550, 600), [650, 1050), each nanosecond to the span
    # covering it: [100, 300) register; [300, 350) and the rest in run
    assert red.idle_ns == {"bench.register": 200, "bench.run": 500}
    b = tr.breakdown(red, top=2)
    assert b["device_ops"] == [["fusion.1", 150e-9], ["fusion.2", 150e-9]]
    assert b["idle_gaps"] == [["bench.run", 500e-9],
                              ["bench.register", 200e-9]]


def test_no_device_work_reads_nothing():
    events = [ev(HOST, "python", tr.SPAN, 0, 100)]
    assert tr.reduce(events) is None
    assert tr.reduce([ev(DEV, tr.OPS_LINE, "x", 0, 10)]) is None


def _recorded(tmp_path, name):
    """The events of a trace kept gzipped under ``data/``."""
    import gzip
    import shutil
    src = Path(__file__).parent / "data" / name
    dst = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    dst.parent.mkdir(parents=True)
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    return tr.load(str(tmp_path))


def test_reduce_a_trace_recorded_on_the_chip(tmp_path):
    """One relay round (64 peers, 3 ticks) traced on a TPU v5e.  The
    expected numbers come from an independent sweep over the same events:
    +1 at each op start, -1 at each end, clipped to the span."""
    red = tr.reduce(_recorded(tmp_path, "relay_round.xplane.pb.gz"))
    assert red.devices == 1
    assert red.window_ns == 566_609_616
    assert red.busy_ns == 460_144_377
    assert red.program_ns == 460_146_223
    assert sorted(red.programs) == ["jit_run(5635585726439486192)",
                                    "jit_run(7146757575846396629)"]
    assert red.idle_share == pytest.approx(1 - 460_144_377 / 566_609_616)
    # by the same sweep: cut the span at every op and span edge, and give
    # each idle piece to the covering span that started last
    assert red.idle_ns == {"bench.traced": 309_639, "bench.register": 843_080,
                           "bench.run": 105_312_520}
    assert sum(red.idle_ns.values()) == red.window_ns - red.busy_ns
    assert red.self_ns == {"bench.traced": 309_639, "bench.register": 843_080,
                           "bench.run": 565_456_897}



def test_program_spans_in_a_trace_recorded_on_the_chip(tmp_path):
    """One engine round of 8 peers at d = 10 (3 ticks, n = 20,000) traced
    on a TPU v5e, the program's ``repro.*`` spans nested in
    ``bench.run``.  The expected numbers come from an independent sweep:
    cut the span at every op and span edge, give each piece to the
    covering span that started last, and count it idle where no op of the
    device covers it."""
    events = _recorded(tmp_path, "d10_round.xplane.pb.gz")
    red = tr.reduce(events)
    assert (red.window_ns, red.busy_ns, red.program_ns) == \
        (40_926_486, 5_016_144, 5_017_122)
    assert red.idle_ns == {
        tr.SPAN: 100_200, "bench.register": 117_469, "bench.run": 179_799,
        "repro.serve": 195_651, "repro.wire.encode": 3_116_101,
        "repro.wire.decode": 2_674_567, "repro.absorb": 2_381_731,
        "repro.tick": 1_928_722, "repro.plan": 34_970,
        "repro.stage": 2_716_674, "repro.wait": 4_000_319,
        "repro.unstage": 580_450, "repro.merge": 17_773_969,
        "repro.report": 109_720}
    assert sum(red.idle_ns.values()) == red.window_ns - red.busy_ns
    assert red.self_ns == dict(
        red.idle_ns, **{"repro.serve": 237_441,
                        "repro.wire.encode": 3_841_951,
                        "repro.wire.decode": 3_138_546,
                        "repro.absorb": 2_898_352, "repro.tick": 2_349_182,
                        "repro.stage": 5_564_118})
    assert tr.breakdown(red, top=3)["idle_gaps"] == [
        ["repro.merge", 0.017773969], ["repro.wait", 0.004000319],
        ["repro.wire.encode", 0.003116101]]
    # the program's spans move no busy, program or op time
    bare = tr.reduce([e for e in events if not e.name.startswith("repro.")])
    assert (bare.window_ns, bare.busy_ns, bare.program_ns, bare.ops) == \
        (red.window_ns, red.busy_ns, red.program_ns, red.ops)
    assert bare.idle_ns == {tr.SPAN: 100_200, "bench.register": 117_469,
                            "bench.run": 35_692_673}


# span [0, 1000): one engine tick inside bench.run that absorbs, stages,
# waits on the device, merges and decodes a frame
NESTED = [
    ev(HOST, "python", tr.SPAN, 0, 1000),
    ev(HOST, "python", "bench.register", 0, 40),       # [0, 40)
    ev(HOST, "python", "bench.run", 50, 950),          # [50, 1000)
    ev(HOST, "python", "repro.tick", 100, 800),        # [100, 900)
    ev(HOST, "python", "repro.absorb", 100, 150),      # [100, 250)
    ev(HOST, "python", "repro.stage", 250, 100),       # [250, 350)
    ev(HOST, "python", "PjitFunction(peel)", 260, 50),  # JAX's own event
    ev(HOST, "python", "repro.wait", 350, 350),        # [350, 700)
    ev(HOST, "python", "repro.merge", 700, 160),       # [700, 860)
    ev(HOST, "python", "repro.wire.decode", 860, 20),  # [860, 880)
    ev(DEV, tr.MODULES_LINE, "jit_peel", 330, 300),
    ev(DEV, tr.OPS_LINE, "fusion.1", 330, 300),        # busy [330, 630)
    ev(DEV, tr.OPS_LINE, "fusion.2", 960, 20),         # busy [960, 980)
]


def test_self_and_idle_time_by_program_span():
    red = tr.reduce(NESTED)
    assert red.self_ns == {tr.SPAN: 10, "bench.register": 40,
                           "bench.run": 950 - 800, "repro.tick": 20,
                           "repro.absorb": 150, "repro.stage": 100,
                           "repro.wait": 350, "repro.merge": 160,
                           "repro.wire.decode": 20}
    # gaps [0, 330), [630, 960), [980, 1000), split exactly: [0, 40)
    # register, [40, 50) no span but the traced one, [50, 100) run,
    # [100, 250) absorb, [250, 330) stage; [630, 700) wait, [700, 860)
    # merge, [860, 880) decode, [880, 900) tick, [900, 960) run;
    # [980, 1000) run
    assert red.idle_ns == {"bench.register": 40, tr.SPAN: 10,
                           "bench.run": 50 + 60 + 20, "repro.absorb": 150,
                           "repro.stage": 80, "repro.wait": 70,
                           "repro.merge": 160, "repro.wire.decode": 20,
                           "repro.tick": 20}
    assert sum(red.idle_ns.values()) == red.window_ns - red.busy_ns
    assert tr.breakdown(red, top=3)["idle_gaps"] == [
        ["repro.merge", 160e-9], ["repro.absorb", 150e-9],
        ["bench.run", 130e-9]]


def test_program_spans_leave_busy_and_program_time_as_they_were():
    red = tr.reduce(NESTED)
    bare = tr.reduce([e for e in NESTED if not e.name.startswith("repro.")])
    for got in (red, bare):
        assert (got.window_ns, got.busy_ns, got.program_ns) == (1000, 320, 300)
        assert got.idle_share == pytest.approx(0.68)
        assert got.ops == {"fusion.1": 300, "fusion.2": 20}
    # without the program's spans, bench.run holds what they held
    assert bare.idle_ns == {"bench.register": 40, tr.SPAN: 10,
                            "bench.run": 630}


@pytest.mark.parametrize("name,want_ns", [
    ("codec_ms_per_recon.fleet", 20),
    ("absorb_ms_per_recon.fleet", 150 + 160),
    ("staging_ms_per_recon.fleet", 100)])
def test_span_readers(name, want_ns):
    reader = cell.load_cell("statesync-fleet8-d1000").reader({"name": name})
    traced = [run.Round(0, 0.0, 1.0, [0, 1], None, {}, None)]

    def window(trace):
        return run.Window(0.0, 1.0, traced, 0, traced, trace)
    # two reconciliations traced: ms per reconciliation
    assert reader.read(window(tr.reduce(NESTED))) == want_ns / 1e6 / 2
    # a program without the spans, or a run without a trace, reads nothing
    bare = tr.reduce([e for e in NESTED if not e.name.startswith("repro.")])
    assert reader.read(window(bare)) is None
    assert reader.read(window(None)) is None
