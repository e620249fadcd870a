"""The reduction from a device trace to busy, idle and program time."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

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
    # gaps: [100, 350) mid 225 in register; [550, 600) and [650, 1050) in run
    assert red.gaps == [("bench.run", 400), ("bench.register", 250),
                        ("bench.run", 50)]
    b = tr.breakdown(red, top=2)
    assert b["device_ops"] == [["fusion.1", 150e-9], ["fusion.2", 150e-9]]
    assert b["idle_gaps"] == [["bench.run", 400e-9],
                              ["bench.register", 250e-9]]


def test_no_device_work_reads_nothing():
    events = [ev(HOST, "python", tr.SPAN, 0, 100)]
    assert tr.reduce(events) is None
    assert tr.reduce([ev(DEV, tr.OPS_LINE, "x", 0, 10)]) is None


def test_reduce_a_trace_recorded_on_the_chip(tmp_path):
    """One relay round (64 peers, 3 ticks) traced on a TPU v5e.  The
    expected numbers come from an independent sweep over the same events:
    +1 at each op start, -1 at each end, clipped to the span."""
    import gzip
    import shutil
    src = Path(__file__).parent / "data" / "relay_round.xplane.pb.gz"
    dst = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    dst.parent.mkdir(parents=True)
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    red = tr.reduce(tr.load(str(tmp_path)))
    assert red.devices == 1
    assert red.window_ns == 566_609_616
    assert red.busy_ns == 460_144_377
    assert red.program_ns == 460_146_223
    assert sorted(red.programs) == ["jit_run(5635585726439486192)",
                                    "jit_run(7146757575846396629)"]
    assert red.idle_share == pytest.approx(1 - 460_144_377 / 566_609_616)
    assert {name for name, _ in red.gaps} == {"bench.run"}
