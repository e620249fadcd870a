"""Self time and the idle split over the program's spans
(``bench/trace.py``), and the readers of the program's device counters."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cell  # noqa: E402
from bench import trace as tr  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(name, start, dur, plane=HOST, line="python"):
    return tr.Event(plane, line, name, float(start), float(dur))


# span [0, 1000): one engine run with a tick that absorbs, stages and waits
EVENTS = [
    ev(tr.SPAN, 0, 1000),
    ev("bench.run", 0, 1000),                          # [0, 1000)
    ev("repro.tick", 100, 800),                        # [100, 900)
    ev("repro.absorb", 100, 200),                      # [100, 300)
    ev("PjitFunction(map_seeds)", 150, 100),           # JAX's own event
    ev("repro.stage", 300, 100),                       # [300, 400)
    ev("PjitFunction(peel_batched)", 320, 60),         # JAX's own event
    ev("repro.wait", 400, 400),                        # [400, 800)
    ev("np.asarray(jax.Array)", 700, 100),             # JAX's own event
    ev("repro.absorb", 950, 100),                      # [950, 1050) clipped
    ev("repro.serve", 500, 50, line="other thread"),   # not the tick's
    ev("jit_peel_batched", 380, 400, DEV, tr.MODULES_LINE),
    ev("fusion.1", 380, 400, DEV, tr.OPS_LINE),        # busy [380, 780)
]


def test_self_time_by_hand():
    # bench.traced and bench.run cover the same stretch: the traced span
    # is left with nothing.  JAX's own events take nothing from a span.
    got = tr.self_ns(EVENTS, [(0, 1000)])
    assert got == {tr.SPAN: 0.0, "bench.run": 1000.0 - 800 - 50,
                   "repro.tick": 800.0 - 200 - 100 - 400,
                   "repro.absorb": 200.0 + 50, "repro.stage": 100.0,
                   "repro.wait": 400.0, "repro.serve": 50.0}
    # clipped to another stretch, spans outside it are not there at all
    assert tr.self_ns(EVENTS, [(300, 400)]) == {
        tr.SPAN: 0.0, "bench.run": 0.0, "repro.tick": 0.0,
        "repro.stage": 100.0}


def test_gaps_are_named_by_the_innermost_program_span():
    assert tr.idle_gaps(EVENTS, 0, 1000) == [(0, 380), (780, 1000)]
    # split exactly: [0, 100) in bench.run, [100, 300) absorb, [300, 380)
    # stage; [780, 800) wait, [800, 900) tick, [900, 950) bench.run,
    # [950, 1000) the second absorb
    assert tr.self_ns(EVENTS, tr.idle_gaps(EVENTS, 0, 1000)) == {
        tr.SPAN: 0.0, "bench.run": 150.0, "repro.tick": 100.0,
        "repro.absorb": 250.0, "repro.stage": 80.0, "repro.wait": 20.0}
    # outside every span the gap keeps the traced span's name
    lone = [ev(tr.SPAN, 0, 100), ev("fusion.1", 0, 40, DEV, tr.OPS_LINE)]
    assert tr.self_ns(lone, tr.idle_gaps(lone, 0, 100)) == {tr.SPAN: 60.0}


def _window(reports):
    return SimpleNamespace(reports=lambda: reports)


@pytest.mark.parametrize("name", ["waves_per_decode.fleet",
                                  "transfer_bytes_per_recon.fleet"])
def test_counter_readers(name):
    reader = cell.load_cell("statesync-fleet8-d1000").reader({"name": name})
    reports = [SimpleNamespace(device_decodes=3, device_waves=12,
                               transfer_bytes=1000),
               SimpleNamespace(device_decodes=1, device_waves=4,
                               transfer_bytes=3000)]
    want = 4.0 if name.startswith("waves") else 2000.0
    assert reader.read(_window(reports)) == want
    # a program without the counters reads nothing and raises nothing
    assert reader.read(_window([SimpleNamespace(device_decodes=3)])) is None
    assert reader.read(_window([])) is None
