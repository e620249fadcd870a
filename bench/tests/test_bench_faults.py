"""The check fails the control and every planted fault: the harness's
whole run, at a small size on the CPU, with its look for a chip skipped
and the timed path broken underneath."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import control, run  # noqa: E402

SEED = 2**33 + 5
SMALL = {"statesync-fleet8-d1000": (3000, {"pool": 9}),
         "statesync-single-d1000": (3000, {"pool": 2})}


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _run(workload, **kw):
    n, traffic = SMALL[workload]
    return run.run_cell(workload, SEED, 0.01, False, require_tpu=False, n=n,
                        traffic=traffic, log=lambda s: None, **kw)


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_control_is_not_correct(workload):
    res = _run(workload, wrap_entry=control.one_sided)
    assert not res["correct"]
    assert res["check"]["wrong_recons"]["value"] > 0


@pytest.mark.parametrize("workload,fault", [
    ("statesync-fleet8-d1000", "unchanged"),
    ("statesync-fleet8-d1000", "half"),
    ("statesync-fleet8-d1000", "altered"),
    ("statesync-single-d1000", "unchanged"),
    ("statesync-single-d1000", "altered")])
def test_planted_fault_is_not_correct(monkeypatch, workload, fault):
    """The fleet's engine decodes in batches, the lone session alone; a
    batch of one has no half to leave out."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "decode_device_batched_start",
                        control.plant(fault))
    if fault != "half":
        monkeypatch.setattr(ops, "decode_device", control.plant_lone(fault))
    res = _run(workload)
    assert not res["correct"]
    assert res["failed"] > 0
