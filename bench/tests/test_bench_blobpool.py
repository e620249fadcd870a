"""The blob-pool cell: its parts in ``BENCHMARK.json``, the peel's byte
count worked by hand, the reader of its share of the HBM roofline, and
the harness's whole run in the cell at a small size on the CPU, where the
check passes the program and fails the control and every planted fault."""
import json
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import cell, control, roofline, run  # noqa: E402

CELL = "blobpool-fleet8-d128"
SEED = 2**33 + 4844
# 64 blobs, two replicas a round of two; d = 2 decodes in one 256-symbol
# bucket of 131,072-B rows
SMALL = dict(n=64, traffic={"pool": 2, "peers_per_round": 2, "d": [2],
                            "warmup_rounds": 0})


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def test_the_cell_and_its_parts():
    c = cell.load_cell(CELL)
    assert c.chips == 1 and c.traffic["entry"] == "engine"
    assert c.config["record"] == {"bytes": 131072, "key_bytes": 32}
    assert c.config["change"] == "add_drop" and c.traffic["d"] == [128]
    assert c.traffic["peers_per_round"] == 8 and c.traffic["pool"] == 9
    assert {m["name"] for m in c.per_layer} == {"peel_hbm_share.blob"}
    assert {m["name"] for m in c.end_to_end} == {
        "recon_per_s", "sync_p50_ms", "wire_bytes_per_item", "setup_s"}
    fleet = cell.load_cell("statesync-fleet8-d1000")
    assert "peel_hbm_share.fleet" in {m["name"] for m in fleet.per_layer}


def test_peel_bytes_by_hand():
    # a blob wave: 256 rows of 32,768 + 3 words, read and written once
    assert roofline.wave_bytes(256, 32768) == 2 * 256 * 131084 == 67_115_008
    rep = types.SimpleNamespace
    reports = [
        # two decodes of 92-B rows in buckets of 256 and 768: a mean
        # bucket of 512 rows of 104 B, over 5 waves
        rep(nbytes=92, device_decodes=2, device_symbols=1024,
            device_waves=5),
        rep(nbytes=131072, device_decodes=1, device_symbols=256,
            device_waves=3),
        rep(nbytes=92, device_decodes=0, device_symbols=0, device_waves=0)]
    assert roofline.peel_bytes(reports) == \
        5 * 2 * 512 * 104 + 3 * 67_115_008
    del reports[0].device_symbols           # a program without the counter
    assert roofline.peel_bytes(reports) is None


def test_hbm_share():
    assert roofline.hbm_share(819e9 / 4, 1.0, "TPU v5 lite") == 25.0
    with pytest.raises(KeyError):
        roofline.hbm_share(1.0, 1.0, "cpu")


def test_reader_of_the_share(monkeypatch):
    import jax
    reader = cell.load_cell(CELL).reader(
        {"name": "peel_hbm_share.blob"})
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(device_kind="TPU v5 lite")])
    rep = types.SimpleNamespace(nbytes=131072, device_decodes=2,
                                device_symbols=512, device_waves=6)
    traced = [types.SimpleNamespace(reports=[rep, rep]),
              types.SimpleNamespace(reports=None)]       # a failed round
    trace = types.SimpleNamespace(programs={
        "jit_peel_batched(123)": 0.5e9, "jit_peel_batched(456)": 0.5e9,
        "jit_other(7)": 9e9})
    w = types.SimpleNamespace(trace=trace, traced_rounds=traced)
    moved = 2 * 6 * 67_115_008
    assert reader.read(w) == pytest.approx(100 * moved / 819e9)
    w.trace = None
    assert reader.read(w) is None
    w.trace = types.SimpleNamespace(programs={"jit_other(7)": 1e9})
    assert reader.read(w) is None
    w.trace = trace
    del rep.device_symbols
    assert reader.read(w) is None


def _run(**kw):
    return run.run_cell(CELL, SEED, 0.01, False, require_tpu=False,
                        log=lambda s: None, **SMALL, **kw)


def test_rehearsal_is_correct():
    res = _run()
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_control_is_not_correct():
    res = _run(wrap_entry=control.one_sided)
    assert not res["correct"]
    assert res["check"]["wrong_recons"]["value"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "decode_device_batched_start",
                        control.plant(fault))
    res = _run()
    assert not res["correct"]
    assert res["failed"] > 0
