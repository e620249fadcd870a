"""CPU rehearsal of the chip benchmark at a small set size: every cell and
the ``session`` entry run through the same round code and reference check,
a wrong answer is caught, and the command refuses a host without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import cell, data, reference, run  # noqa: E402

SEED = 2**31 + 977          # seeds reach past 32 signed bits
# small sets and pools, so that a rehearsal takes seconds
SMALL = {"statesync-fleet8-d1000": (3000, {"pool": 9}),
         "statesync-single-d1000": (3000, {"pool": 2})}


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch, tmp_path):
    # a rehearsal writes no compile cache into the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def rehearse(workload, trace=False, **kw):
    n, traffic = SMALL[workload]
    return run.run_cell(workload, SEED, 0.01, trace, require_tpu=False, n=n,
                        traffic=traffic, log=lambda s: None, **kw)


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_cell_rehearsal_is_correct(workload):
    res = rehearse(workload)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    c = cell.load_cell(workload)
    assert set(res["metrics"]) == {m["name"] for m in c.end_to_end}
    assert res["metrics"]["wire_bytes_per_item"]["value"] > 0


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_traced_rehearsal_reports_the_counters(workload):
    res = rehearse(workload, trace=True)
    assert res["correct"]
    got = res["metrics"]
    # the CPU has no device plane: the trace's metrics are left out
    want = {m["name"] for m in cell.load_cell(workload).per_layer
            if m["source"] == "program_counter"}
    assert set(got) == want and want
    for name, v in got.items():
        if name.startswith("compiles_in_window"):
            assert v["value"] == 0
        if name.startswith("dispatches_per_tick"):
            assert v["value"] == 1.0        # one shape bucket per tick
    assert res["device"]["busy_s"] == 0.0 and "breakdown" not in res


def test_a_configuration_added_as_files_runs(tmp_path):
    """A deployment of 4-B ids that differ by adds and drops, with mixed d
    per peer, runs from a configuration file and a cell entry alone."""
    config = {"name": "ids4", "record": {"bytes": 4, "key_bytes": 4},
              "change": "add_drop", "n": 2000, "max_diff": None,
              "max_m": 4096, "pacing": {"kind": "Exponential", "block": 8,
                                        "growth": 2.0},
              "key": [506097522914230528, 1084818905618843912],
              "reduced": {}}
    (tmp_path / "ids4.json").write_text(json.dumps(config))
    spec = cell.load_spec()
    spec["configs"] = [{"name": "ids4", "file": str(tmp_path / "ids4.json"),
                        "reduced": []}]
    spec["workloads"] = [{"name": "ids4-mixed", "config": "ids4",
                          "traffic": "fleet8-d1000", "chips": 1}]
    res = run.run_cell("ids4-mixed", SEED, 0.01, False, require_tpu=False,
                       spec=spec, log=lambda s: None,
                       traffic={"d": list(range(8)), "pool": 16,
                                "warmup_rounds": 1})
    assert res["correct"], res["check"]
    assert res["attempted"] >= 8 and res["failed"] == 0


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_a_flipped_row_fails_the_check(workload):
    """A replica holds one row the reference does not know of: the check
    reports a wrong reconciliation."""
    def one_row_flipped(entry):
        class Flipped:
            @staticmethod
            def run_round(stream, locals_, session, span):
                local = locals_[0]
                row = np.zeros((1, local.nbytes), np.uint8)
                row[0, 0] = 1
                local.add_items(row)
                try:
                    return entry.run_round(stream, locals_, session, span)
                finally:
                    local.remove_items(row)
        return Flipped
    res = rehearse(workload, wrap_entry=one_row_flipped)
    assert not res["correct"]
    assert res["check"]["wrong_recons"]["value"] >= 1


def _env():
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PYTHONPATH", "JAX_COMPILATION_CACHE"))}
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.mark.parametrize("where", ["checkout", "bench_alone"])
def test_refuses_without_a_tpu(tmp_path, where):
    root = ROOT
    if where == "bench_alone":
        root = tmp_path / "alone"
        shutil.copytree(ROOT / "bench", root / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(ROOT / "BENCHMARK.json", root)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "statesync-fleet8-d1000",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=root, env=_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert not (root / ".jax_cache").exists() or where == "checkout"


def test_seeds_change_rows_not_work():
    traffic = {"d": list(range(17)), "pool": 136}
    a = data.pool_differences(np.random.default_rng(1), traffic)
    b = data.pool_differences(np.random.default_rng(SEED), traffic)
    assert a != b and sorted(a) == sorted(b)


def test_the_stream_cache_covers_the_longest_reconciliation():
    """Doubling windows: d = 1,000 decodes by the 2,048-symbol prefix, a
    slow decode by 4,096, and the pipelined engine has fetched up to 8,192
    by then."""
    config = json.loads((ROOT / "bench/configs/eth-statesync.json")
                        .read_text())
    assert data.cache_symbols(config, {"d": [1000]}) == 8192
    assert data.cache_symbols(config, {"d": [0, 16, 3]}) == 128
    assert data.cache_symbols(config, {"d": [0]}) == 16


def test_warm_up_covers_the_batches_the_window_meets():
    assert run.warmup_peer_counts({"peers_per_round": 8, "d": [1000]}) == []
    assert run.warmup_peer_counts({"peers_per_round": 64,
                                   "d": [0, 16]}) == [1, 2, 4, 8, 16, 32]
    assert run.warmup_peer_counts({"peers_per_round": 1, "d": [0, 1]}) == []


def test_memory_peak_counts_program_temporaries(monkeypatch):
    """Buffers in use and the memory reserved for programs' temporaries
    make up the peak, on the fullest device; a backend without memory
    statistics reads nothing."""
    import jax
    from bench import counters

    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats
    monkeypatch.setattr(jax, "devices", lambda: [
        Dev({"peak_bytes_in_use": 55_034_880,
             "peak_bytes_reserved": 3_454_697_472}),
        Dev({"peak_bytes_in_use": 90_000_000})])
    assert counters.peak_bytes() == 55_034_880 + 3_454_697_472
    monkeypatch.setattr(jax, "devices", lambda: [Dev(None)])
    assert counters.peak_bytes() is None


def test_reference_compares_whole_rows():
    rows = np.zeros((3, 12), np.uint8)
    rows[:, 11] = [1, 2, 3]          # equal 8-byte prefixes
    served = reference.RowSet(rows)
    q = rows.copy()
    q[2, 11] = 9
    assert served.contains(q).tolist() == [True, True, False]
    with pytest.raises(ValueError):
        reference.RowSet(np.concatenate([rows, rows[:1]]))
    only_server, only_replica = reference.expected_difference(
        served, np.array([0]), q[2:])
    assert np.array_equal(only_server, rows[:1])
    assert np.array_equal(only_replica, q[2:])
