"""CPU rehearsal of ``chip_smoke.py``: its phases and checks at a small set
size, its refusal to run without a TPU, and where it puts the compile
cache."""
import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.hashing import bytes_to_words

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PYTHONPATH", "JAX_COMPILATION_CACHE"))}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_phases_recover_the_set_difference(smoke):
    lines = list(smoke.run_phases(seed=0, n=2000, d_single=40,
                                  ds=(0, 1, 10, 40), d_sharded=40))
    assert [line.split()[0] for line in lines] == \
        ["phase=setup", "phase=single", "phase=engine"]
    for line in lines[1:]:
        assert " overflows=0 " in line and " host_decodes=0 " in line


def test_check_report_rejects_a_missing_item(smoke):
    rng = np.random.default_rng(1)
    server = smoke.make_server(rng, 50)
    local = smoke.make_replica(rng, server, 6)
    only_remote = np.setdiff1d(smoke._rows(server), smoke._rows(local))
    only_local = np.setdiff1d(smoke._rows(local), smoke._rows(server))

    def words(rows):
        return bytes_to_words(rows.view(np.uint8).reshape(-1, smoke.NBYTES),
                              smoke.NBYTES)

    from repro.protocol import SessionReport
    good = SessionReport(
        only_remote=words(only_remote), only_local=words(only_local),
        nbytes=smoke.NBYTES, symbols_used=8, symbols_received=8,
        bytes_received=0, remote_items=50, grow_steps=1, device_decodes=1,
        host_decodes=0, overflows=0)
    smoke.check_report(good, server, local)
    for bad in (dataclasses.replace(good, only_remote=good.only_remote[1:]),
                dataclasses.replace(good, overflows=1)):
        with pytest.raises(AssertionError):
            smoke.check_report(bad, server, local)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_a_tpu(tmp_path, where):
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path))
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         env=_env(), capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_goes_to_one_place(tmp_path, env_dir):
    checkout, elsewhere = tmp_path / "checkout", tmp_path / "elsewhere"
    extra = {"JAX_COMPILATION_CACHE_DIR": str(elsewhere)} if env_dir else {}
    code = ("import jax\n"
            "from repro.compile_cache import place_compile_cache\n"
            f"print(place_compile_cache({str(checkout)!r}))\n"
            "jax.jit(lambda x: x * 3 + 1)(2).block_until_ready()\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=tmp_path, capture_output=True,
        text=True, timeout=120,
        env=_env(PYTHONPATH=str(ROOT / "src"),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", **extra))
    assert out.returncode == 0, out.stderr
    want = elsewhere if env_dir else checkout / ".jax_cache"
    assert out.stdout.strip() == str(want)
    written = [p.relative_to(tmp_path) for p in tmp_path.rglob("*")
               if p.is_file()]
    assert written and all(want in (tmp_path / p).parents for p in written)
