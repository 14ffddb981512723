"""`chip_smoke.py` on the CPU: its phases at 2^10 rows in interpret mode
(the Mosaic check is the chip's), and its refusal to run without a TPU."""
from __future__ import annotations

import importlib.util
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_match_host_reference(chip_smoke, capsys):
    recs = chip_smoke.smoke(10, seed=0, mosaic=False)
    assert {r["format"] for r in recs} == set(chip_smoke.FORMATS)
    assert {r["phase"] for r in recs} >= {
        "fd/auto", "fd/bell", "rmat/auto", "rmat/pagerank", "rmat/bfs",
        "rmat/sssp"}
    assert all(r["max_rel_err"] <= chip_smoke.TOL for r in recs)
    assert all(r["many_max_rel_err"] <= chip_smoke.TOL
               for r in recs if "many_max_rel_err" in r)
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_sharded_phase_matches_one_chip(chip_smoke):
    rec = chip_smoke.smoke_sharded(10, seed=0, n_devices=1, mosaic=False)
    assert rec["format"] == "ell-sharded"
    assert rec["max_rel_diff_vs_one_chip"] <= chip_smoke.TOL


def test_reference_catches_a_wrong_result(chip_smoke):
    import numpy as np

    ref = np.array([1.0, -2.0, np.inf])
    assert chip_smoke.rel_err(np.array([1.0, -2.0, np.inf]), ref) == 0.0
    assert chip_smoke.rel_err(np.array([1.0, -1.0, np.inf]), ref) == 0.5
    assert chip_smoke.rel_err(np.array([1.0, -2.0, 3.0]), ref) == np.inf


def test_main_refuses_a_cpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("env", [None, "/elsewhere/cache"])
def test_compile_cache_placement(monkeypatch, env):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set over it;
    otherwise the cache sits at a fixed `<checkout>/.jax_cache`."""
    import jax
    from repro.compile_cache import enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    try:
        got = enable_compile_cache()
        if env is None:
            assert got == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env
            assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = subprocess.run([sys.executable, "chip_smoke.py"],
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
