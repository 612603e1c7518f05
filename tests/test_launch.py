"""Entry points: the serving driver, the chip smoke script and the
persistent compile cache they share."""
from __future__ import annotations

import importlib
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_compile_cache_follows_env(monkeypatch, tmp_path, restore_cache_dir):
    from repro.launch.compile_cache import use_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    from repro.launch.compile_cache import use_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = use_compile_cache()
    assert first == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert use_compile_cache() == first


def test_serve_main_smoke_end_to_end(monkeypatch, tmp_path, capsys,
                                     restore_cache_dir):
    from repro.launch import serve
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = serve.main(["--smoke", "--requests", "4", "--slots", "2",
                     "--max-new", "3", "--max-len", "128"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "4 requests, 12 tokens" in out
    # every other request shares a one-page prefix: the warm path ran
    hit = int(out.split("tok/s), ")[1].split(" prefix-hit")[0])
    assert hit > 0


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def _chip_smoke_module():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_path_at_smoke_width(capsys):
    """The phases chip_smoke.py runs on the chip, rehearsed on the CPU at
    smoke width (kernels in interpret mode): kernel parity, then 12
    arrivals through the paged batcher with prefix hits."""
    from repro.launch.serve import resolve_arch
    cs = _chip_smoke_module()
    cs.check_kernels(0)
    _cell, bat, done = cs.serve_one_chip(resolve_arch("qwen3-4b", smoke=True),
                                         seed=0, log=cs.CompileLog())
    assert len(done) == cs.N_REQUESTS
    assert bat.pool.stats()["prefix_hit_tokens"] > 0
    assert "[serve] 12 requests served" in capsys.readouterr().out


def test_bench_harness_exits_nonzero_on_failed_section(monkeypatch, capsys):
    from benchmarks import run as bench_run

    def ok(rows):
        rows.append({"name": "ok/row", "us_per_call": 1.0, "derived": "x"})

    def boom(rows):
        raise RuntimeError("section failed")

    for name in ("tail_latency", "isolation", "elastic_sched", "channels"):
        monkeypatch.setattr(importlib.import_module(f"benchmarks.{name}"),
                            "run", ok)
    monkeypatch.setattr(importlib.import_module("benchmarks.elasticity"),
                        "run", boom)
    assert bench_run.main() == 1
    out = capsys.readouterr().out
    assert out.count("ok/row") == 4       # the other sections still report
    monkeypatch.setattr(importlib.import_module("benchmarks.elasticity"),
                        "run", ok)
    assert bench_run.main() == 0
