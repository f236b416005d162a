"""The chip entry points: ``chip_smoke.py`` refuses to run without a TPU,
``bench_sim``'s forced-CPU rows refuse to run with one, and the persistent
compilation cache sits at a fixed place."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import use_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    """Restore JAX's cache-directory setting after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_chip_smoke_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=300)
    assert r.returncode != 0
    assert r.stdout == ""                       # no result line at all
    assert "no TPU" in r.stderr


def test_compile_cache_follows_environment(monkeypatch, tmp_path,
                                           cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = str(REPO / ".jax_cache")
    assert use_compile_cache() == path
    assert use_compile_cache() == path           # never moves between calls
    assert jax.config.jax_compilation_cache_dir == path


def test_bench_sim_refuses_cpu_children_on_tpu(monkeypatch):
    """On a TPU backend the forced-CPU-device mesh/tp rows must not run:
    they would report XLA:CPU times under chip-host row names."""
    monkeypatch.syspath_prepend(str(REPO / "benchmarks"))
    import bench_sim
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for fn in (bench_sim.run_mesh_bench_subprocess,
               bench_sim.run_tp_bench_subprocess):
        with pytest.raises(SystemExit, match="forced CPU devices"):
            fn()
