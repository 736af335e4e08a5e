"""The chip entry points off the chip.

``chip_smoke.py`` must refuse a CPU outright (non-zero exit, no result
line), and its phases must hold together end to end: here they rehearse at
a tiny size on the CPU, the kernels in interpret mode.  The compile-cache
helper the entry points call picks its directory as documented.
"""
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from repro.distributed.spatial_shard import SpatialShards
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 8000


def test_gate_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert run.returncode != 0
    assert '"ok": true' not in run.stdout
    assert "no TPU" in run.stderr


@pytest.fixture(scope="module")
def rects():
    return chip_smoke.make_rects(0, N)


def test_served_phase_rehearsal(rects):
    chip_smoke.served_phase(rects, N, 0, batch=8)


def test_kernel_phase_rehearsal(rects):
    fleets = [(layout, SpatialShards.build(rects, 8, fanout=64,
                                           layout=layout))
              for layout in ("d1", "d3")]
    chip_smoke.kernel_phase(fleets, 0, batch=4, backend="pallas_interpret")


def test_compile_cache_dir(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.enable() is None            # CPU: compile fresh
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        assert compile_cache.enable() == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == \
            str(compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR == \
            compile_cache.CHECKOUT / ".jax_cache"
        assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
