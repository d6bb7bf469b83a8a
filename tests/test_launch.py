"""Launch plumbing: the chip smoke test's refusals, the compile-cache
location, the device peak table and the mesh constructor."""
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("env", [{"JAX_PLATFORMS": "cpu"},
                                 {"JAX_PLATFORMS": "cpu",
                                  "REPRO_PALLAS": "interpret"}])
def test_chip_smoke_refuses_without_a_tpu(env):
    """No CPU fallback: without a chip (or with interpret kernels) the
    script exits non-zero and never prints its result line."""
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                       env=dict(os.environ, **env), capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_compile_cache_env_var_wins(monkeypatch):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    assert compile_cache.enable() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        path = compile_cache.enable()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_peaks_unknown_device_kind_raises():
    from repro.launch.roofline import PEAKS, V5E, peaks

    assert peaks("TPU v5 lite") is PEAKS[V5E]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


def test_make_mesh_builds_auto_axes():
    from jax.sharding import AxisType

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


@pytest.mark.parametrize("layout", [[], ["--kv-layout", "paged",
                                         "--page-size", "8",
                                         "--num-pages", "40"]],
                         ids=["dense", "paged"])
def test_serve_summary_reports_row_use(layout, tmp_path):
    """The serving launcher's summary line gives the engine's row shares,
    so an operator sees them without a profiler."""
    import re

    path = os.pathsep.join(filter(None, [os.path.join(ROOT, "src"),
                                         os.environ.get("PYTHONPATH")]))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "smollm-360m",
         "--requests", "6", "--prefill-chunk", "4", *layout],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=path,
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert r.returncode == 0, r.stderr
    share = r"(\d+\.\d%|n/a)"
    assert re.search(rf"tok/s.*; prefill_row_use {share}, decode_row_use "
                     rf"{share}, forced_decode_share {share}$", r.stdout,
                     re.M), r.stdout
