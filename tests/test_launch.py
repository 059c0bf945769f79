"""Launch layer: mesh builders, dry-run subprocess integration, drivers."""
import json
import os
import subprocess
import sys

import jax
import pytest

from conftest import REPO_ROOT, subprocess_env

from repro.launch.mesh import make_elastic_mesh, make_local_mesh


def test_local_mesh_axes():
    mesh = make_local_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.size == jax.device_count()


def test_elastic_mesh_shapes():
    # elastic re-shard after a world-size change keeps TP fixed
    m = make_elastic_mesh(jax.device_count(), model_parallel=1)
    assert m.shape["model"] == 1
    with pytest.raises(AssertionError):
        make_elastic_mesh(3, model_parallel=2)


@pytest.mark.slow
def test_dryrun_subprocess_smallest_cell(tmp_path):
    """End-to-end dry-run integration: 512 placeholder devices, production
    mesh, lower+compile+memory analysis — on the cheapest cell."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "mamba2-1.3b", "--shape", "long_500k", "--no-roofline",
         "--out", str(tmp_path)],
        capture_output=True, text=True, env=subprocess_env(),
        cwd=REPO_ROOT, timeout=420)
    assert r.returncode == 0, r.stdout + r.stderr
    arts = os.listdir(tmp_path)
    assert len(arts) == 1
    with open(tmp_path / arts[0]) as f:
        d = json.load(f)
    assert d["n_devices"] == 256
    assert d["full"]["memory"]["temp_bytes"] < 16e9  # fits v5e HBM


def test_device_count_is_one_outside_dryrun():
    """Smoke tests must see the real device count (the XLA flag is only
    set inside launch/dryrun.py's own process)."""
    assert jax.device_count() == 1


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache goes to the checkout's fixed .jax_cache directory."""
    from repro.launch import compile_cache
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert updates == []

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO_ROOT, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", path)]
