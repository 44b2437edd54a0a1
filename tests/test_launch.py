"""Entry-point helpers: the mesh over the devices present, the compile
cache directory, and ``chip_smoke.py`` refusing to run without a TPU."""

import os
import subprocess
import sys

import jax
import pytest

from repro.launch import cache
from repro.launch.mesh import make_device_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_device_mesh_uses_every_device():
    mesh = make_device_mesh(model=1)
    assert dict(mesh.shape) == {"data": len(jax.devices()), "model": 1}
    assert list(mesh.devices.flat) == jax.devices()


@pytest.mark.parametrize("extra", [None, 1])
def test_device_mesh_refuses_a_count_that_does_not_factor(extra):
    model = 0 if extra is None else len(jax.devices()) + extra
    with pytest.raises(ValueError, match="do not factor"):
        make_device_mesh(model=model)


def test_compile_cache_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cache.ENV_VAR, "/some/set/dir")
    assert cache.enable_compile_cache() == "/some/set/dir"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(cache.ENV_VAR)
    try:
        first = cache.enable_compile_cache()
        assert first == cache.enable_compile_cache() == os.path.join(
            REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert '"ok"' not in res.stdout
