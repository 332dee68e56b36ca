"""Platform selection, the compile-cache location, and the rule that no
fallback hides which device ran the work."""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.kernels import ops
from repro.utils import platform

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_compile_cache_dir_from_env_is_left_to_jax(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert platform.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = platform.enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_entries_land_only_in_env_dir(tmp_path):
    """A fresh process with JAX_COMPILATION_CACHE_DIR set writes its
    cache entries there and nowhere under the checkout."""
    cache = tmp_path / "cache"
    child = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.utils.platform import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
    """)
    local = REPO / ".jax_cache"
    had = set(local.iterdir()) if local.exists() else set()
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert any(cache.iterdir())
    now = set(local.iterdir()) if local.exists() else set()
    assert now == had


def test_set_platform_raises_when_backend_is_live_elsewhere():
    jax.devices()  # the test process runs on the CPU backend
    with pytest.raises(RuntimeError, match="after the JAX backend"):
        platform.set_platform("tpu")
    platform.set_platform("cpu")  # already live: a no-op


def test_set_platform_rejects_unknown_platform():
    with pytest.raises(ValueError, match="unknown platform"):
        platform.set_platform("gpu")


def test_kernels_refuse_other_backends(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(ops.UnsupportedBackendError, match="'gpu'"):
        ops.distance_argmin_l2(jax.numpy.ones((8, 4)),
                               jax.numpy.ones((2, 4)),
                               jax.numpy.ones((2,), bool))


def test_benchmarks_refuse_device_children_off_cpu(monkeypatch):
    sys.path.insert(0, str(REPO))
    try:
        from benchmarks import common
    finally:
        sys.path.remove(str(REPO))
    common.require_cpu_parent("bench_x")  # CPU: allowed
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(common.DeviceChildError, match="bench_x"):
        common.require_cpu_parent("bench_x")
