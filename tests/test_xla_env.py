"""repro.utils.xla_env: the host-device harness leaves the platform alone,
and the compile cache goes where JAX_COMPILATION_CACHE_DIR says, else to one
fixed, gitignored directory at the repository root."""

import os
import pathlib

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.utils import xla_env

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_force_host_device_count_replaces_the_flag_and_not_the_platform(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1 --xla_force_host_platform_device_count=2")
    xla_env.force_host_device_count(4)
    assert os.environ["XLA_FLAGS"] == "--xla_foo=1 --xla_force_host_platform_device_count=4"
    assert "JAX_PLATFORMS" not in os.environ


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_enable_compile_cache_directory(monkeypatch, tmp_path, restore_cache_dir,
                                        from_env):
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(ROOT / ".jax_cache")
    assert xla_env.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_default_cache_dir_is_gitignored():
    assert xla_env.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
