"""Process environment of the launchers: where the compile cache lives,
and the environment the real-process runtime spawns its children with."""
import os

import jax
import pytest

from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
from repro.runtime import child_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_uses_the_environment_dir(monkeypatch, cache_config,
                                                tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set no other


def test_compile_cache_defaults_inside_the_checkout(monkeypatch,
                                                    cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == DEFAULT_DIR
    assert DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
    assert enable_compile_cache() == DEFAULT_DIR               # fixed path


def test_child_env_keeps_runtime_children_off_the_chip(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = child_env("/some/src")
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["PYTHONPATH"] == "/some/src"
    assert os.environ["JAX_PLATFORMS"] == "tpu"               # parent kept
