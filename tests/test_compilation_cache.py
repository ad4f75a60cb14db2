"""Placement of the persistent XLA compilation cache
(utils/compilation_cache.py): the environment decides when it speaks,
otherwise one fixed, gitignored path inside the checkout."""

import os

import jax
import pytest

from kubernetes_tpu.utils import compilation_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_placed_cache_is_left_alone(cache_config, monkeypatch,
                                                 tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compilation_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None  # nothing set in code


def test_default_cache_is_inside_the_checkout_and_ignored(cache_config,
                                                          monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compilation_cache.enable()
    assert path == os.path.join(REPO_ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(REPO_ROOT, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_cache/" in f.read().split()
