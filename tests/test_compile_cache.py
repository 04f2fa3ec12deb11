"""Where the persistent compilation cache lives: the directory
``JAX_COMPILATION_CACHE_DIR`` names, as given, or else one fixed path in
the repo."""
from pathlib import Path

import jax
import pytest

from repro import compat

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache():
    yield
    compat.disable_persistent_compilation_cache()


def test_env_dir_is_used_as_given(monkeypatch, tmp_path, restore_cache):
    want = tmp_path / "cc"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(want))
    assert compat.enable_persistent_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == str(want)
    assert want.is_dir()
    assert compat.compilation_cache_stats()["dir"] == str(want)


def test_fixed_repo_dir_without_env(monkeypatch, restore_cache):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = REPO / "experiments" / "xla_cache"
    assert compat.enable_persistent_compilation_cache() == want
    assert jax.config.jax_compilation_cache_dir == str(want)
