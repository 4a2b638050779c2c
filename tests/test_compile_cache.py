"""Where the trainer keeps JAX's persistent compilation cache."""
import jax
import pytest

from repro.launch import train


@pytest.fixture
def cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_code_sets_nothing(cache_dir, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    train.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir is None  # JAX reads the variable itself


def test_fixed_dir_inside_the_checkout(cache_dir, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    train.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == str(train.REPO_ROOT / ".jax_cache")
    assert (train.REPO_ROOT / "chip_smoke.py").exists()
