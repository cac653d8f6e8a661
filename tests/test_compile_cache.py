"""The persistent compilation cache sits where the environment says, else at
one fixed path in the checkout — never a temporary or per-process path."""
import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("env_dir", [None, "outside"])
def test_compile_cache_dir(env_dir, tmp_path, monkeypatch, restore_cache_config):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(CHECKOUT_CACHE_DIR)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert CHECKOUT_CACHE_DIR.parent.joinpath("pyproject.toml").exists()
