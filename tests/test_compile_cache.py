"""The persistent compile cache helper every entry point calls first."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_cache_honours_env_dir(tmp_path, monkeypatch, restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                              restore_cache_config):
    """Unset: one fixed directory inside the checkout (a moving path would
    never be hit again), the same on every call."""
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert compile_cache.CHECKOUT_CACHE_DIR.parent.joinpath(
        "chip_smoke.py").is_file()                 # the checkout's root
    assert compile_cache.enable_compile_cache() == path
    assert jax.config.jax_compilation_cache_dir == path
