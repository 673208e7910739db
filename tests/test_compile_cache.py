"""The persistent compilation cache follows one rule everywhere
(repro/compile_cache.py): JAX_COMPILATION_CACHE_DIR when set, else
`.jax_cache/` at the checkout root."""
import os

import jax
import pytest

from repro import compile_cache
from repro.kernels.backproject.kernel import resolve_interpret


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", "/untouched")
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == "/untouched"


def test_unset_env_uses_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(compile_cache.CHECKOUT_ROOT, ".jax_cache")
    assert os.path.isdir(os.path.join(compile_cache.CHECKOUT_ROOT, "src",
                                      "repro"))
    assert jax.config.jax_compilation_cache_dir == path


@pytest.mark.parametrize("flag,env,want", [
    (True, "", True), (False, "1", False),
    (None, "1", True), (None, "0", False), (None, "", False),
])
def test_interpret_only_when_asked(monkeypatch, flag, env, want):
    """The backend never picks the Pallas interpreter; the caller does."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", env)
    assert resolve_interpret(flag) is want
