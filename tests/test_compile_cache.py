"""The one helper that chooses where JAX's persistent compilation cache
lives (ISSUE 22): the environment wins, else a fixed git-ignored
directory inside the checkout — never ``~/.cache``."""
import os

import pytest

from pulsarutils_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def recorded_updates(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_set_means_nothing_is_set_in_code(monkeypatch, tmp_path,
                                              recorded_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert recorded_updates == []


def test_env_unset_means_fixed_path_under_the_checkout(monkeypatch,
                                                       recorded_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(REPO, ".pulsarutils_tpu_cache", "jax")
    (name, value), = recorded_updates
    assert name.endswith("cache_dir") and value == path
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".pulsarutils_tpu_cache/" in f.read().split()


def test_tune_cache_default_sits_beside_it(monkeypatch):
    from pulsarutils_tpu.tuning.cache import default_cache_path

    monkeypatch.delenv("PUTPU_TUNE_CACHE", raising=False)
    assert default_cache_path() == os.path.join(
        REPO, ".pulsarutils_tpu_cache", "tune_cache.json")
