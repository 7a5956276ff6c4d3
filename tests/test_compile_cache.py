"""Where JAX's persistent compile cache goes
(``deepspeed_tpu/utils/compile_cache.py``): the environment's directory and no
other when ``JAX_COMPILATION_CACHE_DIR`` is set, ``<checkout>/.jax_cache``
when it is not, the same path on every call."""

import os

import jax
import pytest

from deepspeed_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_jax_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_variable_wins_and_nothing_else_is_set(
        monkeypatch, tmp_path, restore_jax_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", "left-alone")
    assert compile_cache.cache_dir() == str(tmp_path)
    assert compile_cache.enable() == str(tmp_path)
    # jax reads the variable itself: the helper sets no directory in code
    assert jax.config.jax_compilation_cache_dir == "left-alone"


def test_unset_means_the_checkout_and_the_path_is_stable(
        monkeypatch, restore_jax_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    first, second = compile_cache.enable(), compile_cache.enable()
    assert first == second == want == compile_cache.cache_dir()
    assert jax.config.jax_compilation_cache_dir == want
    # built from the checkout alone: no temporary name, process id or time
    assert os.path.dirname(want) == REPO


def test_entry_count_counts_cache_entries(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    assert compile_cache.entry_count() == 0          # no directory yet
    (tmp_path / "cc").mkdir()
    (tmp_path / "cc" / "jit_f-abc-cache").write_bytes(b"x")
    (tmp_path / "cc" / "jit_f-abc-atime").write_bytes(b"x")
    assert compile_cache.entry_count() == 1
