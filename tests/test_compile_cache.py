"""Placement of the persistent compilation cache (`repro.compile_cache`).

JAX's own ``JAX_COMPILATION_CACHE_DIR`` wins: with it set, the program
sets no directory. Without it, the cache sits at one absolute path inside
the checkout, whatever the working directory, because the path is part
of the cache key.
"""

from pathlib import Path

import pytest

from repro import compile_cache

REPO_ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def updates(monkeypatch):
    """Record jax.config updates instead of applying them."""
    seen = {}
    monkeypatch.setattr(
        compile_cache.jax.config, "update", lambda k, v: seen.__setitem__(k, v)
    )
    return seen


@pytest.mark.parametrize("env_dir", ["/elsewhere/cache", "relative/cache"])
def test_env_dir_is_left_to_jax(monkeypatch, updates, env_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    compile_cache.enable_compilation_cache()
    assert "jax_compilation_cache_dir" not in updates


@pytest.mark.parametrize("unset", ["absent", "empty"])
def test_default_dir_is_absolute_and_in_checkout(monkeypatch, updates, unset):
    if unset == "absent":
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    compile_cache.enable_compilation_cache()
    path = Path(updates["jax_compilation_cache_dir"])
    assert path.is_absolute()
    assert path == REPO_ROOT / ".jax_cache"


def test_default_dir_does_not_follow_cwd(monkeypatch, updates, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = []
    for cwd in (tmp_path, REPO_ROOT / "tests", REPO_ROOT):
        monkeypatch.chdir(cwd)
        compile_cache.enable_compilation_cache()
        seen.append(updates["jax_compilation_cache_dir"])
    assert len(set(seen)) == 1
    assert seen[0] == str(compile_cache.CACHE_DIR)
