"""The persistent compile cache is placed from outside
(``JAX_COMPILATION_CACHE_DIR``) or sits at ONE fixed in-checkout path —
never a temp dir, a pid or a timestamp: a cache that moves never hits."""

import subprocess
import sys
from pathlib import Path

import jax
import pytest

from dstack_tpu.utils import backend

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them."""
    calls = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.__setitem__(k, v))
    return calls


def test_env_var_set_helper_sets_no_directory(monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert backend.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: nothing about the directory in code
    assert "jax_compilation_cache_dir" not in config_updates
    assert config_updates  # the thresholds still apply


def test_env_var_unset_uses_the_fixed_checkout_path(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_compile_cache")
    assert backend.enable_compile_cache() == want
    assert config_updates["jax_compilation_cache_dir"] == want


def test_explicit_flag_overrides_the_env_var(monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    flag = str(tmp_path / "flag")
    assert backend.enable_compile_cache(flag) == flag
    assert config_updates["jax_compilation_cache_dir"] == flag


def test_same_path_from_two_directories_and_two_processes(tmp_path):
    """Unset: two processes started in different working directories
    agree on one absolute path inside the checkout."""
    env = {"PATH": "/usr/bin:/bin", "PYTHONPATH": str(REPO)}
    seen = {
        subprocess.run(
            [sys.executable, "-c",
             "from dstack_tpu.utils.backend import compile_cache_dir; "
             "print(compile_cache_dir())"],
            cwd=cwd, env=env, capture_output=True, text=True, timeout=60,
            check=True,
        ).stdout.strip()
        for cwd in (tmp_path, REPO / "tests")
    }
    assert len(seen) == 1
    path = Path(seen.pop())
    assert path.is_absolute() and REPO in path.parents


def test_no_moving_part_in_the_cache_path():
    source = Path(backend.__file__).read_text()
    for moving in ("tempfile", "getpid", "time.", "uuid", "mkdtemp"):
        assert moving not in source, moving


def test_gitignore_lists_the_default_cache():
    ignored = (REPO / ".gitignore").read_text().split()
    assert backend.DEFAULT_COMPILE_CACHE.name + "/" in ignored
