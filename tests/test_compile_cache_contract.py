"""The suite's compile cache (``tests/conftest.py``): every process of a
run shares one directory, and an entry appears there whole or not at all.

Device-free, two child processes. ``share_compile_cache`` stands on three
``jax._src`` names (``compilation_cache.get_file_cache``, called by
``_initialize_cache``; ``lru_cache.LRUCache`` with ``.path`` and ``.get``;
``lru_cache._CACHE_SUFFIX``): a JAX that moves one fails here first.
"""

import inspect
import os
import subprocess
import sys
from pathlib import Path

import pytest
from jax._src import compilation_cache, lru_cache

REPO = Path(__file__).resolve().parents[1]

# what a xdist worker does at start-up, then one small jitted function
CHILD = """
import tests.conftest
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
hits = []
jax.monitoring.register_event_listener(
    lambda event, **kw: hits.append(event) if event.endswith("/cache_hits") else None
)
f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
print(float(f(jnp.ones((8, 8)))), len(hits), jax.config.jax_compilation_cache_dir)
"""


def _child(root: Path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(root), JAX_PLATFORMS="cpu")
    env.pop("DTPU_TEST_NO_COMPILE_CACHE", None)
    out = subprocess.run(
        [sys.executable, "-c", CHILD], cwd=REPO, env=env, timeout=120,
        capture_output=True, text=True, check=True,
    ).stdout.split()
    return float(out[0]), int(out[1]), Path(out[2])


def test_a_second_process_finds_the_first_ones_entry(tmp_path):
    value, hits, where = _child(tmp_path)
    assert hits == 0 and where == tmp_path / "tests"
    first = sorted(p.name for p in where.iterdir())
    assert sum(n.startswith("jit__lambda-") for n in first) == 1
    assert all(n.endswith(lru_cache._CACHE_SUFFIX) for n in first)  # no temporary left
    again, hits, where_again = _child(tmp_path)
    assert (again, hits, where_again) == (value, len(first), where)
    assert sorted(p.name for p in where.iterdir()) == first  # nothing written anew


@pytest.mark.skipif(
    os.environ.get("DTPU_TEST_NO_COMPILE_CACHE") == "1",
    reason="conftest installed no cache in this process",
)
def test_an_entry_is_never_seen_cut(tmp_path, monkeypatch):
    """``put`` writes under a name no reader looks for and renames: at the
    rename the whole value is on disk and the entry's name is not."""
    cache, _ = compilation_cache.get_file_cache(str(tmp_path))
    assert isinstance(cache, lru_cache.LRUCache) and not cache.eviction_enabled
    assert "get_file_cache(path)" in inspect.getsource(compilation_cache._initialize_cache)
    value, seen, rename = os.urandom(1 << 20), [], os.replace

    def spy(src, dst):
        seen.append((Path(src).read_bytes(), [p.name for p in tmp_path.glob("*-cache")]))
        rename(src, dst)

    monkeypatch.setattr(os, "replace", spy)
    cache.put("some-key", value)
    assert seen == [(value, [])]
    assert cache.get("some-key") == value
    assert [p.name for p in tmp_path.iterdir()] == ["some-key" + lru_cache._CACHE_SUFFIX]
    cache.put("some-key", b"another")  # a key is written once
    assert cache.get("some-key") == value and len(seen) == 1
