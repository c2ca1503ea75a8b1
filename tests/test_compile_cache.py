"""Where the persistent compile cache lives (``core/cache.py``).

The cache is placed from OUTSIDE the program: ``JAX_COMPILATION_CACHE_DIR``
when set (JAX reads it itself, the code sets no directory), otherwise one
fixed directory inside the checkout.  ``jax.config.update`` is recorded,
not applied: the cache stays off inside the eight-virtual-device pytest
process (.claude/skills/verify/SKILL.md).
"""

import os

import jax
import pytest

from progen_tpu.core import cache


@pytest.fixture
def config_updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    return calls


def test_cache_dir_from_environment_is_left_alone(monkeypatch, tmp_path,
                                                  config_updates):
    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert cache.enable_compilation_cache() == placed
    assert "jax_compilation_cache_dir" not in config_updates
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == placed
    # nothing is created on JAX's behalf either
    assert not os.path.exists(placed)


def test_cache_dir_defaults_to_fixed_path_in_checkout(monkeypatch,
                                                      config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    made = []
    monkeypatch.setattr(cache.os, "makedirs",
                        lambda path, exist_ok=False: made.append(path))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert cache.enable_compilation_cache() == want == cache.DEFAULT_CACHE_DIR
    assert config_updates["jax_compilation_cache_dir"] == want
    assert made == [want]
    # the same answer every time: no home, temp name, pid or clock in it
    assert cache.enable_compilation_cache() == want


def test_unmakeable_cache_dir_is_an_error(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def refuse(path, exist_ok=False):
        raise PermissionError(13, "read-only checkout", path)

    monkeypatch.setattr(cache.os, "makedirs", refuse)
    with pytest.raises(PermissionError):
        cache.enable_compilation_cache()
    assert "jax_compilation_cache_dir" not in config_updates
