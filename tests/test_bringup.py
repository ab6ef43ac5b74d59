"""Bring-up contracts (ISSUE 21): where the compile cache goes, and which
native build is loaded.

- One function places the persistent compile cache. With
  JAX_COMPILATION_CACHE_DIR set the program sets no directory in code
  (jax read the variable at import); unset, it is `.jax_cache` at the root
  of the checkout — from any working directory.
- The native library's file name carries a hash of its sources and flags.
"""

import os

import jax
import pytest

from flink_ml_tpu import config

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them — the
    cache switch is process-global and must not leak into other tests."""
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda name, value: calls.append((name, value)))
    monkeypatch.setattr(config, "compilation_cache_dir", None)
    return calls


def test_cache_dir_from_environment_sets_no_directory(monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    assert config.enable_compilation_cache() == str(tmp_path / "outside")
    # an explicit path loses to the environment too
    assert config.enable_compilation_cache(str(tmp_path / "mine")) == str(tmp_path / "outside")
    assert "jax_compilation_cache_dir" not in [name for name, _ in config_updates]
    assert config.compilation_cache_dir == str(tmp_path / "outside")


def test_cache_dir_defaults_to_checkout_from_any_cwd(monkeypatch, config_updates, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    expected = os.path.join(_ROOT, ".jax_cache")
    assert config.enable_compilation_cache() == expected
    assert ("jax_compilation_cache_dir", expected) in config_updates
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) in config_updates


def test_native_library_is_keyed_by_source_bytes(monkeypatch, tmp_path):
    """A build left by another commit can never be loaded: the .so's name
    carries a hash of the source bytes and the compiler flags."""
    import flink_ml_tpu.native as nat

    src = tmp_path / "k.cc"
    src.write_text("int f() { return 1; }\n")
    monkeypatch.setattr(nat, "_SOURCES", [str(src)])
    first = nat._lib_path()
    assert first == nat._lib_path()  # stable for the same bytes
    src.write_text("int f() { return 2; }\n")
    second = nat._lib_path()
    assert second != first
    monkeypatch.setattr(nat, "_FLAGS", nat._FLAGS + ["-O0"])
    assert nat._lib_path() != second
