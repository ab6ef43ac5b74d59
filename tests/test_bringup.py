"""Bring-up contracts (ISSUE 21): where the compile cache goes, and a
benchmark driver that can fail.

- One function places the persistent compile cache. With
  JAX_COMPILATION_CACHE_DIR set the program sets no directory in code
  (jax read the variable at import); unset, it is `.jax_cache` at the root
  of the checkout — from any working directory.
- `bench.py main` still prints its line when a stage raises, stamps the
  device into it, names budget-skipped stages as such, and returns
  non-zero.
"""

import json
import os
import sys

import jax
import pytest

from flink_ml_tpu import config

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import bench  # noqa: E402


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


def test_bench_main_prints_its_line_and_fails_when_a_stage_raises(monkeypatch, capsys):
    monkeypatch.setattr(config, "enable_compilation_cache", lambda: None)
    for name in dir(bench):
        if name.startswith("bench_"):
            monkeypatch.setattr(bench, name, lambda *a, **k: {"totalTimeMs": 1.0})
    monkeypatch.setattr(
        bench, "bench_logreg",
        lambda rows, in_budget: {"throughputPerChip": 5.0, "inputThroughput": 5.0},
    )

    def boom():
        raise RuntimeError("stage exploded")

    monkeypatch.setattr(bench, "bench_kmeans", boom)
    # a budget that is spent once the always-run first stage is done
    monkeypatch.setenv("BENCH_BUDGET_S", "100")
    rc = bench.main(["--skip-cpu"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc != 0
    assert line["failedStages"] == ["kmeans"]
    assert "stage exploded" in line["details"]["kmeans"]["failed"]
    assert line["details"]["cpuBaseline"] == {"skipped": "--skip-cpu"}
    assert line["details"]["sparseWideLR"] == {"totalTimeMs": 1.0}
    assert line["value"] == 5.0
    assert line["device"] == {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }

    # no budget left: every later stage is NAMED as skipped, none raised
    monkeypatch.setenv("BENCH_BUDGET_S", "0")
    rc = bench.main([])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["failedStages"] == []
    assert line["details"]["logisticregression"]["throughputPerChip"] == 5.0
    assert line["details"]["kmeans"] == {"skipped": "budget"}


def test_unknown_device_kind_has_no_peaks():
    # the CPU substrate is not in the table: an error, not a default
    with pytest.raises(KeyError, match="no published peaks"):
        bench._device_peaks()
    assert bench.DEVICE_PEAKS["TPU v5 lite"] == {"flops": 197e12, "hbmGBps": 819.0}


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
