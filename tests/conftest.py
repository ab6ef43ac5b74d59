"""Test harness configuration.

Runs the whole test suite on a virtual 8-device CPU mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=8) — the analogue of the
reference's in-JVM MiniCluster test substrate (SURVEY.md §4): collectives,
sharding, and iteration paths execute multi-device without TPU hardware.
Must set env vars before jax initializes, hence the top-of-file placement.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Force the CPU platform in code too: this wins over whatever JAX_PLATFORMS
# the environment carries, so the suite never reaches for a chip.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Concurrency sanitizer (docs/static_analysis.md): FLINK_ML_TPU_SANITIZE=1
# wraps every flow-layer lock/channel/worker and fails the session on
# recorded lock-order cycles, leaked workers, or unclosed pump channels —
# the runtime cross-check of the static lock-order/channel-protocol rules.
from flink_ml_tpu.analysis import sanitizer  # noqa: E402

if sanitizer.enabled_by_env():
    sanitizer.enable(register_atexit=False)


def pytest_sessionfinish(session, exitstatus):
    if not (sanitizer.enabled_by_env() and exitstatus == 0):
        return
    problems = sanitizer.recorder.problems()
    sanitizer.mark_exit_checked()
    reporter = session.config.pluginmanager.get_plugin("terminalreporter")
    emit = reporter.write_line if reporter else print
    if problems:
        for problem in problems:
            emit(f"FLINK_ML_TPU_SANITIZE: {problem}")
        session.exitstatus = 1
    else:
        stats = sanitizer.recorder.stats()
        emit(
            "FLINK_ML_TPU_SANITIZE: clean — "
            f"{stats['acquisitions']} acquisitions, {stats['workers']} workers, "
            f"{stats['channelsClosed']}/{stats['channels']} channels closed, "
            f"{stats['collectives']} collectives in {stats['collectiveGroups']} "
            "scope group(s)"
        )


@pytest.fixture
def mesh8():
    from flink_ml_tpu.parallel import mesh as mesh_lib

    m = mesh_lib.create_mesh((mesh_lib.DATA_AXIS,))
    with mesh_lib.use_mesh(m):
        yield m


@pytest.fixture
def mesh_2d():
    """4x2 (data, model) mesh for feature-sharded tests."""
    from flink_ml_tpu.parallel import mesh as mesh_lib

    m = mesh_lib.create_mesh(
        (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS), shape=(4, 2)
    )
    with mesh_lib.use_mesh(m):
        yield m


@pytest.fixture(autouse=True)
def _reset_default_mesh():
    from flink_ml_tpu.parallel import mesh as mesh_lib

    yield
    mesh_lib.set_default_mesh(None)
