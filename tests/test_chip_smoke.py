"""chip_smoke.py at toy size on the CPU substrate.

The chip check itself only means something on the chip (`main` refuses
any other platform — pinned here); what tier-1 can hold is that every
phase function still runs end to end through the same entry points and
that its checks pass at a size the CPU finishes in seconds, so a change
that breaks the smoke is caught before it costs chip time. With the
suite's eight virtual devices the several-device branches (batched
arrays spanning every device, the 2D-mesh sparse fit, the one-device
comparison) execute too.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def test_main_refuses_the_cpu_platform(capsys):
    assert chip_smoke.main() != 0
    captured = capsys.readouterr()
    assert captured.out == ""  # no result line
    assert "not 'tpu'" in captured.err


def test_result_line_holds_exactly_ok_and_the_device():
    import json

    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 4, "extra": "dropped"}
    )
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
    }


def test_train_phase_toy():
    out = chip_smoke.phase_train(rows=4096, batch=512, parity_rows=2000, forms_rows=4096)
    assert out["rows"] == 4096 and out["lossRelDiff"] <= chip_smoke.LOSS_PARITY_RTOL
    assert out["batchesSpanEveryDevice"] is True
    # off the chip the estimators keep the reduce form; the kernel ran interpreted, called directly
    assert {name: form["onePass"] for name, form in out["denseForms"].items()} == {
        "LogisticRegression": False, "LinearSVC": False, "LinearRegression": False,
    }
    assert all(form["sumsRel"] <= chip_smoke.DENSE_FORMS_RTOL for form in out["denseForms"].values())


def test_dense_forms_toy_as_the_chip_takes_them(monkeypatch):
    """What the chip runs: the estimators' fits on the one-read kernel (here
    interpreted; batches of 96 rows start off the lanes) against the reduce form."""
    from flink_ml_tpu.parallel import mesh as mesh_lib

    monkeypatch.setattr(mesh_lib, "on_tpu", lambda arr: True)
    monkeypatch.setattr(mesh_lib, "rows_minor", lambda arr: arr.ndim == 2)
    forms = chip_smoke.dense_forms(22 * 96, 96, dim=10)
    assert all(form["onePass"] and form["coefRel"] <= chip_smoke.DENSE_FORMS_RTOL for form in forms.values())


def test_loops_sparse_and_one_device_phases_toy():
    loops, table, whole = chip_smoke.phase_loops(
        rows=4000, batch=500, dim=8, kmeans_rows=2000
    )
    assert loops["streamHostSyncs"] > 1 and loops["checkpointSnapshots"] >= 1
    one = chip_smoke.phase_one_device(table, whole, batch=500)
    assert one["oneDeviceVsDefaultMeshRel"] <= chip_smoke.ONE_DEVICE_RTOL
    assert one["walkedVsOneDeviceRel"] == 0.0  # the CPU sums a share's batch as the one device does
    sparse = chip_smoke.phase_sparse(rows=2048, dim=5000, nnz=6, batch=256)
    assert sparse["mesh2dVs1dRel"] <= chip_smoke.CROSS_PATH_RTOL


def test_serve_phase_toy():
    out = chip_smoke.phase_serve(dim=8, fit_rows=2000, n_requests=12)
    assert out["bankedTraces"] == 0 and out["bankHits"] >= 1


def test_failed_check_raises():
    with pytest.raises(RuntimeError, match="chip_smoke check failed"):
        chip_smoke.check(False, "x")


def test_online_phase_toy():
    out = chip_smoke.phase_online(dim=5000, nnz=6, batch=256, batches=4)
    assert out["versions"] == 4 and out["vsNumpyRel"] <= chip_smoke.CROSS_PATH_RTOL
