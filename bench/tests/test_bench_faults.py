"""Whole runs on the CPU: sound ones come out correct; with the timed
path broken underneath (``bench.plant``), incorrect."""
import pytest

from bench import plant
from bench.tests.tiny import run_tiny, tiny_cell

BATCH = "uniform-1e6-a100-pairs"
SERVE = "serve-churn-2x1e6"


@pytest.mark.parametrize("workload", [BATCH, SERVE])
def test_sound_run_is_correct(workload, capsys):
    res = run_tiny(tiny_cell(workload), capsys)
    assert res["correct"] is True, res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload", [BATCH, SERVE])
@pytest.mark.parametrize("kind", ["unchanged", "half", "altered",
                                  "control"])
def test_planted_fault_is_incorrect(workload, kind, capsys):
    cell = tiny_cell(workload)
    with plant.plant(cell.traffic["driver"], kind):
        res = run_tiny(cell, capsys)
    assert res["correct"] is False, (kind, res["checks"])
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_traced_run_reports_per_layer_metrics_only(capsys):
    cell = tiny_cell(BATCH)
    res = run_tiny(cell, capsys, trace=1)
    assert res["correct"] is True
    assert set(res["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
