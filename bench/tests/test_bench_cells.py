"""Both cells end to end on the CPU at a tiny size: set-up, warm-up, the
live window, the check against the reference, and the result line."""
import json

import pytest

import tinycell

CELLS = ["bitmap16m.heavy.closed32", "lineitem_sf11.q6.closed32"]


def _bench():
    return json.loads((tinycell.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(monkeypatch, tmp_path, name):
    cell = tinycell.tiny(tinycell.load(name))
    out = tinycell.run(monkeypatch, tmp_path, cell)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert list(out)[-1] == "checks"
    want = {m["name"] for m in _bench()["end_to_end"]
            if "workloads" not in m or name in m["workloads"]}
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert m["value"] > 0
    json.dumps(out)


def test_cell_rehearsal_traced(monkeypatch, tmp_path):
    name = CELLS[0]
    cell = tinycell.tiny(tinycell.load(name))
    out = tinycell.run(monkeypatch, tmp_path, cell, trace=True)
    assert out["correct"] is True, out["checks"]
    want = {m["name"] for m in _bench()["per_layer"]
            if "workloads" not in m or name in m["workloads"]}
    assert set(out["metrics"]) == want
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert 0 <= out["metrics"]["device_idle_share"]["value"] < 100
    assert out["metrics"]["groups_per_tick"]["value"] >= 1
    assert out["breakdown"]["idle_gaps"]
    assert not (tmp_path / "trace").exists()
