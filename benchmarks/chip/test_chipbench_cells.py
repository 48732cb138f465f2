"""Each cell's open loop, metric arithmetic and check, end to end on the CPU
at ``.smoke()`` widths through the harness's Python entry."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from benchmarks.chip import cpu_smoke  # noqa: E402

CELLS = ["testbed.paper_mix", "cloud_only.long_decode"]
SEED = 2**31 + 77


@pytest.mark.parametrize("name", CELLS)
def test_cell_reports_its_end_to_end_metrics(name):
    r = cpu_smoke.run(name, SEED, 2.0)
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device",
                       "checks"]
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    want = {m["name"] for m in cpu_smoke.cell(name).end_to_end}
    assert set(r["metrics"]) == want
    assert all(m["value"] is not None and m["value"] > 0
               for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("name", CELLS)
def test_traced_cell_reports_only_per_layer_metrics(name):
    r = cpu_smoke.run(name, SEED + 1, 2.0, trace=True)
    assert r["correct"] is True
    per_layer = {m["name"] for m in cpu_smoke.cell(name).per_layer}
    assert "tick_ms" in r["metrics"] and set(r["metrics"]) <= per_layer
    # no TPU plane in a CPU trace: the device metrics stay silent
    assert "idle_share" not in r["metrics"]
    assert "busy_s" not in r["device"]
