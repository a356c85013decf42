"""The two per-layer metrics that read the boundary's forest rebuild run
ahead of its wait (PR 39), in the one cell that lists them,
`mainnet-300k.replay`, on the CPU at a test's size. No timing read here
means anything but its sign."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
for p in (REPO, HERE):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmark import run  # noqa: E402
from test_benchmark_harness import (  # noqa: E402,F401  (fixtures)
    TINY_V, _failed, drive)

CELL = "mainnet-300k.replay"
BROUGHT = ["forest_build_ahead_ms", "forest_dispatch_ms"]


@pytest.mark.parametrize("name", BROUGHT)
def test_the_entry_equals_its_file_and_lists_the_one_cell(name):
    # picked by name, never by place: a later PR appends what it brings
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    file = json.loads(
        (REPO / "benchmark/layer_metrics" / f"{name}.json").read_text())
    assert {k: file[k] for k in entry if k != "workloads"} \
        == {k: v for k, v in entry.items() if k != "workloads"}
    assert entry["workloads"] == [CELL]
    assert (entry["layer"], entry["moves"], entry["source"], entry["unit"]) \
        == ("Merkle forests", "epoch_boundary_s", "program_span", "ms")
    assert name in [m["name"] for m in run.Cell(CELL).per_layer]


def test_the_traced_cell_prints_the_build_run_ahead(monkeypatch, drive):
    # the profiler stops after the first epoch; the window runs on
    monkeypatch.setattr(run, "TRACED_SECONDS", 0.0)
    result, rows = drive(CELL, trace=True)
    assert result["correct"] is True and _failed(rows) == []
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # the download and the final updates ran between dispatch and wait
    assert metrics["forest_build_ahead_ms"] >= (
        metrics["refresh_download_ms"] + metrics["refresh_final_updates_ms"]) \
        * 0.5 > 0
    assert metrics["forest_dispatch_ms"] > 0
    # the parts are the refresh's parts: the wait is one of them still
    assert metrics["forest_dispatch_ms"] + metrics["forest_build_ms.replay"] \
        < metrics["refresh_ms"]
    # the same build, not a smaller one: every leaf of both forests
    # (V - 1 pair lanes over V registry leaves padded to a power of two,
    # and the balances' V / 4 chunks likewise)
    assert metrics["forest_pair_lanes"] >= TINY_V - 1 + TINY_V // 4 - 1
