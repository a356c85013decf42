"""The sixteen per-layer metrics that read distill from inside (PR 38), in the
one cell that lists them, `mainnet-300k.replay`, on the CPU at a test's size.
No timing read here means anything."""
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
PARTS = ["stage_distill_context_ms", "stage_distill_crosslinks_ms",
         "stage_distill_inputs_ms", "stage_distill_place_ms"]
BROUGHT = PARTS + [
    "stage_distill_self_ms", "stage_distill_layouts_ms",
    "stage_distill_participants_ms", "stage_distill_crosslink_roots_ms",
    "stage_distill_flags_ms", "stage_distill_inclusion_ms",
    "stage_distill_winners_ms", "stage_distill_committee_balances_ms",
    "distill_pending_rows", "distill_shuffles",
    "distill_crosslink_roots_hashed_singly", "idle_in_stage_distill"]


def test_the_entries_equal_their_files_and_list_the_one_cell():
    # picked by name, never by place: a later PR appends what it brings
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    mine = [m for m in bench["per_layer"] if m["name"] in BROUGHT]
    assert [m["name"] for m in mine] == BROUGHT and len(BROUGHT) == 16
    for entry in mine:
        file = json.loads(
            (REPO / "benchmark/layer_metrics" / f"{entry['name']}.json")
            .read_text())
        assert {k: file[k] for k in entry if k != "workloads"} \
            == {k: v for k, v in entry.items() if k != "workloads"}
        assert CELL in entry["workloads"]
        assert entry["moves"] == "epoch_boundary_s"
        assert entry["layer"] == ("device" if entry["source"] == "device_trace"
                                  else "host distillation")
    in_cell = [m["name"] for m in run.Cell(CELL).per_layer]
    assert [n for n in in_cell if n in BROUGHT] == BROUGHT


def test_the_traced_cell_prints_distill_from_inside(monkeypatch, drive):
    # the profiler stops after the first epoch; the window runs on
    monkeypatch.setattr(run, "TRACED_SECONDS", 0.0)
    result, rows = drive(CELL, trace=True)
    assert result["correct"] is True and _failed(rows) == []
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # what only a device plane gives is left out on a host backend
    assert set(BROUGHT) - set(metrics) == {"idle_in_stage_distill"}
    # one committee a slot at this size: 63 attestations a list
    assert metrics["distill_pending_rows"] == 2 * 63
    assert metrics["distill_shuffles"] == 0
    assert metrics["distill_crosslink_roots_hashed_singly"] == 0
    # medians part by part: the sum need not be the whole's median, but the
    # parts are the whole's parts
    whole = metrics["stage_distill_ms"]
    assert 0 <= metrics["stage_distill_self_ms"] < whole
    assert 0.5 * whole < sum(metrics[p] for p in PARTS) < 1.5 * whole
    context = sum(metrics[f"stage_distill_{p}_ms"]
                  for p in ("layouts", "participants", "crosslink_roots"))
    assert 0 < context <= 1.5 * metrics["stage_distill_context_ms"]
    for name in BROUGHT[5:12]:
        assert metrics[name] > 0
