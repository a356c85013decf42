"""The three per-layer metrics that read the candidate crosslink groups
formed once a boundary (PR 44), in the one cell that lists them,
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
    _failed, drive)

CELL = "mainnet-300k.replay"
BROUGHT = {"stage_distill_winner_groups_ms": ("program_span", "ms"),
           "distill_winner_unions_in_pass": ("program_counter", "count"),
           "distill_winner_groups": ("program_counter", "count")}


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
    assert (entry["layer"], entry["moves"]) \
        == ("host distillation", "epoch_boundary_s")
    assert (entry["source"], entry["unit"]) == BROUGHT[name]
    assert name in [m["name"] for m in run.Cell(CELL).per_layer]


def test_the_traced_cell_prints_the_groups_and_no_union_in_a_pass(
        monkeypatch, drive):
    # the profiler stops after the first epoch; the window runs on
    monkeypatch.setattr(run, "TRACED_SECONDS", 0.0)
    result, rows = drive(CELL, trace=True)
    assert result["correct"] is True and _failed(rows) == []
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # one committee a slot at this size: 63 attest an epoch list, each one
    # candidate, and every union is computed where the groups are formed
    assert metrics["distill_winner_groups"] == 2 * 63 \
        == metrics["distill_pending_rows"]
    assert metrics["distill_winner_unions_in_pass"] == 0
    assert metrics["stage_distill_winner_groups_ms"] > 0
    # the selections and the committees' sums still record, thrice each
    assert metrics["stage_distill_winners_ms"] > 0
    assert metrics["stage_distill_committee_balances_ms"] > 0
    # a part of the context, beside its three
    assert metrics["stage_distill_winner_groups_ms"] \
        <= 1.5 * metrics["stage_distill_context_ms"]
