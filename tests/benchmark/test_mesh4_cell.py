"""The four-chip cell `mainnet-1m-mesh4.replay`, as BENCHMARK.json commits
it, on four of this process's virtual devices at a test's size; the reader
kind it brought (`trace_op_ms`) and its cost file.

The cell is the harness's `with_mesh_cells` made real: a configuration file
with `chips: 4`, two entries, five per-layer metrics with their files, and
its name appended to the `workloads` lists of the metrics of the cell it is
like (`mainnet-1m.replay`, its control on one chip), all but the one-chip
roofline. No timing read here means anything.
"""
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

from benchmark import reduce, run  # noqa: E402
from benchmark.costs import epoch_program_bytes, epoch_program_bytes_per_chip  # noqa: E402
from benchmark.reduce import Event, Line, Plane  # noqa: E402
from synthetic_run import MS, on_a_host_backend, planes as _planes, seen as _seen  # noqa: E402
from test_benchmark_harness import (  # noqa: E402,F401  (`drive` is a fixture)
    MESH_V, _before_compare, _failed, drive)

CELL = "mainnet-1m-mesh4.replay"
CONTROL = "mainnet-1m.replay"
BROUGHT = ["epoch_program_roofline.mesh4", "epoch_collective_ms",
           "epoch_collective_ops", "forest_collective_ms", "epoch_mesh_size"]
COLLECTIVES = ["all-reduce", "collective-permute"]


# -- the committed entries -------------------------------------------------------

def test_the_cell_is_the_control_laid_out_on_four_chips():
    cell, control = run.Cell(CELL), run.Cell(CONTROL)
    assert cell.chips == cell.config["chips"] == 4 and control.chips == 1
    assert cell.mix == control.mix and cell.row["traffic"] == "replay"
    # the same deployment: every key of the one-chip file but the four that
    # say where it lives
    where = {"name", "chips", "layout", "source"}
    assert set(cell.config) == set(control.config)
    assert {k: v for k, v in cell.config.items() if k not in where} \
        == {k: v for k, v in control.config.items() if k not in where}
    assert cell.config["validators"] == 1_000_000
    assert cell.config["source"].startswith(control.config["source"])
    assert "One chip would hold this state" in cell.config["layout"]


def test_the_cell_reports_the_replay_metrics_and_reads_its_own():
    cell, control = run.Cell(CELL), run.Cell(CONTROL)
    assert [m["name"] for m in cell.end_to_end] \
        == [m["name"] for m in control.end_to_end] \
        == ["replay_slots_per_s", "epoch_boundary_s", "slot_root_p95_ms",
            "setup_s"]
    read = [m["name"] for m in cell.per_layer]
    # the one-chip roofline counts the whole registry's bytes on one chip
    like = [m["name"] for m in control.per_layer
            if m["name"] != "epoch_program_roofline"]
    assert len(like) >= 26 and "epoch_program_roofline" not in read
    assert read == like + BROUGHT
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for entry in bench["per_layer"]:
        if entry["name"] in BROUGHT:
            assert entry["workloads"] == [CELL]


# -- the cell through `drive`, on four virtual devices ---------------------------

def test_the_cell_runs_correct_and_prints_what_a_host_backend_can_read(
        monkeypatch, drive):
    """V = 8,194 is no multiple of four: the mesh pads the columns by two
    inert rows, and every comparison holds on the logical rows. `drive`'s
    `_check_line` holds the line to every per-layer name of the cell whose
    source is not the device's trace."""
    import jax
    from benchmark.drivers import replay
    placed = {}
    _before_compare(monkeypatch, replay, lambda driver: placed.update(
        devices=driver.dep.core.cols.balance.sharding.device_set,
        rows=driver.dep.core.cols.balance.shape[0]))
    result, rows = drive(CELL, trace=True, validators=MESH_V)
    assert placed == {"devices": set(jax.devices()[:4]), "rows": MESH_V + 2}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 64 and _failed(rows) == []
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["epoch_mesh_size"] == 4.0
    assert metrics["compiles_in_window"] == 0 == metrics["guard_events"]
    assert metrics["slot_root_trees_rebuilt"] == 0
    assert metrics["stage_upload_ms"] > 0 and metrics["epoch_device_ms"] > 0
    # what only a device plane gives is left out here, nothing else
    assert set(BROUGHT) - set(metrics) == set(BROUGHT) - {"epoch_mesh_size"}


def test_the_cell_reports_its_end_to_end_metrics_untraced(drive):
    result, rows = drive(CELL, trace=False, validators=MESH_V)
    assert result["correct"] is True and _failed(rows) == []
    assert set(result["metrics"]) == {
        "replay_slots_per_s", "epoch_boundary_s", "slot_root_p95_ms",
        "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_one_gwei_on_the_last_shard_makes_correct_false(monkeypatch, drive):
    """Control 1 where only a sharded core has it: a row of the last
    device's shard, behind the forests' back."""
    import jax
    import numpy as np
    from benchmark.drivers import replay
    touched = {}

    def one_gwei(driver):
        cols = driver.dep.core.cols
        per_shard = cols.balance.shape[0] // 4
        index = 3 * per_shard + driver.seed % (MESH_V - 3 * per_shard)
        shard = next(s for s in cols.balance.addressable_shards
                     if s.index[0].start <= index < s.index[0].stop)
        touched.update(index=index, device=shard.device)
        driver.dep.core.cols = cols._replace(
            balance=cols.balance.at[index].add(np.uint64(1)))
    _before_compare(monkeypatch, replay, one_gwei)
    result, rows = drive(CELL, trace=False, validators=MESH_V)
    assert touched["device"] == jax.devices()[3] and touched["index"] < MESH_V
    assert result["correct"] is False
    assert _failed(rows) == ["balances_root.bytes_differing_from_hashlib",
                             "state_root.bytes_differing_from_hashlib"]


# -- the reader kind the cell brought --------------------------------------------

def _device(index: int, ops, modules) -> Plane:
    return Plane(reduce.DEVICE_PLANE_PREFIX + str(index), [
        Line(reduce.OPS_LINE, [Event(n, a * MS, d * MS) for n, a, d in ops]),
        Line(reduce.MODULES_LINE, [Event(n, a * MS, d * MS)
                                   for n, a, d in modules])])


def _op_reader(stat: str, prefix: str = "jit_epoch") -> dict:
    return {"reader": {"kind": "trace_op_ms", "module_prefix": prefix,
                       "op_prefixes": COLLECTIVES, "stat": stat}}


EPOCH_RUNS = [("jit_epoch(7)", 10, 20), ("jit_epoch(7)", 40, 20),
              ("jit_epoch(7)", 70, 20)]


def test_trace_op_ms_sums_the_wanted_operations_of_each_execution():
    """Three executions: 2 + 1, 4, and 1 + 1 + 1 ms of collectives (the
    `-start` / `-done` halves of an asynchronous one both count); medians 3
    ms and 2 operations. The HLO text after ` = ` is not the name."""
    ops = [("%all-reduce.1 = u64[] all-reduce(u64[] %p), to_apply=%add", 11, 2),
           ("fusion.5", 14, 9), ("collective-permute.2", 25, 1),
           ("all-reduce-start.3", 41, 4), ("all-reducer", 50, 0),
           ("%fusion.9 = u64[] fusion(u64[] %all-reduce.1)", 52, 7),
           ("all-reduce.1", 71, 1), ("collective-permute-start.2", 73, 1),
           ("collective-permute-done.2", 80, 1)]
    host = _planes([], [])[-1]
    seen = _seen(planes=[_device(0, ops, EPOCH_RUNS), host])
    assert run.read_metric(_op_reader("ms"), seen) == pytest.approx(3.0)
    # `all-reducer` starts with a wanted prefix and is counted: the prefixes
    # are chosen so that no other operation of the program starts with one
    assert run.read_metric(_op_reader("count"), seen) == pytest.approx(2.0)


def test_trace_op_ms_leaves_out_what_runs_outside_a_module_or_the_window():
    ops = [("all-reduce.1", 2, 5),                      # before any execution
           ("all-reduce.1", 12, 1), ("all-reduce.1", 33, 4),    # between two
           ("all-reduce.1", 45, 3), ("all-reduce.1", 97, 2)]    # past the end
    runs = [("jit_epoch(7)", 10, 20), ("jit_epoch(7)", 40, 20),
            ("jit_other(1)", 31, 8), ("jit_idle(2)", 62, 3),
            ("jit_epoch(7)", 95, 10)]
    host = _planes([], [])[-1]
    seen = _seen(planes=[_device(0, ops, runs), host])
    assert run.read_metric(_op_reader("ms"), seen) == pytest.approx(2.0)
    assert run.read_metric(_op_reader("count"), seen) == pytest.approx(1.0)
    # a program that ran and held no such operation reads 0, not nothing
    assert run.read_metric(_op_reader("ms", "jit_other"), seen) == 4.0
    assert run.read_metric(_op_reader("ms", "jit_idle"), seen) == 0.0
    assert run.read_metric(_op_reader("ms", "jit_nothing"), seen) is None


def test_trace_op_ms_takes_the_slowest_plane_and_sums_programs():
    """The forest build runs at two capacities, two programs under one
    prefix: each program's median, then their sum, on each plane; the plane
    that reads most stands."""
    runs = [("jit_build(20)", 10, 10), ("jit_build(18)", 25, 5),
            ("jit_build(20)", 50, 10), ("jit_build(18)", 65, 5)]
    quick = [("all-reduce.1", 11, 1), ("all-reduce.1", 26, 1),
             ("all-reduce.1", 51, 1), ("all-reduce.1", 66, 1)]
    slow = [("all-reduce.1", 11, 3), ("all-reduce.1", 15, 2),
            ("all-reduce.1", 26, 1), ("all-reduce.1", 51, 5),
            ("all-reduce.1", 66, 2)]
    host = _planes([], [])[-1]
    seen = _seen(planes=[_device(0, quick, runs), _device(1, slow, runs),
                         host])
    # plane 1: program 20 reads 5 and 5, program 18 reads 1 and 2
    assert run.read_metric(_op_reader("ms", "jit_build"), seen) \
        == pytest.approx(5.0 + 1.5)
    assert run.read_metric(_op_reader("count", "jit_build"), seen) \
        == pytest.approx(1.5 + 1.0)
    assert run.read_metric(_op_reader("ms", "jit_build"),
                           on_a_host_backend(seen)) is None
    assert run.read_metric(_op_reader("ms", "jit_build"), _seen()) is None


# -- the cost file the cell brought ------------------------------------------------

@pytest.mark.parametrize("validators", [0, 8_194, 300_000, 1_000_000])
def test_one_chips_bytes_times_the_chips_cover_the_whole_programs(validators):
    whole = epoch_program_bytes.count({"validators": validators})
    per_chip = epoch_program_bytes_per_chip.count
    # nothing is replicated on one chip: the count is the one-chip file's
    assert per_chip({"validators": validators, "chips": 1}) == whole
    assert per_chip({"validators": validators}) == whole
    replicated = epoch_program_bytes.count({"validators": 0})
    for chips in (2, 4):
        got = chips * per_chip({"validators": validators, "chips": chips})
        assert got >= whole
        padded = -(-validators // chips) * chips
        assert got == (epoch_program_bytes.count({"validators": padded})
                       + (chips - 1) * replicated)


def test_the_mesh_roofline_is_the_one_chip_readers_on_the_per_chip_bytes():
    metric = json.loads((REPO / "benchmark" / "layer_metrics"
                         / "epoch_program_roofline.mesh4.json").read_text())
    assert metric["reader"]["kind"] == "roofline_bytes"
    assert metric["reader"]["bytes"] == "epoch_program_bytes_per_chip"
    config = run.Cell(CELL).config
    least_s = epoch_program_bytes_per_chip.count(config) / 819e9
    name = metric["reader"]["module_prefix"] + "(3)"
    seen = _seen(planes=_planes([], [], modules=[(name, 10, 50.0)]),
                 config=config, peaks={"hbm_bytes_per_s": 819e9})
    share = run.read_metric(metric, seen)
    assert share == pytest.approx(100 * least_s / 0.050) and 0 < share < 100
