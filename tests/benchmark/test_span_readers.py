"""The span and trace readers on hand-built spans and planes, every
per-layer metric of BENCHMARK.json through its own `layer_metrics/*.json` on
a synthetic record, and what holds of `per_layer` whatever it lists: every
entry names the cells that read it, every cell reads the entries that list
it, every file has an entry and every reader kind a source and a record.

Nothing here counts entries or names a metric's file: a PR that adds a
cell, a metric or a reader kind adds files and entries, and a cell's name
to the `workloads` lists of the metrics it reports and reads (the recipe is
`benchmark/run.py`'s docstring), and no line of this file."""
from __future__ import annotations

import importlib.util
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
from synthetic_run import (  # noqa: E402
    OPS, on_a_host_backend, planes as _planes, seen as _seen, span as _span)

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
LAYER_METRICS = REPO / "benchmark" / "layer_metrics"
SOURCES = REPO / "benchmark" / "sources"
RECORDS = HERE / "reader_records"


# idle [0,20] [30,60] [70,100] of the window; the slot root's annotations
# cover [10,50] (its groups nested inside), a boundary's refresh [55,80]
OPS = [("fusion.1", 20, 10), ("fusion.2", 60, 10)]
NOTES = [("resident.slot", 5, 50),
         ("resident.slot_root", 10, 40),
         ("resident.slot_root.history", 12, 20),
         ("resident.slot_root.history.deeper", 15, 5),
         ("resident.forests", 34, 4),   # inside the slot root by position
         ("resident.slot_root_other", 90, 5),       # a name, not a descendant
         ("resident.refresh", 55, 25),
         ("bench.slot", 0, 100)]

READERS = [
    # -- span_arg_median ------------------------------------------------------
    ({"kind": "span_arg_median", "span": "a", "arg": "n"},
     dict(spans=[_span("a", 1.0, n=4), _span("a", 1.0, n=10),
                 _span("a", 1.0, n=6), _span("a", 1.0, other=99),
                 _span("a", 1.0), _span("b", 1.0, n=1000)]), 6.0),
    ({"kind": "span_arg_median", "span": "a", "arg": "n"},
     dict(spans=[_span("a", 1.0, other=1), _span("b", 1.0, n=2)]), None),
    ({"kind": "span_arg_median", "span": "a", "arg": "n"}, {}, None),
    # -- span_arg_slope -------------------------------------------------------
    # n = 100 + 2 * req, with noise that cancels: 2 a step, 128 an epoch
    ({"kind": "span_arg_slope", "span": "a", "arg": "n", "per": 64},
     dict(spans=[_span("a", 1.0, req=0, n=101), _span("a", 1.0, req=1, n=101),
                 _span("a", 1.0, req=2, n=103), _span("a", 1.0, req=3, n=107),
                 _span("b", 1.0, req=9, n=9000), _span("a", 1.0, req=4),
                 _span("a", 1.0, n=7)]), 128.0),
    ({"kind": "span_arg_slope", "span": "a", "arg": "n"},
     dict(spans=[_span("a", 1.0, req=5, n=1), _span("a", 1.0, req=5, n=3)]),
     None),                                                 # one step only
    # a record of the parent commit's program carries no `req`
    ({"kind": "span_arg_slope", "span": "a", "arg": "n"},
     dict(spans=[{"name": "a", "dur": 1.0, "args": {"n": 1}},
                 {"name": "a", "dur": 1.0, "args": {"n": 2}}]), None),
    # -- span_least -----------------------------------------------------------
    ({"kind": "span_least", "span": "a", "scale": 1000.0},
     dict(spans=[_span("a", 0.56), _span("a", 0.22), _span("a", 0.23),
                 _span("b", 0.01)]), 220.0),
    ({"kind": "span_least", "span": "a"}, dict(spans=[_span("b", 1.0)]), None),
    # -- span_self_median -----------------------------------------------------
    # two parents: 1.0 - (0.3 + 0.2) and 2.0 - 0.5; a grandchild's time is
    # its own parent's, not the root's
    ({"kind": "span_self_median", "span": "p", "scale": 1000.0},
     dict(spans=[_span("c", 0.3, 11, 1), _span("c", 0.2, 12, 1),
                 _span("g", 0.1, 13, 11), _span("p", 1.0, 1),
                 _span("c", 0.5, 21, 2), _span("p", 2.0, 2),
                 _span("q", 9.0, 3)]), 1000.0),
    ({"kind": "span_self_median", "span": "p"},
     dict(spans=[_span("p", 1.5, 1)]), 1.5),                # no child
    ({"kind": "span_self_median", "span": "p"},
     dict(spans=[_span("q", 1.0, 1)]), None),
    # a record of the parent commit's program carries no `id`
    ({"kind": "span_self_median", "span": "p"},
     dict(spans=[{"name": "p", "dur": 1.0, "args": None}]), None),
    # -- trace_idle_in_span ---------------------------------------------------
    # slot root [10,50] with all that opens inside it, `resident.forests`
    # too, against idle [0,20] + [30,60]: [10,20] + [30,50] = 30 ms of the
    # 100 ms window
    ({"kind": "trace_idle_in_span", "span": "resident.slot_root"},
     dict(planes=_planes(OPS, NOTES)), 30.0),
    # a nested span counts for its ancestor and for itself:
    # history [12,32] (with .deeper inside) against idle: [12,20] + [30,32]
    ({"kind": "trace_idle_in_span", "span": "resident.slot_root.history"},
     dict(planes=_planes(OPS, NOTES)), 10.0),
    # inside is by position, not by name: the slot [5,55] holds its slot
    # root, [5,20] + [30,55]; `resident.slot_root_other` is neither's
    ({"kind": "trace_idle_in_span", "span": "resident.slot"},
     dict(planes=_planes(OPS, NOTES)), 40.0),
    ({"kind": "trace_idle_in_span", "span": "resident.forests"},
     dict(planes=_planes(OPS, NOTES)), 4.0),
    # the gap [30,60] straddles the slot ([..55]) and the refresh ([55..]):
    # it is split, [55,60] + [70,80] go to the refresh
    ({"kind": "trace_idle_in_span", "span": "resident.refresh"},
     dict(planes=_planes(OPS, NOTES)), 15.0),
    ({"kind": "trace_idle_in_span", "span": "resident.device"},
     dict(planes=_planes(OPS, NOTES)), None),               # no such span
    ({"kind": "trace_idle_in_span", "span": "resident.slot_root"},
     dict(planes=_planes(OPS, NOTES, device=False)), None),  # a host backend
    ({"kind": "trace_idle_in_span", "span": "resident.slot_root"}, {}, None),
]


@pytest.mark.parametrize("reader,seen,want", READERS,
                         ids=[f"{r['kind']}-{i}" for i, (r, _, _) in
                              enumerate(READERS)])
def test_reader(reader, seen, want):
    got = run.read_metric({"reader": reader}, _seen(**seen))
    assert got == (None if want is None else pytest.approx(want))


def test_idle_in_spans_never_exceeds_the_idle_share():
    planes = _planes(OPS, NOTES)
    idle = reduce.idle_share(planes)
    assert idle == pytest.approx(80.0)
    parts = [run.read_metric(
        {"reader": {"kind": "trace_idle_in_span", "span": s}},
        _seen(planes=planes))
        for s in ("resident.slot", "resident.refresh",     # disjoint spans
                  "resident.slot_root_other")]
    assert parts == pytest.approx([40.0, 15.0, 5.0])
    assert sum(parts) <= idle


# -- every per-layer metric, through its own file ------------------------------

def _record(kind: str):
    """`reader_records/<kind>.py`: `record(reader)` gives the fields of a run
    that holds exactly what the reader looks for, and the value it must
    read. A PR that adds `sources/<kind>.py` adds its record beside these."""
    path = RECORDS / f"{kind}.py"
    assert path.is_file(), (
        f"no test drives a {kind!r} reader through its metric's own file: "
        f"add tests/benchmark/reader_records/{kind}.py")
    spec = importlib.util.spec_from_file_location(f"reader_records.{kind}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.record


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_reads_its_record_through_its_own_file(entry):
    metric = json.loads((LAYER_METRICS / f"{entry['name']}.json").read_text())
    fields, want = _record(metric["reader"]["kind"])(metric["reader"])
    seen = _seen(**fields)
    got = run.read_metric(metric, seen)
    assert isinstance(got, float) and got == pytest.approx(want)
    # a run that holds nothing for it (the parent commit's program has no
    # such span, or records without identity): nothing read, nothing raised
    assert run.read_metric(metric, _seen()) is None
    assert run.read_metric(metric, _seen(
        planes=_planes([], [], device=False),
        spans=[{"name": "some.older.span", "ts": 0.0, "dur": 0.02,
                "depth": 0, "parent": "", "tid": 1, "args": None}])) is None
    # a host backend writes no device plane: what the device's trace gives
    # reads nothing there, every other source reads what it read
    host = run.read_metric(metric, on_a_host_backend(seen))
    assert host == (None if entry["source"] == "device_trace" else got)


# -- what holds of `per_layer`, whatever it lists --------------------------------

@pytest.mark.parametrize("name", CELLS)
def test_cell_reads_exactly_the_entries_that_list_it(name):
    listed = {m["name"] for m in BENCH["per_layer"] if name in m["workloads"]}
    assert {m["name"] for m in run.Cell(name).per_layer} == listed
    assert listed, "a cell reports at least one per-layer metric"


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda m: m["name"])
def test_entry_lists_cells_that_exist_and_report_what_it_moves(entry):
    cells = entry.get("workloads")
    assert cells, "an entry names the cells that read it: no list, no default"
    assert len(cells) == len(set(cells)) and set(cells) <= set(CELLS)
    for name in cells:
        reported = {m["name"] for m in BENCH["end_to_end"]
                    if name in m.get("workloads", [name])}
        assert entry["moves"] in reported, (name, entry["moves"])


def test_every_metric_file_has_an_entry_and_every_entry_a_file():
    files = sorted(p.stem for p in LAYER_METRICS.glob("*.json"))
    assert files == sorted(m["name"] for m in BENCH["per_layer"])
    assert [p.name for p in LAYER_METRICS.iterdir() if p.suffix != ".json"] == []


def test_every_reader_kind_has_a_source_and_a_record():
    used = {json.loads(p.read_text())["reader"]["kind"]
            for p in LAYER_METRICS.glob("*.json")}
    sources = {p.stem for p in SOURCES.glob("*.py")} - {"__init__"}
    records = {p.stem for p in RECORDS.glob("*.py")}
    assert used <= sources, "a reader.kind is sources/<kind>.py"
    assert sources == records, "each kind of reader has its synthetic record"


@pytest.mark.parametrize("spoiled,message", [
    (lambda e: e.pop("workloads"), "lists no `workloads`"),
    (lambda e: e.update(moves="restore_s"), "does not report 'restore_s'"),
], ids=["no-list", "moves-what-the-cell-does-not-report"])
def test_an_entry_the_cell_cannot_place_is_refused(tmp_path, spoiled, message):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if CELLS[0] in m["workloads"])
    spoiled(entry)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "benchmark").symlink_to(REPO / "benchmark")
    with pytest.raises(SystemExit, match=message):
        run.Cell(CELLS[0], root=tmp_path)


# what each cell read when its entries got their lists (PR 27): a later PR
# adds to these and takes nothing away
REPLAY = {
    "generator_share", "compiles_in_window", "slot_root_ms", "stage_ms",
    "epoch_device_ms", "refresh_ms", "epoch_program_roofline",
    "device_idle_share.replay", "guard_events", "slot_root_history_ms",
    "slot_root_attestations_ms", "slot_root_pairs_hashed",
    "idle_in_slot_root", "boundary_self_ms", "stage_distill_ms",
    "refresh_download_ms", "refresh_final_updates_ms",
    "forest_build_ms.replay", "forest_pair_lanes", "slot_root_forests_ms",
    "slot_root_small_ms", "slot_root_merkleize_ms",
    "slot_root_pairs_zero_filled", "slot_root_pairs_per_epoch",
    "slot_self_ms", "stage_upload_ms"}
RESTORE = {
    "restore_enter_ms", "checkpoint_write_ms", "device_idle_share.restore",
    "forest_build_ms.restore", "checkpoint_download_ms",
    "checkpoint_assemble_ms", "restore_decode_ms", "restore_upload_ms",
    "idle_in_restore_decode", "checkpoint_ms", "restore_ms",
    "restore_decode_least_ms", "checkpoint_assemble_least_ms"}
READ_SINCE_PR27 = {"mainnet-1m.replay": REPLAY, "mainnet-300k.replay": REPLAY,
                   "mainnet-1m.restore": RESTORE}


@pytest.mark.parametrize("name", sorted(READ_SINCE_PR27))
def test_a_cell_still_reads_what_it_read(name):
    assert {m["name"] for m in run.Cell(name).per_layer} \
        >= READ_SINCE_PR27[name]
