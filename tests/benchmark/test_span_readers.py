"""The readers and per-layer metrics that PR 25 added to the benchmark, on
hand-built spans and planes: `span_arg_median`, `span_arg_slope`,
`span_least`, `span_self_median`, `trace_idle_in_span`, the `traced_on_device` wrapper, and every new
`layer_metrics/*.json` run once on a synthetic record."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmark import reduce, run
from benchmark.reduce import Event, Line, Plane
from benchmark.sources import Seen

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
MS = 1e6    # the planes' clock is nanoseconds

# what PR 24 left; every entry after them is this file's to cover
PR24_METRICS = 12
NEW_METRICS = [m["name"] for m in BENCH["per_layer"][PR24_METRICS:]]


def _planes(ops, notes, device=True):
    """A 100 ms `bench.window`, device operations and host annotations, each
    (name, start ms, duration ms)."""
    host = Plane("/host:CPU", [Line("python", [
        Event("bench.window", 0.0, 100 * MS),
        *(Event(n, a * MS, d * MS) for n, a, d in notes)])])
    if not device:
        return [host]
    return [Plane("/device:TPU:0", [Line("XLA Ops", [
        Event(n, a * MS, d * MS) for n, a, d in ops])]), host]


def _seen(**kw):
    base = dict(spans=[], counters={}, values={}, planes=None,
                config={"validators": 1_000_000}, mix={}, peaks={})
    return Seen(**dict(base, **kw))


def _span(name, dur, id=0, parent_id=0, req=None, **args):
    return {"name": name, "dur": dur, "id": id, "parent_id": parent_id,
            "req": req, "args": args or None}


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
    # -- traced_on_device -----------------------------------------------------
    ({"kind": "traced_on_device",
      "inner": {"kind": "span_median", "span": "a", "scale": 1000.0}},
     dict(spans=[_span("a", 0.25)], planes=_planes(OPS, [])), 250.0),
    ({"kind": "traced_on_device",
      "inner": {"kind": "span_median", "span": "a"}},
     dict(spans=[_span("a", 0.25)], planes=_planes(OPS, [], device=False)),
     None),
    ({"kind": "traced_on_device",
      "inner": {"kind": "span_median", "span": "a"}},
     dict(spans=[_span("a", 0.25)]), None),                 # an untraced run
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


# -- every metric this PR added, through its own file -------------------------

def _record_for(reader):
    """A synthetic run that holds exactly what `reader` looks for."""
    inner = reader.get("inner", reader)
    span = inner["span"]
    if inner["kind"] == "trace_idle_in_span":
        return _seen(planes=_planes(OPS, [(span, 10, 40)])), 30.0
    planes = _planes(OPS, [])
    if inner["kind"] in ("span_median", "span_least"):
        return _seen(planes=planes, spans=[
            _span(span, d) for d in (0.020, 0.030, 0.010)]), \
            20.0 if inner["kind"] == "span_median" else 10.0
    if inner["kind"] == "span_arg_slope":
        return _seen(planes=planes, spans=[
            _span(span, 1.0, req=64 * k, **{inner["arg"]: 1000 + 130 * k})
            for k in range(4)]), 130.0 * inner["per"] / 64
    if inner["kind"] == "span_arg_median":
        return _seen(planes=planes, spans=[
            _span(span, 1.0, **{inner["arg"]: n}) for n in (5, 9, 7)]), 7.0
    assert inner["kind"] == "span_self_median"
    return _seen(planes=planes, spans=[
        _span("child", 0.4, 2, 1), _span(span, 1.0, 1)]), 600.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_reads_its_span(name):
    metric = json.loads((REPO / "benchmark" / "layer_metrics"
                         / f"{name}.json").read_text())
    seen, want = _record_for(metric["reader"])
    assert run.read_metric(metric, seen) == pytest.approx(want)
    assert isinstance(run.read_metric(metric, seen), float)
    # the parent commit's program has no such span: nothing is read, and
    # nothing is raised
    assert run.read_metric(metric, _seen(planes=_planes(OPS, []))) is None
    assert run.read_metric(metric, _seen(
        planes=_planes(OPS, []),
        spans=[{"name": "resident.slot_root", "ts": 0.0, "dur": 0.02,
                "depth": 0, "parent": "", "tid": 1, "args": None}])) is None
    # a host backend's run reports none of them
    host = seen._replace(planes=_planes(OPS, [], device=False))
    assert run.read_metric(metric, host) is None


def test_new_metrics_are_the_issues_sixteen_and_the_reviews_eleven():
    """ISSUE 25's sixteen, then one for each span, counter and field that
    REVIEW.md found without a reader and the least value of the two
    bimodal spans; each cell gets its own."""
    assert len(NEW_METRICS) == 16 + 9 + 2
    got = {cell["name"]: {m["name"] for m in run.Cell(cell["name"]).per_layer}
           & set(NEW_METRICS) for cell in BENCH["workloads"]}
    replay = {"slot_root_history_ms", "slot_root_attestations_ms",
              "slot_root_pairs_hashed", "idle_in_slot_root",
              "boundary_self_ms", "stage_distill_ms", "refresh_download_ms",
              "refresh_final_updates_ms", "forest_build_ms.replay",
              "forest_pair_lanes", "slot_root_forests_ms",
              "slot_root_small_ms", "slot_root_merkleize_ms",
              "slot_root_pairs_zero_filled", "slot_root_pairs_per_epoch",
              "slot_self_ms", "stage_upload_ms"}
    restore = set(NEW_METRICS) - replay
    assert got == {"mainnet-1m.replay": replay, "mainnet-300k.replay": replay,
                   "mainnet-1m.restore": restore}
    assert {"forest_build_ms.restore", "checkpoint_ms",
            "restore_ms", "restore_decode_least_ms"} <= restore
    assert len(restore) == 10
