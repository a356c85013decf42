"""Telemetry subsystem gate (consensus_specs_tpu/telemetry/):

  - span nesting, exit-only fencing, ring buffer; span identity (`id`,
    `parent_id`, the inherited request key `req`) across nesting and
    threads; the profiler bridge (`jax.profiler.TraceAnnotation` per span,
    bound lazily: the package never imports jax);
  - metrics registry (counters/gauges/pow2-bucket histograms), the
    `always=True` trace-time accounting path (fq REDC shims);
  - Prometheus text exposition validity and Chrome-trace JSON schema;
  - the retrace watchdog fires on a deliberately shape-polymorphic loop
    and stays SILENT (zero events, zero re-layouts) across chained
    resident slot steps + an epoch boundary on the 8-device mesh — the
    runtime pjit layout-stability contract (ISSUE 8 acceptance);
  - no-op mode (CSTPU_TELEMETRY=0) overhead bound.
"""
import json
import re
import subprocess
import sys
import threading
import time
from copy import deepcopy

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from consensus_specs_tpu import telemetry as T
from consensus_specs_tpu.telemetry import watchdog as W
from consensus_specs_tpu.crypto import bls
from consensus_specs_tpu.models import phase0
from consensus_specs_tpu.testing import factories


@pytest.fixture(autouse=True)
def tele():
    """Pinned-on telemetry with a clean registry per test; restores env
    control (and fencing) afterwards. Watchdog warm-up state is NOT
    cleared globally — tests use fresh keys or explicit W.reset()."""
    T.set_enabled(True)
    T.reset()
    yield
    T.set_enabled(None)
    T.set_fencing(None)


@pytest.fixture
def spec():
    s = phase0.get_spec("minimal")
    bls.bls_active = False
    s.clear_caches()
    yield s
    s.clear_caches()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_ring_and_aggregates():
    with T.span("outer") as outer:
        with T.span("inner", tag="x") as inner:
            time.sleep(0.003)
    assert outer.duration >= inner.duration > 0
    records = T.ring()
    assert [r["name"] for r in records] == ["inner", "outer"]  # close order
    assert records[0]["parent"] == "outer" and records[0]["depth"] == 1
    assert records[1]["parent"] == "" and records[1]["depth"] == 0
    assert records[0]["args"] == {"tag": "x"}
    snap = T.snapshot()["spans"]
    assert snap["outer"]["count"] == 1
    assert snap["inner"]["last_ms"] == snap["inner"]["total_ms"] > 0
    assert snap["inner"]["last_ms"] == round(inner.duration * 1e3, 3)


# -- identity: id, parent_id, req ---------------------------------------------

OLD_RING_KEYS = {"name", "ts", "dur", "depth", "parent", "tid", "args"}


def test_ring_record_keeps_its_old_keys_and_gains_identity():
    with T.span("id.root", req=7, tag="x"):
        pass
    (rec,) = T.ring()
    assert set(rec) == OLD_RING_KEYS | {"id", "parent_id", "req"}
    assert rec["args"] == {"tag": "x"}      # `req` is a field, not an arg
    assert rec["req"] == 7 and rec["parent_id"] == 0 and rec["id"] > 0


def test_span_ids_are_unique_and_parents_link_by_id():
    with T.span("id.root", req=41) as root:
        with T.span("id.child") as child:
            with T.span("id.grandchild") as grandchild:
                pass
        with T.span("id.sibling", req=42) as sibling:   # its own request key
            with T.span("id.nephew") as nephew:
                pass
    with T.span("id.other") as other:
        pass
    by_name = {r["name"]: r for r in T.ring()}
    assert len({r["id"] for r in by_name.values()}) == 6
    assert by_name["id.root"]["parent_id"] == 0
    assert by_name["id.child"]["parent_id"] == root.id
    assert by_name["id.grandchild"]["parent_id"] == child.id
    assert by_name["id.nephew"]["parent_id"] == sibling.id
    assert [by_name[n]["req"] for n in (
        "id.root", "id.child", "id.grandchild", "id.sibling", "id.nephew",
        "id.other")] == [41, 41, 41, 42, 42, None]
    assert grandchild.req == 41 and nephew.req == 42 and other.parent_id == 0


def test_threads_keep_their_own_span_trees():
    """A span opened on another thread is a root there: it takes neither the
    parent nor the request key of what the first thread has open."""
    n_threads, per_thread = 8, 200
    go = threading.Barrier(n_threads)

    def work(k):
        go.wait(timeout=30)
        for i in range(per_thread):
            with T.span("thr.root", req=(k, i)):
                with T.span("thr.child"):
                    pass

    with T.span("main.open", req="main"):
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    records = T.ring()
    assert len(records) == 2 * n_threads * per_thread + 1
    assert len({r["id"] for r in records}) == len(records)
    roots = {r["id"]: r for r in records if r["name"] == "thr.root"}
    assert all(r["parent_id"] == 0 and r["req"] != "main"
               for r in roots.values())
    for child in (r for r in records if r["name"] == "thr.child"):
        parent = roots[child["parent_id"]]
        assert child["req"] == parent["req"] and child["tid"] == parent["tid"]


# -- the profiler bridge --------------------------------------------------------

def test_importing_telemetry_leaves_jax_out():
    """core.py's contract: `ops/fq.py` and the analyzer fixtures import it
    without dragging jax in; a span there opens no annotation."""
    code = (
        "import sys\n"
        "from consensus_specs_tpu import telemetry as T\n"
        "with T.span('no.jax', req=1) as sp:\n"
        "    pass\n"
        "assert sp._note is None and T.ring()[0]['req'] == 1\n"
        "assert 'jax' not in sys.modules, 'telemetry imported jax'\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


class _FakeAnnotation:
    made: list = []

    def __init__(self, name):
        self.name = name
        self.events = []
        _FakeAnnotation.made.append(self)

    def __enter__(self):
        self.events.append(("enter", time.perf_counter()))

    def __exit__(self, *exc):
        self.events.append(("exit", time.perf_counter()))


@pytest.fixture
def fake_annotation(monkeypatch):
    from consensus_specs_tpu.telemetry import core
    _FakeAnnotation.made = []
    monkeypatch.setattr(core, "_annotation", _FakeAnnotation)
    return _FakeAnnotation


def test_span_opens_one_annotation_of_its_name_round_the_fence(fake_annotation):
    leaf = _FakeLeaf()
    with T.span("note.outer") as sp:
        with T.span("note.inner"):
            pass
        sp.fence(leaf)
    inner, outer = sorted(fake_annotation.made, key=lambda a: a.name)
    assert (inner.name, outer.name) == ("note.inner", "note.outer")
    assert [e for e, _ in outer.events] == ["enter", "exit"]
    # the fence runs inside both the span and its annotation
    assert outer.events[0][1] <= leaf.fetched_at[0] <= outer.events[1][1]
    assert outer.events[0][1] <= inner.events[0][1]
    assert inner.events[1][1] <= outer.events[1][1]


def test_telemetry_off_builds_no_annotation(fake_annotation):
    T.set_enabled(False)
    with T.span("note.off") as sp:
        sp.fence(None)
    assert fake_annotation.made == [] and T.ring() == []


def test_span_binds_the_profilers_annotation_once_jax_is_loaded():
    from consensus_specs_tpu.telemetry import core
    assert core._annotation_factory() is jax.profiler.TraceAnnotation


def test_profiler_session_holds_the_resident_spans(spec, tmp_path):
    """One clock with the device trace: a short profiler session round a
    resident epoch at V = 256 leaves the program's spans in the trace's
    `/host:CPU` plane, read back through the benchmark's own reduction."""
    from benchmark import reduce
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    spe = spec.SLOTS_PER_EPOCH
    state = factories.seed_genesis_state(spec, 256)
    core = ResidentCore(spec, state, mesh=None)
    try:
        core.process_slots(state, spe + 1)      # warm: the programs compile
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(reduce.WINDOW_ANNOTATION):
                core.process_slots(state, 2 * spe + 1)
        finally:
            jax.profiler.stop_trace()
    finally:
        core.exit()
    planes = reduce.load(reduce.find_xplane(str(tmp_path)))
    lo, hi = reduce.window(planes)
    events = reduce.annotations(planes, prefix="resident.")
    names = {e.name for e in events}
    assert names >= {
        "resident.slot", "resident.boundary_slot", "resident.slot_root",
        "resident.slot_root.forests", "resident.slot_root.attestations",
        "resident.slot_root.history", "resident.slot_root.small",
        "resident.slot_root.merkleize", "resident.stage",
        "resident.stage.distill", "resident.stage.distill.place",
        "resident.stage.upload", "resident.device",
        "resident.refresh", "resident.refresh.forests_dispatch",
        "resident.refresh.download", "resident.refresh.final_updates",
        "resident.forests"}
    # the builders' spans carry no `resident.` prefix: one boundary, so one
    # of each part and three of each of the two passes
    parts = [e.name for e in reduce.annotations(planes, prefix="distill.")]
    assert sorted(parts) == sorted([
        "distill.context", "distill.layouts", "distill.participants",
        "distill.crosslink_roots", "distill.winner_groups",
        "distill.crosslinks", "distill.inputs",
        "distill.inputs.flags", "distill.inputs.inclusion"]
        + ["distill.winners", "distill.committee_balances"] * 3)
    assert sum(e.name == "resident.slot_root" for e in events) == spe
    assert all(lo <= e.start_ns and e.end_ns <= hi for e in events)
    # the trace's extents agree with the ring's durations
    ring = [r["dur"] for r in T.ring() if r["name"] == "resident.device"]
    traced = [e.duration_ns / 1e9 for e in events
              if e.name == "resident.device"]
    assert traced[-1] == pytest.approx(ring[-1], rel=0.2, abs=2e-4)


class _FakeLeaf:
    """Duck-typed device array: records when its bytes were fetched."""

    def __init__(self):
        self.fetched_at = []

    def ravel(self):
        self.fetched_at.append(time.perf_counter())
        return np.zeros(4)


def test_span_fences_at_exit_only():
    leaf = _FakeLeaf()
    with T.span("fenced") as sp:
        sp.fence((leaf,))          # nested pytree form
        body_done = time.perf_counter()
    assert len(leaf.fetched_at) == 1
    assert leaf.fetched_at[0] >= body_done     # after the body, at exit
    assert sp.duration >= leaf.fetched_at[0] - sp.t0  # fence inside the span

    T.set_fencing(False)           # CSTPU_TELEMETRY_FENCE=0 equivalent
    silent = _FakeLeaf()
    with T.span("unfenced") as sp2:
        sp2.fence(silent)
    assert silent.fetched_at == []


def test_span_fences_a_mesh_leaf_by_waiting_for_every_shard():
    """A leaf laid out over several devices is fenced by
    `block_until_ready`, not by a fetched element: the fetch would launch
    programs of its own on every device (a gather with its all-reduce among
    them) and the span would book them. A one-device leaf keeps the fetch."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("v",))
    waited = []

    class Watched:
        """A real sharded array behind a recording `block_until_ready`."""

        def __init__(self, array):
            self.array, self.sharding = array, array.sharding

        def block_until_ready(self):
            waited.append(len(self.sharding.device_set))
            return self.array.block_until_ready()

        def ravel(self):
            raise AssertionError("a mesh leaf is not fenced by a fetch")

    sharded = Watched(jax.device_put(np.arange(16), NamedSharding(mesh, P("v"))))
    whole = Watched(jax.device_put(np.arange(4), NamedSharding(mesh, P())))
    single = _FakeLeaf()
    single.sharding = jax.device_put(np.arange(4)).sharding     # one device
    with T.span("mesh.fenced") as sp:
        sp.fence(sharded, (whole, single))
    assert waited == [4, 4] and len(single.fetched_at) == 1


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_identity_and_noop_gating():
    c = T.counter("t.ctr")
    assert c is T.counter("t.ctr")
    c.inc()
    c.inc(4)
    assert c.value == 5
    T.gauge("t.g").set(2.5)
    assert T.snapshot()["gauges"]["t.g"] == 2.5

    T.set_enabled(False)
    c.inc(100)
    T.gauge("t.g").set(9.0)
    assert c.value == 5 and T.gauge("t.g").value == 2.5
    always = T.counter("t.always", always=True)
    always.inc(2)
    assert always.value == 2       # trace-time accounting ignores the switch


def test_histogram_pow2_buckets():
    h = T.histogram("t.h")
    for v in (0.25, 0.3, 1.0, 1.5, 2.0, 5.0, 0.0, -3):
        h.observe(v)
    snap = T.snapshot()["histograms"]["t.h"]
    assert snap["count"] == 8
    assert snap["buckets"] == {"0": 2, "0.25": 1, "0.5": 1, "1": 1,
                               "2": 2, "8": 1}
    assert snap["sum"] == pytest.approx(0.25 + 0.3 + 1.0 + 1.5 + 2.0 + 5.0
                                        + 0.0 - 3)


def test_redc_shims_ride_the_registry_even_when_off():
    from consensus_specs_tpu.ops import fq as F
    T.set_enabled(False)           # lane assertions must survive opt-out
    F.reset_redc_trace_stats()
    jax.make_jaxpr(lambda a, b: F.fq_mul(a, b))(
        jnp.zeros((2, F.L), jnp.int64), jnp.zeros((2, F.L), jnp.int64))
    stats = F.redc_trace_stats()
    assert stats["instances"] == 1 and stats["lanes"] == 2
    assert T.counter("fq.redc.lanes").value == 2


def test_forest_pair_lane_counters():
    from consensus_specs_tpu.utils.ssz.incremental import IncrementalMerkleTree
    rng = np.random.default_rng(0)
    leaves = rng.integers(0, 2 ** 32, (16, 8), dtype=np.uint32)
    base = T.counter("merkle.forest.pair_lanes").value
    tree = IncrementalMerkleTree(leaves)
    lanes = T.counter("merkle.forest.pair_lanes").value - base
    assert lanes == sum(tree.last_pairs_per_level) == 8 + 4 + 2 + 1
    assert T.counter("merkle.forest.builds").value >= 1


# ---------------------------------------------------------------------------
# export surfaces
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+-]+$|"
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*\{le=\"\+Inf\"\} [0-9]+$")


def test_prometheus_exposition_is_valid():
    T.counter("p.ctr").inc(7)
    T.gauge("p.g").set(1.25)
    h = T.histogram("p.h")
    for v in (0.3, 1.0, 9.0):
        h.observe(v)
    with T.span("p.span"):
        pass
    text = T.prometheus_text()
    lines = text.strip().splitlines()
    families = set()
    for line in lines:
        if line.startswith("# TYPE "):
            _, _, family, kind = line.split(" ")
            assert kind in ("counter", "gauge", "histogram")
            families.add(family)
        else:
            assert _SAMPLE_RE.match(line), line
            name = line.split("{")[0].split(" ")[0]
            base = re.sub(r"_(total|bucket|sum|count)$", "", name)
            assert name in families or base in families, line
    # counters follow the _total convention
    assert "cstpu_p_ctr_total 7" in lines
    # histogram buckets are cumulative with the mandatory +Inf == count
    buckets = [line for line in lines if line.startswith("cstpu_p_h_bucket")]
    counts = [int(line.rsplit(" ", 1)[1]) for line in buckets]
    assert counts == sorted(counts) and buckets[-1].endswith("} 3")
    assert "cstpu_p_h_count 3" in lines
    # span aggregates exposed as labeled counters
    assert any(line.startswith('cstpu_span_total{span="p.span"}')
               for line in lines)


def test_beacon_api_serves_metrics(spec):
    from consensus_specs_tpu.api.beacon_node import BeaconNodeAPI
    state = factories.seed_genesis_state(spec, 8)
    api = BeaconNodeAPI(spec, state)
    T.counter("api.test").inc()
    text = api.get_metrics()
    assert "cstpu_api_test_total 1" in text
    # served even while syncing: the operational surface stays up
    api.syncing.is_syncing = True
    assert "cstpu_api_test_total 1" in api.get_metrics()
    assert "traceEvents" in api.get_trace()


def test_chrome_trace_schema_and_dump(tmp_path):
    with T.span("trace.a"):
        with T.span("trace.b", idx=3):
            pass
    doc = T.chrome_trace()
    events = doc["traceEvents"]
    assert len(events) == 2
    for event in events:
        assert event["ph"] == "X"
        assert set(event) >= {"name", "ph", "ts", "dur", "pid", "tid"}
        assert event["ts"] >= 0 and event["dur"] >= 0
    assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)
    child = next(e for e in events if e["name"] == "trace.b")
    assert child["args"]["parent"] == "trace.a" and child["args"]["idx"] == 3
    parent = next(e for e in events if e["name"] == "trace.a")
    assert child["args"]["parent_id"] == parent["args"]["id"] > 0
    assert parent["args"]["parent_id"] == 0 and parent["args"]["req"] is None
    path = tmp_path / "trace.json"
    T.dump_chrome_trace(str(path))
    assert json.loads(path.read_text())["traceEvents"]


def test_jsonl_sink(tmp_path):
    path = tmp_path / "telemetry.jsonl"
    T.counter("sink.n").inc()
    T.write_jsonl(str(path), extra={"stage": "one"})
    T.counter("sink.n").inc()
    T.write_jsonl(str(path))
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(rows) == 2
    assert rows[0]["stage"] == "one"
    assert rows[0]["counters"]["sink.n"] == 1
    assert rows[1]["counters"]["sink.n"] == 2


# ---------------------------------------------------------------------------
# watchdogs
# ---------------------------------------------------------------------------

def test_retrace_watchdog_fires_on_shape_polymorphic_loop():
    f = jax.jit(lambda x: x * 2 + 1)
    base = T.counter("watchdog.retrace_events").value
    with pytest.warns(T.TelemetryWarning, match="retracing"):
        for n in range(1, 6):
            W.dispatch("test.poly", f, jnp.ones(n))
    stats = W.stats("test.poly")
    assert stats["calls"] == 5 and stats["compiles"] == 5
    assert stats["events"] == 4      # first compile is warm-up, rest are not
    assert T.counter("watchdog.retrace_events").value - base == 4


def test_retrace_watchdog_silent_on_cache_hits():
    f = jax.jit(lambda x: x - 1)
    for _ in range(5):
        W.dispatch("test.stable", f, jnp.ones(7))
    assert W.stats("test.stable")["events"] == 0


def test_retrace_watchdog_noop_when_disabled():
    T.set_enabled(False)
    f = jax.jit(lambda x: x + 3)
    for n in range(1, 5):
        W.dispatch("test.off", f, jnp.ones(n))
    assert W.stats("test.off") == {"calls": 0, "compiles": 0, "events": 0}


def _serving_mesh(min_devices=2):
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    n = 1
    while n * 2 <= min(8, len(jax.devices())):
        n *= 2
    if n < min_devices:
        pytest.skip(f"needs >= {min_devices} devices, have {len(jax.devices())}")
    return ServingMesh.create(n)


def test_relayout_watchdog_fires_on_placement_change():
    mesh = _serving_mesh()
    x = jnp.zeros((16, 8), jnp.uint32)
    W.layout_check("test.layout", jax.device_put(x, mesh.shard_v))
    base = T.counter("watchdog.relayout_events").value
    with pytest.warns(T.TelemetryWarning, match="re-laying-out"):
        W.layout_check("test.layout", jax.device_put(x, mesh.replicated))
    assert T.counter("watchdog.relayout_events").value - base == 1
    # and settles once the new placement is steady
    W.layout_check("test.layout", jax.device_put(x, mesh.replicated))
    assert T.counter("watchdog.relayout_events").value - base == 1


def test_watchdogs_silent_on_layout_stable_resident_loop(spec):
    """ISSUE 8 acceptance, test-scale: >= 4 chained resident slot steps
    plus one epoch boundary under the validator-axis mesh report ZERO
    retrace and ZERO re-layout events — the runtime form of the pjit
    staging contract the serving loop was built around (PR 6)."""
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    mesh = _serving_mesh()
    state = factories.seed_genesis_state(spec, 4 * spec.SLOTS_PER_EPOCH)
    factories.advance_slots(spec, state, 2)
    core = ResidentCore(spec, state, mesh=mesh)
    try:
        # one full warm-up epoch (first compiles are free for the
        # watchdog; the measured window below is the steady state)
        spe = spec.SLOTS_PER_EPOCH
        target = (state.slot // spe + 1) * spe + 1
        core.process_slots(state, target)
        retrace0 = T.counter("watchdog.retrace_events").value
        relayout0 = T.counter("watchdog.relayout_events").value
        core.process_slots(state, target + spe)   # >= 4 slots + 1 boundary
        assert T.counter("watchdog.retrace_events").value == retrace0
        assert T.counter("watchdog.relayout_events").value == relayout0
        # the boundary ran: its three terms are span records, the one view
        # of the boundary's times
        spans = T.snapshot()["spans"]
        boundary = {"resident.stage", "resident.device", "resident.refresh"}
        assert set(spans) >= boundary
        assert all(spans[name]["last_ms"] > 0 for name in boundary)
        assert spans["resident.device"]["count"] >= 2
        assert spans["resident.slot_root"]["count"] >= spe + 4
    finally:
        core.exit()


def test_process_epoch_soa_span_derived_timings(spec):
    from consensus_specs_tpu.models.phase0.epoch_soa import process_epoch_soa
    state = factories.seed_genesis_state(spec, 2 * spec.SLOTS_PER_EPOCH)
    factories.advance_slots(spec, state, 2)
    timings = {}
    process_epoch_soa(spec, deepcopy(state), timings=timings)
    assert set(timings) == {"distill", "perm", "device", "writeback"}
    assert timings["device"] > 0 and timings["distill"] > 0
    spans = T.snapshot()["spans"]
    assert spans["epoch.device"]["count"] == 1
    assert spans["epoch.distill"]["count"] == 2   # cols + inputs segments


# ---------------------------------------------------------------------------
# no-op mode overhead
# ---------------------------------------------------------------------------

def test_noop_mode_overhead_bound():
    """CSTPU_TELEMETRY=0 must make the layer disappear: the disabled span
    is a shared singleton and a span+counter round trip stays under a
    generous per-op bound (typical is well under 1 us)."""
    T.set_enabled(False)
    assert T.span("a") is T.span("b")
    n = 20_000
    ctr = T.counter("off.ctr")
    t0 = time.perf_counter()
    for _ in range(n):
        with T.span("off.span") as sp:
            sp.fence(None)
        ctr.inc()
    per_op = (time.perf_counter() - t0) / n
    assert per_op < 20e-6, f"no-op overhead {per_op * 1e6:.2f} us/op"
    assert ctr.value == 0
    T.set_enabled(True)
    assert "off.span" not in T.snapshot()["spans"]
