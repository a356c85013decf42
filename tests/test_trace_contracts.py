"""Trace-tier contract engine (tools/analysis/trace/): seeded-regression
fixtures proving each rule family trips on a REAL traced/lowered
program, plus the ratchet workflow (baseline loosening/tightening,
suppression, staleness, skip) and the committed registry's hygiene.

The op-count assertions for the committed kernel contracts live with
their kernels' tests (tests/test_fq_redc.py asserts the fq_tower/
bls_jax lane pins through the engine, tests/test_scalar_mul.py the
windowed chain); this file owns the ENGINE's behavior: a kernel variant
with one extra REDC lane, a program that silently upcasts to f64, a
chained pair whose lowered shardings disagree — each must fail the
ratchet, and the documented accept paths must clear it.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from consensus_specs_tpu.ops import fq as F
from consensus_specs_tpu.ops import fq_tower as T
from tools.analysis.trace import engine


def _contract(tmp_path, name="fixture.contract", **kw):
    """A synthetic contract anchored in a real tmp file (so inline
    suppressions work exactly like a kernel module's)."""
    path = tmp_path / "kernel_fixture.py"
    if not path.exists():
        path.write_text(f'TRACE_CONTRACTS = [{{"name": "{name}"}}]\n')
    c = dict(name=name, path=str(path),
             line=engine._name_line(path.read_text(), name))
    c.update(kw)
    return c


def _rules(report):
    return sorted(f.rule for f in report.findings)


def _z2():
    return jnp.zeros((2, F.L), jnp.int64)


def _fq2_mul_plus_one_redc(a, b):
    """The seeded regression: fq2_mul (2 REDC lanes under coeff) plus ONE
    gratuitous extra reduction."""
    out = T.fq2_mul(a, b)
    return out + F.fq_mul(a[..., 0, :], b[..., 0, :])[..., None, :]


def _coeff_ctx():
    return F.pinned_fq_redc_backend("coeff")


# ---------------------------------------------------------------------------
# CSA11xx: op-budget ratchet
# ---------------------------------------------------------------------------

def test_extra_redc_lane_trips_budget(tmp_path):
    """+1 REDC lane over an exact pin fails CSA1101 — and the message
    names the measured/declared values."""
    c = _contract(
        tmp_path,
        build=lambda: dict(fn=_fq2_mul_plus_one_redc, args=(_z2(), _z2()),
                           context=_coeff_ctx),
        budgets={"redc_lanes": 2}, exact=("redc_lanes",))
    report = engine.run_contracts([c], baseline={})
    assert _rules(report) == ["CSA1101"]
    assert "3" in report.findings[0].message
    assert report.results[0].measured["redc_lanes"] == 3


def test_regression_vs_baseline_trips_even_within_budget(tmp_path):
    """A non-exact metric inside its budget but above the committed
    snapshot is CSA1102: loosening requires touching the baseline."""
    c = _contract(
        tmp_path,
        build=lambda: dict(fn=_fq2_mul_plus_one_redc, args=(_z2(), _z2()),
                           context=_coeff_ctx),
        budgets={"redc_lanes": 10})
    dirty = engine.run_contracts(
        [c], baseline={"fixture.contract": {"redc_lanes": 2}})
    assert _rules(dirty) == ["CSA1102"]
    # the accept path: a reviewed baseline edit to the measured value
    loosened = engine.run_contracts(
        [c], baseline={"fixture.contract": {"redc_lanes": 3}})
    assert loosened.findings == []
    # improvement below baseline: a tighten notice, never a failure
    slack = engine.run_contracts(
        [c], baseline={"fixture.contract": {"redc_lanes": 7}})
    assert slack.findings == []
    assert any("improved 7 -> 3" in n for n in slack.notices)


def test_missing_baseline_entry_trips(tmp_path):
    c = _contract(
        tmp_path,
        build=lambda: dict(fn=T.fq2_mul, args=(_z2(), _z2()),
                           context=_coeff_ctx),
        budgets={"redc_lanes": 10})
    report = engine.run_contracts([c], baseline={})
    assert _rules(report) == ["CSA1104"]


def test_suppression_on_contract_line(tmp_path):
    """# csa: ignore[...] on the contract's "name": line downgrades the
    finding to suppressed, exactly like the AST tier."""
    path = tmp_path / "kernel_fixture.py"
    path.write_text(
        'TRACE_CONTRACTS = [\n'
        '    # csa: ignore[CSA1101] -- seeded fixture, lane cost accepted\n'
        '    {"name": "fixture.contract"},\n'
        ']\n')
    c = _contract(
        tmp_path,
        build=lambda: dict(fn=_fq2_mul_plus_one_redc, args=(_z2(), _z2()),
                           context=_coeff_ctx),
        budgets={"redc_lanes": 2}, exact=("redc_lanes",))
    report = engine.run_contracts([c], baseline={})
    assert report.findings == []
    assert [f.rule for f in report.suppressed] == ["CSA1101"]


def test_unmeasured_budget_metric_is_a_finding(tmp_path):
    c = _contract(tmp_path, build=lambda: dict(fn=lambda x: x + 1,
                                               args=(jnp.zeros(3),)),
                  budgets={"bogus_metric": 1})
    report = engine.run_contracts([c], baseline={})
    assert _rules(report) == ["CSA1101"]
    assert "never measured" in report.findings[0].message


# ---------------------------------------------------------------------------
# CSA12xx: lowered-program hygiene
# ---------------------------------------------------------------------------

def test_silent_f64_upcast_trips(tmp_path):
    def upcasts(x):
        # the classic: a float literal promotes the math through f64
        return (x.astype(jnp.float64) * 1.5).astype(jnp.int64)

    c = _contract(tmp_path,
                  build=lambda: dict(fn=upcasts, args=(jnp.zeros(
                      4, jnp.int64),)),
                  forbid=("f64",))
    report = engine.run_contracts([c], baseline={})
    assert _rules(report) == ["CSA1201"]


def test_host_callback_trips(tmp_path):
    def chatty(x):
        jax.debug.callback(lambda v: None, x)
        return x * 2

    c = _contract(tmp_path,
                  build=lambda: dict(fn=chatty, args=(jnp.zeros(3),)),
                  forbid=("callback",))
    report = engine.run_contracts([c], baseline={})
    assert _rules(report) == ["CSA1202"]


def test_targeted_device_put_trips_and_constant_staging_does_not(tmp_path):
    def forces_placement(x):
        return jax.device_put(x * 2, jax.devices()[0])

    c = _contract(tmp_path,
                  build=lambda: dict(fn=forces_placement,
                                     args=(jnp.zeros(3),)),
                  forbid=("device_put",))
    report = engine.run_contracts([c], baseline={})
    assert _rules(report) == ["CSA1203"]

    def stages_constants(x):
        # jnp.asarray over host tables is the legitimate constant path
        return x + jnp.asarray(np.arange(3, dtype=np.float32))

    c2 = _contract(tmp_path, name="fixture.clean",
                   build=lambda: dict(fn=stages_constants,
                                      args=(jnp.zeros(3),)),
                   forbid=("device_put",))
    assert engine.run_contracts([c2], baseline={}).findings == []


def test_dropped_donation_trips(tmp_path):
    def f(a, b):
        return a + b

    args = (jnp.zeros(8), jnp.zeros(8))
    c = _contract(tmp_path,
                  build=lambda: dict(fn=f, args=args, jit_kwargs={}),
                  donate_min=1)
    report = engine.run_contracts([c], baseline={})
    assert _rules(report) == ["CSA1204"]
    # with the donation actually declared, the annotation survives
    c2 = _contract(tmp_path, name="fixture.donated",
                   build=lambda: dict(
                       fn=f, args=args,
                       jit_kwargs=dict(donate_argnums=(0,))),
                   donate_min=1)
    assert engine.run_contracts([c2], baseline={}).findings == []


# ---------------------------------------------------------------------------
# CSA13xx: collective / chained-layout drift (8-device virtual mesh)
# ---------------------------------------------------------------------------

N_DEV = 8


def _mesh_or_skip():
    if len(jax.devices()) < N_DEV:
        pytest.skip(f"needs {N_DEV} devices, have {len(jax.devices())}")
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:N_DEV]), ("v",))


def test_chained_sharding_mismatch_trips(tmp_path):
    """A self-chained step whose out sharding differs from its in
    sharding re-lays data out every call — CSA1302, the static form of
    the re-layout watchdog."""
    mesh = _mesh_or_skip()
    from jax.sharding import NamedSharding, PartitionSpec as P
    shard, repl = NamedSharding(mesh, P("v")), NamedSharding(mesh, P())

    def step(x):
        return x * 2

    mismatched = _contract(
        tmp_path,
        build=lambda: dict(fn=step, args=(jnp.zeros(16),),
                           jit_kwargs=dict(in_shardings=(repl,),
                                           out_shardings=shard)),
        chained_prefix=1)
    report = engine.run_contracts([mismatched], baseline={})
    assert _rules(report) == ["CSA1302"]

    matched = _contract(
        tmp_path, name="fixture.stable",
        build=lambda: dict(fn=step, args=(jnp.zeros(16),),
                           jit_kwargs=dict(in_shardings=(shard,),
                                           out_shardings=shard)),
        chained_prefix=1)
    assert engine.run_contracts([matched], baseline={}).findings == []


def test_collective_inventory_drift_trips(tmp_path):
    mesh = _mesh_or_skip()
    from jax.sharding import NamedSharding, PartitionSpec as P
    shard, repl = NamedSharding(mesh, P("v")), NamedSharding(mesh, P())

    def reduces(x):
        return jnp.sum(x)

    c = _contract(
        tmp_path,
        build=lambda: dict(fn=reduces, args=(jnp.zeros(16),),
                           jit_kwargs=dict(in_shardings=(shard,),
                                           out_shardings=repl)),
        collectives=("all-gather",))     # declared wrong: it all-reduces
    report = engine.run_contracts([c], baseline={})
    assert _rules(report) == ["CSA1301"]
    assert "all-reduce" in report.findings[0].message

    c2 = _contract(
        tmp_path, name="fixture.reduce",
        build=lambda: dict(fn=reduces, args=(jnp.zeros(16),),
                           jit_kwargs=dict(in_shardings=(shard,),
                                           out_shardings=repl)),
        collectives=("all-reduce",))
    assert engine.run_contracts([c2], baseline={}).findings == []


def test_unannotated_chain_degrades_loudly_not_vacuously(tmp_path):
    """A chained_prefix check over a program whose lowered signature
    carries NO sharding annotations (partitioner/dialect change) must
    fail, not pass vacuously — the silent-degradation mode the tier
    exists to prevent."""
    c = _contract(
        tmp_path,
        build=lambda: dict(fn=lambda x: x * 2, args=(jnp.zeros(16),),
                           jit_kwargs={}),    # no shardings at all
        chained_prefix=1)
    report = engine.run_contracts([c], baseline={})
    assert _rules(report) == ["CSA1302"]
    assert "vacuously" in report.findings[0].message


def test_bare_int_static_argnums_normalized(tmp_path):
    """`static_argnums=0` (a falsy bare int, valid for jax.jit) must be
    honored when building the measurement jaxpr."""
    def f(n, x):
        return x + n   # n is a static python int under jit

    c = _contract(tmp_path,
                  build=lambda: dict(fn=f, args=(3, jnp.zeros(4)),
                                     jit_kwargs=dict(static_argnums=0)),
                  budgets={"jaxpr_eqns": 10})
    report = engine.run_contracts(
        [c], baseline={"fixture.contract": {"jaxpr_eqns": 10}})
    assert report.findings == [], [f.message for f in report.findings]
    assert report.results[0].measured["jaxpr_eqns"] >= 1


# ---------------------------------------------------------------------------
# Engine plumbing: skip, staleness, baseline IO, snapshot
# ---------------------------------------------------------------------------

def test_underprovisioned_contract_skips_with_notice(tmp_path):
    c = _contract(tmp_path, requires_devices=4096,
                  build=lambda: dict(fn=lambda x: x, args=(jnp.zeros(2),)),
                  budgets={"jaxpr_eqns": 10})
    report = engine.run_contracts(
        [c], baseline={"fixture.contract": {"jaxpr_eqns": 3}})
    assert report.findings == []
    assert any("skipped" in n for n in report.notices)
    # the skipped contract's baseline entry is unverifiable, NOT stale
    assert report.stale_baseline == []


def test_stale_baseline_contract_reported(tmp_path):
    c = _contract(tmp_path,
                  build=lambda: dict(fn=lambda x: x + 1,
                                     args=(jnp.zeros(2),)),
                  budgets={"jaxpr_eqns": 10})
    report = engine.run_contracts(
        [c], baseline={"fixture.contract": {"jaxpr_eqns": 5},
                       "deleted.contract": {"redc_lanes": 1}})
    assert report.stale_baseline == ["deleted.contract"]


def test_baseline_roundtrip_and_snapshot(tmp_path):
    c = _contract(tmp_path,
                  build=lambda: dict(fn=lambda x: x + 1,
                                     args=(jnp.zeros(2),)),
                  budgets={"jaxpr_eqns": 10})
    report = engine.run_contracts([c], baseline={})
    assert _rules(report) == ["CSA1104"]          # unsnapshotted
    path = tmp_path / "trace_baseline.json"
    engine.write_trace_baseline(path, report.snapshot)
    loaded = engine.load_trace_baseline(path)
    assert loaded == report.snapshot
    again = engine.run_contracts([c], baseline=loaded)
    assert again.findings == []
    # the artifact's row shape
    data = json.loads(engine.render_json(report))
    assert data["contracts"][0]["name"] == "fixture.contract"
    assert data["contracts"][0]["measured"]["jaxpr_eqns"] >= 1


# ---------------------------------------------------------------------------
# The committed registry
# ---------------------------------------------------------------------------

def test_committed_registry_shape():
    """Every committed contract is well-formed and every committed
    baseline entry maps to a declared contract + metric. (The full
    measured run is `make contracts`; the cheap structural guarantee
    keeps the suite fast.)"""
    contracts = engine.discover()
    assert len(contracts) >= 20
    names = [c["name"] for c in contracts]
    assert len(names) == len(set(names))
    by_name = {c["name"]: c for c in contracts}
    for c in contracts:
        assert ("build" in c) or ("measure" in c), c["name"]
        assert isinstance(c.get("budgets", {}), dict)
        for m in c.get("exact", ()):
            assert m in c["budgets"], (c["name"], m)
        for v in c.get("budgets", {}).values():
            assert isinstance(v, int), c["name"]
    # the hot programs the tentpole names are all covered
    for needle in ("miller_loop_grouped", "grouped_verdict",
                   "windowed_chain", "cofactor_clear",
                   "pair_hash_level", "epoch_transition",
                   "mesh_epoch_chain", "forest_build",
                   "forest_pair_lanes"):
        assert any(needle in n for n in names), needle
    baseline = engine.load_trace_baseline()
    assert baseline, "trace_baseline.json missing or empty"
    for name, metrics in baseline.items():
        assert name in by_name, f"stale baseline contract {name}"
        declared = by_name[name]
        known_engine_metrics = {"redc_lanes", "jaxpr_eqns", "f64_ops",
                                "collective_ops", "seq_adds",
                                "seq_doubles"}
        for metric in metrics:
            assert metric in declared.get("budgets", {}) \
                or metric not in known_engine_metrics \
                or declared.get("measure") is not None, (name, metric)
    # budget_snapshot never traces: pure declaration
    snap = engine.budget_snapshot(contracts)
    assert snap["ops.fq_tower.fq12_mul[coeff]"] == {"redc_lanes": 12}


def test_trace_rules_registered_without_jax_tier():
    """The trace-tier rule catalog registers through the stdlib-only
    import path (`--list-rules` must show CSA11xx-13xx on the no-jax CI
    lint lane; tracing itself stays lazily imported)."""
    from tools.analysis.core import RULES
    from tools.analysis.trace import TRACE_RULE_IDS
    assert set(TRACE_RULE_IDS) <= set(RULES)
    for rule_id in TRACE_RULE_IDS:
        assert RULES[rule_id].severity in ("error", "notice")


def test_incremental_forest_contract_measures_live():
    """The cheap measured contract (no tracing): the forest pair-lane
    pins, through the engine against the committed baseline."""
    contracts = [c for c in engine.discover()
                 if c["name"] == "utils.ssz.incremental.forest_pair_lanes"]
    assert len(contracts) == 1
    report = engine.run_contracts(contracts)
    assert report.findings == [], [f.message for f in report.findings]
    (res,) = report.results
    assert res.measured == {"build_pair_lanes": 63, "update_pair_lanes": 11}
