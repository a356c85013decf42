"""Memory tier (tools/analysis/memory/): seeded-regression fixtures
proving each CSA16xx rule trips on a REAL traced program, the liveness
model's load-bearing semantics (donated aliases counted once, sub-jaxpr
transients, host-round-trip widening), the baseline loosen/tighten/
stale/suppressed workflow (mirroring tests/test_range_contracts.py),
the committed registry's proofs, and the merged five-tier CLI.

The headline budgets themselves — the V=10M epoch HBM ceiling, the
per-shard bound on the 8-device mesh, the forest-update O(dirty·log V)
fit, the pairing and firehose working sets, the Pallas VMEM footprint —
are committed as MEM_CONTRACTS next to their kernels and run under
`make memory`; this file owns the ENGINE's behavior: a grown buffer, a
superlinear temp, an over-wide BlockSpec — each must fail through the
engine, and the documented accept paths must clear it.
"""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from consensus_specs_tpu.ops import intmath as _intmath  # noqa: F401 -- x64
from tools.analysis.memory import engine
from tools.analysis.memory import liveness as L

REPO = Path(__file__).resolve().parents[1]


def _contract(tmp_path, name="fixture.contract", **kw):
    """A synthetic contract anchored in a real tmp file (so inline
    suppressions work exactly like a kernel module's)."""
    path = tmp_path / "kernel_fixture.py"
    if not path.exists():
        path.write_text(f'MEM_CONTRACTS = [{{"name": "{name}"}}]\n')
    c = dict(name=name, path=str(path),
             line=engine._name_line(path.read_text(), name))
    c.update(kw)
    return c


def _rules(report):
    return sorted(f.rule for f in report.findings)


def _vec(n=1 << 16):
    return jax.ShapeDtypeStruct((n,), jnp.uint64)


# ---------------------------------------------------------------------------
# The liveness model's load-bearing semantics
# ---------------------------------------------------------------------------

def test_donated_alias_counted_once():
    """THE accounting rule the epoch budget rests on: a donated input
    congruent with an output shares ONE buffer. The same program with
    and without donation must differ by exactly the aliased bytes."""
    def f(x, y):
        return x + y, jnp.sum(y)

    closed = jax.make_jaxpr(f)(_vec(), _vec())
    plain = L.analyze(closed)
    donated = L.analyze(closed, donated={0})
    bytes_x = (1 << 16) * 8
    assert donated.alias_bytes == bytes_x
    assert plain.alias_bytes == 0
    assert plain.peak_bytes - donated.peak_bytes == bytes_x
    # the unmatched donated invar (no congruent output) frees instead
    def g(x, y):
        return (x + y).astype(jnp.uint32), jnp.sum(y)
    closed2 = jax.make_jaxpr(g)(_vec(), _vec())
    d2 = L.analyze(closed2, donated={0})
    assert d2.alias_bytes == 0          # uint32 out: nothing congruent


def test_a_constant_value_is_resident_once():
    """Tracing hoists a constant once per array object that carried it,
    and how many objects that is moves with jax (the pairing ratchet read
    1,904 B high under 0.9.0 for it); the executable holds a value once,
    and so does the walk."""
    table = np.arange(1 << 10, dtype=np.uint64)

    def one(x):
        return x + jnp.asarray(table)

    def twice(x):
        return x + jnp.asarray(table) + jnp.asarray(table.copy())

    a = L.analyze(jax.make_jaxpr(one)(_vec(1 << 10)))
    b_closed = jax.make_jaxpr(twice)(_vec(1 << 10))
    b = L.analyze(b_closed)
    assert len(b_closed.jaxpr.constvars) == 2
    assert a.const_bytes == b.const_bytes == table.nbytes


def test_scan_body_transient_contributes_atop_carry():
    """A scan's body peak beyond its own I/O rides atop the live set
    carried across the eqn — a big in-body temp must show up in the
    modeled peak even though it never escapes the scan."""
    def body(c, _):
        big = jnp.zeros((1 << 16,), jnp.uint64) + c
        return jnp.sum(big), None

    def f(x):
        out, _ = jax.lax.scan(body, jnp.sum(x), None, length=4)
        return out

    small = jax.make_jaxpr(lambda x: jnp.sum(x))(_vec(256))
    scan = jax.make_jaxpr(f)(_vec(256))
    assert L.analyze(scan).peak_bytes >= \
        L.analyze(small).peak_bytes + (1 << 16) * 8


def test_host_roundtrip_event_recorded():
    """A pure_callback staged between device eqns while buffers span it
    is a HostEvent carrying the spanning bytes (CSA1605's raw signal)."""
    def f(x):
        y = x * jnp.uint64(2)
        s = jax.pure_callback(
            lambda v: np.uint64(v[0]),
            jax.ShapeDtypeStruct((), jnp.uint64), y)
        return y + s                    # y spans the callback

    model = L.analyze(jax.make_jaxpr(f)(_vec()))
    assert model.host_events
    assert model.host_events[0].spanning_bytes >= (1 << 16) * 8


def test_traffic_bounds_bracket_program():
    lo, hi = L.traffic_bounds(jax.make_jaxpr(lambda x: x + x)(_vec()))
    assert lo == 2 * (1 << 16) * 8      # one read + one write
    assert hi >= lo


def test_fit_order_recovers_slope():
    assert abs(L.fit_order([10, 100, 1000],
                           [10, 100, 1000]) - 1.0) < 1e-9
    assert L.fit_order([10, 100], [7, 7]) == 0.0


# ---------------------------------------------------------------------------
# CSA1601: declared-budget violation (peak, shard bound, compiled)
# ---------------------------------------------------------------------------

def test_budget_violation_trips_and_honest_budget_clears(tmp_path):
    build = lambda: dict(fn=lambda x: x + x, args=(_vec(),))
    over = _contract(tmp_path, build=build, budget_bytes=1 << 10)
    report = engine.run_contracts([over], baseline={})
    assert "CSA1601" in _rules(report)
    honest = _contract(tmp_path, name="fixture.fits", build=build,
                       budget_bytes=1 << 30)
    report2 = engine.run_contracts([honest], baseline={})
    assert "CSA1601" not in _rules(report2)


def test_shard_bound_proves_and_replicated_overrun_trips(tmp_path):
    """A [V] elementwise program shards cleanly under single/N + cap; a
    program whose working set REPLICATES (small leaves) escapes the
    bound and trips."""
    shards = _contract(
        tmp_path,
        build=lambda: dict(fn=lambda x: x * jnp.uint64(3), args=(_vec(),)),
        sharded=dict(devices=8, min_elems=1 << 10,
                     replicated_cap_bytes=1 << 10))
    assert "CSA1601" not in _rules(engine.run_contracts([shards],
                                                        baseline={}))
    replicates = _contract(
        tmp_path, name="fixture.replicates",
        build=lambda: dict(fn=lambda x: x * jnp.uint64(3), args=(_vec(),)),
        sharded=dict(devices=8, min_elems=1 << 30,   # nothing shards
                     replicated_cap_bytes=1 << 10))
    report = engine.run_contracts([replicates], baseline={})
    assert "CSA1601" in _rules(report)
    assert any("replicated cap" in f.message for f in report.findings)


def test_compiled_crosscheck_divergence_trips(tmp_path):
    """Force divergence by lying to the checker: a probe whose args the
    model never saw (the contract's fn ignores its big arg, XLA drops
    it from argument_size) with zero slack must fail the arg check."""
    build = lambda: dict(fn=lambda x: jnp.zeros((4,), jnp.uint64),
                         args=(_vec(1 << 20),))
    c = _contract(tmp_path, build=build,
                  compiled=dict(tol=1.01, slack_bytes=0))
    report = engine.run_contracts([c], baseline={})
    # XLA:CPU prunes the unused [2^20] arg; the model charges it
    assert any(f.rule == "CSA1601" and "diverges" in f.message
               for f in report.findings)


def test_compiled_crosscheck_agreement_clears(tmp_path):
    c = _contract(tmp_path,
                  build=lambda: dict(fn=lambda x: x + jnp.uint64(1),
                                     args=(_vec(1 << 12),)),
                  compiled=True)
    report = engine.run_contracts([c], baseline={})
    assert "CSA1601" not in _rules(report)
    (res,) = report.results
    assert res.detail["compiled"]["argument_bytes"][2] is True


@jax.jit
def _wide_sums(att, gain, keys):
    """epoch_soa._proposer_chunk_sums in small: the chip's compiler runs
    the compare and select inside the reduction, XLA:CPU materialises the
    [V, K] uint64 operand."""
    hit = att[:, None] == keys[None, :]
    return jnp.sum(jnp.where(hit, gain[:, None], jnp.uint64(0)), axis=0)


def _fused_contract(tmp_path, fused_calls=(_wide_sums.__name__,)):
    V, K = 1 << 14, 128
    S = jax.ShapeDtypeStruct

    def fn(att, gain, keys):
        held = gain[:, None] << jnp.arange(64, dtype=jnp.uint64)[None, :]
        return held, _wide_sums(att, gain, keys)

    return V, K, _contract(
        tmp_path, compiled=True,
        build=lambda: dict(fn=fn, fused_calls=fused_calls,
                           args=(S((V,), jnp.int32), S((V,), jnp.uint64),
                                 S((K,), jnp.int32))))


@pytest.mark.parametrize("walk", ["honest", "forgets_a_buffer"])
def test_compiled_crosscheck_of_a_declared_fused_call(tmp_path, monkeypatch,
                                                      walk):
    """`fused_calls` is a claim about the chip's compiler, and the
    cross-check compiles with XLA:CPU, which holds the call's [V, K]
    operand. The call is charged what that compiler holds inside it, so
    the check passes at the default tolerance without the ratchet's peak
    taking the operand in, and it still catches a walk that loses a
    buffer held across the call."""
    V, K, c = _fused_contract(tmp_path)
    if walk == "forgets_a_buffer":
        real = L.analyze

        def forgetful(closed, **kw):
            res = real(closed, **kw)
            res.peak_bytes -= V * 64 * 8        # the [V, 64] `held`
            return res

        monkeypatch.setattr(L, "analyze", forgetful)
    report = engine.run_contracts([c], baseline={})
    (res,) = report.results
    model, compiled, ok = res.detail["compiled"]["peak_bytes"]
    assert compiled > V * K * 8 > res.measured["peak_bytes"] - V * K * 8
    diverges = [f for f in report.findings
                if f.rule == "CSA1601" and "`peak_bytes`" in f.message]
    if walk == "honest":
        assert ok and not diverges, (model, compiled)
    else:
        assert not ok and diverges, (model, compiled)


def test_fused_call_that_names_no_call_is_a_finding(tmp_path):
    _, _, c = _fused_contract(tmp_path, fused_calls=("_renamed_sums",))
    report = engine.run_contracts([c], baseline={})
    assert any(f.rule == "CSA1601" and "_renamed_sums" in f.message
               for f in report.findings)


# ---------------------------------------------------------------------------
# CSA1602: the bytes ratchet (the ISSUE's seeded +1-buffer regression)
# ---------------------------------------------------------------------------

def _ratchet(tmp_path, extra_buffer=False, name="fixture.contract"):
    def lean(x):
        return x * jnp.uint64(2) + jnp.uint64(1)

    def bloated(x):
        # the seeded regression: one avoidable full-width materialization
        spill = jnp.cumsum(x * jnp.uint64(2))
        return x * jnp.uint64(2) + jnp.uint64(1) + (spill[-1] - spill[-1])

    return _contract(
        tmp_path, name=name,
        build=lambda: dict(fn=bloated if extra_buffer else lean,
                           args=(_vec(),)))


def test_seeded_extra_buffer_trips_ratchet_and_loosening_clears(tmp_path):
    clean = engine.run_contracts([_ratchet(tmp_path)], baseline={})
    snap = clean.snapshot
    # the committed posture: clean vs its own snapshot
    assert engine.run_contracts([_ratchet(tmp_path)],
                                baseline=snap).findings == []
    # grow the live set by one [V] buffer -> CSA1602 against the old pin
    dirty = engine.run_contracts([_ratchet(tmp_path, extra_buffer=True)],
                                 baseline=snap)
    assert "CSA1602" in _rules(dirty)
    assert any("regressed" in f.message for f in dirty.findings)
    # the accept path: a reviewed refresh to the new modeled bytes
    grown = engine.run_contracts(
        [_ratchet(tmp_path, extra_buffer=True)],
        baseline=engine.run_contracts(
            [_ratchet(tmp_path, extra_buffer=True)], baseline={}).snapshot)
    assert grown.findings == []


def test_missing_baseline_entry_trips(tmp_path):
    report = engine.run_contracts([_ratchet(tmp_path)], baseline={})
    assert set(_rules(report)) == {"CSA1602"}
    assert all("no memory-baseline entry" in f.message
               for f in report.findings)


def test_shrink_is_a_tighten_notice_not_a_finding(tmp_path):
    snap = engine.run_contracts(
        [_ratchet(tmp_path, extra_buffer=True)], baseline={}).snapshot
    slim = engine.run_contracts([_ratchet(tmp_path)], baseline=snap)
    assert slim.findings == []
    assert any("shrank" in n for n in slim.notices)


def test_stale_baseline_contract_reported(tmp_path):
    snap = engine.run_contracts([_ratchet(tmp_path)], baseline={}).snapshot
    snap["deleted.contract"] = {"peak_bytes": 1}
    report = engine.run_contracts([_ratchet(tmp_path)], baseline=snap)
    assert report.stale_baseline == ["deleted.contract"]
    assert report.findings == []        # stale is reported, not failed


def test_suppression_on_contract_line(tmp_path):
    path = tmp_path / "kernel_fixture.py"
    path.write_text(
        'MEM_CONTRACTS = [\n'
        '    # csa: ignore[CSA1602] -- fixture: snapshot intentionally absent\n'
        '    {"name": "fixture.contract"},\n'
        ']\n')
    report = engine.run_contracts([_ratchet(tmp_path)], baseline={})
    assert report.findings == []
    assert {f.rule for f in report.suppressed} == {"CSA1602"}


def test_baseline_roundtrip_and_json(tmp_path):
    report = engine.run_contracts([_ratchet(tmp_path)], baseline={})
    path = tmp_path / "memory_baseline.json"
    engine.write_memory_baseline(path, report.snapshot)
    loaded = engine.load_memory_baseline(path)
    assert loaded == report.snapshot
    assert engine.run_contracts([_ratchet(tmp_path)],
                                baseline=loaded).findings == []
    data = json.loads(engine.render_json(report))
    assert data["contracts"][0]["name"] == "fixture.contract"
    assert data["contracts"][0]["measured"]["peak_bytes"] > 0


def test_broken_contract_is_a_finding_not_a_crash(tmp_path):
    c = _contract(tmp_path,
                  build=lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    report = engine.run_contracts(
        [c], baseline={"fixture.contract": {"peak_bytes": 1}})
    assert "CSA1601" in _rules(report)
    assert report.results[0].skipped
    assert report.stale_baseline == []  # unverifiable != stale


# ---------------------------------------------------------------------------
# CSA1603: superlinear scaling
# ---------------------------------------------------------------------------

def test_superlinear_probe_trips_and_linear_clears(tmp_path):
    def quadratic(n):
        # [n, n] outer product: peak scales as n^2 against a declared O(n)
        return dict(fn=lambda x: jnp.outer(x, x).sum(axis=0),
                    args=(jax.ShapeDtypeStruct((n,), jnp.uint64),))

    c = _contract(tmp_path,
                  scaling=dict(ns=[64, 256, 1024], build=quadratic,
                               metric="peak_bytes", max_order=1.0))
    report = engine.run_contracts([c], baseline={})
    assert "CSA1603" in _rules(report)
    assert any("n^" in f.message for f in report.findings)

    def linear(n):
        return dict(fn=lambda x: x * jnp.uint64(2) + jnp.uint64(1),
                    args=(jax.ShapeDtypeStruct((n,), jnp.uint64),))

    ok = _contract(tmp_path, name="fixture.linear",
                   scaling=dict(ns=[64, 256, 1024], build=linear,
                                metric="peak_bytes", max_order=1.0))
    assert "CSA1603" not in _rules(engine.run_contracts([ok], baseline={}))


# ---------------------------------------------------------------------------
# CSA1604: VMEM overflow
# ---------------------------------------------------------------------------

def test_vmem_overflow_trips_and_real_blocks_fit(tmp_path):
    over = _contract(
        tmp_path,
        vmem=dict(blocks=[((16, 1 << 18), "uint32")], buffering=2))
    report = engine.run_contracts([over], baseline={})
    assert "CSA1604" in _rules(report)
    assert any("VMEM" in f.message for f in report.findings)
    # the committed kernel's real BlockSpecs, via its own model hook
    from consensus_specs_tpu.ops.sha256_pallas import vmem_block_model
    fits = _contract(tmp_path, name="fixture.fits",
                     vmem=dict(blocks=vmem_block_model, buffering=2))
    clean = engine.run_contracts([fits], baseline={})
    assert "CSA1604" not in _rules(clean)
    assert clean.results[0].measured["vmem_bytes"] == \
        ((16 + 8) * 512 * 4 + 2 * 64 * 4) * 2


# ---------------------------------------------------------------------------
# CSA1605: host round-trip notice through the engine
# ---------------------------------------------------------------------------

def test_host_roundtrip_notice_through_engine(tmp_path):
    def f(x):
        y = x * jnp.uint64(2)
        s = jax.pure_callback(lambda v: np.uint64(v[0]),
                              jax.ShapeDtypeStruct((), jnp.uint64), y)
        return y + s

    c = _contract(tmp_path, build=lambda: dict(fn=f, args=(_vec(),)))
    report = engine.run_contracts([c], baseline={})
    assert "CSA1605" in _rules(report)
    assert any("host round-trip" in f.message for f in report.findings)


# ---------------------------------------------------------------------------
# The committed registry and its theorems
# ---------------------------------------------------------------------------

def test_committed_registry_shape():
    """Discovery finds the six kernel-side contract homes the tier
    documents, with the headline budgets declared."""
    contracts = engine.discover()
    names = {c["name"]: c for c in contracts}
    for needle in ("models.phase0.epoch_soa.", "parallel.sharding.",
                   "streaming.pipeline.", "utils.ssz.incremental.",
                   "ops.bls_jax.", "ops.sha256_pallas."):
        assert any(n.startswith(needle) for n in names), needle
    epoch = names["models.phase0.epoch_soa.epoch_hbm_ceiling"]
    assert epoch["budget_bytes"] == 4 << 30
    assert epoch["scaling"]["ns"][-1] == 10_000_000
    assert names["parallel.sharding.epoch_shard_hbm"]["sharded"][
        "devices"] == 8


def test_committed_fast_contracts_prove_clean():
    """`make memory` in miniature over the sub-minute contracts (the
    epoch ceiling + shard bound + forest pair + VMEM); the pairing
    traces (~40 s each) run under the full `make memory` gate."""
    from tools.analysis.trace.engine import ensure_cpu_devices
    ensure_cpu_devices(8)
    fast = [c for c in engine.discover()
            if "bls_jax" not in c["name"] and "pipeline" not in c["name"]]
    assert len(fast) >= 4
    baseline = {k: v for k, v in engine.load_memory_baseline().items()
                if any(c["name"] == k for c in fast)}
    report = engine.run_contracts(fast, baseline=baseline)
    assert report.findings == [], [
        f"{f.path}:{f.line} {f.rule} {f.message}" for f in report.findings]
    assert report.stale_baseline == []


def test_epoch_contract_donates_and_aliases():
    """The epoch contract's accounting rests on donation: the modeled
    alias bytes must cover every donated [V] state column counted once
    (six uint64 columns + the bool slashed flags = 49 B/validator), and
    the aliased savings must land in the resident footprint
    (args + outs - alias). The mid-program PEAK sits at the crosslink
    muldiv pjit, which precedes the aliased output columns' definitions
    — so donation moves the end-of-program residency, not that site,
    and the peak must never be WORSE with donation on."""
    from consensus_specs_tpu.models.phase0 import epoch_soa as E

    spec = E._epoch_mem_build(100_000)
    closed, donated = engine._trace(spec)
    with_d = L.analyze(closed, donated=donated)
    without = L.analyze(closed)
    assert with_d.alias_bytes == 100_000 * (6 * 8 + 1)
    assert without.alias_bytes == 0
    assert with_d.peak_bytes <= without.peak_bytes
    # the accounting identity both walks must satisfy: peak splits into
    # the resident footprint plus the transient the site report blames
    for r in (with_d, without):
        assert r.peak_bytes == (r.arg_bytes + r.out_bytes - r.alias_bytes
                                + r.const_bytes + r.temp_bytes)


# ---------------------------------------------------------------------------
# CLI: five-tier --list-rules, merged --json, max exit
# ---------------------------------------------------------------------------

def test_list_rules_spans_five_tiers():
    out = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "--list-rules"],
        cwd=REPO, capture_output=True, text=True, check=True).stdout
    for probe in ("CSA101", "CSA1101", "CSA1401", "CSA1501",
                  "CSA1601", "CSA1602", "CSA1603", "CSA1604", "CSA1605"):
        assert probe in out, probe


def test_rules_registered_without_jax_tier():
    from tools.analysis.core import RULES
    from tools.analysis.memory import MEMORY_RULE_IDS
    assert set(MEMORY_RULE_IDS) <= set(RULES)
    assert RULES["CSA1605"].severity == "notice"
    for rule_id in ("CSA1601", "CSA1602", "CSA1603", "CSA1604"):
        assert RULES[rule_id].severity == "error"


def _cli_env():
    import os
    return {**os.environ, "JAX_PLATFORMS": "cpu"}


def test_cli_merged_tiers_json_and_max_exit(tmp_path):
    """An AST-tier finding (host cast under jit) + a clean memory run
    (the shard contract vs the committed baseline, via --memory-filter
    so the CLI lane skips the ~1-minute pairing traces — `make memory`
    runs them): the merged artifact carries both tiers, exit is the
    worst."""
    snippet = tmp_path / "bad_ast.py"
    snippet.write_text(
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return int(x)\n")
    out = tmp_path / "merged.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analysis", str(snippet),
         "--memory", "--memory-filter", "epoch_shard",
         "--json", str(out)],
        cwd=REPO, capture_output=True, text=True, env=_cli_env(),
        timeout=600)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    data = json.loads(out.read_text())
    assert sorted(data["tiers"]) == ["ast", "memory"]
    assert data["tiers"]["memory"]["findings"] == []
    assert data["tiers"]["memory"]["stale_baseline"] == []
    assert any(f["rule"] == "CSA102"
               for f in data["tiers"]["ast"]["findings"])


def test_cli_update_memory_baseline_roundtrip(tmp_path):
    """--update-memory-baseline writes a loadable snapshot whose rerun
    exits clean — real CLI, filtered to the shard contract so the lane
    stays fast."""
    bpath = tmp_path / "mb.json"
    common = [sys.executable, "-m", "tools.analysis",
              "--memory-filter", "epoch_shard",
              "--memory-baseline", str(bpath)]
    proc = subprocess.run(
        common + ["--update-memory-baseline"],
        cwd=REPO, capture_output=True, text=True, env=_cli_env(),
        timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    written = engine.load_memory_baseline(bpath)
    assert "parallel.sharding.epoch_shard_hbm" in written
    out = tmp_path / "m.json"
    proc2 = subprocess.run(
        common + ["--memory", "--json", str(out)],
        cwd=REPO, capture_output=True, text=True, env=_cli_env(),
        timeout=600)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert json.loads(out.read_text())["findings"] == []
