"""Value-range tier (tools/analysis/ranges/): seeded-regression fixtures
proving each CSA14xx rule trips on a REAL traced program, the baseline
loosen/tighten/missing/suppressed workflow (mirroring
tests/test_trace_contracts.py), the committed registry's proofs, and
the doc-constant drift guard the ISSUE demands (fq.py's prose budget ==
the contract constants, so they cannot drift apart again).

The headline theorems themselves — |col| < 2^35 into fq_redc from the
narrow budget, narrow limbs back to [-16, 2^29], shuffle int32 at the
2^30 ceiling, uint64 Gwei math at 10M validators — are committed as
RANGE_CONTRACTS next to their kernels and run under `make ranges`; this
file owns the ENGINE's behavior: a deleted fq_wide_norm, a loop grown
past its invariant, an index upcast at V = 2^31 — each must fail
through the engine, and the documented accept paths must clear it.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from consensus_specs_tpu.ops import fq as F
from consensus_specs_tpu.ops import fq_tower as T
from tools.analysis.ranges import engine
from tools.analysis.ranges import interp as P
from tools.analysis.ranges import interval as I


def _contract(tmp_path, name="fixture.contract", **kw):
    """A synthetic contract anchored in a real tmp file (so inline
    suppressions work exactly like a kernel module's)."""
    path = tmp_path / "kernel_fixture.py"
    if not path.exists():
        path.write_text(f'RANGE_CONTRACTS = [{{"name": "{name}"}}]\n')
    c = dict(name=name, path=str(path),
             line=engine._name_line(path.read_text(), name))
    c.update(kw)
    return c


def _rules(report):
    return sorted(f.rule for f in report.findings)


NARROW = {"lo": -F.NARROW_INPUT_BOUND, "hi": F.NARROW_INPUT_BOUND,
          "top_lo": -F.NARROW_TOP_SPILL, "top_hi": F.NARROW_TOP_SPILL}


def _z12():
    return jnp.zeros((2, 12, F.L), jnp.int64)


# ---------------------------------------------------------------------------
# CSA1401: proved overflow / failed output bound
# ---------------------------------------------------------------------------

def test_deleted_wide_norm_trips_overflow(tmp_path):
    """THE seeded regression the tier exists for: drop the interposed
    fq_wide_norm from the gamma recombination and the raw schoolbook
    columns (14*2^58 each) provably wrap int64 in the >2-term sum —
    CSA1401, caught before any silent pairing corruption."""
    def no_norm(av, bv):
        A = T._apply_int_matrix(T._ALPHA, av)
        Bv = T._apply_int_matrix(T._BETA, bv)
        Pw = F.fq_mul_wide(A, Bv)          # raw columns: no fq_wide_norm
        return T._apply_int_matrix(T._GAMMA, Pw)

    c = _contract(
        tmp_path,
        build=lambda: dict(fn=no_norm, args=(_z12(), _z12()),
                           ranges=(NARROW, NARROW)))
    report = engine.run_contracts([c], baseline={})
    assert "CSA1401" in _rules(report)
    assert any("int64" in f.message and "wrap" in f.message
               for f in report.findings if f.rule == "CSA1401")


def test_declared_output_bound_failure_trips(tmp_path):
    """A bound the interpreter cannot prove (fq_mul_wide columns pinned
    to the REDC budget 2^35 instead of the raw 14*2^58) is CSA1401 with
    the proven interval in the message."""
    c = _contract(
        tmp_path,
        build=lambda: dict(fn=F.fq_mul_wide,
                           args=(jnp.zeros((2, F.L), jnp.int64),) * 2,
                           ranges=(NARROW, NARROW)),
        output={"lo": -F.WIDE_COL_BUDGET, "hi": F.WIDE_COL_BUDGET})
    report = engine.run_contracts([c], baseline={})
    assert "CSA1401" in _rules(report)
    assert any("escapes the declared bound" in f.message
               for f in report.findings)


def test_index_upcast_at_2_31_trips(tmp_path):
    """Upcasting a validator index to int32 at V = 2^31 provably wraps
    the convert — the dtype-pinning regression for the shuffle/epoch
    index columns."""
    def narrows(idx):
        return idx.astype(jnp.int32)

    c = _contract(
        tmp_path,
        build=lambda: dict(
            fn=narrows,
            args=(jax.ShapeDtypeStruct(((1 << 31),), jnp.int64),),
            ranges=({"lo": 0, "hi": (1 << 31)},)))
    report = engine.run_contracts([c], baseline={})
    assert "CSA1401" in _rules(report)
    # at V = 2^31 - 1 the same cast is fine: the ceiling is sharp
    c2 = _contract(
        tmp_path, name="fixture.fits",
        build=lambda: dict(
            fn=narrows,
            args=(jax.ShapeDtypeStruct(((1 << 16),), jnp.int64),),
            ranges=({"lo": 0, "hi": (1 << 31) - 1},)))
    report2 = engine.run_contracts([c2], baseline={})
    assert "CSA1401" not in _rules(report2)


def test_intentional_wrap_declaration_is_not_flagged(tmp_path):
    """The sha256 posture: uint32 modular arithmetic declared wrap_ok
    passes; the identical program without the declaration fails."""
    def mod32(x):
        return x + jnp.uint32(0xFFFFFFFF)

    build = lambda: dict(fn=mod32, args=(jnp.zeros(4, jnp.uint32),),
                         ranges=({"lo": 0, "hi": (1 << 32) - 1},))
    flagged = engine.run_contracts(
        [_contract(tmp_path, build=build)], baseline={})
    assert "CSA1401" in _rules(flagged)
    declared = engine.run_contracts(
        [_contract(tmp_path, name="fixture.mod32", build=build,
                   wrap_ok=("uint32",))], baseline={})
    assert "CSA1401" not in _rules(declared)


# ---------------------------------------------------------------------------
# CSA1402/1403: unprovable ops and loop invariants
# ---------------------------------------------------------------------------

def test_unmodeled_op_widens_with_notice(tmp_path):
    """An op the interpreter has no handler for degrades the proof
    visibly (CSA1402 notice), never silently."""
    def odd(x):
        return jnp.prod(x)         # reduce_prod: deliberately unmodeled

    c = _contract(tmp_path,
                  build=lambda: dict(fn=odd, args=(jnp.ones(4, jnp.int64),),
                                     ranges=({"lo": 0, "hi": 7},)))
    report = engine.run_contracts([c], baseline={})
    assert "CSA1402" in _rules(report)


def test_long_loop_without_invariant_trips_missing(tmp_path):
    """A fori_loop past the unroll window whose carry is not a
    closed-form counter and has no declared invariant is CSA1403 — the
    carries widen to the dtype range instead of passing vacuously."""
    def long_loop(x):
        return jax.lax.fori_loop(0, 4096, lambda i, a: a + a, x)

    c = _contract(tmp_path,
                  build=lambda: dict(fn=long_loop,
                                     args=(jnp.int64(1),),
                                     ranges=({"lo": 0, "hi": 1},)))
    report = engine.run_contracts([c], baseline={})
    assert "CSA1403" in _rules(report)


def test_counter_accumulator_proves_in_closed_form(tmp_path):
    """A pure `carry + const` accumulator (what fori indices lower to)
    needs no invariant at any trip count: its image is closed-form."""
    def accumulating(n):
        return jax.lax.fori_loop(
            0, n, lambda i, a: a + jnp.int64(1 << 29), jnp.int64(0))

    big = _contract(
        tmp_path,
        build=lambda: dict(fn=lambda x: accumulating(100_000) + x,
                           args=(jnp.int64(0),),
                           ranges=({"lo": 0, "hi": 0},)),
        output={"lo": 0, "hi": 100_000 << 29})
    report = engine.run_contracts([big], baseline={})
    assert "CSA1401" not in _rules(report)
    assert "CSA1403" not in _rules(report)


def test_counter_final_value_covered(tmp_path):
    """Soundness pin (review finding): the closed-form counter bound
    must cover the carry OUT of the final iteration (init + length*step),
    not just the body-input values — an output pinned one step short
    must FAIL, the true bound must prove."""
    def count(x):
        return jax.lax.fori_loop(200, 400, lambda i, a: a + 1, x) \
            + jax.lax.fori_loop(0, 400, lambda i, a: a - 1, x)

    tight = _contract(
        tmp_path,
        build=lambda: dict(fn=count, args=(jnp.int64(0),),
                           ranges=({"lo": 0, "hi": 0},)),
        output={"lo": -400, "hi": 199})         # one step short
    assert any("escapes the declared bound" in f.message
               for f in engine.run_contracts([tight], baseline={}).findings)
    true = _contract(
        tmp_path, name="fixture.true",
        build=lambda: dict(fn=count, args=(jnp.int64(0),),
                           ranges=({"lo": 0, "hi": 0},)),
        output={"lo": -400, "hi": 200})
    report = engine.run_contracts([true], baseline={})
    assert not any("escapes" in f.message for f in report.findings)


def test_collapsed_output_checks_body_bound(tmp_path):
    """Soundness pin (review finding): an output that lost positional
    tracking (sort on the trailing axis) must still be held to the
    declared BODY bound — strictly, never vacuously against the looser
    top bound."""
    c = _contract(
        tmp_path,
        build=lambda: dict(fn=lambda a: jnp.sort(a, axis=-1),
                           args=(jnp.zeros((2, F.L), jnp.int64),),
                           ranges=({"lo": 0, "hi": 1 << 38},)),
        output={"lo": -16, "hi": 1 << 29,
                "top_lo": -(1 << 39), "top_hi": 1 << 39})
    report = engine.run_contracts([c], baseline={})
    assert any("escapes the declared bound" in f.message
               for f in report.findings)


def test_contract_names_anchor_exactly():
    """Review finding: "ops.fq.fq_mul" must anchor at its own contract
    line, not the earlier "ops.fq.fq_mul_wide" substring match."""
    src = F.__file__
    lines = open(src).read().splitlines()
    line = engine._name_line(open(src).read(), "ops.fq.fq_mul")
    assert '"ops.fq.fq_mul"' in lines[line - 1]


def test_trip_count_past_invariant_trips_proved_overflow(tmp_path):
    """The ISSUE's seeded regression: a loop that proves by exact
    unrolling at a short trip count fails by induction when the trip
    count grows past what its declared invariant covers — the
    doubling body escapes the invariant (CSA1401)."""
    def doubling(n):
        return jax.lax.fori_loop(0, n, lambda i, a: a + a, jnp.int64(1))

    short = _contract(
        tmp_path,
        build=lambda: dict(fn=lambda x: doubling(8) + x,
                           args=(jnp.int64(0),),
                           ranges=({"lo": 0, "hi": 0},)),
        output={"lo": 0, "hi": 1 << 8})
    ok = engine.run_contracts([short], baseline={})
    assert "CSA1401" not in _rules(ok) and "CSA1403" not in _rules(ok)

    widened = _contract(
        tmp_path, name="fixture.widened",
        build=lambda: dict(fn=lambda x: doubling(100_000) + x,
                           args=(jnp.int64(0),),
                           ranges=({"lo": 0, "hi": 0},)),
        invariants=[[None, {"lo": 0, "hi": 1 << 8}]],
        output={"lo": 0, "hi": 1 << 8})
    bad = engine.run_contracts([widened], baseline={})
    assert "CSA1401" in _rules(bad)
    assert any("invariant" in f.message for f in bad.findings)


def test_inductive_invariant_proves_long_loop(tmp_path):
    """The accept path for big loops: a genuinely inductive invariant
    (a clamped carry) closes the proof at any trip count."""
    def clamped(x):
        def body(i, a):
            return jnp.minimum(a + a + 1, jnp.int64(100))
        return jax.lax.fori_loop(0, 1_000_000, body, x)

    c = _contract(
        tmp_path,
        build=lambda: dict(fn=clamped, args=(jnp.int64(0),),
                           ranges=({"lo": 0, "hi": 0},)),
        invariants=[[None, {"lo": 0, "hi": 100}]],
        output={"lo": 0, "hi": 100})
    report = engine.run_contracts([c], baseline={})
    assert "CSA1401" not in _rules(report)
    assert "CSA1403" not in _rules(report)


def _traced_trips(step):
    """fori_loop over a TRACED trip count (lowers to `while`): the epoch
    program's proposer sum loops over ceil(rows / 128) chunks."""
    def fn(n, x):
        return jax.lax.fori_loop(0, n, lambda i, a: step(a, x), x)
    return fn


@pytest.mark.parametrize("step, hi, proves", [
    # every turn writes the same bound: the join over the turns the loop
    # may leave at is that bound, whatever the trip count
    (lambda a, x: jnp.maximum(a, x + jnp.int64(7)), 107, True),
    # every turn adds: after the 100th possible turn the carry has grown
    # by 100 steps, and a bound one turn short fails
    (lambda a, x: a + jnp.int64(1), 200, True),
    (lambda a, x: a + jnp.int64(1), 199, False),
], ids=["stable", "growing", "growing_one_short"])
def test_bounded_traced_trip_count_joins_the_exits(tmp_path, step, hi, proves):
    """A `while` whose trip count is traced but bounded proves without an
    invariant: the interpreter unrolls while the loop MAY go on, joins the
    carries of every turn at which it may leave, and stops at the turn
    where the decision is definitely no."""
    c = _contract(
        tmp_path,
        build=lambda: dict(fn=_traced_trips(step),
                           args=(jnp.int64(0), jnp.int64(0)),
                           ranges=({"lo": 0, "hi": 100},
                                   {"lo": 0, "hi": 100})),
        output={"lo": 0, "hi": hi})
    report = engine.run_contracts([c], baseline={})
    assert "CSA1403" not in _rules(report)
    assert ("CSA1401" not in _rules(report)) == proves


def test_traced_trip_count_past_the_window_needs_an_invariant(tmp_path):
    """Past the unroll window the bounded-trip path gives way to the
    declared-invariant path, as before."""
    c = _contract(
        tmp_path,
        build=lambda: dict(fn=_traced_trips(lambda a, x: a + jnp.int64(1)),
                           args=(jnp.int64(0), jnp.int64(0)),
                           ranges=({"lo": 0, "hi": 100_000},
                                   {"lo": 0, "hi": 100})))
    assert "CSA1403" in _rules(engine.run_contracts([c], baseline={}))


# ---------------------------------------------------------------------------
# CSA1404: the baseline ratchet (loosen/tighten/missing/suppressed)
# ---------------------------------------------------------------------------

def _simple(tmp_path, name="fixture.contract", hi=100):
    return _contract(
        tmp_path, name=name,
        build=lambda: dict(fn=lambda x: x * 2,
                           args=(jnp.zeros(4, jnp.int64),),
                           ranges=({"lo": 0, "hi": hi},)))


def test_missing_baseline_entry_trips(tmp_path):
    report = engine.run_contracts([_simple(tmp_path)], baseline={})
    assert _rules(report) == ["CSA1404"] * 3      # out_lo / out_hi / widened


def test_regression_vs_baseline_trips_and_loosening_clears(tmp_path):
    base = {"fixture.contract": {"out_lo": 0, "out_hi": 100,
                                 "widened": 0}}
    dirty = engine.run_contracts([_simple(tmp_path, hi=200)], baseline=base)
    assert _rules(dirty) == ["CSA1404"]
    assert "regressed" in dirty.findings[0].message
    # the accept path: a reviewed baseline edit to the proven value
    loosened = engine.run_contracts(
        [_simple(tmp_path, hi=200)],
        baseline={"fixture.contract": {"out_lo": 0, "out_hi": 400,
                                       "widened": 0}})
    assert loosened.findings == []
    # improvement below the committed snapshot: a tighten notice
    slack = engine.run_contracts(
        [_simple(tmp_path, hi=200)],
        baseline={"fixture.contract": {"out_lo": 0, "out_hi": 800,
                                       "widened": 0}})
    assert slack.findings == []
    assert any("tightened" in n for n in slack.notices)


def test_suppression_on_contract_line(tmp_path):
    path = tmp_path / "kernel_fixture.py"
    path.write_text(
        'RANGE_CONTRACTS = [\n'
        '    # csa: ignore[CSA1404] -- fixture: snapshot intentionally absent\n'
        '    {"name": "fixture.contract"},\n'
        ']\n')
    report = engine.run_contracts([_simple(tmp_path)], baseline={})
    assert report.findings == []
    assert [f.rule for f in report.suppressed] == ["CSA1404"] * 3


# ---------------------------------------------------------------------------
# Sites: a finding sits at the line of its own equation
# ---------------------------------------------------------------------------

_SITE_KERNEL = """\
import jax


def top(x):
    return x + x  # WRAPS


@jax.jit
def _inner(x):
    return x + x  # WRAPS-NESTED


def nested(x):
    return _inner(x)


RANGE_CONTRACTS = [
    {"name": "fixture.contract"},
]
"""


def _site_contract(tmp_path, fn_name, source=_SITE_KERNEL):
    """A contract over a kernel file written to tmp_path and imported from
    there, so that jax records that file's lines as the equations' frames
    and the engine parses that file's suppressions."""
    import importlib.util
    path = tmp_path / "kernel_fixture.py"
    path.write_text(source)
    mod_spec = importlib.util.spec_from_file_location("kernel_fixture", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    fn = getattr(module, fn_name)
    return _contract(
        tmp_path,
        build=lambda: dict(fn=fn, args=(jnp.zeros(4, jnp.uint64),),
                           ranges=({"lo": 0, "hi": 1 << 63},)))


@pytest.mark.parametrize("fn_name, marker", [("top", "WRAPS"),
                                             ("nested", "WRAPS-NESTED")])
def test_finding_sits_at_its_equations_own_line(tmp_path, fn_name, marker):
    """The add that can wrap is reported in the kernel's file at the
    add's line, at top level and inside a nested jit, never at the
    contract's declaration (where every finding landed while the site
    lookup failed in silence, PR 22 to PR 29)."""
    c = _site_contract(tmp_path, fn_name)
    report = engine.run_contracts([c], baseline={})
    (wrap,) = [f for f in report.findings if f.rule == "CSA1401"]
    assert wrap.path == c["path"] and wrap.line != c["line"]
    assert _SITE_KERNEL.splitlines()[wrap.line - 1].endswith("# " + marker)


@pytest.mark.parametrize("where, silenced", [("wrap", True),
                                             ("contract", False)])
def test_only_a_suppression_at_the_wrapping_line_silences(tmp_path, where,
                                                          silenced):
    ignore = "# csa: ignore[CSA1401] -- fixture: declared wrap"
    source = (_SITE_KERNEL.replace("# WRAPS\n", ignore + "\n")
              if where == "wrap" else
              _SITE_KERNEL.replace('{"name": "fixture.contract"},',
                                   '{"name": "fixture.contract"},  ' + ignore))
    assert source != _SITE_KERNEL
    c = _site_contract(tmp_path, "top", source)
    report = engine.run_contracts([c], baseline={})
    assert ("CSA1401" in [f.rule for f in report.suppressed]) is silenced
    assert ("CSA1401" not in _rules(report)) is silenced


@pytest.mark.parametrize("mode", ["no_user_frame", "jax_moved"])
def test_a_site_that_cannot_be_read_is_reported(tmp_path, monkeypatch, mode):
    """jax records no user frame for an equation: the finding goes to the
    contract's line and says why it is there. jax's private lookup refuses
    the call (it changed its argument between 0.4 and 0.9): the contract
    is reported unproven. Neither is a finding at line 0, and neither is
    silence."""
    from jax._src import source_info_util

    def moved(traceback):
        raise AttributeError("'SourceInfo' object has no attribute "
                             "'raw_frames'")

    monkeypatch.setattr(source_info_util, "user_frame",
                        moved if mode == "jax_moved" else lambda tb: None)
    c = _site_contract(tmp_path, "top")
    report = engine.run_contracts([c], baseline={})
    (finding,) = [f for f in report.findings if f.rule == "CSA1401"]
    assert finding.line == c["line"] != 0
    if mode == "jax_moved":
        assert report.results[0].skipped
        assert "raw_frames" in finding.message
    else:
        assert "no user frame" in finding.message


def test_stale_baseline_contract_reported(tmp_path):
    base = {"fixture.contract": {"out_lo": 0, "out_hi": 8, "widened": 0},
            "deleted.contract": {"out_hi": 1}}
    report = engine.run_contracts([_simple(tmp_path, hi=4)], baseline=base)
    assert report.stale_baseline == ["deleted.contract"]


def test_baseline_roundtrip_and_json(tmp_path):
    report = engine.run_contracts([_simple(tmp_path)], baseline={})
    path = tmp_path / "ranges_baseline.json"
    engine.write_ranges_baseline(path, report.snapshot)
    loaded = engine.load_ranges_baseline(path)
    assert loaded == report.snapshot
    again = engine.run_contracts([_simple(tmp_path)], baseline=loaded)
    assert again.findings == []
    data = json.loads(engine.render_json(report))
    assert data["contracts"][0]["name"] == "fixture.contract"
    assert data["contracts"][0]["measured"]["out_hi"] == 200


def test_broken_contract_is_a_finding_not_a_crash(tmp_path):
    c = _contract(tmp_path,
                  build=lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    report = engine.run_contracts([c], baseline={})
    assert "CSA1401" in _rules(report)
    assert report.results[0].skipped


# ---------------------------------------------------------------------------
# The committed registry and its theorems
# ---------------------------------------------------------------------------

def test_committed_registry_proves_clean():
    """`make ranges` in miniature: every committed RANGE_CONTRACT proves
    against the committed baseline with zero actionable findings — the
    acceptance bar (>= 10 contracts over fq / fq_tower / scalar_mul /
    sha256 / shuffle / epoch_soa, wide budget proven not asserted)."""
    contracts = engine.discover()
    assert len(contracts) >= 10
    names = [c["name"] for c in contracts]
    for needle in ("ops.fq.", "ops.fq_tower.", "ops.scalar_mul.",
                   "ops.sha256.", "ops.shuffle.",
                   "models.phase0.epoch_soa."):
        assert any(n.startswith(needle) for n in names), needle
    report = engine.run_contracts(contracts)
    assert report.findings == [], [
        f"{f.path}:{f.line} {f.rule} {f.message}" for f in report.findings]
    assert report.stale_baseline == []
    # the FAR-sentinel add is the one declared (inline-suppressed) wrap,
    # silenced at its own line and nowhere else
    (far,) = report.suppressed
    assert far.rule == "CSA1401"
    source = (engine.REPO_ROOT / far.path).read_text().splitlines()
    assert far.path.endswith("models/phase0/epoch_soa.py")
    assert source[far.line - 1].lstrip().startswith("assigned = base_epoch +")
    assert "csa: ignore[CSA1401]" in source[far.line - 2]


def test_wide_budget_is_proven_not_asserted():
    """The acceptance criterion verbatim: the |col| < 2^35 REDC input
    budget is derived by the interpreter from the narrow input budget —
    check the gamma contract's proven hull actually sits under the
    declared 2^35, with real slack (i.e. a nontrivial proof, not a pin
    at the boundary)."""
    contracts = [c for c in engine.discover()
                 if c["name"] == "ops.fq_tower.fq12_mul.redc_cols[coeff]"]
    assert len(contracts) == 1
    res, events, failures = engine._measure(contracts[0])
    assert failures == [] and not [e for e in events
                                   if e.rule == "CSA1401"]
    body_cols = [iv for out in res.outputs
                 for iv in (out["vec"][:-1] if out["vec"] else [])]
    assert body_cols, "gamma output lost positional tracking"
    worst = max(abs(lo) if abs(lo) > hi else hi for lo, hi in body_cols)
    assert worst < F.WIDE_COL_BUDGET
    assert worst > F.WIDE_COL_BUDGET // 8      # nontrivial: real content


def test_doc_constants_match_contract_constants():
    """The fq.py docstring's budget numbers are the exported constants
    the contracts declare — asserted so prose and prover cannot drift
    (the pre-PR state: hand-derived 2^35 / [-1, 2^29] prose nothing
    checked)."""
    doc = F.__doc__
    assert F.WIDE_COL_BUDGET == F.WIDE_ACCUM_FANIN << F.B == 1 << 35
    assert F.WIDE_COL_RAW == F.L << (2 * F.B) == 14 << 58
    assert F.NARROW_LIMB_HI == 1 << 29
    assert F.CANONICAL_TOP == F.Q >> (F.B * (F.L - 1))
    for token in ("NARROW_INPUT_BOUND = 2^32", "NARROW_TOP_SPILL = 2^16",
                  "WIDE_COL_RAW = 14*2^58", "[-16, 2^29]",
                  "WIDE_ACCUM_FANIN * 2^29 = 2^35",
                  "WIDE_TOP_SPILL = 2^38"):
        assert token in doc, f"fq.py docstring lost budget token {token!r}"
    # the tower's fan-in ceiling is the same constant, not a re-derived 64
    import inspect
    assert "F.WIDE_ACCUM_FANIN" in inspect.getsource(T._check_budget)
    # and the redc docstring still carries the proving pointer
    assert "2^35" in F.fq_redc.__doc__


def test_narrow_norm_proof_matches_docstring_interval():
    """The machine-proven post-norm body interval IS the documented
    [NARROW_LIMB_LO, NARROW_LIMB_HI]: prove fq_mul's committed contract
    and compare the body hull directly."""
    contracts = [c for c in engine.discover() if c["name"] == "ops.fq.fq_mul"]
    res, events, failures = engine._measure(contracts[0])
    assert failures == []
    (out,) = res.outputs
    body = out["vec"][:-1]
    lo = min(l for l, _ in body)
    hi = max(h for _, h in body)
    assert F.NARROW_LIMB_LO <= lo and hi <= F.NARROW_LIMB_HI
    assert hi == F.NARROW_LIMB_HI          # the 2^29 ceiling is tight


def test_rules_registered_without_jax_tier():
    """--list-rules must span all three tiers on the no-jax lint lane."""
    from tools.analysis.core import RULES
    from tools.analysis.ranges import RANGE_RULE_IDS
    assert set(RANGE_RULE_IDS) <= set(RULES)
    assert RULES["CSA1402"].severity == "notice"
    for rule_id in ("CSA1401", "CSA1403", "CSA1404"):
        assert RULES[rule_id].severity == "error"


def test_csa901_defers_to_range_contracts(tmp_path):
    """The demoted pre-check: an accumulation inside a function the
    module's RANGE_CONTRACTS section references is NOT double-reported
    by CSA901 (the proving tier owns it); the same code without a
    contract still gets the syntactic notice."""
    from tools.analysis.core import analyze_paths
    body = (
        "def hot(a, b):\n"
        "    w = fq_mul_wide(a, b)\n"
        "    return w + w + w\n")
    bare = tmp_path / "bare.py"
    bare.write_text(body)
    covered = tmp_path / "covered.py"
    covered.write_text(body + "\nRANGE_CONTRACTS = [dict(name='x.hot', "
                       "build=lambda: dict(fn=hot))]\n")
    assert [f.rule for f in analyze_paths([str(bare)]).findings] == ["CSA901"]
    assert analyze_paths([str(covered)]).findings == []


# ---------------------------------------------------------------------------
# Interpreter internals worth pinning
# ---------------------------------------------------------------------------

def test_carry_rounds_summary_matches_concrete():
    """The jitted _carry_rounds summary is the exact positional
    transfer: drive random in-budget arrays through the CONCRETE kernel
    and check every limb lands inside the summary's proven interval."""
    import numpy as np
    rng = np.random.default_rng(7)
    arr = rng.integers(-(1 << 32), 1 << 32, size=(64, F.L))
    arr[:, -1] = rng.integers(-(1 << 16), 1 << 16, size=64)
    out = np.asarray(F._carry_rounds(jnp.asarray(arr), 3))

    with F.staged_helpers():
        closed = jax.make_jaxpr(lambda t: F._carry_rounds(t, 3))(
            jnp.zeros((2, F.L), jnp.int64))
    vals = [P.for_aval(closed.jaxpr.invars[0].aval,
                       {"lo": -(1 << 32), "hi": 1 << 32,
                        "top_lo": -(1 << 16), "top_hi": 1 << 16})]
    it = P.Interp()
    (res,) = it.run(closed, vals)
    assert it.events == []
    for pos in range(F.L):
        lo, hi = res.vec[pos].lo, res.vec[pos].hi
        assert lo <= int(out[:, pos].min()) and int(out[:, pos].max()) <= hi


def test_interval_arithmetic_exactness():
    a = I.Interval(-3, 5)
    b = I.Interval(2, 4)
    assert I.mul(a, b) == I.Interval(-12, 20)
    assert I.floordiv(I.Interval(-7, 7), I.Interval(2, 2)).lo == -4
    assert I.ashr(I.Interval(-8, 8), I.iv(1)) == I.Interval(-4, 4)
    assert I.and_(I.Interval(-100, 100), I.Interval(0, 15)) == \
        I.Interval(0, 15)
    assert I.isqrt(I.Interval(0, 17)) == I.Interval(0, 4)
    assert I.scale(I.Interval(1, 3), 10) == I.Interval(1, 30)
