"""Kernel-contract engine: discover TRACE_CONTRACTS, trace/lower the
real programs, ratchet measured values against the committed baseline.

A **contract** is a plain dict a kernel module exports in its
`TRACE_CONTRACTS` list (plain data so the package never imports
tools.*; the engine imports the kernel modules, not the reverse):

    name             unique id, e.g. "ops.fq_tower.fq12_mul[coeff]"
    build            () -> {"fn": traceable, "args": tuple,
                            "jit_kwargs": dict (optional),
                            "context": () -> contextmanager (optional,
                              e.g. pinning CSTPU_FQ_REDC for tracing)}
                     (optional when the contract only has `measure`)
    budgets          {metric: int} — declared maxima. Engine-computed
                     metrics: "redc_lanes" (QINV-tagged multiply lanes
                     / L), "jaxpr_eqns" (whole-graph eqn count),
                     "seq_adds"/"seq_doubles" (with count_point_ops),
                     "collective_ops" (with collectives). Any other
                     name must come from `measure`.
    exact            metric names that must EQUAL the budget — drift in
                     either direction is a contract violation (the lane
                     counts: an improvement should edit the contract
                     consciously, not float)
    measure          () -> {metric: int} — module-provided measured
                     metrics (counted pair-hash lanes, the analytic
                     seq-adds model at the hot shapes, ...)
    count_point_ops  True: run fn(*args) EAGERLY under
                     tracer.counted_point_ops and record
                     seq_adds/seq_doubles (the dependent-chain
                     convention of ops/scalar_mul.sequential_*)
    forbid           subset of ("f64", "callback", "device_put") —
                     lowered/traced hygiene (CSA12xx)
    donate_min       minimum tf.aliasing_output annotations that must
                     survive lowering (CSA1204); 0 = unchecked
    collectives      iterable of collective kinds the COMPILED program
                     must contain exactly (CSA1301); None = unchecked
                     (compiling is the engine's only expensive step —
                     only contracts that declare collectives or budget
                     "collective_ops" pay it)
    chained_prefix   first n flattened outputs' lowered shardings must
                     equal the first n flattened args' (CSA1302) — the
                     static form of watchdog.layout_check on a
                     self-chained serving-loop step; 0 = unchecked
    requires_devices engine skips the contract (with a notice) when
                     jax.device_count() is smaller

The ratchet (trace_baseline.json maps contract -> {metric: value}):
measured > budget (or != for `exact`) is CSA1101 — fix the kernel or
change the contract; measured > baseline is CSA1102 — loosening means
editing the committed snapshot; measured < baseline is a CSA1103
notice (tighten cue; --update-trace-baseline refreshes); a metric with
no baseline entry is CSA1104 (new contracts commit their snapshot).
Inline `# csa: ignore[...]` suppressions on the contract's `"name":`
line (or the line above) work exactly like the AST tier's.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional

from ..core import Finding, _parse_suppressions

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_BASELINE = Path(__file__).resolve().parents[1] / "trace_baseline.json"

_HYGIENE_RULES = {"f64": "CSA1201", "callback": "CSA1202",
                  "device_put": "CSA1203"}


def ensure_cpu_devices(n: int = 8) -> None:
    """Pin XLA:CPU with >= n virtual devices BEFORE jax initializes a
    backend (the __graft_entry__ idiom): the contract driver must run in
    seconds on any machine, never touch an accelerator, and the
    ServingMesh contracts need the 8-device virtual mesh. A no-op once a
    backend exists (pytest's conftest already pinned it)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from consensus_specs_tpu.utils import cpu_devices
    if cpu_devices.request(n):
        jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# Discovery
# ---------------------------------------------------------------------------

def discover(package_root: Optional[Path] = None) -> List[dict]:
    """Collect every TRACE_CONTRACTS entry under consensus_specs_tpu.

    Cheap static pre-filter (only files whose text mentions
    TRACE_CONTRACTS are imported), then each contract is annotated with
    its defining module's `path` and the `line` of its `"name"` literal
    so findings anchor — and inline suppressions apply — exactly like
    the AST tier's."""
    import importlib
    root = Path(package_root or REPO_ROOT / "consensus_specs_tpu")
    contracts: List[dict] = []
    seen = set()
    for path in sorted(root.rglob("*.py")):
        source = path.read_text()
        if "TRACE_CONTRACTS" not in source:
            continue
        rel = path.relative_to(root.parent).with_suffix("")
        module = importlib.import_module(".".join(rel.parts))
        for contract in getattr(module, "TRACE_CONTRACTS", []):
            c = dict(contract)
            name = c["name"]
            assert name not in seen, f"duplicate trace contract {name}"
            seen.add(name)
            c.setdefault("path", str(path))
            c.setdefault("line", _name_line(source, name))
            contracts.append(c)
    return contracts


def _name_line(source: str, name: str) -> int:
    """Anchor line for a contract's findings/suppressions: the line its
    full name literal appears on, else the module's TRACE_CONTRACTS
    assignment (names built by f-string helpers anchor there)."""
    lines = source.splitlines()
    for i, line in enumerate(lines, 1):
        if name in line:
            return i
    for i, line in enumerate(lines, 1):
        if "TRACE_CONTRACTS" in line:
            return i
    return 1


def budget_snapshot(contracts: Optional[Iterable[dict]] = None) -> dict:
    """{contract: {metric: budget}} without tracing anything: the
    declared budgets alone, as plain data."""
    return {c["name"]: dict(c.get("budgets", {}))
            for c in (contracts if contracts is not None else discover())}


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------

def load_trace_baseline(path=None) -> Dict[str, Dict[str, int]]:
    p = Path(path or DEFAULT_BASELINE)
    if not p.exists():
        return {}
    return {k: dict(v) for k, v in
            json.loads(p.read_text()).get("contracts", {}).items()}


def write_trace_baseline(path, snapshot: Dict[str, Dict[str, int]]) -> None:
    ordered = {k: {m: snapshot[k][m] for m in sorted(snapshot[k])}
               for k in sorted(snapshot)}
    Path(path).write_text(json.dumps(
        {"version": 1,
         "comment": "Measured trace-tier snapshot (the CSA1102 ratchet). "
                    "Loosening an entry is a reviewed edit; "
                    "--update-trace-baseline refreshes after wins.",
         "contracts": ordered}, indent=2) + "\n")


# ---------------------------------------------------------------------------
# Evaluation
# ---------------------------------------------------------------------------

@dataclass
class ContractResult:
    name: str
    path: str
    line: int
    measured: Dict[str, int] = field(default_factory=dict)
    budgets: Dict[str, int] = field(default_factory=dict)
    hygiene: Dict[str, object] = field(default_factory=dict)
    skipped: str = ""          # non-empty reason when the contract didn't run


@dataclass
class TraceReport:
    findings: List[Finding]            # actionable
    suppressed: List[Finding]
    results: List[ContractResult]
    notices: List[str]
    stale_baseline: List[str]          # baseline contract names nothing matched

    @property
    def snapshot(self) -> Dict[str, Dict[str, int]]:
        return {r.name: dict(r.measured) for r in self.results
                if not r.skipped and r.measured}


def _measure(contract: dict) -> ContractResult:
    """Run one contract's programs and collect every measured metric and
    hygiene observation. Pure measurement — ratchet classification
    happens in run_contracts so tests can re-classify one measurement
    against many baselines."""
    from . import tracer
    import contextlib
    import jax

    res = ContractResult(name=contract["name"], path=contract["path"],
                         line=contract["line"],
                         budgets=dict(contract.get("budgets", {})))
    need = jax.device_count()
    want = int(contract.get("requires_devices", 1))
    if need < want:
        res.skipped = f"needs {want} devices, have {need}"
        return res

    measured: Dict[str, int] = {}
    hygiene: Dict[str, object] = {}
    build = contract.get("build")
    if build is not None:
        spec = build()
        fn, args = spec["fn"], tuple(spec["args"])
        jit_kwargs = dict(spec.get("jit_kwargs", {}))
        ctx_factory = spec.get("context")
        ctx = ctx_factory() if ctx_factory else contextlib.nullcontext()
        budgets = contract.get("budgets", {})
        forbid = tuple(contract.get("forbid", ()))
        with ctx:
            need_jaxpr = ("redc_lanes" in budgets or "jaxpr_eqns" in budgets
                          or "f64_ops" in budgets or forbid)
            if need_jaxpr:
                static = jit_kwargs.get("static_argnums", ())
                # normalize BEFORE truthiness: a bare `static_argnums=0`
                # (valid for jax.jit) is falsy as an int
                static = (static,) if isinstance(static, int) else \
                    tuple(static)
                if static:
                    closed = tracer.fresh_jaxpr(
                        lambda *dyn: fn(*[
                            args[i] if i in static else dyn[_dyn_index(
                                i, static)] for i in range(len(args))]),
                        *[a for i, a in enumerate(args) if i not in static])
                else:
                    closed = tracer.fresh_jaxpr(fn, *args)
                qinv = None
                if "redc_lanes" in budgets:
                    from consensus_specs_tpu.ops import fq as F
                    qinv = F.QINV_NEG
                scan = tracer.scan_program(closed, tagged_const=qinv)
                if "redc_lanes" in budgets:
                    assert scan["tagged_lanes"] % F.L == 0, scan
                    measured["redc_lanes"] = scan["tagged_lanes"] // F.L
                if "jaxpr_eqns" in budgets:
                    measured["jaxpr_eqns"] = scan["eqns"]
                if "f64_ops" in budgets:
                    # a budgeted (usually exact) f64 count: the contract
                    # declares its DELIBERATE float64 ops (e.g. the
                    # isqrt_u64 Newton seed) so any new upcast fails
                    measured["f64_ops"] = scan["f64_ops"]
                if "callback" in forbid:
                    hygiene["callbacks"] = scan["callbacks"]
                if "device_put" in forbid:
                    hygiene["device_puts"] = scan["device_puts"]
                if "f64" in forbid:
                    hygiene["f64"] = scan["f64_ops"]
            need_lowered = (contract.get("donate_min")
                            or contract.get("chained_prefix"))
            need_compiled = (contract.get("collectives") is not None
                             or "collective_ops" in budgets)
            if need_lowered or need_compiled:
                # lower ONCE; the StableHLO text and the compiled HLO
                # both read off the same Lowered object (the sharded
                # epoch program is the expensive one here)
                import jax
                lowered = jax.jit(fn, **jit_kwargs).lower(*args)
            if need_lowered:
                text = lowered.as_text()
                if contract.get("donate_min"):
                    hygiene["donated"] = tracer.donated_count(text)
                n_chain = int(contract.get("chained_prefix", 0))
                if n_chain:
                    arg_sh, out_sh = tracer.signature_shardings(text)
                    if len(arg_sh) < n_chain or len(out_sh) < n_chain:
                        # fewer flattened args/results than the declared
                        # prefix: the contract no longer matches the
                        # program — a mismatch, not a silent pass
                        hygiene["chain"] = [
                            (i,
                             arg_sh[i] if i < len(arg_sh) else "<missing>",
                             out_sh[i] if i < len(out_sh) else "<missing>")
                            for i in range(n_chain)
                            if i >= len(arg_sh) or i >= len(out_sh)]
                    elif all(arg_sh[i] is None and out_sh[i] is None
                             for i in range(n_chain)):
                        # no mhlo.sharding annotations at all (e.g. a jax
                        # upgrade moving to Shardy's sdy.sharding): the
                        # check would pass VACUOUSLY — degrade loudly
                        # instead, this is the silent-degradation mode
                        # the tier exists to prevent
                        hygiene["chain_unannotated"] = n_chain
                    else:
                        hygiene["chain"] = [
                            (i, arg_sh[i], out_sh[i])
                            for i in range(n_chain)
                            if arg_sh[i] != out_sh[i]]
            if need_compiled:
                inv = tracer.collective_inventory(
                    lowered.compile().as_text())
                hygiene["collectives"] = inv
                if "collective_ops" in budgets:
                    measured["collective_ops"] = sum(inv.values())
            if contract.get("count_point_ops"):
                with tracer.counted_point_ops() as counts:
                    fn(*args)
                measured["seq_adds"] = counts["jac_add"]
                measured["seq_doubles"] = (counts["jac_double"]
                                           - counts["jac_add"])
    if contract.get("measure") is not None:
        measured.update({k: int(v)
                         for k, v in contract["measure"]().items()})
    res.measured = measured
    res.hygiene = hygiene
    return res


def _dyn_index(i: int, static) -> int:
    return i - sum(1 for s in static if s < i)


def run_contracts(contracts: Optional[List[dict]] = None,
                  baseline: Optional[Dict[str, Dict[str, int]]] = None,
                  baseline_path=None) -> TraceReport:
    """Measure every contract and classify against budgets + baseline."""
    if contracts is None:
        contracts = discover()
    if baseline is None:
        baseline = load_trace_baseline(baseline_path)
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    results: List[ContractResult] = []
    notices: List[str] = []
    matched = set()
    suppression_cache: Dict[str, Dict[int, set]] = {}

    def emit(contract, res, rule, message):
        f = Finding(rule, res.path, res.line, message, context=res.name)
        sup = suppression_cache.get(res.path)
        if sup is None:
            try:
                sup = _parse_suppressions(Path(res.path).read_text())
            except OSError:
                sup = {}
            suppression_cache[res.path] = sup
        for line in (res.line, res.line - 1):
            rules = sup.get(line)
            if rules and ("*" in rules or rule in rules):
                suppressed.append(f)
                return
        findings.append(f)

    for contract in contracts:
        res = _measure(contract)
        results.append(res)
        if res.skipped:
            notices.append(
                f"trace: contract {res.name} skipped ({res.skipped})")
            matched.add(res.name)     # unverifiable, not stale
            continue
        base = baseline.get(res.name, {})
        if res.name in baseline:
            matched.add(res.name)
        exact = set(contract.get("exact", ()))
        hygiene = res.hygiene

        for metric, budget in res.budgets.items():
            got = res.measured.get(metric)
            if got is None:
                emit(contract, res, "CSA1101",
                     f"budgeted metric `{metric}` was never measured "
                     f"(no engine kind and no `measure` entry)")
                continue
            if metric in exact:
                if got != budget:
                    emit(contract, res, "CSA1101",
                         f"`{metric}` = {got}, contract pins exactly "
                         f"{budget}")
            elif got > budget:
                emit(contract, res, "CSA1101",
                     f"`{metric}` = {got} exceeds the declared budget "
                     f"{budget}")
        for metric, got in res.measured.items():
            if metric in exact:
                continue            # the pin already owns its drift
            prior = base.get(metric)
            if prior is None:
                emit(contract, res, "CSA1104",
                     f"`{metric}` = {got} has no trace-baseline entry "
                     f"(run --update-trace-baseline and commit)")
            elif got > prior:
                emit(contract, res, "CSA1102",
                     f"`{metric}` = {got} regressed vs the committed "
                     f"baseline {prior}")
            elif got < prior:
                notices.append(
                    f"trace: {res.name} `{metric}` improved {prior} -> "
                    f"{got}; tighten via --update-trace-baseline")

        if hygiene.get("f64"):
            emit(contract, res, "CSA1201",
                 f"traced program stages {hygiene['f64']} float64 op(s)")
        if hygiene.get("callbacks"):
            emit(contract, res, "CSA1202",
                 f"host callback primitives staged: "
                 f"{', '.join(hygiene['callbacks'])}")
        if hygiene.get("device_puts"):
            emit(contract, res, "CSA1203",
                 f"{hygiene['device_puts']} device_put op(s) staged "
                 f"inside the program")
        want_donated = int(contract.get("donate_min", 0))
        if want_donated and hygiene.get("donated", 0) < want_donated:
            emit(contract, res, "CSA1204",
                 f"only {hygiene.get('donated', 0)} donated buffers "
                 f"survive lowering; contract requires >= {want_donated}")
        if contract.get("collectives") is not None:
            want = sorted(contract["collectives"])
            got_inv = sorted(hygiene.get("collectives", {}))
            if got_inv != want:
                emit(contract, res, "CSA1301",
                     f"collective inventory {got_inv or ['<none>']} != "
                     f"declared {want or ['<none>']}")
        for (i, in_sh, out_sh) in hygiene.get("chain", []):
            emit(contract, res, "CSA1302",
                 f"chained operand {i}: out sharding {out_sh!r} != in "
                 f"sharding {in_sh!r}")
        if hygiene.get("chain_unannotated"):
            emit(contract, res, "CSA1302",
                 f"none of the {hygiene['chain_unannotated']} chained "
                 f"operands carry an mhlo.sharding annotation — the "
                 f"layout check cannot see the lowered placement "
                 f"(partitioner/dialect change?); it must not pass "
                 f"vacuously")

    stale = sorted(set(baseline) - matched)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return TraceReport(findings=findings, suppressed=suppressed,
                       results=results, notices=notices,
                       stale_baseline=stale)


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------

def render_human(report: TraceReport) -> str:
    from ..core import RULES
    out = []
    for f in report.findings:
        out.append(f"{f.path}:{f.line}: [{f.rule}] {RULES[f.rule].severity}:"
                   f" {f.context}: {f.message}")
        if RULES[f.rule].hint:
            out.append(f"    hint: {RULES[f.rule].hint}")
    for name in report.stale_baseline:
        out.append(f"trace-baseline: stale contract (removed? delete it): "
                   f"{name}")
    for note in report.notices:
        out.append(f"notice: {note}")
    ran = sum(1 for r in report.results if not r.skipped)
    out.append(f"contracts: {len(report.results)} declared, {ran} run, "
               f"{len(report.findings)} finding(s), "
               f"{len(report.suppressed)} suppressed")
    return "\n".join(out)


def render_json(report: TraceReport) -> str:
    from ..core import RULES

    def row(f: Finding):
        return {"rule": f.rule, "path": f.path, "line": f.line,
                "contract": f.context, "message": f.message,
                "severity": RULES[f.rule].severity,
                "fingerprint": f.fingerprint()}

    return json.dumps({
        "findings": [row(f) for f in report.findings],
        "suppressed": [row(f) for f in report.suppressed],
        "contracts": [
            {"name": r.name, "path": r.path, "line": r.line,
             "skipped": r.skipped, "budgets": r.budgets,
             "measured": r.measured}
            for r in report.results],
        "notices": report.notices,
        "stale_baseline": report.stale_baseline,
    }, indent=2)
