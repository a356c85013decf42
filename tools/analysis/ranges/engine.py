"""Range-contract engine: discover RANGE_CONTRACTS, run the interval
interpreter over the real jaxprs, ratchet the proven intervals against
the committed baseline.

A **range contract** is a plain dict a kernel module exports in its
`RANGE_CONTRACTS` list (plain data, the TRACE_CONTRACTS idiom — the
engine imports the kernel modules, never the reverse):

    name         unique id, e.g. "ops.fq.fq_redc"
    build        () -> {"fn": traceable (all args traced — close over
                        static config), "args": tuple of arrays or
                        jax.ShapeDtypeStruct pytrees (nothing is
                        executed: the ceiling shapes — V = 10^7
                        validators, n near the shuffle bound — cost
                        nothing to trace), "ranges": pytree congruent
                        to args whose dict leaves declare the input
                        intervals {"lo", "hi"} (+ optional "top_lo"/
                        "top_hi" overriding the LAST trailing position
                        — the narrow-limb budget is positional: body
                        limbs and the top value-spill limb have
                        different documented bounds),
                        "context": () -> contextmanager (optional)}
    output       declared bound the interpreter must PROVE: a dict
                 spec applied to every output leaf, a pytree of them
                 congruent to fn's output, or None (no pin — the proof
                 is then only the absence of undeclared wraps, plus
                 the baseline ratchet on the derived hull)
    wrap_ok      iterable of "dtype" / "dtype:kind" (kind in add/sub/
                 mul/shl/convert/div) declaring INTENTIONAL modular
                 arithmetic, e.g. ("uint32",) for SHA-256
    wrap_ok_sources  filename fragments whose staged ops may wrap
                 (ops/intmath.py's documented 128-bit machinery)
    invariants   per-loop carry invariants, consumed in loop encounter
                 order for loops beyond the unroll window: "dtype" |
                 {"lo","hi"} | [per-carry spec]
    max_unroll   abstract unroll window (default interp.DEFAULT_MAX_UNROLL)

The ratchet (ranges_baseline.json maps contract -> {metric: value},
metrics "out_lo"/"out_hi" = the proven output hull, "widened" = count
of CSA1402 degradations): a proven interval that GREW (out_hi up,
out_lo down, widened up) vs the committed snapshot is CSA1404 — as is
a contract with no snapshot. Wrap/bound/invariant failures are CSA1401,
degraded ops CSA1402 (notice), missing invariants CSA1403. Overflow
findings anchor at the *staging source line* when jax can resolve it,
so inline `# csa: ignore[CSA1401]` suppressions sit next to the
arithmetic they justify, exactly like the AST tier's.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

from ..core import Finding, _parse_suppressions

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_BASELINE = Path(__file__).resolve().parents[1] / \
    "ranges_baseline.json"

# ratchet direction per metric: +1 = bigger is a regression, -1 = smaller
METRIC_SIGN = {"out_hi": 1, "out_lo": -1, "widened": 1}


# ---------------------------------------------------------------------------
# Discovery (mirrors trace/engine.discover)
# ---------------------------------------------------------------------------

def discover(package_root: Optional[Path] = None) -> List[dict]:
    import importlib
    root = Path(package_root or REPO_ROOT / "consensus_specs_tpu")
    contracts: List[dict] = []
    seen = set()
    for path in sorted(root.rglob("*.py")):
        source = path.read_text()
        if "RANGE_CONTRACTS" not in source:
            continue
        rel = path.relative_to(root.parent).with_suffix("")
        module = importlib.import_module(".".join(rel.parts))
        for contract in getattr(module, "RANGE_CONTRACTS", []):
            c = dict(contract)
            name = c["name"]
            assert name not in seen, f"duplicate range contract {name}"
            seen.add(name)
            c.setdefault("path", str(path))
            c.setdefault("line", _name_line(source, name))
            contracts.append(c)
    return contracts


def _name_line(source: str, name: str) -> int:
    lines = source.splitlines()
    # quoted match first: a bare substring scan would anchor
    # "ops.fq.fq_mul" at the earlier "ops.fq.fq_mul_wide" line,
    # mis-placing findings and their inline suppressions
    for i, line in enumerate(lines, 1):
        if f'"{name}"' in line or f"'{name}'" in line:
            return i
    for i, line in enumerate(lines, 1):
        if name in line:
            return i
    for i, line in enumerate(lines, 1):
        if "RANGE_CONTRACTS" in line:
            return i
    return 1


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------

def load_ranges_baseline(path=None) -> Dict[str, Dict[str, int]]:
    p = Path(path or DEFAULT_BASELINE)
    if not p.exists():
        return {}
    return {k: dict(v) for k, v in
            json.loads(p.read_text()).get("contracts", {}).items()}


def write_ranges_baseline(path, snapshot: Dict[str, Dict[str, int]]) -> None:
    ordered = {k: {m: snapshot[k][m] for m in sorted(snapshot[k])}
               for k in sorted(snapshot)}
    Path(path).write_text(json.dumps(
        {"version": 1,
         "comment": "Proven value-range snapshot (the CSA1404 ratchet). "
                    "out_lo/out_hi are the interval hull the interpreter "
                    "PROVED over the contract's outputs; widened counts "
                    "CSA1402 degradations. Loosening an entry is a "
                    "reviewed edit; --update-ranges-baseline refreshes "
                    "after wins.",
         "contracts": ordered}, indent=2) + "\n")


# ---------------------------------------------------------------------------
# Evaluation
# ---------------------------------------------------------------------------

@dataclass
class RangeResult:
    name: str
    path: str
    line: int
    measured: Dict[str, int] = field(default_factory=dict)
    outputs: List[dict] = field(default_factory=list)  # per-leaf proven hulls
    skipped: str = ""


@dataclass
class RangeReport:
    findings: List[Finding]
    suppressed: List[Finding]
    results: List[RangeResult]
    notices: List[str]
    stale_baseline: List[str]

    @property
    def snapshot(self) -> Dict[str, Dict[str, int]]:
        return {r.name: dict(r.measured) for r in self.results
                if not r.skipped and r.measured}


def _is_spec(x) -> bool:
    return isinstance(x, dict) and "lo" in x


def _flat_specs(spec, n_leaves, tree=None):
    """Flatten a contract range/output spec against a pytree arity."""
    import jax
    if spec is None:
        return [None] * n_leaves
    if _is_spec(spec):
        return [spec] * n_leaves
    leaves = jax.tree_util.tree_leaves(spec, is_leaf=_is_spec)
    assert len(leaves) == n_leaves, \
        f"spec arity {len(leaves)} != leaf arity {n_leaves}"
    return leaves


def _rel(path: str) -> str:
    try:
        return str(Path(path).resolve().relative_to(REPO_ROOT))
    except ValueError:
        return path


def _measure(contract: dict):
    """Trace one contract's program and run the interpreter. Returns
    (RangeResult, events, interp)."""
    from . import interp as P
    from . import interval as I
    import contextlib
    import jax

    res = RangeResult(name=contract["name"], path=contract["path"],
                      line=contract["line"])
    spec = contract["build"]()
    fn, args = spec["fn"], tuple(spec["args"])
    ctx_factory = spec.get("context")
    with contextlib.ExitStack() as stack:
        if ctx_factory:
            stack.enter_context(ctx_factory())
        # stage ops/fq's carry-round helper as a named call so the
        # interpreter's exact summary can replace it (production
        # tracing keeps it inlined — see fq.staged_helpers)
        try:
            from consensus_specs_tpu.ops import fq as _fq
            stack.enter_context(_fq.staged_helpers())
        except ImportError:
            pass
        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
    in_leaves = jax.tree_util.tree_leaves(args)
    range_specs = _flat_specs(spec.get("ranges"), len(in_leaves))
    assert len(closed.jaxpr.invars) == len(range_specs), \
        (len(closed.jaxpr.invars), len(range_specs))
    in_vals = [P.for_aval(v.aval, s)
               for v, s in zip(closed.jaxpr.invars, range_specs)]
    it = P.Interp(wrap_ok=tuple(contract.get("wrap_ok", ())),
                  wrap_ok_sources=tuple(contract.get("wrap_ok_sources", ())),
                  invariants=list(contract.get("invariants", ())),
                  max_unroll=int(contract.get(
                      "max_unroll", P.DEFAULT_MAX_UNROLL)))
    outs = it.run(closed, in_vals)

    out_leaves = jax.tree_util.tree_leaves(out_shape)
    out_specs = _flat_specs(contract.get("output"), len(out_leaves))
    bound_failures = []
    hull_lo, hull_hi = None, None
    for i, (val, ospec) in enumerate(zip(outs, out_specs)):
        dtype = val.dtype
        h = val.hull()
        res.outputs.append({"index": i, "dtype": dtype,
                            "lo": h.lo, "hi": h.hi,
                            "vec": [[v.lo, v.hi] for v in val.vec]
                            if val.positional else None})
        if I.is_int_dtype(dtype) or dtype == "bool":
            hull_lo = h.lo if hull_lo is None else min(hull_lo, h.lo)
            hull_hi = h.hi if hull_hi is None else max(hull_hi, h.hi)
        if ospec is None:
            continue
        body = I.Interval(ospec["lo"], ospec["hi"])
        top = I.Interval(ospec.get("top_lo", ospec["lo"]),
                         ospec.get("top_hi", ospec["hi"]))
        vec = val.vec
        if val.positional and len(vec) >= 2:
            ok = (all(v.within(body) for v in vec[:-1])
                  and vec[-1].within(top))
        else:
            # positional tracking was lost (or the trailing axis is
            # degenerate): body and top positions are indistinguishable,
            # so the SOUND check is the hull against both bounds —
            # strict rather than vacuous (a collapsing op downgrading a
            # body-bound check to the looser top bound would otherwise
            # report PROVEN)
            hl = val.hull()
            ok = hl.within(body) and hl.within(top)
        if not ok:
            worst = val.hull()
            bound_failures.append(
                f"output {i}: proven interval [{worst.lo}, {worst.hi}] "
                f"escapes the declared bound [{body.lo}, {body.hi}]"
                + (f" (top [{top.lo}, {top.hi}])" if "top_hi" in ospec
                   else ""))
    res.measured = {"out_lo": hull_lo if hull_lo is not None else 0,
                    "out_hi": hull_hi if hull_hi is not None else 0,
                    "widened": it.widened()}
    return res, it.events, bound_failures


def run_contracts(contracts: Optional[List[dict]] = None,
                  baseline: Optional[Dict[str, Dict[str, int]]] = None,
                  baseline_path=None) -> RangeReport:
    if contracts is None:
        contracts = discover()
    if baseline is None:
        baseline = load_ranges_baseline(baseline_path)
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    results: List[RangeResult] = []
    notices: List[str] = []
    matched = set()
    suppression_cache: Dict[str, Dict[int, set]] = {}

    def emit(res, rule, message, path=None, line=None):
        path = _rel(path or res.path)
        line = line or res.line
        f = Finding(rule, path, line, message, context=res.name)
        sup = suppression_cache.get(path)
        if sup is None:
            try:
                sup = _parse_suppressions(
                    (REPO_ROOT / path).read_text()
                    if not Path(path).is_absolute()
                    else Path(path).read_text())
            except OSError:
                sup = {}
            suppression_cache[path] = sup
        for ln in (line, line - 1):
            rules = sup.get(ln)
            if rules and ("*" in rules or rule in rules):
                suppressed.append(f)
                return
        findings.append(f)

    for contract in contracts:
        try:
            res, events, bound_failures = _measure(contract)
        except Exception as exc:   # a broken contract is a finding, not a crash
            res = RangeResult(name=contract["name"], path=contract["path"],
                              line=contract["line"],
                              skipped=f"{type(exc).__name__}: {exc}")
            results.append(res)
            emit(res, "CSA1401",
                 f"contract failed to trace/interpret: {res.skipped}")
            matched.add(res.name)     # unverifiable, not stale: the
            continue                  # baseline entry must survive
        results.append(res)
        for ev in events:
            emit(res, ev.rule, ev.message,
                 path=ev.path or None, line=ev.line or None)
        for msg in bound_failures:
            emit(res, "CSA1401", msg)

        base = baseline.get(res.name, {})
        if res.name in baseline:
            matched.add(res.name)
        for metric, got in res.measured.items():
            sign = METRIC_SIGN.get(metric, 1)
            prior = base.get(metric)
            if prior is None:
                emit(res, "CSA1404",
                     f"`{metric}` = {got} has no ranges-baseline entry "
                     f"(run --update-ranges-baseline and commit)")
            elif sign * (got - prior) > 0:
                emit(res, "CSA1404",
                     f"proven `{metric}` = {got} regressed vs the "
                     f"committed baseline {prior}")
            elif got != prior:
                notices.append(
                    f"ranges: {res.name} `{metric}` tightened "
                    f"{prior} -> {got}; refresh via "
                    f"--update-ranges-baseline")

    stale = sorted(set(baseline) - matched)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return RangeReport(findings=findings, suppressed=suppressed,
                       results=results, notices=notices,
                       stale_baseline=stale)


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------

def render_human(report: RangeReport) -> str:
    from ..core import RULES
    out = []
    for f in report.findings:
        out.append(f"{f.path}:{f.line}: [{f.rule}] {RULES[f.rule].severity}:"
                   f" {f.context}: {f.message}")
        if RULES[f.rule].hint:
            out.append(f"    hint: {RULES[f.rule].hint}")
    for name in report.stale_baseline:
        out.append(f"ranges-baseline: stale contract (removed? delete it): "
                   f"{name}")
    for note in report.notices:
        out.append(f"notice: {note}")
    ran = sum(1 for r in report.results if not r.skipped)
    out.append(f"ranges: {len(report.results)} contract(s), {ran} proven, "
               f"{len(report.findings)} finding(s), "
               f"{len(report.suppressed)} suppressed")
    return "\n".join(out)


def render_json(report: RangeReport) -> str:
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
            {"name": r.name, "path": _rel(r.path), "line": r.line,
             "skipped": r.skipped, "measured": r.measured,
             "outputs": r.outputs}
            for r in report.results],
        "notices": report.notices,
        "stale_baseline": report.stale_baseline,
    }, indent=2)
