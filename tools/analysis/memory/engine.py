"""Memory-contract engine: discover MEM_CONTRACTS, run the peak-
liveness interpreter over the real jaxprs, cross-check the model
against what XLA allocates, ratchet the modeled bytes against the
committed baseline.

A **memory contract** is a plain dict a kernel module exports in its
`MEM_CONTRACTS` list (plain data, the TRACE_CONTRACTS idiom — the
engine imports the kernel modules, never the reverse):

    name           unique id, e.g. "models.phase0.epoch_soa.epoch_10m_hbm"
    build          () -> {"fn": traceable, "args": tuple of arrays or
                   jax.ShapeDtypeStruct pytrees (ceiling shapes cost
                   nothing to trace), "context": () -> contextmanager
                   (optional), "donate_argnums": top-level arg positions
                   whose buffers the production dispatch donates
                   (optional — expanded over each argument's leaves, so
                   the liveness model aliases them onto congruent
                   outputs and counts the pair ONCE)}
    budget_bytes   declared peak-HBM ceiling the modeled peak must stay
                   under (CSA1601); absent = ratchet only
    sharded        {"devices": N, "min_elems": int, "replicated_cap_bytes":
                   int} — rerun the walk with the per-shard byte
                   function (a leaf with >= min_elems elements shards
                   over N, everything else replicates: the repo's
                   placement policy) and PROVE
                   shard_peak <= ceil(single_peak / N) + replicated_cap
                   (CSA1601)
    scaling        {"ns": [2-3 probe sizes], "build": n -> build-spec,
                   "metric": "peak_bytes" | "temp_bytes", "max_order":
                   float, "tol": slope slack (default 0.15)} — fit the
                   log-log slope of the metric over the probes and
                   assert it <= max_order + tol (CSA1603)
    compiled       {"build": () -> build-spec at a documented probe
                   shape (default: the contract's own build), "tol":
                   ratio (default 1.25), "slack_bytes": abs slack
                   (default 4096)} or True — lower + compile the probe
                   and check the model against compiled.
                   memory_analysis(): argument/output/alias bytes
                   (exact on every backend) and peak vs
                   arg+out-alias+temp. A call the build spec declares
                   fused (`fused_calls`) is, for this check only,
                   charged what the SAME compiler holds inside it
                   (_call_transients): the declaration is about the
                   chip's compiler, the check about the liveness of
                   everything else. Divergence beyond tolerance is
                   CSA1601: the model is wrong, fix the model, never
                   trust it quietly.
    vmem           {"blocks": [((rows, cols), "dtype"), ...] or a
                   callable returning that list, "buffering": pipeline
                   copies (default 2, the Pallas double-buffered
                   pipeline), "budget_bytes": default 16 MiB/core} —
                   bound the BlockSpec footprint (CSA1604). A contract
                   may be vmem-only (no "build").

The ratchet (memory_baseline.json maps contract -> {metric: value},
metrics "peak_bytes"/"temp_bytes" + "shard_peak_bytes"/"vmem_bytes"
when the contract declares those checks): modeled bytes that GREW vs
the committed snapshot are CSA1602 — as is a contract with no
snapshot. Shrunk bytes are a notice (refresh the baseline). Host
round-trips the walk detects while buffers span them are CSA1605
notices.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

from ..core import Finding, _parse_suppressions
from . import liveness as L

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_BASELINE = Path(__file__).resolve().parents[1] / \
    "memory_baseline.json"

VMEM_BUDGET_BYTES = 16 * 1024 * 1024      # per-core VMEM (v4/v5 class)

# ratchet direction per metric: bytes only grow by a reviewed edit
METRIC_SIGN = {"peak_bytes": 1, "temp_bytes": 1,
               "shard_peak_bytes": 1, "vmem_bytes": 1}


# ---------------------------------------------------------------------------
# Discovery (mirrors ranges/engine.discover)
# ---------------------------------------------------------------------------

def discover(package_root: Optional[Path] = None) -> List[dict]:
    import importlib
    root = Path(package_root or REPO_ROOT / "consensus_specs_tpu")
    contracts: List[dict] = []
    seen = set()
    for path in sorted(root.rglob("*.py")):
        source = path.read_text()
        if "MEM_CONTRACTS" not in source:
            continue
        rel = path.relative_to(root.parent).with_suffix("")
        module = importlib.import_module(".".join(rel.parts))
        for contract in getattr(module, "MEM_CONTRACTS", []):
            c = dict(contract)
            name = c["name"]
            assert name not in seen, f"duplicate memory contract {name}"
            seen.add(name)
            c.setdefault("path", str(path))
            c.setdefault("line", _name_line(source, name))
            contracts.append(c)
    return contracts


def _name_line(source: str, name: str) -> int:
    lines = source.splitlines()
    # quoted match first — a bare substring scan would anchor a name at
    # a longer name containing it, mis-placing inline suppressions
    for i, line in enumerate(lines, 1):
        if f'"{name}"' in line or f"'{name}'" in line:
            return i
    for i, line in enumerate(lines, 1):
        if name in line:
            return i
    for i, line in enumerate(lines, 1):
        if "MEM_CONTRACTS" in line:
            return i
    return 1


# ---------------------------------------------------------------------------
# Baseline
# ---------------------------------------------------------------------------

def load_memory_baseline(path=None) -> Dict[str, Dict[str, int]]:
    p = Path(path or DEFAULT_BASELINE)
    if not p.exists():
        return {}
    return {k: dict(v) for k, v in
            json.loads(p.read_text()).get("contracts", {}).items()}


def write_memory_baseline(path, snapshot: Dict[str, Dict[str, int]]) -> None:
    ordered = {k: {m: snapshot[k][m] for m in sorted(snapshot[k])}
               for k in sorted(snapshot)}
    Path(path).write_text(json.dumps(
        {"version": 1,
         "comment": "Modeled peak-liveness snapshot (the CSA1602 bytes "
                    "ratchet). peak_bytes/temp_bytes are what the "
                    "liveness model derived over the contract's ceiling "
                    "shapes; shard_peak_bytes the per-shard walk, "
                    "vmem_bytes the Pallas block footprint. Loosening "
                    "an entry is a reviewed edit; "
                    "--update-memory-baseline refreshes after wins.",
         "contracts": ordered}, indent=2) + "\n")


# ---------------------------------------------------------------------------
# Evaluation
# ---------------------------------------------------------------------------

@dataclass
class MemResult:
    name: str
    path: str
    line: int
    measured: Dict[str, int] = field(default_factory=dict)
    detail: Dict[str, object] = field(default_factory=dict)
    skipped: str = ""


@dataclass
class MemReport:
    findings: List[Finding]
    suppressed: List[Finding]
    results: List[MemResult]
    notices: List[str]
    stale_baseline: List[str]

    @property
    def snapshot(self) -> Dict[str, Dict[str, int]]:
        return {r.name: dict(r.measured) for r in self.results
                if not r.skipped and r.measured}


def _rel(path: str) -> str:
    try:
        return str(Path(path).resolve().relative_to(REPO_ROOT))
    except ValueError:
        return path


def _flat_donated(args, donate_argnums) -> set:
    """Expand jit-level donate_argnums (top-level positions) to FLAT
    invar indices over the argument pytree's leaves."""
    import jax
    donated = set()
    offset = 0
    donate = set(donate_argnums or ())
    for i, arg in enumerate(args):
        n = len(jax.tree_util.tree_leaves(arg))
        if i in donate:
            donated.update(range(offset, offset + n))
        offset += n
    return donated


def _trace(spec):
    """Trace one build spec to (ClosedJaxpr, flat donated indices)."""
    import contextlib
    import jax
    fn, args = spec["fn"], tuple(spec["args"])
    with contextlib.ExitStack() as stack:
        ctx_factory = spec.get("context")
        if ctx_factory:
            stack.enter_context(ctx_factory())
        closed = jax.make_jaxpr(fn)(*args)
    return closed, _flat_donated(args, spec.get("donate_argnums"))


def _declared_fused(spec) -> Dict[str, int]:
    """The build spec's `fused_calls` as the walk takes them: no
    transient inside a call the contract declares fused."""
    return dict.fromkeys(spec.get("fused_calls", ()), 0)


def _analyze_spec(spec, bytes_fn=L.aval_bytes) -> L.Liveness:
    closed, donated = _trace(spec)
    return L.analyze(closed, donated=donated, bytes_fn=bytes_fn,
                     fused_calls=_declared_fused(spec))


def _vmem_bytes(vmem: dict) -> int:
    blocks = vmem["blocks"]
    if callable(blocks):
        blocks = blocks()
    import numpy as np
    total = 0
    for shape, dtype in blocks:
        n = 1
        for d in shape:
            n *= int(d)
        total += n * np.dtype(dtype).itemsize
    return total * int(vmem.get("buffering", 2))


def _compile_fresh(fn, args, **jit_kwargs):
    """Compile FRESH, never through the persistent compilation cache:
    an XLA:CPU executable deserialized from the cache drops its
    donated-aliasing metadata (the PR 3 caveat CSA1504 codifies), so
    memory_analysis() on a cache hit reports alias 0 and a different
    temp — the cross-check would flag the model for the cache's
    dishonesty. conftest.py points the cache at .cache/xla for the test
    lanes; unset it for the probe compiles only."""
    import jax
    cache_dir = jax.config.jax_compilation_cache_dir
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", None)
    try:
        return jax.jit(fn, **jit_kwargs).lower(*args).compile()
    finally:
        if cache_dir is not None:
            jax.config.update("jax_compilation_cache_dir", cache_dir)


def _call_transients(closed, names) -> Dict[str, int]:
    """{name: temp bytes} of each call the contract declares fused,
    compiled alone by THIS backend at the shapes the probe calls it with.

    `fused_calls` is a claim about the chip's compiler, held to it by
    tests/test_tpu_compile.py; the cross-check compiles with whatever
    backend runs the analyzer, which may materialise what the chip fuses
    (XLA:CPU holds the proposer sum's [V, 128] uint64 operand, 268 MB at
    the 2^18 probe, where the v5e compiler holds none). Asking the same
    compiler what it holds inside the call keeps the check on what
    CSA1601 is for, the liveness of everything around it, at the
    tolerance every other contract gets. A declared name that no call
    carries is a stale declaration and raises."""
    import jax
    from jax.extend import core as jex_core
    found: Dict[str, int] = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.params.get("name")
            if name in names:
                sub = eqn.params["jaxpr"]
                stats = _compile_fresh(
                    jex_core.jaxpr_as_fun(sub),
                    [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                     for v in sub.jaxpr.invars]).memory_analysis()
                found[name] = max(found.get(name, 0),
                                  int(stats.temp_size_in_bytes))
            else:
                for sub in L._sub_jaxprs(eqn):
                    walk(getattr(sub, "jaxpr", sub))

    walk(closed.jaxpr)
    missing = sorted(set(names) - set(found))
    if missing:
        raise ValueError(f"fused_calls names no traced call: {missing}")
    return found


def _compiled_check(spec, tol: float, slack: int) -> Dict[str, object]:
    """Lower + compile the probe spec and compare the liveness model's
    bytes against compiled.memory_analysis(). Returns {"checked":
    {metric: [model, compiled, ok]}, "failures": [msg, ...]}."""
    import contextlib
    fn, args = spec["fn"], tuple(spec["args"])
    jit_kwargs = {}
    if spec.get("donate_argnums"):
        jit_kwargs["donate_argnums"] = tuple(spec["donate_argnums"])
    closed, donated = _trace(spec)
    with contextlib.ExitStack() as stack:
        ctx_factory = spec.get("context")
        if ctx_factory:
            stack.enter_context(ctx_factory())
        compiled = _compile_fresh(fn, args, **jit_kwargs)
        model_small = L.analyze(
            closed, donated=donated,
            fused_calls=_call_transients(closed, _declared_fused(spec)))
    stats = compiled.memory_analysis()
    if stats is None:
        raise RuntimeError("the backend reports no memory_analysis(): the "
                           "declared compiled cross-check cannot run")

    def close(model, actual):
        if abs(model - actual) <= slack:
            return True
        lo, hi = sorted((model, actual))
        return lo > 0 and hi / lo <= tol

    checked, failures = {}, []
    # argument/output/alias bytes are exact on every backend; the peak
    # (arg + out - alias + temp) is the quantity the budgets are about
    pairs = [
        ("argument_bytes", model_small.arg_bytes,
         stats.argument_size_in_bytes),
        ("output_bytes", model_small.out_bytes, stats.output_size_in_bytes),
        ("alias_bytes", model_small.alias_bytes, stats.alias_size_in_bytes),
        ("peak_bytes", model_small.peak_bytes,
         stats.argument_size_in_bytes + stats.output_size_in_bytes
         - stats.alias_size_in_bytes + stats.temp_size_in_bytes),
    ]
    for metric, model, actual in pairs:
        ok = close(model, actual)
        checked[metric] = [int(model), int(actual), ok]
        if not ok:
            failures.append(
                f"model `{metric}` = {model} diverges from "
                f"compiled.memory_analysis() = {actual} beyond the "
                f"documented tolerance (x{tol}, slack {slack} B)")
    return {"checked": checked, "failures": failures}


def _measure(contract: dict):
    """Evaluate one contract. Returns (MemResult, findings) where
    findings is a list of (rule, message)."""
    res = MemResult(name=contract["name"], path=contract["path"],
                    line=contract["line"])
    found: List[tuple] = []

    model = None
    if "build" in contract:
        spec = contract["build"]()
        closed, donated = _trace(spec)
        fused_calls = _declared_fused(spec)
        model = L.analyze(closed, donated=donated, fused_calls=fused_calls)
        res.measured["peak_bytes"] = model.peak_bytes
        res.measured["temp_bytes"] = model.temp_bytes
        res.detail["arg_bytes"] = model.arg_bytes
        res.detail["out_bytes"] = model.out_bytes
        res.detail["alias_bytes"] = model.alias_bytes
        res.detail["const_bytes"] = model.const_bytes
        res.detail["n_eqns"] = model.n_eqns
        if model.peak_site:
            i, prim, bytes_at = model.peak_site
            res.detail["peak_site"] = {"eqn": i, "primitive": prim,
                                       "live_bytes": bytes_at}
        for ev in model.host_events:
            found.append((
                "CSA1605",
                f"host round-trip (`{ev.primitive}` at eqn "
                f"{ev.eqn_index}) while {ev.spanning_bytes} bytes of "
                f"device buffers span it — their live ranges widen by "
                f"host latency"))

        budget = contract.get("budget_bytes")
        if budget is not None and model.peak_bytes > int(budget):
            found.append((
                "CSA1601",
                f"modeled peak {model.peak_bytes} B exceeds the "
                f"declared budget {int(budget)} B"))

        sharded = contract.get("sharded")
        if sharded:
            n = int(sharded["devices"])
            shard_model = L.analyze(
                closed, donated=donated, fused_calls=fused_calls,
                bytes_fn=L.sharded_bytes_fn(n, int(sharded["min_elems"])))
            cap = int(sharded["replicated_cap_bytes"])
            bound = -(-model.peak_bytes // n) + cap
            res.measured["shard_peak_bytes"] = shard_model.peak_bytes
            res.detail["shard_bound"] = {"devices": n, "cap_bytes": cap,
                                         "bound_bytes": bound}
            if shard_model.peak_bytes > bound:
                found.append((
                    "CSA1601",
                    f"per-shard modeled peak {shard_model.peak_bytes} B "
                    f"escapes single/N + replicated cap = "
                    f"{model.peak_bytes}/{n} + {cap} = {bound} B"))

        comp = contract.get("compiled")
        if comp:
            comp = comp if isinstance(comp, dict) else {}
            cc = _compiled_check(comp["build"]() if "build" in comp
                                 else spec,
                                 float(comp.get("tol", 1.25)),
                                 int(comp.get("slack_bytes", 4096)))
            res.detail["compiled"] = cc["checked"]
            for msg in cc["failures"]:
                found.append(("CSA1601", msg))

    scaling = contract.get("scaling")
    if scaling:
        metric = scaling.get("metric", "peak_bytes")
        ns = list(scaling["ns"])
        values = [getattr(_analyze_spec(scaling["build"](n)), metric)
                  for n in ns]
        order = L.fit_order(ns, values)
        max_order = float(scaling["max_order"])
        tol = float(scaling.get("tol", 0.15))
        res.detail["scaling"] = {"ns": ns, metric: values,
                                 "fitted_order": round(order, 4),
                                 "max_order": max_order}
        if order > max_order + tol:
            found.append((
                "CSA1603",
                f"`{metric}` scales as n^{order:.2f} over probes {ns}, "
                f"above the declared O(n^{max_order}) (+{tol} slack)"))

    vmem = contract.get("vmem")
    if vmem:
        total = _vmem_bytes(vmem)
        budget = int(vmem.get("budget_bytes", VMEM_BUDGET_BYTES))
        res.measured["vmem_bytes"] = total
        res.detail["vmem_budget_bytes"] = budget
        if total > budget:
            found.append((
                "CSA1604",
                f"BlockSpec footprint {total} B (blocks x dtype x "
                f"buffering {vmem.get('buffering', 2)}) exceeds the "
                f"{budget} B per-core VMEM budget"))

    return res, found


def run_contracts(contracts: Optional[List[dict]] = None,
                  baseline: Optional[Dict[str, Dict[str, int]]] = None,
                  baseline_path=None) -> MemReport:
    if contracts is None:
        contracts = discover()
    if baseline is None:
        baseline = load_memory_baseline(baseline_path)
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    results: List[MemResult] = []
    notices: List[str] = []
    matched = set()
    suppression_cache: Dict[str, Dict[int, set]] = {}

    def emit(res, rule, message):
        path = _rel(res.path)
        line = res.line
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
            res, found = _measure(contract)
        except Exception as exc:   # a broken contract is a finding, not a crash
            res = MemResult(name=contract["name"], path=contract["path"],
                            line=contract["line"],
                            skipped=f"{type(exc).__name__}: {exc}")
            results.append(res)
            emit(res, "CSA1601",
                 f"contract failed to trace/model: {res.skipped}")
            matched.add(res.name)     # unverifiable, not stale: the
            continue                  # baseline entry must survive
        results.append(res)
        for rule, message in found:
            emit(res, rule, message)

        base = baseline.get(res.name, {})
        if res.name in baseline:
            matched.add(res.name)
        for metric, got in res.measured.items():
            sign = METRIC_SIGN.get(metric, 1)
            prior = base.get(metric)
            if prior is None:
                emit(res, "CSA1602",
                     f"`{metric}` = {got} has no memory-baseline entry "
                     f"(run --update-memory-baseline and commit)")
            elif sign * (got - prior) > 0:
                emit(res, "CSA1602",
                     f"modeled `{metric}` = {got} regressed vs the "
                     f"committed baseline {prior}")
            elif got != prior:
                notices.append(
                    f"memory: {res.name} `{metric}` shrank "
                    f"{prior} -> {got}; refresh via "
                    f"--update-memory-baseline")

    stale = sorted(set(baseline) - matched)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return MemReport(findings=findings, suppressed=suppressed,
                     results=results, notices=notices,
                     stale_baseline=stale)


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------

def render_human(report: MemReport) -> str:
    from ..core import RULES
    out = []
    for f in report.findings:
        out.append(f"{f.path}:{f.line}: [{f.rule}] {RULES[f.rule].severity}:"
                   f" {f.context}: {f.message}")
        if RULES[f.rule].hint:
            out.append(f"    hint: {RULES[f.rule].hint}")
    for name in report.stale_baseline:
        out.append(f"memory-baseline: stale contract (removed? delete it): "
                   f"{name}")
    for note in report.notices:
        out.append(f"notice: {note}")
    ran = sum(1 for r in report.results if not r.skipped)
    out.append(f"memory: {len(report.results)} contract(s), {ran} modeled, "
               f"{len(report.findings)} finding(s), "
               f"{len(report.suppressed)} suppressed")
    return "\n".join(out)


def render_json(report: MemReport) -> str:
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
             "detail": r.detail}
            for r in report.results],
        "notices": report.notices,
        "stale_baseline": report.stale_baseline,
    }, indent=2)
