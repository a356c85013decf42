"""Buffer-lifetime engine: interprocedural donation/aliasing prover.

Pipeline (see the package docstring for the rule catalog):

  1. parse the default target set (the runtime package + the
     donation-bearing entry points) into the call-graph IR
     (callgraph.build — same modules, dotted names and import
     resolution the CSA5xx jit-taint pass uses);
  2. discover DONORS — callables that consume (donate) some of their
     arguments: decorated jits, wrapper-assign jits, partial forms,
     `platform_donated_jit` helper instances and their `.donated` /
     `.undonated` / `.resolve()` projections, all resolved across
     module boundaries through from-imports and module aliases;
  3. fixpoint two interprocedural summary maps over every module-level
     def and class method: CALL summaries ("calling f donates its arg
     k") and RETURN summaries ("f() returns a donor with signature
     s"), so `guarded_dispatch(key, _epoch_transition_jit(), cfg,
     cols, ...)` resolves through both the wrapper shift and the
     factory return;
  4. cross-check against REAL lowerings: the trace tier's donate_min
     contracts are lowered and `tf.aliasing_output` annotations
     counted (trace/tracer.donated_count) — a donor whose donation
     was dropped by lowering is INERT (declared but dead: a notice,
     never a finding);
  5. run a path-based abstract interpreter over every function body:
     paths ("cols", "cols.balance", "self._ring", "levels[0]",
     non-constant subscripts widened to "[*]") carry LIVE / DONATED /
     MAYBE-DONATED states through assignments (may-alias edges),
     branches (joined), loops (re-executed to a second pass over the
     joined state, so cross-iteration hazards surface), donor calls,
     dispatch wrappers (`watchdog.dispatch` / `guarded_dispatch`
     shift donated positions by their two leading host args), tuple
     destructuring and attribute stores.

The dispatch-wrapper convention and the rebind idioms this engine
exonerates are exactly the house style: `cols = out[0]` chaining,
`self._ring = dispatch(..., ring, ...)` same-statement rebind, and
handing ownership to the caller via `return dispatch(...)`.
"""
from __future__ import annotations

import ast
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .. import callgraph
from ..core import (Finding, RULES, iter_py_files_rooted, load_baseline,
                    load_module)
from ..jitmap import _const_ints, _const_strs, _dotted, _jit_call_of

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_BASELINE = Path(__file__).resolve().parents[1] / \
    "lifetime_baseline.json"

# The donation-bearing surface: the runtime package plus every entry
# point PR 3 hand-audited for donated-call reuse.
DEFAULT_TARGETS = ("consensus_specs_tpu", "__graft_entry__.py",
                   "tests/test_multichip.py")

# Dispatch wrappers that forward `fn(*args)` after two host-side
# leading arguments (key, fn): telemetry.watchdog.dispatch and
# resilience.guarded_dispatch.
_WRAPPER_NAMES = {"dispatch", "guarded_dispatch"}
_WRAPPER_SHIFT = 2

_HELPER_NAMES = {"platform_donated_jit", "PlatformDonatedJit"}


# ---------------------------------------------------------------------------
# Donation signatures
# ---------------------------------------------------------------------------

@dataclass
class DSig:
    """What calling a value donates: arg position / kwarg name ->
    flavor ("always" | "cond"). `src`/`line` anchor messages at the
    donating program's declaration."""
    pos: Dict[int, str] = field(default_factory=dict)
    names: Dict[str, str] = field(default_factory=dict)
    src: str = ""
    line: int = 0
    fn_name: str = ""     # wrapped traced fn, for the lowering match
    module: str = ""
    inert: bool = False   # lowering dropped the donation

    def live(self) -> bool:
        return (not self.inert) and bool(self.pos or self.names)


def _donate_kwargs(call: ast.Call) -> Tuple[Tuple[int, ...],
                                            Tuple[str, ...], bool]:
    """(argnums, argnames, conditional) declared on a jit-ish call.
    An IfExp donate value (`(0,) if donate else ()`) is a platform
    guard: the donation is conditional."""
    argnums: List[int] = []
    argnames: List[str] = []
    conditional = False
    for kw in call.keywords:
        if kw.arg not in ("donate_argnums", "donate_argnames"):
            continue
        value = kw.value
        if isinstance(value, ast.IfExp):
            conditional = True
            parts = [value.body, value.orelse]
        else:
            parts = [value]
        for part in parts:
            if kw.arg == "donate_argnums":
                argnums.extend(_const_ints(part))
            else:
                argnames.extend(_const_strs(part))
    return tuple(dict.fromkeys(argnums)), tuple(dict.fromkeys(argnames)), \
        conditional


def _wrapped_fn_name(expr: ast.AST) -> str:
    """The traced fn a jit/helper application wraps, by name:
    `f`, `partial(f, cfg)` -> "f"."""
    name = _dotted(expr)
    if name:
        return name.split(".")[-1]
    if isinstance(expr, ast.Call) and \
            _dotted(expr.func).split(".")[-1] == "partial" and expr.args:
        return _wrapped_fn_name(expr.args[0])
    return ""


def _sig_of_jit_application(call: ast.Call, module: str) -> Optional[DSig]:
    """DSig for `jax.jit(f, donate_argnums=...)` /
    `partial(jax.jit, donate_argnums=...)(f)` /
    `platform_donated_jit(f, donate_argnums=...)` value expressions.
    None when the application donates nothing."""
    callee = _dotted(call.func).split(".")[-1]
    carrier: Optional[ast.Call] = None
    wrapped = ""
    helper = False
    if callee in _HELPER_NAMES:
        carrier = call
        wrapped = _wrapped_fn_name(call.args[0]) if call.args else ""
        helper = True
    else:
        jc = _jit_call_of(call)
        if jc is call:           # jax.jit(f, ...) directly
            carrier = call
            wrapped = _wrapped_fn_name(call.args[0]) if call.args else ""
        elif isinstance(call.func, ast.Call):
            inner = _jit_call_of(call.func)
            if inner is not None:   # partial(jax.jit, ...)(f)
                carrier = call.func
                wrapped = _wrapped_fn_name(call.args[0]) if call.args else ""
    if carrier is None:
        return None
    argnums, argnames, conditional = _donate_kwargs(carrier)
    if not argnums and not argnames:
        return None
    flavor = "cond" if (helper or conditional) else "always"
    return DSig(pos={i: flavor for i in argnums},
                names={n: flavor for n in argnames},
                src=wrapped or "jit", line=call.lineno,
                fn_name=wrapped, module=module)


def _resig(sig: DSig, flavor: str) -> DSig:
    return DSig(pos={k: flavor for k in sig.pos},
                names={k: flavor for k in sig.names},
                src=sig.src, line=sig.line, fn_name=sig.fn_name,
                module=sig.module, inert=sig.inert)


def _ordered_stmts(fn: ast.FunctionDef) -> List[ast.stmt]:
    """Every statement of a function body in SOURCE order, descending
    into compound statements but not into nested defs/classes."""
    out: List[ast.stmt] = []

    def rec(stmts):
        for s in stmts:
            out.append(s)
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            for attr in ("body", "orelse", "finalbody"):
                sub = getattr(s, attr, None)
                if isinstance(sub, list):
                    rec(sub)
            for handler in getattr(s, "handlers", []):
                rec(handler.body)
    rec(fn.body)
    return out


# ---------------------------------------------------------------------------
# Whole-program donation context
# ---------------------------------------------------------------------------

class DonationContext:
    """Donor tables + interprocedural summaries over a callgraph
    Program, with the lowering facts applied."""

    def __init__(self, program: callgraph.Program,
                 facts: Optional[dict] = None):
        self.program = program
        self.facts = facts
        # module name -> local name -> DSig (calling that name donates)
        self.donors: Dict[str, Dict[str, DSig]] = {}
        # module name -> local name of a helper INSTANCE (projections
        # .donated/.undonated/.resolve() apply) -> DSig
        self.helpers: Dict[str, Dict[str, DSig]] = {}
        # raw unconditional jit applications, for CSA1504
        self.unguarded: List[Tuple[str, int, str, DSig]] = []
        # def summaries: id(FunctionDef) -> DSig (call donates args)
        self.call_summaries: Dict[int, DSig] = {}
        # def summaries: id(FunctionDef) -> DSig (return value IS a donor)
        self.return_summaries: Dict[int, DSig] = {}
        # method name -> DSig | None(ambiguous); positions exclude self
        self.method_summaries: Dict[str, Optional[DSig]] = {}
        self._discover_donors()
        self._apply_facts()
        self._fix_summaries()

    # -- donor discovery ----------------------------------------------------

    def _discover_donors(self) -> None:
        for node in self.program.modules.values():
            donors: Dict[str, DSig] = {}
            helpers: Dict[str, DSig] = {}
            # decorated defs (module-level and methods)
            for sub in ast.walk(node.info.tree):
                if not isinstance(sub, ast.FunctionDef):
                    continue
                for deco in sub.decorator_list:
                    jc = _jit_call_of(deco)
                    if jc is None or not isinstance(deco, ast.Call):
                        continue
                    argnums, argnames, conditional = _donate_kwargs(jc)
                    if not argnums and not argnames:
                        continue
                    flavor = "cond" if conditional else "always"
                    sig = DSig(pos={i: flavor for i in argnums},
                               names={n: flavor for n in argnames},
                               src=sub.name, line=sub.lineno,
                               fn_name=sub.name, module=node.name)
                    donors[sub.name] = sig
                    if not conditional:
                        self.unguarded.append(
                            (node.info.path, sub.lineno, sub.name, sig))
            # wrapper assignments anywhere in the module
            for sub in ast.walk(node.info.tree):
                if not isinstance(sub, ast.Assign) or \
                        not isinstance(sub.value, ast.Call):
                    continue
                sig = _sig_of_jit_application(sub.value, node.name)
                if sig is None:
                    continue
                callee = _dotted(sub.value.func).split(".")[-1]
                is_helper = callee in _HELPER_NAMES
                targets = [t.id for t in sub.targets
                           if isinstance(t, ast.Name)]
                for tname in targets:
                    sig2 = DSig(pos=dict(sig.pos), names=dict(sig.names),
                                src=tname, line=sub.lineno,
                                fn_name=sig.fn_name, module=node.name)
                    if is_helper:
                        helpers[tname] = sig2
                        donors[tname] = sig2   # calling the instance
                    else:
                        donors[tname] = sig2
                        if all(f == "always" for f in
                               list(sig.pos.values())
                               + list(sig.names.values())):
                            self.unguarded.append(
                                (node.info.path, sub.lineno, tname, sig2))
            # projections of helper instances: name = helper.donated
            for sub in ast.walk(node.info.tree):
                if not isinstance(sub, ast.Assign) or \
                        not isinstance(sub.value, ast.Attribute):
                    continue
                base = _dotted(sub.value.value)
                if base in helpers and sub.value.attr == "donated":
                    for t in sub.targets:
                        if isinstance(t, ast.Name):
                            donors[t.id] = _resig(helpers[base], "always")
            self.donors[node.name] = donors
            self.helpers[node.name] = helpers

        # bare unconditional donate jits used as plain expressions
        # (not assigned, not decorating) still need the CSA1504 sweep
        for node in self.program.modules.values():
            covered = set()
            for s in ast.walk(node.info.tree):
                if isinstance(s, ast.Assign):
                    covered.add(id(s.value))
                    if isinstance(s.value, ast.Call):
                        # partial(jax.jit, ...)(f): the inner carrier
                        # was already attributed to the assignment
                        covered.add(id(s.value.func))
                elif isinstance(s, ast.FunctionDef):
                    for deco in s.decorator_list:
                        covered.add(id(deco))
            for sub in ast.walk(node.info.tree):
                if not isinstance(sub, ast.Call) or id(sub) in covered:
                    continue
                callee = _dotted(sub.func).split(".")[-1]
                if callee in _HELPER_NAMES:
                    continue
                sig = _sig_of_jit_application(sub, node.name)
                if sig is None:
                    continue
                if all(f == "always" for f in
                       list(sig.pos.values()) + list(sig.names.values())):
                    self.unguarded.append(
                        (node.info.path, sub.lineno,
                         sig.fn_name or "jit", sig))

    def _apply_facts(self) -> None:
        """Mark donors whose donation the REAL lowering dropped as
        inert: declared but dead (notice-only, never a finding)."""
        if not self.facts:
            return
        by_name = {k[1]: v for k, v in self.facts.items()}
        for donors in self.donors.values():
            for sig in donors.values():
                fact = self.facts.get((sig.module, sig.fn_name)) \
                    or by_name.get(sig.fn_name)
                if fact is not None and fact.get("survived") == 0:
                    sig.inert = True

    # -- value-level donor resolution ---------------------------------------

    def _module_donor(self, node: callgraph.ModuleNode,
                      name: str) -> Optional[DSig]:
        """DSig for a bare name in `node`: a local donor, a
        from-imported donor, or a def with a call summary."""
        sig = self.donors.get(node.name, {}).get(name)
        if sig is not None:
            return sig
        fi = node.from_imports.get(name)
        if fi is not None:
            src, remote = fi
            sig = self.donors.get(src, {}).get(remote)
            if sig is not None:
                return sig
            src_mod = self.program.modules.get(src)
            if src_mod is not None and remote in src_mod.defs:
                return self.call_summaries.get(
                    id(src_mod.defs[remote]))
        if name in node.defs:
            return self.call_summaries.get(id(node.defs[name]))
        return None

    def _helper_of(self, node: callgraph.ModuleNode,
                   name: str) -> Optional[DSig]:
        sig = self.helpers.get(node.name, {}).get(name)
        if sig is not None:
            return sig
        fi = node.from_imports.get(name)
        if fi is not None:
            return self.helpers.get(fi[0], {}).get(fi[1])
        return None

    def callable_sig(self, node: callgraph.ModuleNode, expr: ast.AST,
                     env: Optional[Dict[str, DSig]] = None
                     ) -> Optional[DSig]:
        """The donation signature of a VALUE used as a callable:
        donor names (local/imported), helper projections, jit
        applications, factory-call returns, defs with call summaries,
        uniquely-named methods."""
        env = env or {}
        if isinstance(expr, ast.Name):
            if expr.id in env:
                return env[expr.id]
            return self._module_donor(node, expr.id)
        if isinstance(expr, ast.Attribute):
            base = _dotted(expr.value)
            # helper projection: pd.donated / pd.undonated
            helper = env.get(base) if base in env else \
                self._helper_of(node, base)
            if helper is not None:
                if expr.attr == "donated":
                    return _resig(helper, "always")
                if expr.attr == "undonated":
                    return None
            target = callgraph.resolve_module(node, base, self.program) \
                if base else None
            if target is not None:
                sig = self.donors.get(target.name, {}).get(expr.attr)
                if sig is not None:
                    return sig
                if expr.attr in target.defs:
                    return self.call_summaries.get(
                        id(target.defs[expr.attr]))
                return None
            # method by unique name (self.m / obj.m)
            return self.method_summaries.get(expr.attr) or None
        if isinstance(expr, ast.Call):
            # jit application used inline
            sig = _sig_of_jit_application(expr, node.name)
            if sig is not None:
                return sig
            # pd.resolve() — the backend-selected twin (conditional)
            if isinstance(expr.func, ast.Attribute) and \
                    expr.func.attr == "resolve":
                base = _dotted(expr.func.value)
                helper = env.get(base) if base in env else \
                    self._helper_of(node, base)
                if helper is not None:
                    return helper
            # factory call: f() returns a donor
            return self.returned_sig(node, expr, env)
        return None

    def returned_sig(self, node: callgraph.ModuleNode, call: ast.Call,
                     env: Optional[Dict[str, DSig]] = None
                     ) -> Optional[DSig]:
        """DSig of a CALL's return value, when the callee is a factory
        whose return summary says it hands back a donor
        (`_epoch_transition_jit()`, `_ring_scatter_jit()`)."""
        resolved = callgraph.resolve_call(node, call, self.program)
        if resolved is None or resolved[1] is None:
            return None
        return self.return_summaries.get(id(resolved[1]))

    def call_donations(self, node: callgraph.ModuleNode, call: ast.Call,
                       env: Optional[Dict[str, DSig]] = None
                       ) -> Tuple[Optional[DSig], Dict[int, str],
                                  Dict[str, str], bool]:
        """(sig, donated arg positions -> flavor, donated kwarg names
        -> flavor, via_dispatch_wrapper) for one call site. Positions
        index `call.args` (wrapper shift applied)."""
        env = env or {}
        func = call.func
        last = _dotted(func).split(".")[-1]
        if last in _WRAPPER_NAMES and len(call.args) >= 2:
            inner = self.callable_sig(node, call.args[1], env)
            if inner is None or not inner.live():
                return inner, {}, {}, True
            pos = {p + _WRAPPER_SHIFT: f for p, f in inner.pos.items()}
            return inner, pos, dict(inner.names), True
        sig = self.callable_sig(node, func, env)
        if sig is None or not sig.live():
            return sig, {}, {}, False
        return sig, dict(sig.pos), dict(sig.names), False

    # -- interprocedural summaries ------------------------------------------

    def _scan_def(self, node: callgraph.ModuleNode, fn: ast.FunctionDef,
                  is_method: bool) -> Tuple[Optional[DSig],
                                            Optional[DSig]]:
        """(call summary, return summary) for one def: a SOURCE-ORDER
        statement walk maintaining a local donor env — enough to see
        through `pd = platform_donated_jit(...); fn = pd.resolve();
        guarded_dispatch(key, fn, cols, ...)`."""
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        if is_method and params and params[0] == "self":
            params = params[1:]
        env: Dict[str, DSig] = {}
        call_sig: Optional[DSig] = None
        ret_sig: Optional[DSig] = None
        for stmt in _ordered_stmts(fn):
            for call in ast.walk(stmt):
                if not isinstance(call, ast.Call):
                    continue
                sig, pos, names, _ = \
                    self.call_donations(node, call, env)
                if not pos and not names:
                    continue
                for p, flavor in pos.items():
                    if p < len(call.args) and \
                            isinstance(call.args[p], ast.Name):
                        pname = call.args[p].id
                        if pname in params:
                            if call_sig is None:
                                call_sig = DSig(src=sig.src,
                                                line=sig.line,
                                                fn_name=sig.fn_name,
                                                module=node.name)
                            call_sig.pos[params.index(pname)] = flavor
                for kwname, flavor in names.items():
                    for kw in call.keywords:
                        if kw.arg == kwname and \
                                isinstance(kw.value, ast.Name) and \
                                kw.value.id in params:
                            if call_sig is None:
                                call_sig = DSig(src=sig.src,
                                                line=sig.line,
                                                fn_name=sig.fn_name,
                                                module=node.name)
                            call_sig.pos[
                                params.index(kw.value.id)] = flavor
            if isinstance(stmt, ast.Assign):
                value_sig = self.callable_sig(node, stmt.value, env) \
                    if isinstance(stmt.value,
                                  (ast.Call, ast.Attribute, ast.Name)) \
                    else None
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        if value_sig is not None and value_sig.live():
                            env[t.id] = value_sig
                        else:
                            env.pop(t.id, None)
            elif isinstance(stmt, ast.Return) and stmt.value is not None:
                rs = None
                if isinstance(stmt.value,
                              (ast.Name, ast.Attribute, ast.Call)):
                    rs = self.callable_sig(node, stmt.value, env)
                if rs is not None and rs.live():
                    ret_sig = rs
        return call_sig, ret_sig

    def _fix_summaries(self) -> None:
        # (node, fn, is_method) worklist covering module-level defs and
        # class methods of every target module
        items: List[Tuple[callgraph.ModuleNode, ast.FunctionDef, bool]] = []
        for node in self.program.modules.values():
            for fn in node.defs.values():
                items.append((node, fn, False))
            for stmt in node.info.tree.body:
                if isinstance(stmt, ast.ClassDef):
                    for sub in stmt.body:
                        if isinstance(sub, ast.FunctionDef):
                            items.append((node, sub, True))
        for _ in range(4):       # summaries stabilize in a few rounds
            changed = False
            method_sigs: Dict[str, List[Optional[DSig]]] = {}
            for node, fn, is_method in items:
                call_sig, ret_sig = self._scan_def(node, fn, is_method)
                if call_sig is not None:
                    prev = self.call_summaries.get(id(fn))
                    if prev is None or prev.pos != call_sig.pos:
                        self.call_summaries[id(fn)] = call_sig
                        changed = True
                if ret_sig is not None and \
                        self.return_summaries.get(id(fn)) is not ret_sig:
                    if id(fn) not in self.return_summaries:
                        changed = True
                    self.return_summaries[id(fn)] = ret_sig
                if is_method:
                    method_sigs.setdefault(fn.name, []).append(
                        self.call_summaries.get(id(fn)))
            # a method summary applies only when every same-named
            # method agrees (otherwise attribute dispatch is ambiguous)
            self.method_summaries = {}
            for name, sigs in method_sigs.items():
                live = [s for s in sigs if s is not None]
                if len(live) == len(sigs) and live and \
                        all(s.pos == live[0].pos for s in live):
                    self.method_summaries[name] = live[0]
            if not changed:
                break


# ---------------------------------------------------------------------------
# Lowering cross-check
# ---------------------------------------------------------------------------

def lowering_facts() -> Tuple[Optional[dict], List[str]]:
    """Lower every trace contract that pins donate_min and count the
    `tf.aliasing_output` annotations that actually survived; keyed by
    (traced fn's module, fn name). Returns (facts | None, notices) —
    None when jax is unavailable (the prover then trusts declarations,
    which is the conservative direction)."""
    notices: List[str] = []
    try:
        from ..trace.engine import ensure_cpu_devices
        ensure_cpu_devices(8)
        import jax
    except ImportError:
        return None, ["lifetime: jax unavailable — lowering cross-check "
                      "skipped, declared donations trusted"]
    from ..trace import engine as tengine
    from ..trace import tracer
    facts: dict = {}
    for contract in tengine.discover():
        if not contract.get("donate_min"):
            continue
        try:
            spec = contract["build"]()
            fn = spec["fn"]
            text = jax.jit(fn, **dict(spec.get("jit_kwargs", {}))) \
                .lower(*spec["args"]).as_text()
        except Exception as exc:
            notices.append(f"lifetime: contract {contract['name']} failed "
                           f"to lower ({type(exc).__name__}: {exc}); "
                           f"its donor stays effective")
            continue
        survived = tracer.donated_count(text)
        facts[(fn.__module__, fn.__name__)] = {
            "contract": contract["name"],
            "declared": int(contract["donate_min"]),
            "survived": survived,
        }
        if survived == 0:
            notices.append(
                f"lifetime: {contract['name']} declares donation but "
                f"lowering dropped every tf.aliasing_output — donor "
                f"treated as inert")
    return facts, notices


# ---------------------------------------------------------------------------
# Abstract interpreter
# ---------------------------------------------------------------------------

def _segments(path: str) -> List[str]:
    """"self.levels[0]" -> ["self", ".levels", "[0]"]."""
    segs: List[str] = []
    cur = ""
    for ch in path:
        if ch in ".[":
            if cur:
                segs.append(cur)
            cur = ch
        elif ch == "]":
            segs.append(cur + "]")
            cur = ""
        else:
            cur += ch
    if cur:
        segs.append(cur)
    return segs


def _seg_match(a: str, b: str) -> bool:
    if a == b:
        return True
    wild = a.endswith("[*]") or b.endswith("[*]")
    return wild and a.startswith("[") and b.startswith("[")


def _covers(donated: str, read: str) -> bool:
    """True when `donated` being dead makes reading `read` unsafe:
    equal paths, or `donated` is a (wildcard-compatible) prefix of
    `read` (donating `cols` kills `cols.balance`; donating
    `levels[*]` kills `levels[0]`)."""
    d, r = _segments(donated), _segments(read)
    if len(d) > len(r):
        return False
    return all(_seg_match(x, y) for x, y in zip(d, r))


@dataclass
class Donation:
    flavor: str          # "always" | "cond"
    src: str             # donating program display name
    line: int            # donation site line
    via_dispatch: bool   # launched through an async dispatch wrapper
    token: int           # unique id, ties aliases of one donation


class AbsState:
    def __init__(self):
        self.donated: Dict[str, Donation] = {}
        self.edges: Dict[str, Set[str]] = {}
        # attribute-rooted donations awaiting a rebind (escape check):
        # token -> (path, Donation)
        self.pending: Dict[int, Tuple[str, Donation]] = {}
        # roots whose attribute paths outlive the frame (self + params);
        # set once by FunctionProver.run, shared by copies
        self.escape_roots: Set[str] = {"self"}

    def copy(self) -> "AbsState":
        s = AbsState()
        s.donated = dict(self.donated)
        s.edges = {k: set(v) for k, v in self.edges.items()}
        s.pending = dict(self.pending)
        s.escape_roots = self.escape_roots
        return s

    def replace(self, other: "AbsState") -> None:
        """Adopt `other`'s facts wholesale (a branch superseded us)."""
        self.donated = dict(other.donated)
        self.edges = {k: set(v) for k, v in other.edges.items()}
        self.pending = dict(other.pending)

    def drop_conditional(self) -> None:
        """A terminating platform-guarded branch absolved this path:
        platform-conditional (MAYBE-DONATED) buffers are alive here —
        the donating world raised/returned out."""
        for p in [p for p, d in self.donated.items()
                  if d.flavor == "cond"]:
            del self.donated[p]
        for tok in [t for t, (_, d) in self.pending.items()
                    if d.flavor == "cond"]:
            del self.pending[tok]

    def join(self, other: "AbsState") -> None:
        self.donated.update(
            {k: v for k, v in other.donated.items()
             if k not in self.donated})
        for k, v in other.edges.items():
            self.edges.setdefault(k, set()).update(v)
        self.pending.update(other.pending)

    def alias(self, a: str, b: str) -> None:
        if a == b:
            return
        self.edges.setdefault(a, set()).add(b)
        self.edges.setdefault(b, set()).add(a)

    def closure(self, path: str) -> Set[str]:
        out = {path}
        work = [path]
        while work:
            p = work.pop()
            for q in self.edges.get(p, ()):
                if q not in out:
                    out.add(q)
                    work.append(q)
        return out

    def dead(self, path: str) -> Optional[Donation]:
        for p in self.closure(path):
            for d, don in self.donated.items():
                if _covers(d, p):
                    return don
        return None

    def donate(self, path: str, don: Donation) -> None:
        closure = self.closure(path)
        for p in closure:
            self.donated[p] = don
        # attribute paths rooted at self/a parameter outlive the frame
        # (the stale handle is caller-visible): track them until a
        # rebind (or a return handoff) exonerates. Subscripts of LOCAL
        # names (`single[0]`) die with the frame — donating one as its
        # final use is the normal contract, not an escape.
        for p in sorted(closure):
            segs = _segments(p)
            if len(segs) > 1 and "." in p and \
                    segs[0] in self.escape_roots:
                self.pending[don.token] = (p, don)
                break

    def rebind(self, path: str) -> None:
        """Assignment to `path` kills its donated/alias facts (and any
        extension facts: rebinding `cols` clears `cols.balance`)."""
        for d in [d for d in self.donated if _covers(path, d)]:
            del self.donated[d]
        for tok in [t for t, (p, _) in self.pending.items()
                    if _covers(path, p)]:
            del self.pending[tok]
        for p in [p for p in self.edges if _covers(path, p)]:
            for q in self.edges.pop(p):
                self.edges.get(q, set()).discard(p)


def _path_of(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _path_of(node.value)
        return f"{base}.{node.attr}" if base else None
    if isinstance(node, ast.Subscript):
        base = _path_of(node.value)
        if base is None:
            return None
        idx = node.slice
        if isinstance(idx, ast.Constant) and \
                isinstance(idx.value, (int, str)):
            return f"{base}[{idx.value}]"
        return f"{base}[*]"
    return None


_COPY_ATTRS = {"copy"}
_COPY_CALLS = {"jnp.copy", "np.copy", "numpy.copy"}
_MATERIALIZE = {"block_until_ready"}

# aval metadata survives donation (jax keeps the abstract value on the
# deleted array) — reading it is always legal
_METADATA = {".shape", ".dtype", ".ndim", ".size", ".nbytes",
             ".sharding", ".aval", ".weak_type", ".itemsize"}

# attributes whose presence in a branch test marks it as a PLATFORM
# guard (the donate-on-accel / alive-on-CPU split the house idiom
# builds on): jax.default_backend(), pd.donate_now(), device.platform
_PLATFORM_ATTRS = {"default_backend", "donate_now", "platform"}


def _is_platform_test(test: ast.AST) -> bool:
    for sub in ast.walk(test):
        if isinstance(sub, ast.Attribute) and sub.attr in _PLATFORM_ATTRS:
            return True
    return False


def _is_copy_expr(node: ast.AST) -> Optional[ast.AST]:
    """The copied source expression when `node` is a defensive copy:
    x.copy(), jnp.copy(x), jnp.array(x, copy=True), np.array(x,
    copy=True)."""
    if not isinstance(node, ast.Call):
        return None
    dotted = _dotted(node.func)
    if isinstance(node.func, ast.Attribute) and \
            node.func.attr in _COPY_ATTRS and not node.args:
        return node.func.value
    if dotted in _COPY_CALLS and node.args:
        return node.args[0]
    if dotted.split(".")[-1] in ("array", "asarray") and node.args:
        for kw in node.keywords:
            if kw.arg == "copy" and \
                    isinstance(kw.value, ast.Constant) and \
                    kw.value.value is True:
                return node.args[0]
    return None


class FunctionProver:
    """Path-based abstract interpretation of one function body."""

    def __init__(self, ctx: DonationContext, node: callgraph.ModuleNode,
                 fn: ast.FunctionDef, qualname: str, emit):
        self.ctx = ctx
        self.node = node
        self.fn = fn
        self.qualname = qualname
        self.emit = emit            # (rule, line, message) -> None
        self.env: Dict[str, DSig] = {}   # local donor-valued names
        self._token = iter(range(1, 1 << 30))

    def run(self) -> None:
        state = AbsState()
        args = self.fn.args
        state.escape_roots = {"self"} | {
            a.arg for a in args.posonlyargs + args.args + args.kwonlyargs}
        self._block(self.fn.body, state)
        for path, don in state.pending.values():
            self.emit("CSA1502", don.line,
                      f"donated `{path}` (to `{don.src}`) is never "
                      f"rebound in `{self.qualname}` — the stale "
                      f"handle escapes through the attribute")

    # -- statements ---------------------------------------------------------

    def _block(self, stmts: Iterable[ast.stmt],
               state: AbsState) -> bool:
        """Interpret a statement list; True when the block TERMINATES
        (return/raise/break/continue) — its state never falls through,
        so loop second passes and branch joins must not absorb it."""
        for stmt in stmts:
            if self._stmt(stmt, state):
                return True
        return False

    def _stmt(self, stmt: ast.stmt, state: AbsState) -> bool:
        if isinstance(stmt, ast.Assign):
            self._assign(stmt.targets, stmt.value, state)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self._assign([stmt.target], stmt.value, state)
        elif isinstance(stmt, ast.AugAssign):
            self._expr(stmt.value, state)
            path = _path_of(stmt.target)
            if path is not None:
                self._check_read(path, stmt.target.lineno, state)
                state.rebind(path)
        elif isinstance(stmt, ast.Expr):
            self._expr(stmt.value, state)
        elif isinstance(stmt, ast.Return):
            if stmt.value is not None:
                self._expr(stmt.value, state, returning=True)
            return True
        elif isinstance(stmt, (ast.Break, ast.Continue)):
            return True
        elif isinstance(stmt, ast.If):
            self._expr(stmt.test, state)
            s_else = state.copy()
            t_body = self._block(stmt.body, state)
            t_else = self._block(stmt.orelse, s_else)
            if t_body and t_else:
                return True
            guard = _is_platform_test(stmt.test)
            if t_body:
                # only the else path survives; if the terminated branch
                # was a platform guard (`if backend != "cpu": raise`),
                # the survivors are the world where conditional
                # donations never happened — the PR 3 recovery idiom
                state.replace(s_else)
                if guard:
                    state.drop_conditional()
            elif t_else:
                if guard:
                    state.drop_conditional()
            else:
                state.join(s_else)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._expr(stmt.iter, state)
            tpath = _path_of(stmt.target)
            before = state.copy()
            if tpath is not None:
                state.rebind(tpath)
            t1 = self._block(stmt.body, state)
            state.join(before)
            # second pass over the joined state surfaces
            # cross-iteration hazards (findings dedup upstream);
            # a terminated first pass never reaches iteration two
            if not t1:
                if tpath is not None:
                    state.rebind(tpath)
                self._block(stmt.body, state)
            self._block(stmt.orelse, state)
            state.join(before)
        elif isinstance(stmt, ast.While):
            self._expr(stmt.test, state)
            before = state.copy()
            t1 = self._block(stmt.body, state)
            state.join(before)
            if not t1:
                self._expr(stmt.test, state)
                self._block(stmt.body, state)
            self._block(stmt.orelse, state)
            state.join(before)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._expr(item.context_expr, state)
                if item.optional_vars is not None:
                    p = _path_of(item.optional_vars)
                    if p is not None:
                        state.rebind(p)
            return self._block(stmt.body, state)
        elif isinstance(stmt, ast.Try):
            t_body = self._block(stmt.body, state)
            # handlers see the post-body state: an exception raised
            # DURING a donating dispatch consumed the buffers just as
            # surely as success did (resident.py's recovery comment)
            h_terms = [self._block(h.body, state)
                       for h in stmt.handlers]
            if not t_body:
                t_body = self._block(stmt.orelse, state)
            if self._block(stmt.finalbody, state):
                return True
            return t_body and bool(h_terms) and all(h_terms) or \
                (t_body and not stmt.handlers)
        elif isinstance(stmt, ast.Raise):
            for child in ast.iter_child_nodes(stmt):
                if isinstance(child, ast.expr):
                    self._expr(child, state)
            return True
        elif isinstance(stmt, ast.Assert):
            for child in ast.iter_child_nodes(stmt):
                if isinstance(child, ast.expr):
                    self._expr(child, state)
        elif isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                p = _path_of(t)
                if p is not None:
                    state.rebind(p)
        # nested defs / classes / imports: out of scope (documented)
        return False

    def _assign(self, targets: List[ast.AST], value: ast.AST,
                state: AbsState) -> None:
        # donor-valued locals: fn = _epoch_transition_jit() / pd.resolve()
        vsig = None
        if isinstance(value, (ast.Call, ast.Attribute, ast.Name)):
            vsig = self.ctx.callable_sig(self.node, value, self.env)
        self._expr(value, state)
        vpath = _path_of(value)
        for t in targets:
            if isinstance(t, (ast.Tuple, ast.List)):
                for i, elt in enumerate(t.elts):
                    p = _path_of(elt)
                    if p is None:
                        continue
                    state.rebind(p)
                    if vpath is not None:
                        state.alias(p, f"{vpath}[{i}]")
                continue
            p = _path_of(t)
            if p is None:
                continue
            state.rebind(p)
            if isinstance(t, ast.Name):
                if vsig is not None and vsig.live():
                    self.env[t.id] = vsig
                else:
                    self.env.pop(t.id, None)
            if vpath is not None:
                state.alias(p, vpath)

    # -- expressions --------------------------------------------------------

    def _check_read(self, path: str, line: int, state: AbsState,
                    returning: bool = False,
                    dispatching: bool = False) -> None:
        if any(seg in _METADATA for seg in _segments(path)):
            return   # .shape/.dtype/... stay readable on a dead array
        don = state.dead(path)
        if don is None:
            return
        flavor = "dead on every backend" if don.flavor == "always" else \
            "dead on accelerator backends (platform-conditional donation)"
        if returning:
            self.emit("CSA1502", line,
                      f"`{path}` escapes `{self.qualname}` after being "
                      f"donated to `{don.src}` (line {don.line}) — "
                      f"the caller receives a {flavor} handle")
        elif dispatching and don.via_dispatch:
            self.emit("CSA1503", line,
                      f"`{path}` is already in flight (donated to "
                      f"`{don.src}` at line {don.line}) and reaches a "
                      f"second dispatch with no materialization point "
                      f"between")
        else:
            self.emit("CSA1501", line,
                      f"`{path}` used after donation to `{don.src}` "
                      f"(line {don.line}) — the buffer is {flavor}")

    def _expr(self, node: ast.AST, state: AbsState,
              returning: bool = False) -> None:
        if isinstance(node, ast.Call):
            self._call(node, state, returning)
            return
        path = _path_of(node)
        if path is not None:
            self._check_read(path, node.lineno, state,
                             returning=returning)
            return
        if isinstance(node, (ast.Tuple, ast.List)):
            for elt in node.elts:
                self._expr(elt, state, returning=returning)
            return
        if isinstance(node, ast.IfExp):
            self._expr(node.test, state)
            self._expr(node.body, state, returning=returning)
            self._expr(node.orelse, state, returning=returning)
            return
        if isinstance(node, ast.Lambda):
            # a separate scope whose body runs at CALL time (usually
            # under trace) — its params must not shadow-donate ours
            return
        if isinstance(node, (ast.ListComp, ast.SetComp,
                             ast.GeneratorExp, ast.DictComp)):
            for gen in node.generators:
                self._expr(gen.iter, state)
            tmp = state.copy()   # comp targets live in their own scope
            for gen in node.generators:
                p = _path_of(gen.target)
                if p is not None:
                    tmp.rebind(p)
                for cond in gen.ifs:
                    self._expr(cond, tmp)
            parts = (node.key, node.value) \
                if isinstance(node, ast.DictComp) else (node.elt,)
            for part in parts:
                self._expr(part, tmp)
            return
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self._expr(child, state)

    def _call(self, call: ast.Call, state: AbsState,
              returning: bool = False) -> None:
        sig, pos, names, via_wrapper = \
            self.ctx.call_donations(self.node, call, self.env)
        dotted = _dotted(call.func)
        attr = dotted.split(".")[-1]
        # the callee expression itself may read state (self.f(...)):
        # attribute bases are reads only when themselves donated
        fpath = _path_of(call.func.value) \
            if isinstance(call.func, ast.Attribute) else None
        if fpath is not None:
            self._check_read(fpath, call.lineno, state)
        donated_args: List[Tuple[str, str]] = []
        for i, arg in enumerate(call.args):
            apath = _path_of(arg)
            flavor = pos.get(i)
            if flavor is not None and sig is not None:
                if apath is not None:
                    self._check_read(apath, arg.lineno, state,
                                     dispatching=True)
                    donated_args.append((apath, flavor))
                else:
                    self._expr(arg, state)
            elif apath is not None:
                self._check_read(apath, arg.lineno, state,
                                 dispatching=via_wrapper)
                self._copy_check(arg, sig, state)
            else:
                self._expr(arg, state)
                self._copy_check(arg, sig, state)
        for kw in call.keywords:
            kpath = _path_of(kw.value)
            flavor = names.get(kw.arg) if kw.arg else None
            if flavor is not None and sig is not None and \
                    kpath is not None:
                self._check_read(kpath, kw.value.lineno, state,
                                 dispatching=True)
                donated_args.append((kpath, flavor))
            elif kpath is not None:
                self._check_read(kpath, kw.value.lineno, state)
            else:
                self._expr(kw.value, state)
        # materialization fences clear the in-flight marker
        if attr in _MATERIALIZE:
            for don in state.donated.values():
                don.via_dispatch = False
        # apply the donations AFTER every argument was read live
        for apath, flavor in donated_args:
            don = Donation(flavor=flavor, src=sig.src or attr,
                           line=call.lineno, via_dispatch=via_wrapper,
                           token=next(self._token))
            if returning and ("." in apath or "[" in apath):
                # `return dispatch(..., self.cols, ...)`: ownership is
                # handed to the caller (who rebinds) — the documented
                # chaining convention, not an escape
                state.donate(apath, don)
                state.pending.pop(don.token, None)
            else:
                state.donate(apath, don)

    def _copy_check(self, arg: ast.AST, sig: Optional[DSig],
                    state: AbsState) -> None:
        """CSA1505: a defensive copy feeding a NON-donated position of
        a resolved program whose donation signature we know."""
        src = _is_copy_expr(arg)
        if src is None or sig is None:
            return
        spath = _path_of(src)
        if spath is None:
            return
        self.emit("CSA1505", arg.lineno,
                  f"defensive copy of `{spath}` feeds `{sig.src}`, "
                  f"which never consumes this argument — the copy is "
                  f"pure overhead")


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

@dataclass
class LifetimeReport:
    findings: List[Finding]
    suppressed: List[Finding]
    baselined: List[Finding]
    stale_baseline: List[str]
    notices: List[str]
    files_checked: int = 0
    donors: int = 0
    facts: Optional[dict] = None


def _rel(path: str) -> str:
    try:
        return str(Path(path).resolve().relative_to(REPO_ROOT))
    except ValueError:
        return path


def run_lifetime(targets: Optional[Iterable[str]] = None,
                 baseline: Optional[Dict[str, str]] = None,
                 baseline_path=None, lower: bool = True
                 ) -> LifetimeReport:
    if targets is None:
        targets = [str(REPO_ROOT / t) for t in DEFAULT_TARGETS
                   if (REPO_ROOT / t).exists()]
    if baseline is None:
        baseline = load_baseline(
            str(baseline_path or DEFAULT_BASELINE))
    rooted = []
    for root, path in iter_py_files_rooted([str(t) for t in targets]):
        mod = load_module(path)
        if mod is not None:
            rooted.append((root, mod))
    program = callgraph.build(rooted, {})

    notices: List[str] = []
    facts: Optional[dict] = None
    if lower:
        facts, fact_notices = lowering_facts()
        notices.extend(fact_notices)
    else:
        notices.append("lifetime: lowering cross-check disabled "
                       "(--no-lower) — declared donations trusted")
    ctx = DonationContext(program, facts)

    raw: List[Finding] = []
    seen_keys: Set[Tuple[str, str, int, str]] = set()

    for node in program.modules.values():
        def emit_for(qualname: str):
            def emit(rule: str, line: int, message: str) -> None:
                key = (node.info.path, rule, line, message)
                if key in seen_keys:
                    return
                seen_keys.add(key)
                raw.append(Finding(rule, _rel(node.info.path), line,
                                   message, context=qualname))
            return emit

        fns: List[Tuple[ast.FunctionDef, str]] = []
        for fn in node.defs.values():
            fns.append((fn, node.info.qualname(fn)))
        for stmt in node.info.tree.body:
            if isinstance(stmt, ast.ClassDef):
                for sub in stmt.body:
                    if isinstance(sub, ast.FunctionDef):
                        fns.append((sub, node.info.qualname(sub)))
        for fn, qualname in fns:
            FunctionProver(ctx, node, fn, qualname,
                           emit_for(qualname)).run()

    # CSA1504: unconditional donate jits outside the blessed helper
    by_path = {mod.path: mod for _, mod in rooted}
    for path, line, name, sig in ctx.unguarded:
        nums = sorted(sig.pos)
        argnames = sorted(sig.names)
        detail = f"donate_argnums={tuple(nums)}" if nums else \
            f"donate_argnames={tuple(argnames)}"
        raw.append(Finding("CSA1504", _rel(path), line,
                           f"`{name}` donates ({detail}) with no "
                           f"platform guard — XLA:CPU needs the "
                           f"undonated twin "
                           f"(utils.donation.platform_donated_jit)",
                           context=name))

    # donation declared but dead after lowering — visibility only
    if facts:
        for (mod_name, fn_name), fact in sorted(facts.items()):
            if fact["survived"] == 0:
                notices.append(
                    f"lifetime: {mod_name}.{fn_name} — donation "
                    f"declared but dropped by lowering (contract "
                    f"{fact['contract']})")

    # classify through inline suppressions and the baseline ratchet
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    baselined: List[Finding] = []
    matched: Set[str] = set()
    for f in sorted(raw, key=lambda f: (f.path, f.line, f.rule)):
        mod = by_path.get(str(REPO_ROOT / f.path)) or by_path.get(f.path)
        if mod is not None and mod.suppressed(f):
            suppressed.append(f)
        elif f.fingerprint() in baseline:
            matched.add(f.fingerprint())
            baselined.append(f)
        else:
            findings.append(f)
    stale = sorted(set(baseline) - matched)
    donors = sum(len(d) for d in ctx.donors.values())
    return LifetimeReport(findings=findings, suppressed=suppressed,
                          baselined=baselined, stale_baseline=stale,
                          notices=notices, files_checked=len(rooted),
                          donors=donors, facts=facts)


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------

def render_human(report: LifetimeReport) -> str:
    out = []
    for f in report.findings:
        out.append(f"{f.path}:{f.line}: [{f.rule}] "
                   f"{RULES[f.rule].severity}: {f.message}")
        if RULES[f.rule].hint:
            out.append(f"    hint: {RULES[f.rule].hint}")
    for fp in report.stale_baseline:
        out.append(f"lifetime-baseline: stale entry (fixed? delete it): "
                   f"{fp}")
    for note in report.notices:
        out.append(f"notice: {note}")
    out.append(f"lifetime: {report.files_checked} files, "
               f"{report.donors} donor(s), "
               f"{len(report.findings)} finding(s), "
               f"{len(report.suppressed)} suppressed, "
               f"{len(report.baselined)} baselined")
    return "\n".join(out)


def render_json(report: LifetimeReport) -> str:
    def row(f: Finding):
        return {"rule": f.rule, "path": f.path, "line": f.line,
                "message": f.message, "context": f.context,
                "severity": RULES[f.rule].severity,
                "fingerprint": f.fingerprint()}
    facts = None
    if report.facts is not None:
        facts = [{"module": k[0], "fn": k[1], **v}
                 for k, v in sorted(report.facts.items())]
    return json.dumps({
        "findings": [row(f) for f in report.findings],
        "suppressed": [row(f) for f in report.suppressed],
        "baselined": [row(f) for f in report.baselined],
        "stale_baseline": report.stale_baseline,
        "notices": report.notices,
        "files_checked": report.files_checked,
        "donors": report.donors,
        "lowering_facts": facts,
    }, indent=2)
