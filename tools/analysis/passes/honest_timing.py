"""CSA10xx — honest timing around async dispatch.

CSA1001: a `time.perf_counter()` delta measured around a call to a
known-jitted callable with no device fence between the dispatch and the
second clock read. JAX dispatch is asynchronous: the call returns as soon
as the program is enqueued, so the delta records launch overhead (often
well under 1% of the real cost) while looking exactly like a wall-clock
measurement. Every committed bench number in this repo fences by
materializing output bytes (`np.asarray(out.ravel()[0:1])` — the repo's
`_sync` idiom; `jax.block_until_ready` alone is accepted as a fence too),
or routes through `telemetry.span(...).fence(out)`, which fences at span
exit.

Detection (per statement block, nested bodies of the timed region
included):

    t0 = time.perf_counter()          # opens a timed region for `t0`
    y = f_jit(x)                      # jitted dispatch (plain name, or an
                                      #   attribute call `m.f_jit(x)` of a
                                      #   module whose jit map names it)
    dt = time.perf_counter() - t0     # closes the region -> FINDING if no
                                      #   fence call appeared in between

A region also closes at the next `t1 = time.perf_counter()` assignment
(the t0/t1/t2 chained-bucket style): the elapsed segment is checked, then
a new region opens. Fences recognized anywhere in the region:
`block_until_ready`, `device_get`, `np.asarray`/`np.array`/`onp.asarray`,
`.tolist()`, `.item()`, and calls to a local `_sync`/`sync` helper.

Dispatch resolution is a program pass over the call-graph IR: plain-name
calls resolve through the module's own jit map (imported jitted names
included — callgraph's fixpoint already folds `from m import f_jit` in),
and attribute calls `mod.f_jit(...)` resolve the base through the
program's import graph to the defining module's jitted names — the
dispatch form the resident loop actually uses, which PR 1's
per-module pass documented as out of scope. Cross-block `t0` captures
remain out of scope (the goal is catching the pattern the repo itself
used to hand-roll, at zero false positives on the shipped tree).
"""
from __future__ import annotations

import ast

from ..core import Finding, register_program_pass, register_rule
from .. import callgraph, jitmap

register_rule(
    "CSA1001",
    "perf_counter delta spans a jitted dispatch with no device fence",
    "warning",
    "materialize output bytes (np.asarray(out.ravel()[0:1]) — the _sync "
    "idiom) or jax.block_until_ready(out) before the closing "
    "perf_counter() read, or wrap the region in telemetry.span(...) and "
    "register the output with .fence(out)",
)

# call-name suffixes that complete device work before returning
_FENCE_SUFFIXES = ("block_until_ready", "device_get", "asarray", "array",
                   "tolist", "item")
# local helper names treated as fences (the repo's honest-fence wrappers)
_FENCE_NAMES = {"_sync", "sync"}


def _is_perf_counter_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and jitmap._dotted(node.func).split(".")[-1] == "perf_counter")


def _perf_assign_target(stmt: ast.stmt):
    """`t0 = time.perf_counter()` -> "t0" (single Name target only)."""
    if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
            and isinstance(stmt.targets[0], ast.Name) \
            and _is_perf_counter_call(stmt.value):
        return stmt.targets[0].id
    return None


def _closing_vars(stmt: ast.stmt, open_vars) -> set:
    """Timer vars whose delta this statement reads: a BinOp subtraction
    pairing a perf_counter() call with an open timer Name (either side)."""
    closed = set()
    for node in ast.walk(stmt):
        if not isinstance(node, ast.BinOp) or \
                not isinstance(node.op, ast.Sub):
            continue
        sides = (node.left, node.right)
        for a, b in (sides, sides[::-1]):
            if _is_perf_counter_call(a) and isinstance(b, ast.Name) \
                    and b.id in open_vars:
                closed.add(b.id)
    return closed


def _region_calls(stmts):
    """Every Call node in a statement span, nested bodies included."""
    for stmt in stmts:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                yield node


def _has_fence(calls) -> bool:
    for call in calls:
        dotted = jitmap._dotted(call.func)
        last = dotted.split(".")[-1]
        if last in _FENCE_NAMES or last in _FENCE_SUFFIXES:
            return True
    return False


def _make_dispatch_resolver(node, program):
    """A predicate `is_jitted_dispatch(call)` for one module: plain-name
    calls against the module's own jitted names (imported names included
    — the callgraph fixpoint folded those in), attribute calls against
    the jitted names of the module their base resolves to through the
    program's import graph."""
    own_jitted = set(node.info.jit_map.jitted_names)

    def is_jitted_dispatch(call: ast.Call) -> bool:
        func = call.func
        if isinstance(func, ast.Name):
            return func.id in own_jitted
        if isinstance(func, ast.Attribute):
            base = jitmap._dotted(func.value)
            target = callgraph.resolve_module(node, base, program)
            if target is not None and target is not node:
                return func.attr in target.info.jit_map.jitted_names
        return False

    return is_jitted_dispatch


def _scan_block(stmts, mod, is_dispatch, context, findings) -> None:
    open_vars = {}          # timer var -> index of its perf_counter assign
    for i, stmt in enumerate(stmts):
        # close first: `t1 = perf_counter()` both closes open regions
        # (chained-bucket style) and opens its own
        closers = set(_closing_vars(stmt, open_vars))
        new_var = _perf_assign_target(stmt)
        if new_var is not None:
            closers |= set(open_vars)            # every open region ends here
        for var in closers:
            start = open_vars[var]
            region = list(_region_calls(stmts[start + 1:i]))
            if any(is_dispatch(c) for c in region) \
                    and not _has_fence(region):
                findings.append(Finding(
                    "CSA1001", mod.path, stmt.lineno,
                    f"perf_counter delta over `{var}` times a jitted "
                    f"dispatch with no fence before the second read",
                    context=context))
            if new_var is None:
                # a `dt = pc() - t0` read leaves the region open (bench
                # re-reads the same t0 after more work) but advances its
                # start: the checked segment never double-reports
                open_vars[var] = i
        if new_var is not None:
            open_vars = {new_var: i}
        # recurse into nested statement blocks (loops/with/try/if) for
        # regions fully inside them; function and class bodies are scanned
        # separately by run() with their own qualname context
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        for attr in ("body", "orelse", "finalbody"):
            inner = getattr(stmt, attr, None)
            if inner:
                _scan_block(inner, mod, is_dispatch, context, findings)
        for handler in getattr(stmt, "handlers", ()) or ():
            _scan_block(handler.body, mod, is_dispatch, context, findings)


@register_program_pass
def run(program):
    findings = []
    for node in program.modules.values():
        mod = node.info
        if "perf_counter" not in mod.source:
            continue
        is_dispatch = _make_dispatch_resolver(node, program)
        _scan_block(mod.tree.body, mod, is_dispatch, "<module>", findings)
        for fn in ast.walk(mod.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                _scan_block(fn.body, mod, is_dispatch, mod.qualname(fn),
                            findings)
    return findings
