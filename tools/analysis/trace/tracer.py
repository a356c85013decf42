"""Shared tracer library: walk real jaxprs, lower to StableHLO text,
count tagged op classes.

This is the one home of the jaxpr-walking op models that used to be
hand-rolled in tests/test_fq_redc.py (`_iter_subjaxprs` /
`qinv_mul_lanes` / `_fresh_jaxpr`) and tests/test_scalar_mul.py (the
monkeypatched sequential-add counter): the contract engine
(tools/analysis/trace/engine.py) and the op-count tests now both assert
through these helpers, so the REDC/add op models have one source of
truth.

Unlike the rest of tools/analysis this module imports jax (it operates
on programs, not source); the AST tier never loads it.
"""
from __future__ import annotations

import contextlib
import re
from collections import Counter
from typing import Dict, Iterable, Optional, Tuple

import numpy as np

import jax
from jax.extend import core as jex_core


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def iter_subjaxprs(params) -> Iterable[Tuple[object, list]]:
    """Yield (jaxpr, consts) for every sub-jaxpr in an eqn's params —
    fori/scan/cond/custom_* bodies, nested arbitrarily in lists/tuples."""
    for v in params.values():
        stack = [v]
        while stack:
            x = stack.pop()
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr, x.consts
            elif isinstance(x, jex_core.Jaxpr):
                yield x, []
            elif isinstance(x, (list, tuple)):
                stack.extend(x)


def fresh_jaxpr(fn, *xs, **kwargs):
    """Trace through a FRESH wrapper so jax's trace cache (keyed on
    function identity + avals, blind to backend globals like
    CSTPU_FQ_REDC) cannot hand back another mode's jaxpr — the very
    staleness ops/bls_jax.py's mode-keyed jitted programs exist to
    prevent."""
    return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*xs)


def walk_eqns(closed):
    """Yield (eqn, const_env) for every eqn in a closed jaxpr including
    every sub-jaxpr body (loop bodies count ONCE — these are
    traced-graph walks, not execution counts). const_env maps the
    enclosing jaxpr's constvars to their values."""
    stack = [(closed.jaxpr, closed.consts)]
    while stack:
        jaxpr, consts = stack.pop()
        env = dict(zip(jaxpr.constvars, consts))
        for eqn in jaxpr.eqns:
            stack.extend(iter_subjaxprs(eqn.params))
            yield eqn, env


def _scalar_const_of(invar, env) -> Optional[int]:
    if isinstance(invar, jex_core.Literal):
        val = invar.val
    elif invar in env:
        val = env[invar]
    else:
        return None
    if np.ndim(val) == 0:
        try:
            return int(val)
        except (TypeError, ValueError):
            return None
    return None


def qinv_mul_lanes(closed) -> int:
    """Total REDC lanes in a traced program, read off the jaxpr itself:
    each REDC instance multiplies by the Montgomery constant QINV_NEG
    exactly L times (once per interleaved-reduction step), and each such
    multiply's shape is the stacked lane batch. Nothing else multiplies
    by that 29-bit constant, so lanes = sum(prod(shape)) / L."""
    from consensus_specs_tpu.ops import fq as F
    total = scan_program(closed, tagged_const=F.QINV_NEG)["tagged_lanes"]
    assert total % F.L == 0, total
    return total // F.L


def scan_program(closed, tagged_const: Optional[int] = None) -> dict:
    """ONE traversal computing everything the contract engine reads off
    a traced graph (the big pairing programs run to ~150k eqns — walking
    them once instead of once per check keeps `make contracts` fast):

      eqns            whole-graph eqn count (sub-jaxprs included) — the
                      coarse program-size ratchet
      tagged_lanes    output lanes of `mul`-by-`tagged_const` eqns (pick
                      a constant nothing else multiplies by and the op
                      class reads straight off the graph — QINV_NEG)
      callbacks       host-callback primitive names staged (pure_ /
                      io_ / debug_callback, debug_print)
      device_puts     device_put eqns with an EXPLICIT placement target
                      (a device/sharding) — a mid-program transfer.
                      Target-less puts do not count: that is how
                      jnp.asarray stages trace-time constants (the
                      `_Q_SHIFTS` idiom — jax threads them through loop
                      bodies as ALIAS/devices=[None] puts), and a bare
                      jax.device_put(x) under jit is a no-op
      f64_ops         eqns with a float64 output aval
    """
    eqns = 0
    tagged = 0
    callbacks = set()
    device_puts = 0
    f64_ops = 0
    for eqn, env in walk_eqns(closed):
        eqns += 1
        name = eqn.primitive.name
        if any(f in name for f in _CALLBACK_FRAGMENTS):
            callbacks.add(name)
        if name == "device_put":
            targets = list(eqn.params.get("devices", ())) \
                + list(eqn.params.get("srcs", ()))
            if any(t is not None for t in targets):
                device_puts += 1
        if any(getattr(ov.aval, "dtype", None) == np.float64
               for ov in eqn.outvars):
            f64_ops += 1
        if tagged_const is not None and name == "mul":
            for iv in eqn.invars:
                if _scalar_const_of(iv, env) == tagged_const:
                    tagged += int(np.prod(eqn.outvars[0].aval.shape,
                                          dtype=np.int64))
                    break
    return {"eqns": eqns, "tagged_lanes": tagged,
            "callbacks": sorted(callbacks), "device_puts": device_puts,
            "f64_ops": f64_ops}


_CALLBACK_FRAGMENTS = ("callback", "debug_print")


# ---------------------------------------------------------------------------
# Lowering (StableHLO text) and compiled-HLO scans
# ---------------------------------------------------------------------------

def donated_count(text: str) -> int:
    """tf.aliasing_output annotations in the lowered signature — one per
    flattened donated argument that survived lowering."""
    return text.count("tf.aliasing_output")


# An HLO *instruction* whose opcode is a collective: the opcode token sits
# right before its operand list's "(" and is never "%"-prefixed (operand
# REFERENCES like `%all-reduce.1` are — counting those would measure uses,
# not ops). `-start` async halves carry the op; `-done` (whose opcode ends
# in -done, so the "(" never directly follows the base name) does not.
_COLLECTIVE_RE = re.compile(
    r"(?<!%)\b(all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute|collective-broadcast)(?:-start)?\(")


def collective_inventory(text: str) -> Dict[str, int]:
    """collective kind -> instruction count in a compiled-HLO text."""
    counts: Counter = Counter()
    for line in text.splitlines():
        if "=" not in line:
            continue
        m = _COLLECTIVE_RE.search(line.split("=", 1)[1])
        if m:
            counts[m.group(1)] += 1
    return dict(counts)


def _split_top_level(s: str) -> list:
    """Split on commas not nested in (), <>, {}, [] or quotes."""
    out, depth, start, in_str = [], 0, 0, False
    for i, ch in enumerate(s):
        if in_str:
            if ch == '"':
                in_str = False
            continue
        if ch == '"':
            in_str = True
        elif ch in "(<{[":
            depth += 1
        elif ch in ")>}]":
            depth -= 1
        elif ch == "," and depth == 0:
            out.append(s[start:i].strip())
            start = i + 1
    tail = s[start:].strip()
    if tail:
        out.append(tail)
    return out


_SDY_ATTR = "sdy.sharding = #sdy.sharding<"
_SDY_MESH_RE = re.compile(r"sdy\.mesh\s+(@[\w.$-]+)\s*=\s*(<[^\n]*>)")


def _balanced(text: str, i: int, open_ch: str, close_ch: str) -> int:
    """Index of the `close_ch` matching the `open_ch` at text[i]."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == open_ch:
            depth += 1
        elif text[j] == close_ch:
            depth -= 1
            if depth == 0:
                return j
    raise ValueError(f"unbalanced {open_ch}{close_ch} from offset {i}")


def signature_shardings(text: str):
    """(arg_shardings, result_shardings) of the @main function of a
    lowered StableHLO module: per flattened arg/result, the Shardy
    `sdy.sharding` annotation with the mesh it names spelled out
    (`<["v"=4]>, [{"v"}, {}]`), or None when unannotated. Two operands
    compare equal only when axes AND mesh shape agree."""
    meshes = dict(_SDY_MESH_RE.findall(text))
    anchor = text.index("func.func public @main(")
    i = text.index("(", anchor)
    j = _balanced(text, i, "(", ")")
    args_src = text[i + 1:j]
    k = text.index("(", text.index("->", j))
    results_src = text[k + 1:_balanced(text, k, "(", ")")]

    def shard_of(entry: str):
        at = entry.find(_SDY_ATTR)
        if at < 0:
            return None
        lt = at + len(_SDY_ATTR) - 1
        mesh, _, dims = entry[lt + 1:_balanced(entry, lt, "<", ">")] \
            .partition(",")
        return f"{meshes.get(mesh.strip(), mesh.strip())},{dims}"

    return ([shard_of(e) for e in _split_top_level(args_src)],
            [shard_of(e) for e in _split_top_level(results_src)])


# ---------------------------------------------------------------------------
# Counted call chains (the sequential-add cost model's measurement arm)
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def counted_calls(module, names: Tuple[str, ...]):
    """Wrap `module.<name>` for each name with a counting shim (callees
    resolved through the module's own globals are counted too); yields
    the live {name: count} dict and restores the originals on exit."""
    counts = {n: 0 for n in names}
    originals = {n: getattr(module, n) for n in names}

    def wrap(name, real):
        def counted(*args, **kwargs):
            counts[name] += 1
            return real(*args, **kwargs)
        return counted

    for n in names:
        setattr(module, n, wrap(n, originals[n]))
    try:
        yield counts
    finally:
        for n in names:
            setattr(module, n, originals[n])


@contextlib.contextmanager
def counted_point_ops():
    """Count the REAL jac_add / jac_double chain of an (eager, unrolled)
    scalar-mul evaluation — the windowed kernel resolves both through
    ops/scalar_mul.py's module globals, so wrapping there sees every
    dependent step. Yields {"jac_add": n, "jac_double": n}. NOTE the
    cost-model convention: every jac_add internally evaluates one
    jac_double (the branch-free P1 == P2 fallback), so the *dependent
    doubling chain* is counts["jac_double"] - counts["jac_add"]."""
    from consensus_specs_tpu.ops import scalar_mul as SM
    with counted_calls(SM, ("jac_add", "jac_double")) as counts:
        yield counts
