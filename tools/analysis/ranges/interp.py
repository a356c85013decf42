"""The interval abstract interpreter over jaxprs.

Abstraction: an array is a vector of per-element magnitude intervals
along its TRAILING axis (uniform over every leading batch axis), or a
single interval when the trailing axis is wide/untracked. The trailing
axis is where this codebase keeps its limb/column structure
(`[..., L]` narrow elements, `[..., 2L]` wide columns, `[..., 16]`
SHA-256 words), so positional tracking is what lets structural facts —
"schoolbook column 27 is identically zero", "`_Q_SHIFTS[i]` never
touches the top column" — survive into the proof; those facts are
exactly why the committed budgets hold at all.

Soundness contract: every transfer function's output interval contains
every concretely reachable value, *in ideal (unbounded) arithmetic*.
Wrapping is the checked property, not part of the domain: when an int
op's ideal interval escapes its dtype, the interpreter (a) widens the
result to the dtype range — the wrapped value really can be anywhere —
and (b) records a proved-overflow event (CSA1401) unless the contract
declared that wrap intentional (`wrap_ok` dtype / dtype:kind entries,
or a `wrap_ok_sources` file match for e.g. ops/intmath.py's documented
128-bit machinery). Widened values are TAINTED so one root cause yields
one finding, not a cascade.

Loops (`while`/`scan`, what fori_loop lowers to) unroll abstractly while
the count stays under `max_unroll` (a `while` whose decision is open,
a traced but bounded trip count, joins the carries of every turn it may
leave at); past that the contract must supply the carry invariant
and the interpreter checks the body maps invariant -> invariant
(CSA1401 if not, CSA1403 if none declared), widening on failure.

Named-jit summaries: a nested-jit call boundary survives into the jaxpr
as a `pjit` eqn carrying the callee's name; `SUMMARIES` maps the two
ops/intmath.py helpers to their exact mathematical interval images
(`math.isqrt`, exact 128-bit muldiv bounds) — those helpers are
differentially tested bit-exact against Python bigints, so the summary
is a theorem about the function, not an assumption about the code.
Everything else recurses into the sub-jaxpr.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

from . import interval as I
from .interval import Interval

TRACK_MAX = 64          # widest trailing axis tracked positionally
DEFAULT_MAX_UNROLL = 128


@dataclasses.dataclass
class AbsVal:
    """Abstract array: per-trailing-position intervals (len == shape[-1])
    or a single hull interval (len == 1), uniform over leading axes."""
    shape: Tuple[int, ...]
    dtype: str
    vec: Tuple[Interval, ...]
    tainted: bool = False

    @property
    def positional(self) -> bool:
        return len(self.shape) >= 1 and len(self.vec) == self.shape[-1]

    def hull(self) -> Interval:
        return I.join_all(self.vec)


def _uniform(shape, dtype, ivl, tainted=False) -> AbsVal:
    return AbsVal(tuple(shape), str(dtype), (ivl,), tainted)


def _vec(shape, dtype, vec, tainted=False) -> AbsVal:
    vec = tuple(vec)
    if len(shape) == 0 or len(vec) != shape[-1] or shape[-1] > TRACK_MAX:
        vec = (I.join_all(vec),)
    return AbsVal(tuple(shape), str(dtype), vec, tainted)


def from_concrete(x, aval) -> AbsVal:
    """Lift a trace-time constant (numpy array / python scalar) exactly;
    per-position mins/maxes over leading axes when tracked."""
    import numpy as np
    arr = np.asarray(x)
    shape, dtype = tuple(arr.shape), str(aval.dtype)
    if arr.size == 0:
        return _uniform(shape, dtype, I.iv(0))
    if arr.ndim >= 1 and shape[-1] <= TRACK_MAX:
        flat = arr.reshape(-1, shape[-1])
        if flat.dtype == np.bool_:
            flat = flat.astype(np.int64)
        los = flat.min(axis=0)
        his = flat.max(axis=0)
        return AbsVal(shape, dtype,
                      tuple(Interval(_py(l), _py(h))
                            for l, h in zip(los, his)))
    if arr.dtype == np.bool_:
        arr = arr.astype(np.int64)
    return _uniform(shape, dtype, Interval(_py(arr.min()), _py(arr.max())))


def _py(x):
    """numpy scalar -> exact python number."""
    import numpy as np
    if isinstance(x, (np.floating,)):
        return float(x)
    if isinstance(x, (np.bool_,)):
        return int(x)
    return int(x)


def for_aval(aval, spec: Optional[dict] = None) -> AbsVal:
    """AbsVal for an input aval from a contract range spec
    ({"lo", "hi"} with optional {"top_lo", "top_hi"} overriding the last
    trailing position); no spec -> full dtype range."""
    shape, dtype = tuple(aval.shape), str(aval.dtype)
    if spec is None:
        return _uniform(shape, dtype, I.dtype_range(dtype))
    body = Interval(spec["lo"], spec["hi"])
    n = shape[-1] if shape else 0
    if "top_lo" in spec and len(shape) >= 1 and 1 < n <= TRACK_MAX:
        top = Interval(spec["top_lo"], spec["top_hi"])
        return AbsVal(shape, dtype, (body,) * (n - 1) + (top,))
    if len(shape) >= 1 and 1 <= n <= TRACK_MAX:
        return AbsVal(shape, dtype, (body,) * n)
    return _uniform(shape, dtype, body)


# ---------------------------------------------------------------------------
# Events
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Event:
    rule: str            # CSA1401 / CSA1402 / CSA1403
    message: str
    path: str            # the equation's own site; "" where jax recorded
                         # no user frame (the message then says so)
    line: int
    prim: str


def _eqn_site(eqn) -> Optional[Tuple[str, int]]:
    """(file, line) of the innermost frame outside jax that staged `eqn`;
    None where jax recorded no such frame. `user_frame` is private to jax
    and has changed its argument before (0.9.0 takes the traceback, not
    the SourceInfo): a call it refuses raises, and the engine reports the
    contract as unproven, rather than placing every finding at line 0."""
    from jax._src import source_info_util
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return None
    return str(frame.file_name), int(frame.start_line)


# ---------------------------------------------------------------------------
# Interpreter
# ---------------------------------------------------------------------------

class Interp:
    def __init__(self, wrap_ok: Sequence[str] = (),
                 wrap_ok_sources: Sequence[str] = (),
                 invariants: Sequence[object] = (),
                 max_unroll: int = DEFAULT_MAX_UNROLL):
        self.wrap_ok = frozenset(wrap_ok)
        self.wrap_ok_sources = tuple(wrap_ok_sources)
        self.invariants = list(invariants)
        self.max_unroll = int(max_unroll)
        self.events: List[Event] = []
        self._event_keys = set()
        self._loop_idx = 0
        self._defs: Dict[object, object] = {}   # Var -> defining eqn

    # -- events -------------------------------------------------------------

    def _emit(self, rule, message, eqn):
        site = _eqn_site(eqn)
        if site is None:
            message += (" (jax recorded no user frame for this equation: "
                        "reported at the contract's line)")
        path, line = site or ("", 0)
        key = (rule, path, line, eqn.primitive.name, message.split(":")[0])
        if key in self._event_keys:
            return
        self._event_keys.add(key)
        self.events.append(Event(rule, message, path, line,
                                 eqn.primitive.name))

    def widened(self) -> int:
        return sum(1 for e in self.events if e.rule == "CSA1402")

    # -- wrap discipline ----------------------------------------------------

    def _wrap_allowed(self, dtype: str, kind: str, eqn) -> bool:
        if dtype in self.wrap_ok or f"{dtype}:{kind}" in self.wrap_ok:
            return True
        site = _eqn_site(eqn)
        return site is not None and any(s in site[0]
                                        for s in self.wrap_ok_sources)

    def _finish(self, eqn, shape, dtype, vec, kind, tainted) -> AbsVal:
        """Clamp an ideal-arithmetic result against its dtype; flag a
        possible wrap unless tainted/declared."""
        dtype = str(dtype)
        rng = I.dtype_range(dtype)
        if not I.is_int_dtype(dtype) and dtype != "bool":
            return _vec(shape, dtype, vec, tainted)          # floats saturate
        if all(v.within(rng) for v in vec):
            return _vec(shape, dtype, vec, tainted)
        out = tuple(v if v.within(rng) else rng for v in vec)
        if tainted:
            return _vec(shape, dtype, out, True)
        if kind is None:
            return _vec(shape, dtype, out, False)
        if self._wrap_allowed(dtype, kind, eqn):
            # declared-intentional wrap: the value really can be anywhere
            # in the dtype, and everything derived from it is modular
            # arithmetic by declaration — taint so downstream ops do not
            # re-flag the same declared root cause
            return _vec(shape, dtype, out, True)
        worst = I.join_all(v for v in vec if not v.within(rng))
        self._emit("CSA1401",
                   f"`{eqn.primitive.name}` on {dtype} can wrap: ideal "
                   f"interval [{worst.lo}, {worst.hi}] escapes "
                   f"[{rng.lo}, {rng.hi}]", eqn)
        return _vec(shape, dtype, out, True)

    def _widen(self, eqn, why: str) -> List[AbsVal]:
        outs = []
        for ov in eqn.outvars:
            dtype = str(ov.aval.dtype)
            if I.is_int_dtype(dtype) or dtype == "bool":
                self._emit("CSA1402",
                           f"`{eqn.primitive.name}` not modeled ({why}); "
                           f"result widened to the {dtype} range", eqn)
                outs.append(_uniform(ov.aval.shape, dtype,
                                     I.dtype_range(dtype), tainted=True))
            else:
                outs.append(_uniform(ov.aval.shape, dtype,
                                     I.dtype_range(dtype)))
        return outs

    # -- jaxpr evaluation ---------------------------------------------------

    def run(self, closed, in_vals: Sequence[AbsVal]) -> List[AbsVal]:
        consts = [from_concrete(c, v.aval)
                  for c, v in zip(closed.consts, closed.jaxpr.constvars)]
        return self.eval_jaxpr(closed.jaxpr, consts, list(in_vals))

    def eval_jaxpr(self, jaxpr, consts, args) -> List[AbsVal]:
        env: Dict[object, AbsVal] = {}
        for var, val in zip(jaxpr.constvars, consts):
            env[var] = val
        for var, val in zip(jaxpr.invars, args):
            env[var] = val

        def read(atom) -> AbsVal:
            if hasattr(atom, "val"):          # Literal
                return from_concrete(atom.val, atom.aval)
            return env[atom]

        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                self._defs[ov] = eqn
            in_vals = [read(v) for v in eqn.invars]
            handler = _HANDLERS.get(eqn.primitive.name)
            if handler is None:
                outs = self._widen(eqn, "no handler")
            else:
                outs = handler(self, eqn, in_vals)
                if isinstance(outs, AbsVal):
                    outs = [outs]
            assert len(outs) == len(eqn.outvars), eqn.primitive.name
            for var, val in zip(eqn.outvars, outs):
                env[var] = val
        return [read(v) for v in jaxpr.outvars]

    def eval_closed(self, closed, args) -> List[AbsVal]:
        consts = [from_concrete(c, v.aval)
                  for c, v in zip(closed.consts, closed.jaxpr.constvars)]
        return self.eval_jaxpr(closed.jaxpr, consts, list(args))

    # -- elementwise plumbing -----------------------------------------------

    def _aligned(self, val: AbsVal, n: int) -> Tuple[Interval, ...]:
        """Operand intervals aligned to an output trailing size n: its
        own positions when they line up, else its hull everywhere (a
        broadcast size-1 trailing axis contributes its single value)."""
        if len(val.vec) == n:
            return val.vec
        return (val.hull(),) * n

    def _joined(self, a: AbsVal, b: AbsVal) -> AbsVal:
        """The join of two abstract values of one shape and dtype."""
        n = max(len(a.vec), len(b.vec))
        return AbsVal(a.shape, a.dtype,
                      tuple(I.join(p, q) for p, q in zip(
                          self._aligned(a, n), self._aligned(b, n))),
                      a.tainted or b.tainted)

    def _ew(self, eqn, vals, fn, kind=None) -> AbsVal:
        out_aval = eqn.outvars[0].aval
        shape = tuple(out_aval.shape)
        n = shape[-1] if (shape and shape[-1] <= TRACK_MAX) else 1
        cols = [self._aligned(v, n) for v in vals]
        vec = []
        punted = False
        for pos in range(n):
            r = fn(*[c[pos] for c in cols])
            if r is None:                       # handler punts -> dtype range
                r = I.dtype_range(out_aval.dtype)
                punted = True
            vec.append(r)
        tainted = any(v.tainted for v in vals)
        if punted:
            # operands outside the modeled sub-domain (out-of-range
            # shift amount, fully-signed bitwise op): a degradation
            # like any other unmodeled op — taint + count it, so the
            # `widened` ratchet moves and downstream ops don't cascade
            self._emit("CSA1402",
                       f"`{eqn.primitive.name}` operands outside the "
                       f"modeled domain; result widened to the "
                       f"{out_aval.dtype} range", eqn)
            tainted = True
        return self._finish(eqn, shape, out_aval.dtype, vec, kind, tainted)


# ---------------------------------------------------------------------------
# Handlers
# ---------------------------------------------------------------------------

_HANDLERS = {}


def handler(*names):
    def wrap(fn):
        for n in names:
            _HANDLERS[n] = fn
        return fn
    return wrap


@handler("add", "add_any")
def _add(self, eqn, vals):
    return self._ew(eqn, vals, I.add, kind="add")


@handler("sub")
def _sub(self, eqn, vals):
    if _sub_is_nonneg(self, eqn, vals):
        # the saturating-subtraction idioms — x - min(x, y),
        # max(x, y) - y, cumsum(x) - x — are pointwise >= 0 by algebra
        # the interval box cannot see; a one-step def-use look-back
        # recovers them so the hot guards do not degrade to a
        # declared-wrap taint
        return self._ew(eqn, vals,
                        lambda a, b: _clamp_lo0(I.sub(a, b)), kind="sub")
    return self._ew(eqn, vals, I.sub, kind="sub")


def _clamp_lo0(v):
    return Interval(max(v.lo, 0), max(v.hi, 0))


class _DefProxy:
    """A sub-eqn lifted through a trivial pjit wrapper, with its invars
    rewritten into the enclosing scope's atoms."""
    __slots__ = ("primitive", "params", "invars")


def _def_of(self, atom):
    """Defining eqn of a var, looking through single-eqn pjit wrappers
    (jnp.cumsum and friends stage `pjit[name=cumsum] { cumsum }`)."""
    if hasattr(atom, "val"):              # Literal: no def, unhashable
        return None
    d = self._defs.get(atom)
    if d is None or d.primitive.name not in ("jit", "pjit"):
        return d
    inner = d.params.get("jaxpr")
    if inner is None:
        return d
    j = inner.jaxpr
    if (len(j.eqns) != 1 or len(j.outvars) != 1 or len(d.outvars) != 1
            or j.outvars[0] is not j.eqns[0].outvars[0]):
        return d
    mapping = dict(zip(j.invars, d.invars))
    p = _DefProxy()
    p.primitive = j.eqns[0].primitive
    p.params = j.eqns[0].params
    p.invars = [mapping.get(iv, iv) if not hasattr(iv, "val") else iv
                for iv in j.eqns[0].invars]
    return p


def _same_value(self, x, y) -> bool:
    """x and y are the same var, or the same convert of the same var
    (uncse'd `v.astype(t)` appearing twice stages two convert eqns)."""
    if x is y:
        return True
    dx, dy = _def_of(self, x), _def_of(self, y)
    return (dx is not None and dy is not None
            and dx.primitive.name == dy.primitive.name
            == "convert_element_type"
            and dx.params.get("new_dtype") == dy.params.get("new_dtype")
            and dx.invars[0] is dy.invars[0])


def _sub_is_nonneg(self, eqn, vals) -> bool:
    a_atom, b_atom = eqn.invars
    b_def = _def_of(self, b_atom)
    if b_def is not None and b_def.primitive.name == "min" \
            and any(_same_value(self, iv, a_atom) for iv in b_def.invars):
        return True                       # x - min(x, y) >= 0
    a_def = _def_of(self, a_atom)
    if a_def is not None and a_def.primitive.name == "max" \
            and any(_same_value(self, iv, b_atom) for iv in a_def.invars):
        return True                       # max(x, y) - y >= 0
    if a_def is not None and a_def.primitive.name == "cumsum" \
            and not a_def.params.get("reverse") \
            and any(_same_value(self, iv, b_atom) for iv in a_def.invars) \
            and vals[1].hull().lo >= 0:
        return True                       # cumsum(x) - x >= 0 for x >= 0
    return False


@handler("mul")
def _mul(self, eqn, vals):
    return self._ew(eqn, vals, I.mul, kind="mul")


@handler("neg")
def _neg(self, eqn, vals):
    return self._ew(eqn, vals, I.neg, kind="sub")


@handler("max")
def _max(self, eqn, vals):
    return self._ew(eqn, vals, I.max_)


@handler("min")
def _min(self, eqn, vals):
    return self._ew(eqn, vals, I.min_)


@handler("abs")
def _abs(self, eqn, vals):
    return self._ew(eqn, vals, I.abs_, kind="sub")


@handler("sign")
def _sign(self, eqn, vals):
    def f(a):
        lo = -1 if a.lo < 0 else (0 if a.lo == 0 else 1)
        hi = 1 if a.hi > 0 else (0 if a.hi == 0 else -1)
        return Interval(lo, hi)
    return self._ew(eqn, vals, f)


@handler("clamp")
def _clamp(self, eqn, vals):
    return self._ew(eqn, vals,
                    lambda lo, x, hi: I.min_(I.max_(x, lo), hi))


@handler("div")
def _div(self, eqn, vals):
    a, b = vals
    if I.is_int_dtype(str(eqn.outvars[0].aval.dtype)):
        bh = b.hull()
        if bh.lo <= 0 <= bh.hi:
            return self._widen(eqn, "possible division by zero")
    return self._ew(eqn, vals, I.floordiv, kind="div")


@handler("rem")
def _rem(self, eqn, vals):
    a, b = vals
    bh = b.hull()
    if bh.lo <= 0 <= bh.hi:
        return self._widen(eqn, "possible remainder by zero")
    if bh.hi < 0:
        vals = [a, AbsVal(b.shape, b.dtype,
                          tuple(I.neg(v) for v in b.vec), b.tainted)]
    return self._ew(eqn, vals, I.rem)


@handler("pow", "integer_pow")
def _pow(self, eqn, vals):
    y = eqn.params.get("y")
    if y is None or not isinstance(y, int) or y < 0:
        return self._widen(eqn, "non-static exponent")

    def f(a):
        cs = [a.lo ** y, a.hi ** y]
        if y % 2 == 0 and a.lo < 0 < a.hi:
            cs.append(0)
        return Interval(min(cs), max(cs))
    return self._ew(eqn, vals, f, kind="mul")


@handler("shift_left")
def _shl(self, eqn, vals):
    bits = I.dtype_range(str(eqn.outvars[0].aval.dtype))
    width = (bits.hi - bits.lo + 1).bit_length() - 1

    def f(a, s):
        if s.lo < 0 or s.hi >= width:
            return None
        return I.shl(a, s)
    return self._ew(eqn, vals, f, kind="shl")


@handler("shift_right_arithmetic")
def _ashr(self, eqn, vals):
    def f(a, s):
        if s.lo < 0:
            return None
        return I.ashr(a, Interval(s.lo, min(s.hi, 1 << 10)))
    return self._ew(eqn, vals, f)


@handler("shift_right_logical")
def _lshr(self, eqn, vals):
    rng = I.dtype_range(str(eqn.outvars[0].aval.dtype))
    nbits = (rng.hi - rng.lo + 1).bit_length() - 1

    def f(a, s):
        if s.lo < 0:
            return None
        if a.lo < 0:                  # reinterpreted as unsigned bits
            return Interval(0, ((1 << nbits) - 1) >> s.lo)
        return I.ashr(a, Interval(s.lo, min(s.hi, 1 << 10)))
    return self._ew(eqn, vals, f)


@handler("and")
def _and(self, eqn, vals):
    return self._ew(eqn, vals, I.and_)


@handler("or", "xor")
def _or(self, eqn, vals):
    return self._ew(eqn, vals, I.or_xor)


@handler("not")
def _not(self, eqn, vals):
    if str(eqn.outvars[0].aval.dtype) == "bool":
        return self._ew(eqn, vals,
                        lambda a: Interval(1 - a.hi, 1 - a.lo))
    return self._ew(eqn, vals, I.not_)


@handler("population_count", "clz")
def _popcount(self, eqn, vals):
    rng = I.dtype_range(str(eqn.outvars[0].aval.dtype))
    nbits = (rng.hi - rng.lo + 1).bit_length() - 1
    return self._ew(eqn, vals, lambda a: Interval(0, nbits))


# -- comparisons / selection -------------------------------------------------

def _cmp(op):
    def f(a, b):
        if op == "lt":
            if a.hi < b.lo:
                return I.TRUE
            if a.lo >= b.hi:
                return I.FALSE
        elif op == "le":
            if a.hi <= b.lo:
                return I.TRUE
            if a.lo > b.hi:
                return I.FALSE
        elif op == "gt":
            if a.lo > b.hi:
                return I.TRUE
            if a.hi <= b.lo:
                return I.FALSE
        elif op == "ge":
            if a.lo >= b.hi:
                return I.TRUE
            if a.hi < b.lo:
                return I.FALSE
        elif op == "eq":
            if a.singleton and b.singleton and a.lo == b.lo:
                return I.TRUE
            if a.hi < b.lo or b.hi < a.lo:
                return I.FALSE
        elif op == "ne":
            if a.singleton and b.singleton and a.lo == b.lo:
                return I.FALSE
            if a.hi < b.lo or b.hi < a.lo:
                return I.TRUE
        return I.BOOL
    return f


for _name in ("lt", "le", "gt", "ge", "eq", "ne"):
    def _mk(nm):
        def h(self, eqn, vals):
            return self._ew(eqn, vals, _cmp(nm))
        return h
    _HANDLERS[_name] = _mk(_name)


@handler("select_n")
def _select_n(self, eqn, vals):
    pred, *cases = vals

    def f(p, *cs):
        if p.singleton and 0 <= p.lo < len(cs):
            return cs[p.lo]
        return I.join_all(cs)
    return self._ew(eqn, [pred] + cases, f)


@handler("is_finite")
def _is_finite(self, eqn, vals):
    return self._ew(eqn, vals, lambda a: I.BOOL)


# -- float transcendentals ---------------------------------------------------

@handler("sqrt")
def _sqrt(self, eqn, vals):
    return self._ew(eqn, vals, I.sqrt)


@handler("rsqrt", "exp", "log", "log1p", "expm1", "tanh", "erf", "logistic",
         "sin", "cos", "floor", "ceil", "round", "real", "imag")
def _float_misc(self, eqn, vals):
    dtype = str(eqn.outvars[0].aval.dtype)
    if eqn.primitive.name == "floor":
        return self._ew(eqn, vals,
                        lambda a: Interval(math.floor(a.lo), math.floor(a.hi))
                        if _finite(a) else a)
    if eqn.primitive.name == "ceil":
        return self._ew(eqn, vals,
                        lambda a: Interval(math.ceil(a.lo), math.ceil(a.hi))
                        if _finite(a) else a)
    return self._ew(eqn, vals, lambda a: I.dtype_range(dtype))


def _finite(a):
    return not (math.isinf(a.lo) or math.isinf(a.hi))


@handler("convert_element_type")
def _convert(self, eqn, vals):
    (a,) = vals
    out_dtype = str(eqn.outvars[0].aval.dtype)

    def f(v):
        lo, hi = v.lo, v.hi
        if isinstance(lo, float) or isinstance(hi, float):
            if I.is_int_dtype(out_dtype) or out_dtype == "bool":
                lo = math.floor(lo) if _finite(v) else I.dtype_range(out_dtype).lo
                hi = math.ceil(hi) if _finite(v) else I.dtype_range(out_dtype).hi
        if out_dtype == "bool":
            return Interval(1 if lo > 0 or hi < 0 else 0,
                            0 if lo == hi == 0 else 1)
        return Interval(lo, hi)
    return self._ew(eqn, vals, f, kind="convert")


@handler("bitcast_convert_type", "reduce_precision")
def _bitcast(self, eqn, vals):
    if eqn.primitive.name == "reduce_precision":
        return vals[0]
    return self._widen(eqn, "bitcast")


# -- structural ops ----------------------------------------------------------

@handler("device_put", "copy", "stop_gradient", "opt-barrier",
         "optimization_barrier")
def _identity(self, eqn, vals):
    outs = []
    for ov, v in zip(eqn.outvars, vals):
        outs.append(AbsVal(tuple(ov.aval.shape), str(ov.aval.dtype),
                           v.vec, v.tainted))
    return outs


@handler("broadcast_in_dim")
def _broadcast(self, eqn, vals):
    (a,) = vals
    out = eqn.outvars[0].aval
    bdims = tuple(eqn.params["broadcast_dimensions"])
    if (a.positional and bdims and bdims[-1] == len(out.shape) - 1
            and a.shape[-1] == out.shape[-1]):
        return _vec(out.shape, out.dtype, a.vec, a.tainted)
    return _uniform(out.shape, out.dtype, a.hull(), a.tainted)


@handler("reshape")
def _reshape(self, eqn, vals):
    (a,) = vals
    out = eqn.outvars[0].aval
    if a.positional and out.shape and out.shape[-1] == a.shape[-1]:
        return _vec(out.shape, out.dtype, a.vec, a.tainted)
    return _uniform(out.shape, out.dtype, a.hull(), a.tainted)


@handler("squeeze")
def _squeeze(self, eqn, vals):
    (a,) = vals
    out = eqn.outvars[0].aval
    dims = tuple(eqn.params["dimensions"])
    if a.positional and len(a.shape) - 1 not in dims:
        return _vec(out.shape, out.dtype, a.vec, a.tainted)
    return _uniform(out.shape, out.dtype, a.hull(), a.tainted)


@handler("expand_dims")
def _expand(self, eqn, vals):
    (a,) = vals
    out = eqn.outvars[0].aval
    if a.positional and out.shape and out.shape[-1] == a.shape[-1]:
        return _vec(out.shape, out.dtype, a.vec, a.tainted)
    return _uniform(out.shape, out.dtype, a.hull(), a.tainted)


@handler("transpose")
def _transpose(self, eqn, vals):
    (a,) = vals
    out = eqn.outvars[0].aval
    perm = tuple(eqn.params["permutation"])
    if a.positional and perm and perm[-1] == len(a.shape) - 1:
        return _vec(out.shape, out.dtype, a.vec, a.tainted)
    return _uniform(out.shape, out.dtype, a.hull(), a.tainted)


@handler("rev")
def _rev(self, eqn, vals):
    (a,) = vals
    out = eqn.outvars[0].aval
    dims = tuple(eqn.params["dimensions"])
    if a.positional and len(a.shape) - 1 in dims:
        return _vec(out.shape, out.dtype, tuple(reversed(a.vec)), a.tainted)
    return AbsVal(tuple(out.shape), str(out.dtype), a.vec, a.tainted)


@handler("iota")
def _iota(self, eqn, vals):
    out = eqn.outvars[0].aval
    dim = int(eqn.params["dimension"])
    n = out.shape[dim]
    if dim == len(out.shape) - 1 and n <= TRACK_MAX:
        return _vec(out.shape, out.dtype, tuple(I.iv(k) for k in range(n)))
    return _uniform(out.shape, out.dtype, Interval(0, max(n - 1, 0)))


@handler("concatenate")
def _concat(self, eqn, vals):
    out = eqn.outvars[0].aval
    dim = int(eqn.params["dimension"])
    tainted = any(v.tainted for v in vals)
    if dim == len(out.shape) - 1 and out.shape[-1] <= TRACK_MAX:
        vec = []
        for v in vals:
            n = v.shape[-1]
            vec.extend(v.vec if len(v.vec) == n else (v.hull(),) * n)
        return _vec(out.shape, out.dtype, vec, tainted)
    n = out.shape[-1] if out.shape else 0
    if n and n <= TRACK_MAX and all(len(v.vec) in (1, n) for v in vals):
        cols = [self._aligned(v, n) for v in vals]
        return _vec(out.shape, out.dtype,
                    [I.join_all(c[pos] for c in cols) for pos in range(n)],
                    tainted)
    return _uniform(out.shape, out.dtype,
                    I.join_all(v.hull() for v in vals), tainted)


@handler("slice")
def _slice(self, eqn, vals):
    (a,) = vals
    out = eqn.outvars[0].aval
    if not a.positional:
        return _uniform(out.shape, out.dtype, a.hull(), a.tainted)
    start = eqn.params["start_indices"][-1]
    limit = eqn.params["limit_indices"][-1]
    strides = eqn.params.get("strides")
    step = strides[-1] if strides else 1
    return _vec(out.shape, out.dtype, a.vec[start:limit:step], a.tainted)


@handler("pad")
def _pad(self, eqn, vals):
    a, pv = vals
    out = eqn.outvars[0].aval
    cfg = eqn.params["padding_config"]
    tainted = a.tainted or pv.tainted
    p = pv.hull()
    if not (a.positional and out.shape
            and out.shape[-1] <= TRACK_MAX):
        return _uniform(out.shape, out.dtype, I.join(a.hull(), p), tainted)
    lo, hi, inner = cfg[-1]
    vec = []
    for i, v in enumerate(a.vec):
        vec.append(v)
        if inner and i < len(a.vec) - 1:
            vec.extend([p] * inner)
    vec = [p] * max(lo, 0) + (vec[-lo:] if lo < 0 else vec)
    vec = (vec + [p] * max(hi, 0))[:None if hi >= 0 else hi]
    if any(c[0] > 0 or c[1] > 0 or c[2] > 0 for c in cfg[:-1]):
        vec = [I.join(v, p) for v in vec]
    return _vec(out.shape, out.dtype, vec, tainted)


@handler("dynamic_slice")
def _dynamic_slice(self, eqn, vals):
    a, *starts = vals
    out = eqn.outvars[0].aval
    sizes = tuple(eqn.params["slice_sizes"])
    tainted = a.tainted
    if not a.positional:
        return _uniform(out.shape, out.dtype, a.hull(), tainted)
    n, s = a.shape[-1], sizes[-1]
    if s == n:
        return _vec(out.shape, out.dtype, a.vec, tainted)
    st = starts[-1].hull()
    if st.singleton:
        c = max(0, min(int(st.lo), n - s))
        return _vec(out.shape, out.dtype, a.vec[c:c + s], tainted)
    return _uniform(out.shape, out.dtype, a.hull(), tainted)


@handler("dynamic_update_slice")
def _dus(self, eqn, vals):
    a, u, *starts = vals
    out = eqn.outvars[0].aval
    tainted = a.tainted or u.tainted
    if not a.positional:
        return _uniform(out.shape, out.dtype,
                        I.join(a.hull(), u.hull()), tainted)
    n, m = a.shape[-1], (u.shape[-1] if u.shape else 1)
    st = starts[-1].hull() if starts else I.iv(0)
    uvec = u.vec if len(u.vec) == m else (u.hull(),) * m
    vec = list(a.vec)
    if st.singleton:
        c = max(0, min(int(st.lo), n - m))
        vec[c:c + m] = uvec
    else:
        uh = u.hull()
        vec = [I.join(v, uh) for v in vec]
    return _vec(out.shape, out.dtype, vec, tainted)


@handler("gather")
def _gather(self, eqn, vals):
    a, idx = vals
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    sizes = tuple(eqn.params["slice_sizes"])
    tainted = a.tainted
    fill = "FILL_OR_DROP" in str(eqn.params.get("mode", ""))
    last = len(a.shape) - 1
    if (a.positional and last not in dn.collapsed_slice_dims
            and last not in dn.start_index_map
            and sizes[last] == a.shape[-1]
            and dn.offset_dims and dn.offset_dims[-1] == len(out.shape) - 1):
        vec = a.vec
        if fill:
            vec = tuple(I.join(v, I.iv(0)) for v in vec)
        return _vec(out.shape, out.dtype, vec, tainted)
    h = a.hull()
    if fill:
        h = I.join(h, I.iv(0))
    return _uniform(out.shape, out.dtype, h, tainted)


@handler("scatter", "scatter-add")
def _scatter(self, eqn, vals):
    a, idx, u = vals
    out = eqn.outvars[0].aval
    add = eqn.primitive.name == "scatter-add"
    dn = eqn.params["dimension_numbers"]
    tainted = a.tainted or u.tainted
    last = len(a.shape) - 1
    uh = u.hull()
    # updates landing per target position: every non-window update element
    n_upd = 1
    for d, size in enumerate(u.shape):
        if d not in dn.update_window_dims:
            n_upd *= size

    def bump(v):
        if not add:
            return I.join(v, uh)
        if n_upd == 1:
            return I.add(v, uh)
        return I.add(v, Interval(min(0, n_upd * uh.lo),
                                 max(0, n_upd * uh.hi)))

    if not a.positional:
        vec = [bump(a.hull())] if add else [I.join(a.hull(), uh)]
        return self._finish(eqn, out.shape, out.dtype, vec,
                            "add" if add else None, tainted)
    vec = list(a.vec)
    trailing_window = (last not in dn.inserted_window_dims
                       and last not in dn.scatter_dims_to_operand_dims)
    if trailing_window:
        # trailing axis rides the update window: pairwise against the
        # update's own trailing positions
        un = u.shape[-1] if u.shape else 1
        uvec = u.vec if len(u.vec) == un == len(vec) else (uh,) * len(vec)
        if add and n_upd == 1 and _exact_single(dn, idx, a):
            vec = [I.add(v, uu) for v, uu in zip(vec, uvec)]
        elif add:
            vec = [I.add(v, Interval(min(0, n_upd * uu.lo),
                                     max(0, n_upd * uu.hi)))
                   for v, uu in zip(vec, uvec)]
        else:
            vec = [I.join(v, uu) for v, uu in zip(vec, uvec)]
        return self._finish(eqn, out.shape, out.dtype, vec,
                            "add" if add else None, tainted)
    ih = idx.hull()
    if (tuple(dn.scatter_dims_to_operand_dims) == (last,) and ih.singleton
            and n_upd == 1):
        k = int(ih.lo)
        if 0 <= k < len(vec):
            vec[k] = I.add(vec[k], uh) if add else uh
        return self._finish(eqn, out.shape, out.dtype, vec,
                            "add" if add else None, tainted)
    vec = [bump(v) for v in vec]
    return self._finish(eqn, out.shape, out.dtype, vec,
                        "add" if add else None, tainted)


def _exact_single(dn, idx, a):
    return False   # conservative: window updates may overlap


# -- reductions --------------------------------------------------------------

@handler("reduce_sum")
def _reduce_sum(self, eqn, vals):
    (a,) = vals
    out = eqn.outvars[0].aval
    axes = tuple(eqn.params["axes"])
    n_red = 1
    for ax in axes:
        n_red *= a.shape[ax]
    last = len(a.shape) - 1
    tainted = a.tainted
    if a.positional and last in axes:
        m = n_red // a.shape[-1]
        total = I.iv(0)
        for v in a.vec:
            total = I.add(total, v)
        return self._finish(eqn, out.shape, out.dtype,
                            [I.scale(total, max(m, 1))], "add", tainted)
    if a.positional and last not in axes:
        vec = [I.scale(v, n_red) for v in a.vec]
        return self._finish(eqn, out.shape, out.dtype, vec, "add", tainted)
    return self._finish(eqn, out.shape, out.dtype,
                        [I.scale(a.hull(), n_red)], "add", tainted)


@handler("reduce_max", "reduce_min", "reduce_and", "reduce_or", "cummax",
         "cummin")
def _reduce_minmax(self, eqn, vals):
    (a,) = vals
    out = eqn.outvars[0].aval
    axes = tuple(eqn.params.get("axes", (eqn.params.get("axis", 0),)))
    last = len(a.shape) - 1
    if a.positional and last in axes:
        return _vec(out.shape, out.dtype, (a.hull(),), a.tainted)
    return AbsVal(tuple(out.shape), str(out.dtype), a.vec, a.tainted)


@handler("reduce_prod")
def _reduce_prod(self, eqn, vals):
    return self._widen(eqn, "product reduction")


@handler("argmax", "argmin")
def _argminmax(self, eqn, vals):
    out = eqn.outvars[0].aval
    axes = tuple(eqn.params["axes"])
    n = max(vals[0].shape[ax] for ax in axes)
    return _uniform(out.shape, out.dtype, Interval(0, max(n - 1, 0)))


@handler("cumsum")
def _cumsum(self, eqn, vals):
    (a,) = vals
    out = eqn.outvars[0].aval
    axis = int(eqn.params["axis"])
    last = len(a.shape) - 1
    if a.positional and axis == last and not eqn.params.get("reverse"):
        vec, run = [], I.iv(0)
        for v in a.vec:
            run = I.add(run, v)
            vec.append(run)
        return self._finish(eqn, out.shape, out.dtype, vec, "add", a.tainted)
    n = a.shape[axis]
    if a.positional and axis != last:
        vec = [I.scale(v, n) for v in a.vec]
        return self._finish(eqn, out.shape, out.dtype, vec, "add", a.tainted)
    return self._finish(eqn, out.shape, out.dtype,
                        [I.scale(a.hull(), n)], "add", a.tainted)


@handler("sort")
def _sort(self, eqn, vals):
    out_avals = [ov.aval for ov in eqn.outvars]
    dim = int(eqn.params["dimension"])
    outs = []
    for ov, v in zip(out_avals, vals):
        if dim == len(v.shape) - 1:
            outs.append(_vec(ov.shape, ov.dtype, (v.hull(),), v.tainted))
        else:
            outs.append(AbsVal(tuple(ov.shape), str(ov.dtype), v.vec,
                               v.tainted))
    return outs


@handler("dot_general")
def _dot_general(self, eqn, vals):
    a, b = vals
    out = eqn.outvars[0].aval
    ((lc, rc), _) = eqn.params["dimension_numbers"]
    n = 1
    for d in lc:
        n *= a.shape[d]
    prod = I.mul(a.hull(), b.hull())
    return self._finish(eqn, out.shape, out.dtype,
                        [I.scale(prod, max(n, 1))], "mul",
                        a.tainted or b.tainted)


# -- control flow ------------------------------------------------------------

def _invariant_avals(self, spec, carry_avals) -> Optional[List[AbsVal]]:
    """Materialize a declared invariant for a loop's carry avals."""
    def one(entry, aval):
        if entry in (None, "dtype"):
            return for_aval(aval, None)
        return for_aval(aval, entry)
    if spec in ("dtype",):
        return [for_aval(av, None) for av in carry_avals]
    if isinstance(spec, dict):
        return [one(spec, av) for av in carry_avals]
    if isinstance(spec, (list, tuple)):
        assert len(spec) == len(carry_avals), \
            f"invariant arity {len(spec)} != carry arity {len(carry_avals)}"
        return [one(e, av) for e, av in zip(spec, carry_avals)]
    return None


def _within(val: AbsVal, inv: AbsVal) -> bool:
    if len(inv.vec) == 1:
        h = inv.vec[0]
        return all(v.within(h) for v in val.vec)
    if len(val.vec) == len(inv.vec):
        return all(v.within(w) for v, w in zip(val.vec, inv.vec))
    return val.hull().within(inv.hull())


def _loop_fallback(self, eqn, body_closed, consts, init, n_carry,
                   what) -> List[AbsVal]:
    """Invariant path for a loop the interpreter could not unroll."""
    carry_avals = [v.aval for v in body_closed.jaxpr.invars[
        len(consts):len(consts) + n_carry]]
    spec = (self.invariants[self._loop_idx]
            if self._loop_idx < len(self.invariants) else None)
    self._loop_idx += 1
    inv = _invariant_avals(self, spec, carry_avals) if spec is not None \
        else None
    if inv is None:
        self._emit("CSA1403",
                   f"{what} beyond the unroll window with no declared "
                   f"invariant; carries widened to their dtype ranges", eqn)
        inv = [dataclasses.replace(for_aval(av, None), tainted=True)
               for av in carry_avals]
        entry_ok = True
    else:
        entry_ok = all(_within(v, w) for v, w in zip(init, inv))
        if not entry_ok:
            self._emit("CSA1401",
                       f"{what} invariant does not hold at loop entry", eqn)
    return inv, spec is not None and entry_ok


@handler("while")
def _while(self, eqn, vals):
    cn = int(eqn.params["cond_nconsts"])
    bn = int(eqn.params["body_nconsts"])
    cond = eqn.params["cond_jaxpr"]
    body = eqn.params["body_jaxpr"]
    cond_consts, body_consts = vals[:cn], vals[cn:cn + bn]
    carry = list(vals[cn + bn:])
    init = list(carry)
    # a trip count that is traced but bounded (`i < n`, n in [0, 127]):
    # the loop may leave at every turn where the decision is open, so the
    # result is the join of the carries at those turns, and the unrolling
    # ends at the turn where the decision is definitely no
    left = None
    for _ in range(self.max_unroll):
        pred = self.eval_closed(cond, cond_consts + carry)[0].hull()
        if pred != I.TRUE:
            left = carry if left is None else [
                self._joined(a, b) for a, b in zip(left, carry)]
        if pred == I.FALSE:
            return left
        carry = self.eval_closed(body, body_consts + carry)
    inv, check = _loop_fallback(self, eqn, body, body_consts, init,
                                len(init), "while loop")
    if check:
        out = self.eval_closed(body, body_consts + inv)
        if not all(_within(v, w) for v, w in zip(out, inv)):
            self._emit("CSA1401",
                       "while-loop body escapes the declared invariant; "
                       "carries widened to their dtype ranges", eqn)
            inv = [dataclasses.replace(for_aval(w.aval, None), tainted=True)
                   for w in eqn.outvars]
    return inv


@handler("scan")
def _scan(self, eqn, vals):
    params = eqn.params
    nc, n_carry = int(params["num_consts"]), int(params["num_carry"])
    length = int(params["length"])
    body = params["jaxpr"]
    consts = vals[:nc]
    carry = list(vals[nc:nc + n_carry])
    xs = vals[nc + n_carry:]
    xs_slices = []
    for x in xs:
        inner_shape = tuple(x.shape[1:])
        vec = x.vec if (inner_shape and len(x.vec) == inner_shape[-1]) \
            else (x.hull(),)
        xs_slices.append(AbsVal(inner_shape, x.dtype, vec, x.tainted))
    n_ys = len(eqn.outvars) - n_carry
    ys_join: List[Optional[AbsVal]] = [None] * n_ys

    def note_ys(ys):
        for i, y in enumerate(ys):
            if ys_join[i] is None:
                ys_join[i] = y
            else:
                ys_join[i] = self._joined(ys_join[i], y)

    if length <= self.max_unroll:
        for _ in range(length):
            outs = self.eval_closed(body, consts + carry + xs_slices)
            carry = outs[:n_carry]
            note_ys(outs[n_carry:])
    else:
        inv, check_idx = _scan_invariants(self, eqn, body, nc, n_carry,
                                          carry, length)
        if check_idx:
            outs = self.eval_closed(body, consts + inv + xs_slices)
            if not all(_within(outs[k], inv[k]) for k in check_idx):
                self._emit("CSA1401",
                           "scan body escapes the declared invariant; "
                           "carries widened to their dtype ranges", eqn)
                inv = [dataclasses.replace(
                    for_aval(v.aval, None), tainted=True)
                    for v in body.jaxpr.invars[nc:nc + n_carry]]
                outs = self.eval_closed(body, consts + inv + xs_slices)
        else:
            outs = self.eval_closed(body, consts + inv + xs_slices)
        carry = inv
        note_ys(outs[n_carry:])

    result = list(carry)
    for i, ov in enumerate(eqn.outvars[n_carry:]):
        y = ys_join[i]
        if y is None:
            y = for_aval(ov.aval, None)
        result.append(AbsVal(tuple(ov.aval.shape), str(ov.aval.dtype),
                             y.vec, y.tainted))
    return [AbsVal(tuple(ov.aval.shape), str(ov.aval.dtype), v.vec,
                   v.tainted)
            for ov, v in zip(eqn.outvars, result)]


def _counter_bound(body, nc, k, init, length):
    """Exact range of a scan carry that is a pure counter (`c + const`,
    what fori_loop's index lowers to) or a passthrough — those have no
    inductive interval (a counter strictly increases), but their image
    over `length` trips is closed-form."""
    j = body.jaxpr
    outv = j.outvars[k]
    carry_in = j.invars[nc + k]
    if outv is carry_in:
        return init.hull()                       # loop-invariant carry
    for e in j.eqns:
        if any(ov is outv for ov in e.outvars):
            if e.primitive.name not in ("add", "sub"):
                return None
            a, b = e.invars
            lit = None
            if a is carry_in and hasattr(b, "val"):
                lit = int(b.val)
                if e.primitive.name == "sub":
                    lit = -lit
            elif b is carry_in and hasattr(a, "val") \
                    and e.primitive.name == "add":
                lit = int(a.val)
            if lit is None:
                return None
            h = init.hull()
            # `length` full steps: the carry OUT of the final iteration
            # is init + length*lit (the hull covers every intermediate
            # value AND the loop's returned final value)
            step = lit * max(length, 0)
            return Interval(h.lo + min(0, step), h.hi + max(0, step))
    return None


def _scan_invariants(self, eqn, body, nc, n_carry, init, length):
    """Carry intervals for a scan beyond the unroll window: counters
    bound in closed form, everything else from the contract's declared
    invariant (checked inductively by the caller over `check_idx`);
    missing declarations widen to the dtype range with CSA1403."""
    spec = (self.invariants[self._loop_idx]
            if self._loop_idx < len(self.invariants) else None)
    self._loop_idx += 1
    entries = None
    if isinstance(spec, (list, tuple)):
        assert len(spec) == n_carry, (len(spec), n_carry)
        entries = list(spec)
    elif spec is not None:
        entries = [spec] * n_carry
    carry_avals = [v.aval for v in body.jaxpr.invars[nc:nc + n_carry]]
    inv, check_idx, missing = [], [], False
    for k, aval in enumerate(carry_avals):
        auto = _counter_bound(body, nc, k, init[k], length)
        if auto is not None:
            inv.append(_uniform(aval.shape, aval.dtype, auto,
                                init[k].tainted))
            continue
        entry = entries[k] if entries is not None else None
        if entry in (None, "dtype"):
            if entries is None:
                missing = True
            inv.append(dataclasses.replace(for_aval(aval, None),
                                           tainted=True))
        else:
            val = for_aval(aval, entry)
            if not _within(init[k], val):
                self._emit("CSA1401",
                           f"scan of length {length}: declared invariant "
                           f"does not hold at loop entry (carry {k})", eqn)
            inv.append(val)
            check_idx.append(k)
    if missing:
        self._emit("CSA1403",
                   f"scan of length {length} beyond the unroll window "
                   f"with no declared invariant; non-counter carries "
                   f"widened to their dtype ranges", eqn)
    return inv, check_idx


@handler("cond")
def _cond(self, eqn, vals):
    idx, *ops = vals
    branches = eqn.params["branches"]
    h = idx.hull()
    if h.singleton and 0 <= h.lo < len(branches):
        return self.eval_closed(branches[int(h.lo)], ops)
    outs = None
    for br in branches:
        res = self.eval_closed(br, ops)
        if outs is None:
            outs = res
        else:
            outs = [AbsVal(a.shape, a.dtype,
                           tuple(I.join(p, q) for p, q in zip(
                               self._aligned(a, max(len(a.vec), len(b.vec))),
                               self._aligned(b, max(len(a.vec), len(b.vec))))),
                           a.tainted or b.tainted)
                    for a, b in zip(outs, res)]
    return outs


# -- named-jit summaries (exact images of the intmath helpers) ---------------

def _sum_isqrt(self, eqn, in_vals):
    (n,) = in_vals
    out = eqn.outvars[0].aval
    h = n.hull()
    return [_uniform(out.shape, out.dtype, I.isqrt(h), n.tainted)]


def _sum_muldiv(self, eqn, in_vals):
    a, b, d = in_vals
    out = eqn.outvars[0].aval
    ah, bh, dh = a.hull(), b.hull(), d.hull()
    if dh.lo < 1 or ah.lo < 0 or bh.lo < 0:
        return None
    top = I.dtype_range(out.dtype).hi
    lo = min(ah.lo * bh.lo // dh.hi, top)
    hi = ah.hi * bh.hi // dh.lo
    # the static bound escaping the dtype means the proof leans on the
    # helper's documented caller guarantee (quotient fits 64 bits) —
    # taint so that assumption is not silently compounded downstream
    assumed = hi > top
    return [_uniform(out.shape, out.dtype, Interval(lo, min(hi, top)),
                     a.tainted or b.tainted or d.tainted or assumed)]


def _sum_mulwide(self, eqn, in_vals):
    a, b = in_vals
    ah, bh = a.hull(), b.hull()
    if ah.lo < 0 or bh.lo < 0:
        return None
    p = I.mul(ah, bh)
    tainted = a.tainted or b.tainted
    hi_aval, lo_aval = eqn.outvars[0].aval, eqn.outvars[1].aval
    hi = Interval(p.lo >> 64, p.hi >> 64)
    lo = Interval(p.lo, p.hi) if p.hi < (1 << 64) \
        else I.dtype_range(lo_aval.dtype)
    return [_uniform(hi_aval.shape, hi_aval.dtype, hi, tainted),
            _uniform(lo_aval.shape, lo_aval.dtype, lo, tainted)]


def _sum_carry_rounds(self, eqn, in_vals):
    """Exact positional transfer of ops/fq._carry_rounds (jitted so the
    boundary is visible here). Per round, per element:

        new[0]   = old[0] & MASK
        new[k]   = (old[k] & MASK) + (old[k-1] >> B)      0 < k < top
        new[top] = old[top] + (old[top-1] >> B)

    the top identity because (x & MASK) + ((x >> B) << B) == x — the
    algebraic cancellation the interval domain cannot see positionally
    (it would otherwise grow the top limb ~2^29 per round). The round
    count is read back off the staged body (one scatter-add per round)."""
    (a,) = in_vals
    if not a.positional:
        return None                      # recurse: still sound, just loose
    from consensus_specs_tpu.ops.fq import B, MASK
    inner = eqn.params.get("jaxpr")
    n = sum(1 for e in inner.jaxpr.eqns
            if e.primitive.name == "scatter-add") if inner is not None else 0
    if n == 0:
        return None
    shift = I.iv(B)
    mask = Interval(0, MASK)

    def lo_part(v):
        return v if (v.lo >= 0 and v.hi <= MASK) else mask

    vec = list(a.vec)
    for _ in range(n):
        new = [lo_part(vec[0])]
        for k in range(1, len(vec)):
            new.append(I.add(lo_part(vec[k]), I.ashr(vec[k - 1], shift)))
        new[-1] = I.add(vec[-1], I.ashr(vec[-2], shift))
        vec = new
    out = eqn.outvars[0].aval
    return [self._finish(eqn, tuple(out.shape), out.dtype, vec, "add",
                         a.tainted)]


def _sum_roll(self, eqn, in_vals):
    """jnp.roll is a permutation: its image is exactly the operand's
    interval. The summary also sidesteps jnp's negative-start
    normalization arm (`start + 2n`), whose ideal value exceeds int32
    near the 2^30 shuffle ceiling on a branch the select provably
    discards — a dead-arm wrap the interval domain would otherwise
    flag."""
    a = in_vals[0]
    out = eqn.outvars[0].aval
    return [_uniform(out.shape, out.dtype, a.hull(), a.tainted)]


SUMMARIES = {
    "isqrt_u64": _sum_isqrt,
    "muldiv_u64": _sum_muldiv,
    "mulwide_u64": _sum_mulwide,
    "_carry_rounds_impl": _sum_carry_rounds,
    "_roll_dynamic": _sum_roll,
    "_roll_static": _sum_roll,
}


@handler("jit", "pjit", "closed_call", "core_call", "xla_call", "remat",
         "remat_call", "checkpoint", "custom_jvp_call", "custom_vjp_call",
         "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr")
def _call(self, eqn, vals):
    name = eqn.params.get("name")
    summary = SUMMARIES.get(name)
    if summary is not None:
        outs = summary(self, eqn, vals)
        if outs is not None:
            return outs
    inner = None
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in eqn.params:
            inner = eqn.params[key]
            break
    if inner is None:
        return self._widen(eqn, f"opaque call {name or ''}")
    if hasattr(inner, "consts"):
        return self.eval_closed(inner, vals)
    return self.eval_jaxpr(inner, [], vals)
