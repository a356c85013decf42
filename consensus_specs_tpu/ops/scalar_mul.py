"""Windowed signed-digit (wNAF-style) scalar multiplication over the generic
`fo` field-ops protocol — plus the Jacobian point-op layer both it and the
double-and-add reference build on (moved here from ops/bls_jax.py, which
re-exports; this module deliberately imports neither fq nor fq_tower, so the
ops/ import DAG stays `bls_jax -> scalar_mul -> jax`).

Why: after the Merkle forest removed the hashing bottleneck, the longest
sequential chain left in block verification was `jac_scalar_mul`'s MSB-first
double-and-add — one full `jac_add` per scalar bit (256 dependent adds per
G1/G2 scalar mul, ~508 for the G2 cofactor clearing that dominates
hash_to_G2). A batched `jac_add` is wide but its latency is serial: the
fori_loop trip count IS the critical path.

The windowed backend cuts the dependent-add chain ~3.5x:

- **Host recoding** (`recode_signed_windows`): k is a host Python int at
  every call site (privkeys, the fixed G2 cofactor), so the signed-digit
  decomposition runs in exact host arithmetic — never traced. The
  Joye–Tunstall regular recoding writes odd k' as ceil(nbits/w)+1 odd
  digits d_i in {±1, ±3, .., ±(2^w − 1)} (d = (k' mod 2^{w+1}) − 2^w;
  k' = (k' − d)/2^w), every digit nonzero by construction — no zero-digit
  select in the device loop. Even k uses k' = k+1 with one post-loop
  subtraction of P (k = 0 degenerates to [1]P − P = O). Digits are
  memoized per (k, nbits, w) and shipped as tiny [m] int32 arrays, so the
  jit cache still keys only on shapes.
- **Device table** (`build_odd_multiples`): the odd multiples
  [1P, 3P, .., (2^w − 1)P] — one doubling for 2P plus a 2^{w-1} − 1 add
  chain, all batched over the point axis, stacked on a leading table axis.
- **Device loop** (`windowed_scalar_mul`): ceil(nbits/w) trips of
  (w doublings + ONE table-gather add). Digit selection is a `jnp.take`
  on the table axis (the scalar is shared across the batch) and negation
  is the cheap y -> −y `fo.select` — everything branch-free and
  trace-safe.

Sequential-add cost (the bench/test-asserted model, `sequential_adds`):
    double_add:  nbits
    window:      ceil(nbits/w) + 2^{w-1}     (loop + table chain + fixup)
256-bit at w=4: 256 -> 72 (3.6x); the ~507-bit cofactor: 507 -> 135 (3.8x).
Doublings stay ~equal (w·ceil(nbits/w) + 1 vs nbits), and the table build
amortizes across the batch axis.

Backend selection mirrors CSTPU_MERKLE_BACKEND: CSTPU_SCALAR_MUL=
window|double_add (default window; double_add is the reference oracle),
CSTPU_SCALAR_WINDOW overrides the width (default 4). The dispatchers live
in ops/bls_jax.py (`g1_scalar_mul`/`g2_scalar_mul`).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Generic Jacobian point ops over a field namespace (G1: Fq, G2: Fq2)
# ---------------------------------------------------------------------------

def jac_infinity(fo, batch=()):
    """The point at infinity: (0, 1, 0)."""
    return (fo.zeros(batch), fo.ones(batch), fo.zeros(batch))


def jac_double(fo, p):
    """2P in Jacobian coordinates, a = 0 curve. Handles P = O and 2-torsion
    (Y = 0) via Z3 = 2YZ = 0."""
    X, Y, Z = p
    A = fo.sqr(X)
    B = fo.sqr(Y)
    C = fo.sqr(B)
    D = fo.sub(fo.sqr(fo.add(X, B)), fo.add(A, C))
    D = fo.add(D, D)
    E = fo.add(fo.add(A, A), A)
    Fv = fo.sqr(E)
    X3 = fo.sub(Fv, fo.add(D, D))
    C8 = fo.add(C, C)
    C8 = fo.add(C8, C8)
    C8 = fo.add(C8, C8)
    Y3 = fo.sub(fo.mul(E, fo.sub(D, X3)), C8)
    Z3 = fo.mul(Y, Z)
    Z3 = fo.add(Z3, Z3)
    return (X3, Y3, Z3)


def jac_add(fo, p1, p2):
    """P1 + P2 in Jacobian coordinates with full special-case handling
    (either infinity, P1 == P2 -> double, P1 == -P2 -> infinity), resolved
    by selects so the op is branch-free and batchable."""
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    inf1 = fo.is_zero(Z1)
    inf2 = fo.is_zero(Z2)
    Z1Z1 = fo.sqr(Z1)
    Z2Z2 = fo.sqr(Z2)
    U1 = fo.mul(X1, Z2Z2)
    U2 = fo.mul(X2, Z1Z1)
    S1 = fo.mul(fo.mul(Y1, Z2), Z2Z2)
    S2 = fo.mul(fo.mul(Y2, Z1), Z1Z1)
    H = fo.sub(U2, U1)
    Rr = fo.sub(S2, S1)
    Rr = fo.add(Rr, Rr)
    h_zero = fo.is_zero(H)
    r_zero = fo.is_zero(Rr)
    H2 = fo.add(H, H)
    I = fo.sqr(H2)
    J = fo.mul(H, I)
    V = fo.mul(U1, I)
    X3 = fo.sub(fo.sub(fo.sqr(Rr), J), fo.add(V, V))
    S1J = fo.mul(S1, J)
    Y3 = fo.sub(fo.mul(Rr, fo.sub(V, X3)), fo.add(S1J, S1J))
    Z3 = fo.mul(fo.sub(fo.sqr(fo.add(Z1, Z2)), fo.add(Z1Z1, Z2Z2)), H)
    out = (X3, Y3, Z3)
    dbl = jac_double(fo, p1)
    batch = X1.shape[:-fo.val_ndim]
    inf = jac_infinity(fo, batch)
    both = ~inf1 & ~inf2
    out = tuple(fo.select(both & h_zero & r_zero, d, o) for d, o in zip(dbl, out))
    out = tuple(fo.select(both & h_zero & ~r_zero, i, o) for i, o in zip(inf, out))
    out = tuple(fo.select(inf1, b, o) for b, o in zip(p2, out))
    out = tuple(fo.select(inf2, a, o) for a, o in zip(p1, out))
    return out


def jac_to_affine(fo, p):
    """Jacobian -> (x, y, is_infinity). x/y are garbage when infinite."""
    X, Y, Z = p
    zi = fo.inv(Z)
    zi2 = fo.sqr(zi)
    x = fo.mul(X, zi2)
    y = fo.mul(Y, fo.mul(zi2, zi))
    return x, y, fo.is_zero(Z)


def _lift_affine(fo, aff, inf=None):
    """Affine (x, y) -> Jacobian (x, y, 1); batch elements flagged in the
    optional `inf` mask lift to z = 0 instead (the infinity encoding every
    jac op already propagates)."""
    x, y = aff
    batch = x.shape[:-fo.val_ndim]
    z = fo.ones(batch)
    if inf is not None:
        z = fo.select(inf, fo.zeros(batch), z)
    return (x, y, z)


def jac_scalar_mul(fo, aff, bits, inf=None):
    """[k]P for affine P, k given MSB-first as a [nbits] uint8 array (traced
    data, static length). Double-and-add over a fori_loop; the add handles
    the initial infinity accumulator. The REFERENCE backend the windowed
    path is diffed against (CSTPU_SCALAR_MUL=double_add selects it)."""
    lifted = _lift_affine(fo, aff, inf)
    batch = lifted[0].shape[:-fo.val_ndim]

    def body(i, acc):
        acc = jac_double(fo, acc)
        added = jac_add(fo, acc, lifted)
        take = bits[i] == 1
        return tuple(fo.select(take, a, o) for a, o in zip(added, acc))

    acc0 = jac_infinity(fo, batch)
    n = bits.shape[0]
    return jax.lax.fori_loop(0, n, body, acc0)


# ---------------------------------------------------------------------------
# Host recoding (exact int arithmetic; memoized — never traced)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4096)
def scalar_bits(k: int, width: int = 256) -> np.ndarray:
    """MSB-first [width] uint8 bit array of k — the double-and-add input.

    Memoized per (k, width) and vectorized (np.unpackbits), replacing the
    256-entry Python list comprehension the staging path used to rebuild
    per call. The returned array is shared across callers and marked
    read-only."""
    assert 0 <= k < (1 << width), (k, width)
    raw = np.frombuffer(int(k).to_bytes((width + 7) // 8, "big"), np.uint8)
    bits = np.unpackbits(raw)[-width:]
    bits.flags.writeable = False
    return bits


class SignedWindows(NamedTuple):
    """Host-recoded signed windows of one scalar (see recode_signed_windows).

    idx/sign are MSB-window-first, read-only, and shared across callers
    (the recoding is memoized)."""
    idx: np.ndarray        # [m] int32: odd-multiple table index (|d| - 1) / 2
    sign: np.ndarray       # [m] int32: +1 / -1
    correction: bool       # subtract P once post-loop (k was even; k=0 -> O)
    w: int
    nbits: int


def n_windows(nbits: int, w: int) -> int:
    """Digit count of the fixed-length recoding: ceil(nbits/w) + 1."""
    return -(-nbits // w) + 1


@functools.lru_cache(maxsize=4096)
def recode_signed_windows(k: int, nbits: int, w: int) -> SignedWindows:
    """Fixed-length Joye–Tunstall signed-window recoding of k over `nbits`.

    k' = k (odd) or k + 1 (even, correction flag set) decomposes into
    exactly n_windows(nbits, w) ODD digits in {±1, ±3, .., ±(2^w − 1)}:
        d_i = (k' mod 2^{w+1}) − 2^w;   k' <- (k' − d_i) / 2^w
    The invariant k' = Σ d_i 2^{wi} holds at every step and the final
    digit is always +1 (k' < 2^nbits forces the remainder to 1), so the
    device loop needs no zero-digit or empty-accumulator handling. The
    reconstruction is asserted here in exact host arithmetic."""
    assert w >= 1 and 0 <= k < (1 << nbits), (k, nbits, w)
    correction = (k % 2 == 0)
    n = k + 1 if correction else k
    m = n_windows(nbits, w)
    digits = []
    for _ in range(m - 1):
        d = (n & ((1 << (w + 1)) - 1)) - (1 << w)
        digits.append(d)
        n = (n - d) >> w
    assert n == 1, (k, nbits, w, n)   # the fixed-length tail digit
    digits.append(n)
    value = 0
    for d in reversed(digits):
        assert d % 2 != 0 and abs(d) < (1 << w), d
        value = (value << w) + d
    assert value == (k + 1 if correction else k), (k, value)
    digits_msb = np.array(digits[::-1], dtype=np.int64)
    idx = ((np.abs(digits_msb) - 1) // 2).astype(np.int32)
    sign = np.where(digits_msb < 0, -1, 1).astype(np.int32)
    idx.flags.writeable = False
    sign.flags.writeable = False
    return SignedWindows(idx, sign, correction, w, nbits)


# ---------------------------------------------------------------------------
# Device kernel
# ---------------------------------------------------------------------------

def build_odd_multiples(fo, p_jac, w: int, unroll: bool = False):
    """[1P, 3P, .., (2^w − 1)P] for a batched Jacobian P: one doubling (2P)
    plus a 2^{w-1} − 1 add chain, every entry batched over the point axes
    and stacked on a NEW leading table axis (gather target for the traced
    digit indices).

    The chain is sequential either way; by default it runs as a fori_loop
    scattering into the stacked table so the traced graph holds ONE
    jac_add instance instead of 2^{w-1} − 1 of them (an unrolled w=4/w=5
    chain alone pushed XLA:CPU compile past its slow-compile alarm).
    `unroll=True` keeps the trace-time Python chain — same math, one op
    instance per add — for the op-counting tests."""
    n_tab = 2 ** (w - 1)
    if n_tab == 1:
        return tuple(c[None] for c in p_jac)
    p2 = jac_double(fo, p_jac)
    if unroll:
        entries = [p_jac]
        for _ in range(n_tab - 1):
            entries.append(jac_add(fo, entries[-1], p2))
        return tuple(jnp.stack([e[c] for e in entries]) for c in range(3))

    def body(i, tab):
        prev = tuple(jnp.take(t, i - 1, axis=0) for t in tab)
        nxt = jac_add(fo, prev, p2)
        return tuple(t.at[i].set(x) for t, x in zip(tab, nxt))

    tab0 = tuple(jnp.broadcast_to(c[None], (n_tab,) + c.shape) for c in p_jac)
    return jax.lax.fori_loop(1, n_tab, body, tab0)


def windowed_scalar_mul(fo, aff, idx, sign, correction, w: int,
                        inf=None, unroll: bool = False):
    """[k]P from host-recoded signed windows (Jacobian out).

    aff = (x, y) affine batch (one shared scalar across the batch);
    idx/sign are the [m] MSB-window-first arrays of a SignedWindows (traced
    or static — the jit cache keys only on their shape), `correction` a
    scalar bool (traced ok). Main loop: m − 1 trips of w doublings + ONE
    table-gather add; digit negation is the y -> −y select. `inf` marks
    batch elements that are the point at infinity (propagates through the
    table and loop to an infinite result).

    Loops are fori_loops (outer over windows, inner over the w doublings,
    plus the table-build chain), so the traced graph carries a CONSTANT
    ~3 jac_add + 2 jac_double instances at any (nbits, w) — compile cost
    stays at double-and-add's scale. `unroll=True` swaps every loop for a
    trace-time Python loop — bigger graph, same math; it is what lets
    tests count the real jac_add chain op-by-op."""
    lifted = _lift_affine(fo, aff, inf)
    table = build_odd_multiples(fo, lifted, w, unroll=unroll)

    def entry(i):
        tx, ty, tz = (jnp.take(t, idx[i], axis=0) for t in table)
        ty = fo.select(sign[i] < 0, fo.neg(ty), ty)
        return (tx, ty, tz)

    def step(i, acc):
        if unroll:
            for _ in range(w):
                acc = jac_double(fo, acc)
        else:
            acc = jax.lax.fori_loop(
                0, w, lambda j, a: jac_double(fo, a), acc)
        return jac_add(fo, acc, entry(i))

    acc = entry(0)
    m = int(idx.shape[0])
    if unroll:
        for i in range(1, m):
            acc = step(i, acc)
    elif m > 1:
        acc = jax.lax.fori_loop(1, m, step, acc)
    # even-k fixup: one unconditional trailing add, kept or discarded by a
    # select (k = 0 rides this too: [1]P − P = O). asarray: `correction`
    # may arrive as a static Python bool (the SignedWindows field)
    correction = jnp.asarray(correction)
    minus_p = (lifted[0], fo.neg(lifted[1]), lifted[2])
    fixed = jac_add(fo, acc, minus_p)
    return tuple(fo.select(correction, f, a) for f, a in zip(fixed, acc))


# ---------------------------------------------------------------------------
# Backend knob (mirrors ops/sha256.set_merkle_pair_backend)
# ---------------------------------------------------------------------------

_SCALAR_MUL_BACKENDS = ("window", "double_add")
_backend_override: Optional[str] = None


def set_scalar_mul_backend(name: Optional[str]) -> None:
    """Pin the scalar-mul backend ("window"/"double_add"); None returns
    control to the CSTPU_SCALAR_MUL environment variable (default
    "window")."""
    global _backend_override
    assert name is None or name in _SCALAR_MUL_BACKENDS, name
    _backend_override = name


def scalar_mul_backend_name() -> str:
    name = _backend_override or os.environ.get("CSTPU_SCALAR_MUL", "window")
    if name not in _SCALAR_MUL_BACKENDS:
        raise ValueError(
            f"CSTPU_SCALAR_MUL must be one of {_SCALAR_MUL_BACKENDS}, "
            f"got {name!r}")
    return name


def scalar_mul_window() -> int:
    """Window width w for the windowed backend (CSTPU_SCALAR_WINDOW,
    default 4 — the sequential-adds sweet spot for 256-bit scalars: the
    2^{w-1}-entry table build starts out-costing the saved loop adds
    beyond w=5)."""
    w = int(os.environ.get("CSTPU_SCALAR_WINDOW", "4"))
    if not 1 <= w <= 8:
        raise ValueError(f"CSTPU_SCALAR_WINDOW must be in [1, 8], got {w}")
    return w


# ---------------------------------------------------------------------------
# Cost model (asserted against op-by-op counts in tests/test_scalar_mul.py)
# ---------------------------------------------------------------------------

def sequential_adds(backend: str, nbits: int, w: Optional[int] = None) -> int:
    """Length of the dependent jac_add chain one scalar mul executes —
    the critical-path currency of the window-vs-double-add choice."""
    if backend == "double_add":
        return nbits
    assert backend == "window" and w is not None
    return (2 ** (w - 1) - 1) + (n_windows(nbits, w) - 1) + 1


def sequential_doubles(backend: str, nbits: int, w: Optional[int] = None) -> int:
    """Dependent jac_double chain length (windowed pays ≤ w − 1 extra from
    rounding nbits up to whole windows, plus the table's 2P)."""
    if backend == "double_add":
        return nbits
    assert backend == "window" and w is not None
    return (1 if w > 1 else 0) + w * (n_windows(nbits, w) - 1)


# ---------------------------------------------------------------------------
# Trace-tier kernel contract (tools/analysis/trace/, `make contracts`)
# ---------------------------------------------------------------------------
# The measured arm of the dependent-add cost model: an UNROLLED eager
# windowed evaluation at a small shape, counted op-by-op through the
# shared tracer's counted_point_ops (the counter that used to be
# hand-rolled in tests/test_scalar_mul.py), pinned exactly to
# sequential_adds/sequential_doubles — the model the hot-shape budgets
# in ops.bls_jax.cofactor_clear_model are computed from.

def _windowed_chain_build():
    from . import bls_jax as BJ
    from ..crypto import bls12_381 as gt
    nbits, w = 24, 3
    k = 0b101100111010110011101011 - 1   # even: exercises the fixup add
    rec = recode_signed_windows(k, nbits, w)
    arr = BJ.g1_to_limbs(gt.ec_mul(gt.G1_GEN, 9))
    return dict(
        fn=lambda x, y: windowed_scalar_mul(
            BJ.G1_OPS, (x, y), rec.idx, rec.sign, rec.correction,
            w=w, unroll=True),
        args=(jnp.asarray(arr[0]), jnp.asarray(arr[1])))


TRACE_CONTRACTS = [
    dict(
        name="ops.scalar_mul.windowed_chain",
        build=_windowed_chain_build,
        count_point_ops=True,
        budgets={"seq_adds": sequential_adds("window", 24, 3),
                 "seq_doubles": sequential_doubles("window", 24, 3)},
        exact=("seq_adds", "seq_doubles"),
    ),
]


# ---------------------------------------------------------------------------
# Value-range contract (tools/analysis/ranges/, `make ranges`)
# ---------------------------------------------------------------------------
# Jacobian coordinate limbs across the windowed loop: from a canonical
# affine G1 point (limbs in [0, 2^29), top limb <= q >> 377), the
# interval interpreter walks the REAL fori_loop program — table build,
# window trips, even-k fixup — unrolling each loop abstractly, and
# proves no int64 wrap anywhere in the chained jac_add/jac_double field
# ops and that the accumulator limbs stay inside the lazy narrow budget
# (a few times 2^29; the per-mul defensive carry rounds are what keep
# the chain from compounding). Same canonical 24-bit/w=3 shape as the
# trace-tier chain contract above.

def _windowed_ranges_build():
    from . import bls_jax as BJ
    from . import fq  # lazy: module-level scalar_mul stays fq-free
    nbits, w = 24, 3
    k = 0b101100111010110011101011 - 1   # even: exercises the fixup add
    rec = recode_signed_windows(k, nbits, w)
    z = jnp.zeros((2, fq.L), jnp.int64)
    canon = {"lo": 0, "hi": fq.MASK, "top_lo": 0, "top_hi": fq.CANONICAL_TOP}
    return dict(
        fn=lambda x, y: windowed_scalar_mul(
            BJ.G1_OPS, (x, y), jnp.asarray(rec.idx), jnp.asarray(rec.sign),
            rec.correction, w=w),
        args=(z, z), ranges=(canon, canon))


RANGE_CONTRACTS = [
    dict(
        name="ops.scalar_mul.windowed_loop_limbs",
        build=_windowed_ranges_build,
        # X/Y/Z accumulator limbs: body within ~9*2^29, top spill-only
        output={"lo": -(1 << 33), "hi": 1 << 33,
                "top_lo": -(1 << 12), "top_hi": 1 << 12},
    ),
]
