"""Batched BLS12-381 base-field arithmetic in JAX: Montgomery form, lazy
signed 29-bit limbs, double-width lazy reduction.

The reference delegates all field math to pure-Python bignums (py_ecc there,
crypto/bls12_381.py here — /root/reference specs/bls_signature.md:96-146 for
the contract). On TPU there is no wide multiplier, so an Fq element is a
`[..., L]` int64 array of 29-bit limbs (14x29 = 406 >= 381 bits), and a
double-width product is a `[..., 2L]` int64 array of schoolbook columns.

Design (third iteration — the first used uint64 limbs with serial per-op
carry chains; the second reduced every bilinear leaf product in full even
though the tower recombination that follows is linear):

- **Lazy signed limbs.** add/sub/neg are single vector ops; limbs drift out
  of [0, 2^29) and may go negative between multiplications. Only the
  multiply/reduce ops and the boundary ops re-normalize.
- **Split multiply.** `fq_mul_wide` is the reduction-free schoolbook
  (`[..., L] x [..., L] -> [..., 2L]` int64 columns); `fq_redc` is the
  interleaved Montgomery reduction (`[..., 2L] -> [..., L]`, one
  14-step dependent carry chain per lane). `fq_mul = fq_redc o
  fq_mul_wide` — and the tower (ops/fq_tower.py) exploits the split:
  because REDC is Z-linear, Karatsuba recombinations run on the WIDE
  columns and reduce once per output coefficient instead of once per
  leaf product (Aranha et al., EUROCRYPT 2011): fq12_mul 54 -> 12 REDC
  lanes, the sparse line multiply 39 -> 12, squarings 36 -> 12, the
  cyclotomic squaring 30 -> 12 (`CSTPU_FQ_REDC=coeff|leaf` selects;
  `leaf` keeps per-leaf reduction as the differential oracle).
- **Montgomery absorbs laziness.** `fq_mul`/`fq_mul_wide` accept any
  inputs whose limbs fit ~2^32 and whose VALUES satisfy |v_a|*|v_b| < q*R
  (true for sums of up to ~2^10 field-bounded terms); `fq_redc` output
  value is in (-2q, 2q). So lazily-accumulated values flow straight into
  the next multiply with no conditional subtracts anywhere.
- **Vectorized carry rounds.** Normalization is rounds of
  (lo = v & MASK, hi = v >> B arithmetic, v = lo + shift_up(hi)) — whole-
  vector ops, value-preserving, length-generic (the same `_carry_rounds`
  serves L-limb elements and 2L-limb wide columns). Three rounds crush
  magnitudes to limbs in [-1, 2^29]; exact ripple needs L+3 rounds and is
  reserved for the boundary ops (`fq_canon`, `fq_is_zero`, `fq_eq`).
- **No integer matmuls, ever.** The TPU v5e has no 64-bit integer dot
  unit: XLA's X64 rewriter emulates elementwise s64 mul/add/shift but
  rejects `s64 dot_general`. The schoolbook is therefore L statically
  placed shifted adds of elementwise limb products (pad + add — shapes
  static, fully fusable), and every "matrix apply" elsewhere in the BLS
  stack (fq_tower's bilinear tables) is unrolled the same way.

Every function is elementwise over leading batch axes; stacking independent
lanes along a batch axis (see fq_tower's bilinear fq12 product) is the
intended usage pattern — the traced graph is the same size for a batch of 2
and a batch of 10^6.

Laziness budget — MACHINE-CHECKED: the constants below are exported as
module constants, declared in this module's RANGE_CONTRACTS, and proven
by the value-range tier's interval interpreter over the real jaxprs
(tools/analysis/ranges/, `make ranges`, rules CSA1401-1404);
tests/test_range_contracts.py asserts these documented numbers equal
the contract constants so prose and prover cannot drift apart:

- *Narrow domain* (`[..., L]`, inputs to fq_mul/fq_mul_wide): body
  limbs |l| <= NARROW_INPUT_BOUND = 2^32 with the top limb carrying
  only the value spill |l_13| <= NARROW_TOP_SPILL = 2^16 (values are
  sums/differences of at most ~2^10 Montgomery outputs: |v| < 2^10 *
  2q < 2^393, so the top limb holds < 2^(393-377); canonical elements
  x < q have top limb <= CANONICAL_TOP = q >> 377 = 13). Three
  defensive carry rounds provably crush the body into
  [NARROW_LIMB_LO, NARROW_LIMB_HI] = [-16, 2^29] (the hand ripple
  argument gives [-1, 2^29]; the committed interval proof carries the
  slightly looser machine floor).
- *Wide domain* (`[..., 2L]` columns): a single `fq_mul_wide` of
  normalized operands yields |col| <= WIDE_COL_RAW = 14*2^58 < 2^62 —
  NO headroom for accumulation (three raw products already overflow
  int64). Any >2-term wide accumulation must interpose `fq_wide_norm`
  (value-preserving wide carry rounds, body back to [-16, 2^29])
  first; CSA901 pre-checks this syntactically and the range tier
  proves it on the traced values. `fq_redc` accepts body columns
  |col| < WIDE_COL_BUDGET = WIDE_ACCUM_FANIN * 2^29 = 2^35 (the
  gamma fan-in ceiling fq_tower's `_check_budget` enforces) plus a
  top column carrying only spill |col_27| < WIDE_TOP_SPILL = 2^38,
  and its output window is (v/R - q, v/R + q), i.e. (-2q, 2q)
  whenever |value| < q*R; iterated additive passthroughs must enter
  the wide domain through a reduction-free multiply by one (value <=
  |a|*q, keeps the window contracting — fq_tower.fq12_cyclo_sqr), not
  the shift-lift `fq_wide_from_mont` (value |a|*R, window grows per
  step).
"""
from __future__ import annotations

import contextlib
import os
from functools import partial
from typing import Optional, Sequence

import numpy as np

from ..telemetry import counter as _tele_counter
from . import intmath  # noqa: F401  (enables jax_enable_x64 before jnp use)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

Q = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
B = 29                      # bits per limb
L = 14                      # limbs (14*29 = 406 bits)
MASK = (1 << B) - 1
R_MONT = (1 << (B * L)) % Q
R2_MONT = (R_MONT * R_MONT) % Q
QINV_NEG = pow(-Q, -1, 1 << B)   # -q^{-1} mod 2^B (Montgomery constant)

NORM_FULL = L + 3           # rounds for exact ripple propagation

# ---------------------------------------------------------------------------
# Laziness-budget constants (the module docstring's numbers, exported so
# the RANGE_CONTRACTS below — and fq_tower's — declare and prove exactly
# these; tests/test_range_contracts.py pins doc prose == constants)
# ---------------------------------------------------------------------------

NARROW_LIMB_LO = -16                    # proven post-norm body floor
NARROW_LIMB_HI = 1 << B                 # proven post-norm body ceiling (2^29)
NARROW_INPUT_BOUND = 1 << 32            # declared |body limb| budget into mul
NARROW_TOP_SPILL = 1 << 16              # declared top-limb spill (|v| < 2^393)
CANONICAL_TOP = Q >> (B * (L - 1))      # = 13: top limb of canonical x < q
WIDE_COL_RAW = L << (2 * B)             # 14*2^58: one raw schoolbook column
WIDE_ACCUM_FANIN = 64                   # gamma abs-fan-in ceiling (fq_tower)
WIDE_COL_BUDGET = WIDE_ACCUM_FANIN << B  # 2^35: fq_redc body-column budget
WIDE_TOP_SPILL = 1 << 38                # fq_redc top-column (spill) budget


def int_to_limbs(x: int) -> np.ndarray:
    """Host: python int (>= 0, < 2^406) -> [L] int64 limb array."""
    out = np.zeros(L, dtype=np.int64)
    for i in range(L):
        out[i] = (x >> (B * i)) & MASK
    return out


def limbs_to_int(limbs) -> int:
    """Host: [L] limb array (possibly lazy/signed) -> python int mod q."""
    arr = np.asarray(limbs, dtype=np.int64)
    return sum(int(arr[..., i]) << (B * i) for i in range(L)) % Q


Q_LIMBS = int_to_limbs(Q)
_Q_NP = np.asarray(Q_LIMBS, dtype=np.int64)
_Q2_NP = int_to_limbs(2 * Q)     # 2q < 2^383: fits 14 limbs


def _signed_rep(x: int) -> np.ndarray:
    """Host: the unique limb rep with limbs 0..L-2 in [0, 2^29) and the sign
    carried by the top limb — what NORM_FULL carry rounds converge to."""
    out = np.zeros(L, dtype=np.int64)
    for i in range(L - 1):
        li = x & MASK
        out[i] = li
        x = (x - li) >> B
    out[L - 1] = x
    return out


_ZERO_PAT = np.zeros(L, dtype=np.int64)
_Q_PAT = _signed_rep(Q)
_NEGQ_PAT = _signed_rep(-Q)


def to_mont(x: int) -> np.ndarray:
    """Host: int -> Montgomery-form limb array (for staging constants)."""
    return int_to_limbs((x % Q) * R_MONT % Q)


def from_mont(limbs) -> int:
    """Host: Montgomery-form limb array (lazy ok) -> canonical int."""
    return limbs_to_int(limbs) * pow(R_MONT, -1, Q) % Q


def stack_mont(values: Sequence[int]) -> np.ndarray:
    """Host: [N] ints -> [N, L] Montgomery limb arrays."""
    return np.stack([to_mont(v) for v in values])


# ---------------------------------------------------------------------------
# Backend knob: where the tower reduces (mirrors CSTPU_SCALAR_MUL)
# ---------------------------------------------------------------------------

_REDC_BACKENDS = ("coeff", "leaf")
_redc_override: Optional[str] = None


def set_fq_redc_backend(name: Optional[str]) -> None:
    """Pin the tower reduction placement ("coeff" = one REDC per output
    coefficient over wide columns, "leaf" = one REDC per bilinear leaf
    product — the differential oracle); None returns control to the
    CSTPU_FQ_REDC environment variable (default "coeff")."""
    global _redc_override
    assert name is None or name in _REDC_BACKENDS, name
    _redc_override = name


def fq_redc_backend_name() -> str:
    name = _redc_override or os.environ.get("CSTPU_FQ_REDC", "coeff")
    if name not in _REDC_BACKENDS:
        raise ValueError(
            f"CSTPU_FQ_REDC must be one of {_REDC_BACKENDS}, got {name!r}")
    return name


@contextlib.contextmanager
def pinned_fq_redc_backend(name: str):
    """Pin the backend for a scope — ops/bls_jax.py wraps every call into
    its mode-keyed jitted pairing programs with this, so the mode read at
    TRACE time always matches the program being traced."""
    # trace-time-once is the POINT here: the write pins the backend for
    # the duration of tracing (bls_jax._redc_mode_jit keys one program
    # per mode); nothing reads the global at run time.
    # csa: ignore[CSA302]
    global _redc_override
    assert name in _REDC_BACKENDS, name
    prev = _redc_override
    _redc_override = name
    try:
        yield
    finally:
        _redc_override = prev


# Trace-time REDC accounting: every fq_redc call (fq_mul included) adds its
# static lane count — prod(batch shape) of the stacked reduction — so
# tracing a program with the counters reset yields its traced-graph REDC
# instance/lane totals (loop bodies count once). The counts live in the
# telemetry metrics registry (`fq.redc.instances` / `fq.redc.lanes`,
# `always=True`: trace-time accounting that tests assert regardless of the
# CSTPU_TELEMETRY switch); reset_redc_trace_stats/redc_trace_stats stay as
# thin shims for tests/test_fq_redc.py and tests/test_telemetry.py.
_REDC_INSTANCES = _tele_counter("fq.redc.instances", always=True)
_REDC_LANES = _tele_counter("fq.redc.lanes", always=True)


def reset_redc_trace_stats() -> None:
    _REDC_INSTANCES.reset()
    _REDC_LANES.reset()


def redc_trace_stats() -> dict:
    return {"instances": int(_REDC_INSTANCES.value),
            "lanes": int(_REDC_LANES.value)}


# ---------------------------------------------------------------------------
# Normalization (device)
# ---------------------------------------------------------------------------

def _carry_rounds(t, n: int):
    """n rounds of vectorized carry/borrow propagation (value-preserving:
    the top limb keeps its own overflow in place, so values up to int64
    range at the top limb survive; callers keep |value| < ~2^395 narrow /
    < q*R wide). Length-generic: works on [..., L] elements and
    [..., 2L] wide columns alike.

    Under `staged_helpers()` (the value-range tier's tracing context,
    `make ranges`) the body routes through a jitted twin so the call
    boundary survives into enclosing jaxprs as a NAMED pjit eqn, which
    the interval interpreter replaces with its EXACT per-position
    transfer — new[k] = (old[k] & MASK) + (old[k-1] >> B), top =
    old[top] + (old[top-1] >> B) — because the positional interval
    domain cannot see the (x & MASK) + ((x >> B) << B) == x cancellation
    and would otherwise grow the top limb ~2^29 per round. Production
    and test paths keep the helper inlined: an always-on jit boundary
    measured ~5x slower on the eager scalar-mul chains (per-call
    dispatch on a micro-op), and nested jit inlines at lowering anyway,
    so the staged and inline forms compile identically."""
    if _STAGE_HELPERS:
        return _carry_rounds_staged(t, n)
    return _carry_rounds_impl(t, n)


def _carry_rounds_impl(t, n: int):
    for _ in range(n):
        lo = t & MASK
        hi = t >> B          # arithmetic shift: borrows propagate as -1
        top = hi[..., -1]
        up = jnp.concatenate(
            [jnp.zeros_like(hi[..., :1]), hi[..., :-1]], axis=-1)
        t = lo + up
        t = t.at[..., -1].add(top << B)
    return t


_carry_rounds_staged = partial(jax.jit, static_argnums=(1,))(
    _carry_rounds_impl)

_STAGE_HELPERS = False


@contextlib.contextmanager
def staged_helpers():
    """Trace-scope switch: stage _carry_rounds as a named jit call so
    analysis tiers can see (and summarize) the helper boundary. The
    range engine enters this around every contract trace; nothing else
    should."""
    # trace-time-once is the point (the flag pins staging for the
    # duration of one make_jaxpr; nothing reads it at run time)
    global _STAGE_HELPERS
    prev = _STAGE_HELPERS
    _STAGE_HELPERS = True
    try:
        yield
    finally:
        _STAGE_HELPERS = prev


def fq_norm(a, rounds: int = 3):
    """Crush limb magnitudes: 3 rounds bring |limb| <= 2^33 inputs into
    [-1, 2^29] (a stable lazy form — products still fit int64 columns).
    Use NORM_FULL rounds for the unique signed-top representation."""
    return _carry_rounds(a, rounds)


def fq_wide_norm(t, rounds: int = 3):
    """Value-preserving carry rounds over [..., 2L] wide columns: 3 rounds
    crush raw schoolbook columns (|col| <= 14*2^58 < 2^62) into
    [-1, 2^29] — except the TOP column, which keeps the value spill in
    place (|top| ~ value >> 29*27, a handful for in-budget values) —
    restoring the headroom that >2-term wide accumulation (the tower's
    gamma combinations, fan-in up to 36) needs: the interposed round the
    laziness budget (module docstring) and the CSA901 analyzer rule
    require."""
    return _carry_rounds(jnp.asarray(t), rounds)


# ---------------------------------------------------------------------------
# Lazy arithmetic (device) — single-op add/sub/neg
# ---------------------------------------------------------------------------

def fq_add(a, b):
    return a + b


def fq_sub(a, b):
    return a - b


def fq_neg(a):
    return -a


def fq_select(cond, a, b):
    """where(cond, a, b) broadcasting cond over the limb axis."""
    return jnp.where(cond[..., None], a, b)


def fq_zeros(shape=()):
    return jnp.zeros(tuple(shape) + (L,), dtype=jnp.int64)


def fq_ones(shape=()):
    """Montgomery one (R mod q), broadcast to shape."""
    one = jnp.asarray(to_mont(1))
    return jnp.broadcast_to(one, tuple(shape) + (L,))


# ---------------------------------------------------------------------------
# Multiplication (device)
# ---------------------------------------------------------------------------

# static pre-shifted copies of q's limbs 1..L-1 for the interleaved
# reduction (limb 0 is folded into the running carry): row i holds q[1..13]
# at columns i+1..i+13
_Q_SHIFTS = np.zeros((L, 2 * L), dtype=np.int64)
for _i in range(L):
    _Q_SHIFTS[_i, _i + 1:_i + L] = _Q_NP[1:]


def fq_mul_wide(a, b):
    """Schoolbook double-width product — NO reduction. [..., L] x [..., L]
    -> [..., 2L] int64 columns with cols[k] = sum_{i+j=k} a_i b_j.

    Inputs: limbs |l| < ~2^32 (three defensive carry rounds bring them to
    [-1, 2^29]), values per the narrow laziness budget. Output columns
    reach 14*2^58 < 2^62 — NOT accumulable more than two deep without an
    interposed fq_wide_norm (see the module docstring's wide budget).

    TPU-legal by construction: the v5e has no 64-bit integer dot unit, so
    the schoolbook is L unrolled shifted adds of elementwise products —
    never a matmul."""
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape)
    b = jnp.broadcast_to(b, shape)
    a = _carry_rounds(a, 3)
    b = _carry_rounds(b, 3)
    pad = [(0, 0)] * (len(shape) - 1)
    return sum(
        jnp.pad(a[..., i:i + 1] * b, pad + [(i, L - i)]) for i in range(L))


def fq_wide_from_mont(a):
    """Montgomery element [..., L] -> wide columns [..., 2L] carrying the
    value a*R (limbs shifted up L columns after a defensive
    normalization), so `fq_redc` maps it back to a mod q.

    Value-window caveat: the lift is mod-q exact but NOT contracting —
    the wide value is |a|*R, so mixing it into a REDC input pushes the
    output window out by |a| (fq_redc returns values in (v/R - q, v/R +
    q)). One-shot additive mixes are fine; ITERATED passthroughs (the
    cyclotomic squaring chain) must instead enter as a reduction-free
    wide multiply by one (value |a|*(R mod q) <= |a|*q — see
    fq_tower.fq12_cyclo_sqr), or the window doubles per step and escapes
    |v| < q*R after ~25 squarings."""
    a = _carry_rounds(jnp.asarray(a), 3)
    return jnp.concatenate([jnp.zeros_like(a), a], axis=-1)


def fq_redc(cols):
    """Interleaved Montgomery reduction: [..., 2L] wide columns of value v
    -> [..., L] limbs of value v * R^-1 mod q — LAZY out.

    Input bound (the laziness budget, asserted against exact host bignums
    in tests/test_fq_redc.py): limbs |col| < 2^35 (the 64-abs-fan-in
    gamma ceiling x 2^29 — raw fq_mul_wide columns at 14*2^58 < 2^62 are
    fine too, but only ONE deep; >2-term accumulations must interpose
    fq_wide_norm first) and |value| < q*R. Output: limbs in [-1, 2^29],
    value in (-2q, 2q). No conditional subtracts.

    The 14-step reduction is unrolled at ~8 ops per step; m and the carry
    are sign-correct (& MASK works on two's complement, >> is arithmetic
    = exact floor division since v + m*q0 is divisible by 2^B). Batch
    leading axes aggressively — the per-lane cost is why the tower
    reduces per output coefficient, not per leaf."""
    cols = jnp.asarray(cols)
    shape = cols.shape
    assert shape[-1] == 2 * L, shape
    lanes = 1
    for d in shape[:-1]:
        lanes *= int(d)
    _REDC_INSTANCES.inc()
    _REDC_LANES.inc(lanes)
    carry = jnp.zeros(shape[:-1], dtype=jnp.int64)
    qinv = jnp.int64(QINV_NEG)
    mask = jnp.int64(MASK)
    q0 = jnp.int64(int(_Q_NP[0]))
    for i in range(L):
        v = cols[..., i] + carry
        m = ((v & mask) * qinv) & mask
        carry = (v + m * q0) >> B
        cols = cols + m[..., None] * jnp.asarray(_Q_SHIFTS[i])
    upper = cols[..., L:].at[..., 0].add(carry)
    return _carry_rounds(upper, 3)


def fq_mul(a, b):
    """Montgomery product a*b*R^-1 mod q — LAZY in and out: exactly
    fq_redc(fq_mul_wide(a, b)). See those for the bounds; output limbs in
    [-1, 2^29], value in (-2q, 2q)."""
    return fq_redc(fq_mul_wide(a, b))


def fq_sqr(a):
    return fq_mul(a, a)


# ---------------------------------------------------------------------------
# Boundary ops: canonicalization, equality (device)
# ---------------------------------------------------------------------------

def _reduce_range(a):
    """a (any lazy value within budget) -> value-equivalent limbs with value
    in (-2q, 2q): one Montgomery multiply by R (= to_mont(1)), which maps
    x -> x * R * R^-1 = x mod q without leaving the Montgomery domain."""
    return fq_mul(a, fq_ones(a.shape[:-1]))


def fq_is_zero(a):
    y = _carry_rounds(_reduce_range(a), NORM_FULL)

    def match(pat):
        return jnp.all(y == jnp.asarray(pat), axis=-1)

    # value in (-2q, 2q) and ≡ 0 mod q  <=>  value in {-q, 0, q}
    return match(_ZERO_PAT) | match(_Q_PAT) | match(_NEGQ_PAT)


def fq_eq(a, b):
    return fq_is_zero(a - b)


def fq_canon(a):
    """Unique canonical limbs in [0, q) (for compression/host/hashing)."""
    t = _carry_rounds(_reduce_range(a), NORM_FULL)   # value in (-2q, 2q)
    neg = t[..., -1] < 0
    t = jnp.where(neg[..., None], t + jnp.asarray(_Q2_NP), t)  # -> [0, 2q)
    t = _carry_rounds(t, NORM_FULL)
    d = _carry_rounds(t - jnp.asarray(_Q_NP), NORM_FULL)
    return jnp.where((d[..., -1] >= 0)[..., None], d, t)


# ---------------------------------------------------------------------------
# Exponentiation: inversion, square roots (device)
# ---------------------------------------------------------------------------

def _exp_bits(e: int) -> np.ndarray:
    """Static exponent -> bit array (MSB first) for fori_loop exponentiation."""
    bits = bin(e)[2:]
    return np.frombuffer(bits.encode(), dtype=np.uint8) - ord("0")


_INV_EXP_BITS = _exp_bits(Q - 2)
_SQRT_EXP_BITS = _exp_bits((Q + 1) // 4)

# Fixed-window width for the static exponents (q-2, (q+1)/4): the
# multiply-count sweet spot (2^w - 2 table muls + ceil(nbits/w) walk muls;
# w=4 at 381 bits: 109 vs 381 per-bit select-muls, a 3.5x cut — w=5's
# bigger table already costs more than the walk saves).
_POW_WINDOW = 4


def _exp_window_digits(bits_np: np.ndarray, w: int) -> np.ndarray:
    """Host: MSB-first bit array -> [ceil(n/w)] int32 w-bit window digits
    (MSB-window first, zero-padded at the top) — the exponent-level
    analogue of ops/scalar_mul's host recoding: static data, never
    traced."""
    n = int(bits_np.shape[0])
    m = -(-n // w)
    padded = np.concatenate(
        [np.zeros(m * w - n, np.uint8), bits_np.astype(np.uint8)])
    weights = 1 << np.arange(w - 1, -1, -1, dtype=np.int64)
    return (padded.reshape(m, w) @ weights).astype(np.int32)


def pow_static_muls(nbits: int, w: int) -> int:
    """Analytic multiply count of the windowed walk (squarings excluded —
    both paths square once per bit): table build + one gathered multiply
    per window. The per-bit oracle pays `nbits` select-muls."""
    return ((1 << w) - 2) + (-(-nbits // w) - 1)


def _fq_pow_static(a, bits_np: np.ndarray, w: Optional[int] = None):
    """a^e with e a static bit array — fixed-window evaluation.

    Device: a power table [a^0 .. a^(2^w - 1)] built by one fori chain
    (scattered into a stacked table axis, so the traced graph holds ONE
    fq_mul instance), then ceil(nbits/w) trips of (w squarings + ONE
    gathered multiply). Zero digits multiply by table[0] = one — regular
    structure, no select. Digits are host-recoded static int32s
    (_exp_window_digits); the per-bit form (_fq_pow_static_per_bit) stays
    as the differential oracle in tests."""
    if w is None:
        w = _POW_WINDOW
    digits_np = _exp_window_digits(bits_np, w)
    m = int(digits_np.shape[0])
    a = fq_norm(a)
    n_tab = 1 << w
    ones = fq_ones(a.shape[:-1])
    table = jnp.broadcast_to(ones[None], (n_tab,) + ones.shape)
    table = table.at[1].set(a)

    def tab_body(j, tab):
        return tab.at[j].set(fq_mul(jnp.take(tab, j - 1, axis=0), a))

    if n_tab > 2:
        table = jax.lax.fori_loop(2, n_tab, tab_body, table)
    digits = jnp.asarray(digits_np)

    def body(i, acc):
        acc = jax.lax.fori_loop(0, w, lambda j, x: fq_mul(x, x), acc)
        return fq_mul(acc, jnp.take(table, digits[i], axis=0))

    acc = jnp.take(table, digits[0], axis=0)
    if m > 1:
        acc = jax.lax.fori_loop(1, m, body, acc)
    return acc


def _fq_pow_static_per_bit(a, bits_np: np.ndarray):
    """a^e, one square + select-mul per bit — the windowed walk's
    differential oracle (tests/test_fq_redc.py)."""
    bits = jnp.asarray(bits_np.astype(np.uint8))
    n = int(bits_np.shape[0])
    a = fq_norm(a)

    def body(i, acc):
        acc = fq_mul(acc, acc)
        mul = fq_mul(acc, a)
        return fq_select(bits[i] == 1, mul, acc)

    return jax.lax.fori_loop(0, n, body, fq_ones(a.shape[:-1]))


def fq_inv(a):
    """a^(q-2) — batched Fermat inversion (Montgomery in, Montgomery out)."""
    return _fq_pow_static(a, _INV_EXP_BITS)


def fq_sqrt_candidate(a):
    """a^((q+1)/4): THE square root if a is a QR (q = 3 mod 4); else garbage.

    Caller must check candidate^2 == a (reference decompress_g1,
    crypto/bls12_381.py:361-378 does the same check)."""
    return _fq_pow_static(a, _SQRT_EXP_BITS)


# ---------------------------------------------------------------------------
# Value-range contracts (tools/analysis/ranges/, `make ranges`)
# ---------------------------------------------------------------------------
# The laziness budget as machine-checked theorems over the real jaxprs:
# each contract declares the documented input intervals (body limbs +
# the top-limb value spill, positional along the trailing axis) and the
# interval interpreter PROVES the declared output bound and the absence
# of int64 wraparound anywhere in the traced program. Shapes carry a
# small leading batch axis — the kernels are elementwise over batch, and
# batched indexing stages positional slice/scatter ops the interpreter
# tracks exactly.

def _narrow_spec():
    """The lazy narrow-domain input budget (module docstring)."""
    return {"lo": -NARROW_INPUT_BOUND, "hi": NARROW_INPUT_BOUND,
            "top_lo": -NARROW_TOP_SPILL, "top_hi": NARROW_TOP_SPILL}


def _canonical_spec():
    """Canonical elements x < q: limbs in [0, 2^29), top <= q >> 377."""
    return {"lo": 0, "hi": MASK, "top_lo": 0, "top_hi": CANONICAL_TOP}


def _norm_out_spec(top_lo, top_hi):
    return {"lo": NARROW_LIMB_LO, "hi": NARROW_LIMB_HI,
            "top_lo": top_lo, "top_hi": top_hi}


def _z(shape):
    return jnp.zeros(shape, jnp.int64)


RANGE_CONTRACTS = [
    dict(
        # the schoolbook at canonical operands: every column <= 14*2^58
        # and column 27 is IDENTICALLY ZERO (the structural fact that
        # keeps chained fq_mul top limbs small)
        name="ops.fq.fq_mul_wide",
        build=lambda: dict(fn=fq_mul_wide, args=(_z((2, L)), _z((2, L))),
                           ranges=(_canonical_spec(), _canonical_spec())),
        output={"lo": -WIDE_COL_RAW, "hi": WIDE_COL_RAW,
                "top_lo": 0, "top_hi": 0},
    ),
    dict(
        # fq_redc's documented input budget -> lazy output: body limbs
        # land in [-16, 2^29], the top keeps only the value spill
        name="ops.fq.fq_redc",
        build=lambda: dict(
            fn=fq_redc, args=(_z((2, 2 * L)),),
            ranges=({"lo": -WIDE_COL_BUDGET, "hi": WIDE_COL_BUDGET,
                     "top_lo": -WIDE_TOP_SPILL, "top_hi": WIDE_TOP_SPILL},)),
        output=_norm_out_spec(-(1 << 39), 1 << 39),
    ),
    dict(
        # the composed Montgomery product from the full lazy budget:
        # no int64 wrap anywhere, output back inside the narrow budget
        # with a tiny top limb (mul_wide's zero column 27 in action)
        name="ops.fq.fq_mul",
        build=lambda: dict(fn=fq_mul, args=(_z((2, L)), _z((2, L))),
                           ranges=(_narrow_spec(), _narrow_spec())),
        output=_norm_out_spec(-64, 64),
    ),
    dict(
        # three rounds crush the narrow body to [-16, 2^29] (top limb is
        # value-preserving: it keeps the input spill)
        name="ops.fq.fq_norm",
        build=lambda: dict(
            fn=fq_norm, args=(_z((2, L)),),
            ranges=({"lo": -(1 << 33), "hi": 1 << 33},)),
        output=_norm_out_spec(-((1 << 33) + 64), (1 << 33) + 64),
    ),
    dict(
        # the wide re-normalization that buys gamma its accumulation
        # headroom: raw schoolbook columns back to a [-16, 2^29] body
        name="ops.fq.fq_wide_norm",
        build=lambda: dict(
            fn=fq_wide_norm, args=(_z((2, 2 * L)),),
            ranges=({"lo": -WIDE_COL_RAW, "hi": WIDE_COL_RAW},)),
        output=_norm_out_spec(-(1 << 62), 1 << 62),
    ),
]
