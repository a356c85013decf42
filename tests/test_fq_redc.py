"""Double-width lazy Montgomery (CSTPU_FQ_REDC=coeff): fq_mul_wide /
fq_wide_norm / fq_redc against exact Python bignums, the coeff-vs-leaf
tower bit-exactness, and the traced REDC lane counts.

Three layers, mirroring tests/test_scalar_mul.py's structure: the host
oracle algebra on the wide-column representation (exact ints, including
worst-case-magnitude limbs at the documented laziness budget), device
bit-exactness of every tower op across both backends, and the op-count
model — REDC instances/lanes counted in the actual traced jaxprs (each
REDC contributes exactly L multiplies by the Montgomery constant
QINV_NEG, a 29-bit value nothing else in the program multiplies by).
"""
import random

import numpy as np
import pytest

import jax.numpy as jnp

from consensus_specs_tpu.crypto import bls12_381 as gt
from consensus_specs_tpu.ops import fq as F
from consensus_specs_tpu.ops import fq_tower as T

rng = random.Random(0x2EDC)

Q = gt.q
R = F.R_MONT
QR = Q * (1 << (F.B * F.L))
RINV = pow(R, -1, Q)


def rand_fq():
    return rng.randrange(Q)


def fq_batch(values):
    return np.stack([F.to_mont(v) for v in values])


def wide_to_int(cols) -> int:
    """Exact (un-reduced) value of a [2L] wide-column array."""
    cols = np.asarray(cols)
    return sum(int(cols[..., i]) << (F.B * i) for i in range(2 * F.L))


def redc_oracle(cols) -> int:
    """What fq_redc must compute: value * R^-1 mod q."""
    return wide_to_int(cols) * RINV % Q


# ---------------------------------------------------------------------------
# Backend knob
# ---------------------------------------------------------------------------

def test_backend_knob_and_env(monkeypatch):
    """Mirrors CSTPU_SCALAR_MUL's override/env semantics."""
    assert F.fq_redc_backend_name() == "coeff"   # default
    F.set_fq_redc_backend("leaf")
    try:
        assert F.fq_redc_backend_name() == "leaf"
    finally:
        F.set_fq_redc_backend(None)
    assert F.fq_redc_backend_name() == "coeff"
    with pytest.raises(AssertionError):
        F.set_fq_redc_backend("bogus")
    monkeypatch.setenv("CSTPU_FQ_REDC", "nope")
    with pytest.raises(ValueError):
        F.fq_redc_backend_name()
    monkeypatch.setenv("CSTPU_FQ_REDC", "leaf")
    assert F.fq_redc_backend_name() == "leaf"
    with F.pinned_fq_redc_backend("coeff"):
        assert F.fq_redc_backend_name() == "coeff"
    assert F.fq_redc_backend_name() == "leaf"


# ---------------------------------------------------------------------------
# fq_mul_wide / fq_wide_norm / fq_redc vs exact host bignums
# ---------------------------------------------------------------------------

def test_mul_wide_then_redc_is_fq_mul():
    """fq_redc(fq_mul_wide(a, b)) is bit-identical to fq_mul(a, b) (the
    refactor is a pure split) and equals a*b under the bignum oracle."""
    a_vals = [0, 1, Q - 1] + [rand_fq() for _ in range(8)]
    b_vals = [Q - 1, 1, 0] + [rand_fq() for _ in range(8)]
    a, b = fq_batch(a_vals), fq_batch(b_vals)
    wide = F.fq_mul_wide(a, b)
    assert wide.shape == a.shape[:-1] + (2 * F.L,)
    out = np.asarray(F.fq_redc(wide))
    assert np.array_equal(out, np.asarray(F.fq_mul(a, b)))
    for i, (x, y) in enumerate(zip(a_vals, b_vals)):
        # wide columns hold the exact double-width product of the
        # (carry-normalized) Montgomery representatives
        assert wide_to_int(np.asarray(wide)[i]) % Q == (
            (x * R % Q) * (y * R % Q)) % Q
        assert F.from_mont(out[i]) == x * y % Q


def test_wide_norm_value_preserving_and_crushing():
    """fq_wide_norm preserves the exact column value and crushes non-top
    limb magnitudes from the raw-product scale (~2^61) into [-1, 2^29].
    The TOP column keeps the value spill in place (value-preserving by
    design — its weight has nowhere to carry to), bounded by the
    neighbor's carry: < 2^61 >> 29 + 2^30 here, and ~8 per accumulated
    term for in-budget pipeline values (< q*R)."""
    nprng = np.random.default_rng(0xA11CE)
    cols = nprng.integers(-(1 << 61), 1 << 61, (6, 2 * F.L), dtype=np.int64)
    out = np.asarray(F.fq_wide_norm(jnp.asarray(cols)))
    for i in range(cols.shape[0]):
        assert wide_to_int(out[i]) == wide_to_int(cols[i])
        body = out[i][:-1]
        assert body.min() >= -1 and body.max() <= (1 << F.B)
        # the top column keeps its own input magnitude plus the spill
        assert abs(int(out[i][-1])) < (1 << 61) + (1 << 33)

    # in-budget shape: the top column of a real (raw-product) wide array
    # is carry-only, so the stable spill is small
    a = fq_batch([rand_fq() for _ in range(4)])
    b = fq_batch([rand_fq() for _ in range(4)])
    prod = np.asarray(F.fq_wide_norm(F.fq_mul_wide(a, b)))
    assert prod.min() >= -1 and prod.max() <= (1 << (F.B + 1))


def test_redc_adversarial_budget_inputs():
    """fq_redc at the documented laziness budget: limbs at the full
    +/-(2^35 - 1) magnitude (the gamma fan-in ceiling 64 x 2^29) on every
    column the value bound |v| < q*R permits, checked against the exact
    host bignum, with the output contract (value in (-2q, 2q), limbs in
    [-1, 2^29]) asserted too."""
    lim = (1 << 35) - 1
    cases = []
    top = np.zeros(2 * F.L, np.int64)
    top[:26] = lim                      # all-max positive
    cases.append(top)
    cases.append(-top)                  # all-max negative
    nprng = np.random.default_rng(0xB16)
    for _ in range(8):
        c = nprng.integers(-lim, lim + 1, 2 * F.L).astype(np.int64)
        c[26:] = 0                      # keep |value| < q*R
        cases.append(c)
    cols = np.stack(cases)
    for c in cases:
        assert abs(wide_to_int(c)) < QR
    out = np.asarray(F.fq_redc(jnp.asarray(cols)))
    for i, c in enumerate(cases):
        assert F.limbs_to_int(out[i]) == redc_oracle(c)
        assert out[i].min() >= -1 and out[i].max() <= (1 << F.B)
        val = sum(int(out[i][k]) << (F.B * k) for k in range(F.L))
        assert -2 * Q < val < 2 * Q


def test_redc_gamma_shaped_accumulation():
    """The coeff pipeline's exact shape: 36 wide products (the fq12_mul
    gamma fan-in ceiling), wide-normalized, accumulated with coefficients
    in {-2..2}, one REDC — vs the same accumulation in exact bignums."""
    n = 36
    a_vals = [rand_fq() for _ in range(n)]
    b_vals = [rand_fq() for _ in range(n)]
    coeffs = [rng.choice([-2, -1, 1, 2]) for _ in range(n)]
    wide = F.fq_wide_norm(F.fq_mul_wide(fq_batch(a_vals), fq_batch(b_vals)))
    acc = sum(int(c) * wide[i] for i, c in enumerate(coeffs))
    out = np.asarray(F.fq_redc(acc[None]))[0]
    # out value = sum( c * xR * yR ) * R^-1 = mont(sum c*x*y), so
    # from_mont strips the remaining R factor
    want = sum(c * x * y for c, x, y in zip(coeffs, a_vals, b_vals)) % Q
    assert F.from_mont(out) == want


def test_wide_from_mont_contributes_identity_through_redc():
    """fq_wide_from_mont lifts a Montgomery element into the wide domain
    with an extra R factor, so it passes through fq_redc unchanged — the
    cyclo-squaring passthrough path."""
    vals = [0, 1, Q - 1] + [rand_fq() for _ in range(5)]
    a = fq_batch(vals)
    lifted = F.fq_wide_from_mont(a)
    out = np.asarray(F.fq_redc(lifted))
    for i, v in enumerate(vals):
        assert F.from_mont(out[i]) == v
    # and it composes additively with real products
    prod = F.fq_wide_norm(F.fq_mul_wide(a, a))
    out2 = np.asarray(F.fq_redc(prod + 2 * lifted))
    for i, v in enumerate(vals):
        assert F.from_mont(out2[i]) == (v * v + 2 * v) % Q


# ---------------------------------------------------------------------------
# Tower ops: coeff vs leaf vs the bignum oracle
# ---------------------------------------------------------------------------

def rand_fq2():
    return gt.Fq2(rand_fq(), rand_fq())


def rand_fq12():
    return gt.Fq12(gt.Fq6(rand_fq2(), rand_fq2(), rand_fq2()),
                   gt.Fq6(rand_fq2(), rand_fq2(), rand_fq2()))


def fq2_batch(vals):
    return np.stack([T.fq2_to_limbs(v) for v in vals])


def fq12_batch(vals):
    return np.stack([T.fq12_to_limbs(v) for v in vals])


def fq12_out(arr):
    arr = np.asarray(arr)
    return [T.fq12_from_limbs(arr[i]) for i in range(arr.shape[0])]


def _both_backends(fn):
    out = {}
    for mode in ("leaf", "coeff"):
        F.set_fq_redc_backend(mode)
        try:
            out[mode] = fn()
        finally:
            F.set_fq_redc_backend(None)
    return out


def test_fq2_mul_backends_match_oracle():
    a_vals = [gt.FQ2_ZERO, gt.FQ2_ONE, gt.XI] + [rand_fq2() for _ in range(5)]
    b_vals = [rand_fq2() for _ in range(len(a_vals))]
    a, b = fq2_batch(a_vals), fq2_batch(b_vals)
    # lazy rep: +q on every limb of one operand must not change values
    lazy = a + np.asarray(F.int_to_limbs(Q))
    want = [x * y for x, y in zip(a_vals, b_vals)]
    res = _both_backends(lambda: (np.asarray(T.fq2_mul(a, b)),
                                  np.asarray(T.fq2_mul(lazy, b))))
    for mode, (r, rl) in res.items():
        got = [T.fq2_from_limbs(r[i]) for i in range(r.shape[0])]
        gotl = [T.fq2_from_limbs(rl[i]) for i in range(rl.shape[0])]
        assert got == want, mode
        assert gotl == want, mode


@pytest.mark.parametrize("op,n_ops", [
    ("mul", 2), ("sqr", 1), ("line", 4), ("cyclo", 1)])
def test_fq12_ops_backends_match_oracle(op, n_ops):
    if op == "cyclo":
        # cyclotomic-subgroup elements (the _pow_abs precondition)
        a_vals = []
        for _ in range(2):
            f = rand_fq12()
            easy = f.conj() * f.inv()
            a_vals.append((easy ** (gt.q ** 2)) * easy)
    else:
        a_vals = [gt.FQ12_ONE, rand_fq12(), rand_fq12()]
    a = fq12_batch(a_vals)
    if op == "mul":
        b_vals = [rand_fq12() for _ in a_vals]
        b = fq12_batch(b_vals)
        run = lambda: np.asarray(T.fq12_mul(a, b))
        want = [x * y for x, y in zip(a_vals, b_vals)]
    elif op == "sqr":
        run = lambda: np.asarray(T.fq12_sqr(a))
        want = [x.square() for x in a_vals]
    elif op == "line":
        zero2 = gt.Fq2(0, 0)
        c_a = [rand_fq2() for _ in a_vals]
        c_v = [rand_fq2() for _ in a_vals]
        c_vw = [rand_fq2() for _ in a_vals]
        run = lambda: np.asarray(T.fq12_mul_line(
            a, fq2_batch(c_a), fq2_batch(c_v), fq2_batch(c_vw)))
        want = [f * gt.Fq12(gt.Fq6(x, v, zero2), gt.Fq6(zero2, vw, zero2))
                for f, x, v, vw in zip(a_vals, c_a, c_v, c_vw)]
    else:
        run = lambda: np.asarray(T.fq12_cyclo_sqr(a))
        want = [g * g for g in a_vals]
    res = _both_backends(run)
    assert fq12_out(res["leaf"]) == want
    assert fq12_out(res["coeff"]) == want


def test_cyclo_sqr_chained_50_coeff():
    """The value-growth regression under the coeff backend: every chained
    squaring's passthrough now rides the output REDC (no explicit
    multiply-by-one normalization), so 50 chained squarings — longer than
    the BLS parameter's 47-zero run — must stay exact."""
    f = rand_fq12()
    easy = f.conj() * f.inv()
    g = (easy ** (gt.q ** 2)) * easy
    F.set_fq_redc_backend("coeff")
    try:
        chained = fq12_batch([g])
        for _ in range(50):
            chained = T.fq12_cyclo_sqr(chained)
        assert fq12_out(chained) == [g ** (2 ** 50)]
    finally:
        F.set_fq_redc_backend(None)


# ---------------------------------------------------------------------------
# Traced REDC lane counts (the acceptance bound)
# ---------------------------------------------------------------------------
# The jaxpr walkers (`fresh_jaxpr` / `qinv_mul_lanes`) this section
# hand-rolled through PR 8 now live in the shared tracer library the
# contract engine uses (tools/analysis/trace/tracer.py) — one source of
# truth for the REDC op model; these tests assert the same numbers the
# trace tier ratchets (`make contracts`).

from tools.analysis.trace import engine as trace_engine  # noqa: E402
from tools.analysis.trace import tracer  # noqa: E402

_fresh_jaxpr = tracer.fresh_jaxpr
qinv_mul_lanes = tracer.qinv_mul_lanes


@pytest.mark.parametrize("name,leaf_lanes,coeff_lanes", [
    ("fq2_mul", 3, 2),
    ("fq12_mul", 54, 12),
    ("fq12_sqr", 36, 12),
    ("fq12_mul_line", 39, 12),
    ("fq12_cyclo_sqr", 30, 12),
])
def test_redc_lane_counts_in_traced_programs(name, leaf_lanes, coeff_lanes):
    """The headline claim, asserted on the real jaxprs: 54→12 / 39→12 /
    36→12 / 30→12 REDC lanes per tower op (and 3→2 for fq2_mul), cross-
    checked against fq.py's trace-time lane counters."""
    z2 = jnp.zeros((2, F.L), jnp.int64)
    z12 = jnp.zeros((2, 3, 2, F.L), jnp.int64)
    progs = {
        "fq2_mul": (lambda: _fresh_jaxpr(T.fq2_mul, z2, z2)),
        "fq12_mul": (lambda: _fresh_jaxpr(T.fq12_mul, z12, z12)),
        "fq12_sqr": (lambda: _fresh_jaxpr(T.fq12_sqr, z12)),
        "fq12_mul_line": (lambda: _fresh_jaxpr(
            lambda f, c: T.fq12_mul_line(f, c, c, c), z12, z2)),
        "fq12_cyclo_sqr": (lambda: _fresh_jaxpr(T.fq12_cyclo_sqr, z12)),
    }
    for mode, want in (("leaf", leaf_lanes), ("coeff", coeff_lanes)):
        F.set_fq_redc_backend(mode)
        try:
            F.reset_redc_trace_stats()
            closed = progs[name]()
            stats = F.redc_trace_stats()
        finally:
            F.set_fq_redc_backend(None)
        assert qinv_mul_lanes(closed) == want, (name, mode)
        assert stats["lanes"] == want, (name, mode)
    ratio = leaf_lanes / coeff_lanes
    if name.startswith("fq12"):
        assert ratio >= 2.5, (name, ratio)


def test_grouped_pairing_traced_lane_cut():
    """The whole-path bound: the
    grouped Miller + final-exponentiation traced programs carry >=2.5x
    fewer REDC lanes under coeff than leaf."""
    from consensus_specs_tpu.ops import bls_jax as BJ
    g1 = jnp.zeros((1, 2, 2, F.L), jnp.int64)
    g2 = jnp.zeros((1, 2, 2, 2, F.L), jnp.int64)
    f12 = jnp.zeros((1, 2, 3, 2, F.L), jnp.int64)
    lanes = {}
    for mode in ("leaf", "coeff"):
        with F.pinned_fq_redc_backend(mode):
            F.reset_redc_trace_stats()
            _fresh_jaxpr(BJ.miller_loop_grouped, g1, g2)
            _fresh_jaxpr(BJ.final_exponentiation_3x, f12)
            lanes[mode] = F.redc_trace_stats()["lanes"]
    assert lanes["leaf"] >= 2.5 * lanes["coeff"], lanes


def test_fq_tower_contracts_clean_and_pinned():
    """The tower's lane counts asserted THROUGH the contract engine: the
    committed TRACE_CONTRACTS run clean against the committed
    trace_baseline.json, every budget is an exact pin the engine
    re-measured, and the pins match this file's expectation table — so
    the test suite and `make contracts` cannot drift apart."""
    want = {
        "fq2_mul": (3, 2), "fq12_mul": (54, 12), "fq12_sqr": (36, 12),
        "fq12_mul_line": (39, 12), "fq12_cyclo_sqr": (30, 12)}
    contracts = [c for c in trace_engine.discover()
                 if c["name"].startswith("ops.fq_tower.")]
    assert len(contracts) == 2 * len(want)
    report = trace_engine.run_contracts(contracts)
    assert report.findings == [], [f.message for f in report.findings]
    measured = {r.name: r.measured for r in report.results}
    for op, (leaf, coeff) in want.items():
        assert measured[f"ops.fq_tower.{op}[leaf]"]["redc_lanes"] == leaf
        assert measured[f"ops.fq_tower.{op}[coeff]"]["redc_lanes"] == coeff
    # the pairing-path contracts' exact pins carry the >=2.5x whole-path
    # lane cut (miller + verdict, leaf vs coeff) as committed budgets
    budgets = {c["name"]: c["budgets"] for c in trace_engine.discover()
               if c["name"].startswith("ops.bls_jax.")}
    leaf_total = (budgets["ops.bls_jax.miller_loop_grouped[leaf]"]["redc_lanes"]
                  + budgets["ops.bls_jax.grouped_verdict[leaf]"]["redc_lanes"])
    coeff_total = (
        budgets["ops.bls_jax.miller_loop_grouped[coeff]"]["redc_lanes"]
        + budgets["ops.bls_jax.grouped_verdict[coeff]"]["redc_lanes"])
    assert leaf_total >= 2.5 * coeff_total, (leaf_total, coeff_total)


# ---------------------------------------------------------------------------
# Windowed static exponentiation (fq_inv / fq_sqrt_candidate)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w", [1, 2, 4])
def test_pow_static_windowed_matches_per_bit_and_host(w):
    vals = [1, Q - 1] + [rand_fq() for _ in range(3)]
    a = fq_batch(vals)
    exps = [3, 0b10110111, rng.randrange(1, 1 << 64)]
    for e in exps:
        bits = F._exp_bits(e)
        win = np.asarray(F._fq_pow_static(a, bits, w=w))
        ref = np.asarray(F._fq_pow_static_per_bit(a, bits))
        for i, v in enumerate(vals):
            want = pow(v, e, Q)
            assert F.from_mont(win[i]) == want, (e, w, i)
            assert F.from_mont(ref[i]) == want, (e, i)


def test_inv_and_sqrt_use_windowed_path():
    """fq_inv / fq_sqrt_candidate ride the windowed walk by default and
    still match the host oracle (table muls included)."""
    vals = [1, Q - 1] + [rand_fq() for _ in range(3)]
    a = fq_batch(vals)
    inv = np.asarray(F.fq_inv(a))
    for i, v in enumerate(vals):
        assert F.from_mont(inv[i]) == pow(v, -1, Q)
    sq = [pow(rand_fq(), 2, Q) for _ in range(3)]
    cands = np.asarray(F.fq_sqrt_candidate(fq_batch(sq)))
    for v, c in zip(sq, cands):
        r = F.from_mont(c)
        assert r * r % Q == v
    # the windowed walk multiplies ~nbits/w + 2^w times instead of ~nbits
    per_bit = int(F._INV_EXP_BITS.shape[0])
    windowed = F.pow_static_muls(per_bit, F._POW_WINDOW)
    assert per_bit >= 2.5 * windowed, (per_bit, windowed)


# ---------------------------------------------------------------------------
# Full-path verdict parity (slow: two extra pairing compiles)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_grouped_pairing_verdict_parity_across_modes():
    """grouped_pairing_check verdicts are bit-identical between the leaf
    and coeff backends — one genuinely-cancelling group (e(P,Q)*e(-P,Q))
    and one non-identity group (e(P,Q)^2)."""
    from consensus_specs_tpu.ops import bls_jax as BJ
    P = gt.G1_GEN
    Qp = gt.G2_GEN
    negP = gt.ec_neg(P)
    g1 = np.stack([
        np.stack([BJ.g1_to_limbs(P), BJ.g1_to_limbs(negP)]),
        np.stack([BJ.g1_to_limbs(P), BJ.g1_to_limbs(P)]),
    ])
    g2 = np.stack([
        np.stack([BJ.g2_to_limbs(Qp), BJ.g2_to_limbs(Qp)]),
        np.stack([BJ.g2_to_limbs(Qp), BJ.g2_to_limbs(Qp)]),
    ])
    res = _both_backends(lambda: np.asarray(
        BJ.grouped_pairing_check(jnp.asarray(g1), jnp.asarray(g2))))
    assert res["leaf"].tolist() == [True, False]
    assert res["coeff"].tolist() == [True, False]
