"""Differential tests: JAX limb-array field tower vs the bignum ground truth.

Every op in ops/fq.py and ops/fq_tower.py is checked bit-for-bit against
crypto/bls12_381.py on random values and the edge cases 0, 1, q-1. These are
the building blocks of the TPU pairing (ops/bls_jax.py); a subtle Montgomery
or Frobenius bug here corrupts every signature check above, so the tower gets
its own oracle suite (the gap ADVICE round 1 flagged).
"""
import random

import numpy as np
import pytest

from consensus_specs_tpu.crypto import bls12_381 as gt
from consensus_specs_tpu.ops import fq as F
from consensus_specs_tpu.ops import fq_tower as T

rng = random.Random(0xB15)

EDGE = [0, 1, gt.q - 1]


def rand_fq():
    return rng.randrange(gt.q)


def fq_batch(values):
    """ints -> [N, L] Montgomery device array."""
    return np.stack([F.to_mont(v) for v in values])


def fq_out(arr):
    return [F.from_mont(np.asarray(arr)[i]) for i in range(np.asarray(arr).shape[0])]


# ---------------------------------------------------------------------------
# Fq
# ---------------------------------------------------------------------------

def test_fq_roundtrip():
    vals = EDGE + [rand_fq() for _ in range(5)]
    assert fq_out(fq_batch(vals)) == vals


def test_fq_add_sub_neg():
    a_vals = EDGE + [rand_fq() for _ in range(8)]
    b_vals = [rand_fq() for _ in range(len(a_vals) - 1)] + [gt.q - 1]
    a, b = fq_batch(a_vals), fq_batch(b_vals)
    assert fq_out(F.fq_add(a, b)) == [(x + y) % gt.q for x, y in zip(a_vals, b_vals)]
    assert fq_out(F.fq_sub(a, b)) == [(x - y) % gt.q for x, y in zip(a_vals, b_vals)]
    assert fq_out(F.fq_neg(a)) == [(-x) % gt.q for x in a_vals]


def test_fq_mul():
    a_vals = EDGE + [rand_fq() for _ in range(8)]
    b_vals = [gt.q - 1, 1, 0] + [rand_fq() for _ in range(8)]
    out = fq_out(F.fq_mul(fq_batch(a_vals), fq_batch(b_vals)))
    assert out == [x * y % gt.q for x, y in zip(a_vals, b_vals)]


def test_fq_inv():
    vals = [1, gt.q - 1] + [rand_fq() for _ in range(4)]
    out = fq_out(F.fq_inv(fq_batch(vals)))
    assert out == [pow(v, -1, gt.q) for v in vals]


def test_fq_sqrt_candidate():
    # squares -> candidate recovers a root; non-residues -> candidate fails check
    sq = [pow(rand_fq(), 2, gt.q) for _ in range(4)]
    cands = fq_out(F.fq_sqrt_candidate(fq_batch(sq)))
    for v, c in zip(sq, cands):
        assert c * c % gt.q == v
    # find a non-residue (Euler criterion) and confirm the candidate is garbage
    while True:
        nr = rand_fq()
        if pow(nr, (gt.q - 1) // 2, gt.q) == gt.q - 1:
            break
    c = fq_out(F.fq_sqrt_candidate(fq_batch([nr])))[0]
    assert c * c % gt.q != nr


# ---------------------------------------------------------------------------
# Fq2 / Fq6 / Fq12
# ---------------------------------------------------------------------------

def rand_fq2():
    return gt.Fq2(rand_fq(), rand_fq())


def rand_fq6():
    return gt.Fq6(rand_fq2(), rand_fq2(), rand_fq2())


def rand_fq12():
    return gt.Fq12(rand_fq6(), rand_fq6())


def fq2_batch(vals):
    return np.stack([T.fq2_to_limbs(v) for v in vals])


def fq2_out(arr):
    arr = np.asarray(arr)
    return [T.fq2_from_limbs(arr[i]) for i in range(arr.shape[0])]


def test_fq2_ops():
    a_vals = [gt.FQ2_ZERO, gt.FQ2_ONE, gt.XI] + [rand_fq2() for _ in range(5)]
    b_vals = [rand_fq2() for _ in range(len(a_vals))]
    a, b = fq2_batch(a_vals), fq2_batch(b_vals)
    assert fq2_out(T.fq2_mul(a, b)) == [x * y for x, y in zip(a_vals, b_vals)]
    assert fq2_out(T.fq2_sqr(a)) == [x.square() for x in a_vals]
    assert fq2_out(T.fq2_add(a, b)) == [x + y for x, y in zip(a_vals, b_vals)]
    assert fq2_out(T.fq2_sub(a, b)) == [x - y for x, y in zip(a_vals, b_vals)]
    assert fq2_out(T.fq2_conj(a)) == [x.conj() for x in a_vals]
    assert fq2_out(T.fq2_mul_xi(a)) == [x * gt.XI for x in a_vals]


def test_fq2_inv():
    vals = [gt.FQ2_ONE, gt.Fq2(0, 1)] + [rand_fq2() for _ in range(3)]
    assert fq2_out(T.fq2_inv(fq2_batch(vals))) == [v.inv() for v in vals]


def fq6_batch(vals):
    return np.stack([T.fq6_to_limbs(v) for v in vals])


def fq6_out(arr):
    arr = np.asarray(arr)
    return [T.fq6_from_limbs(arr[i]) for i in range(arr.shape[0])]


def test_fq6_ops():
    a_vals = [gt.FQ6_ONE] + [rand_fq6() for _ in range(3)]
    b_vals = [rand_fq6() for _ in range(len(a_vals))]
    a, b = fq6_batch(a_vals), fq6_batch(b_vals)
    assert fq6_out(T.fq6_mul(a, b)) == [x * y for x, y in zip(a_vals, b_vals)]
    assert fq6_out(T.fq6_mul_by_v(a)) == [x.mul_by_v() for x in a_vals]
    assert fq6_out(T.fq6_inv(fq6_batch(b_vals))) == [v.inv() for v in b_vals]


def fq12_batch(vals):
    return np.stack([T.fq12_to_limbs(v) for v in vals])


def fq12_out(arr):
    arr = np.asarray(arr)
    return [T.fq12_from_limbs(arr[i]) for i in range(arr.shape[0])]


def test_fq12_ops():
    a_vals = [gt.FQ12_ONE, gt.FQ12_W] + [rand_fq12() for _ in range(3)]
    b_vals = [rand_fq12() for _ in range(len(a_vals))]
    a, b = fq12_batch(a_vals), fq12_batch(b_vals)
    assert fq12_out(T.fq12_mul(a, b)) == [x * y for x, y in zip(a_vals, b_vals)]
    assert fq12_out(T.fq12_conj(a)) == [x.conj() for x in a_vals]
    assert fq12_out(T.fq12_inv(fq12_batch(b_vals))) == [v.inv() for v in b_vals]


@pytest.mark.parametrize("k", [1, 2, 3])
def test_fq12_frobenius(k):
    """fq12_frobenius(x, k) == x^(q^k) — the bug ADVICE r1 found trips here."""
    vals = [gt.FQ12_W, rand_fq12()]
    out = fq12_out(T.fq12_frobenius(fq12_batch(vals), k))
    assert out == [v ** (gt.q ** k) for v in vals]


# ---------------------------------------------------------------------------
# Boundary ops on adversarial lazy representations
# ---------------------------------------------------------------------------

def test_fq_canon_and_eq_adversarial():
    """fq_canon/fq_is_zero/fq_eq on cascade-forcing lazy reps.

    Patterns: all-MASK limbs (+1 value), exact multiples of q as lazy sums,
    negative values, and Montgomery outputs."""
    import numpy as np
    one = F.fq_ones()
    # value 2^406-1 as limbs (all MASK), canonized
    allmask = np.full((1, F.L), F.MASK, dtype=np.int64)
    expect = ((1 << (F.B * F.L)) - 1) % gt.q
    assert F.limbs_to_int(np.asarray(F.fq_canon(allmask))[0]) == expect

    # k*q lazy sums must be exactly zero for k in {-3..3}
    qlimbs = np.asarray(F.int_to_limbs(gt.q))
    for k in range(-3, 4):
        lazy = (qlimbs * k)[None, :]
        assert bool(np.asarray(F.fq_is_zero(lazy))[0]), f"k={k}"
        assert F.limbs_to_int(np.asarray(F.fq_canon(lazy))[0]) == 0

    # x vs x + q vs x - 2q: all fq_eq, canon identical, nonzero
    x = rand_fq()
    reps = np.stack([
        np.asarray(F.int_to_limbs(x)),
        np.asarray(F.int_to_limbs(x)) + qlimbs,
        np.asarray(F.int_to_limbs(x)) - 2 * qlimbs,
    ])
    canon = np.asarray(F.fq_canon(reps))
    for i in range(3):
        assert F.limbs_to_int(canon[i]) == x
        assert not bool(np.asarray(F.fq_is_zero(reps[i:i+1]))[0])
    assert bool(np.asarray(F.fq_eq(reps[0:1], reps[1:2]))[0])
    assert bool(np.asarray(F.fq_eq(reps[1:2], reps[2:3]))[0])
    assert not bool(np.asarray(F.fq_eq(reps[0:1], one[None, :] * 0 + np.asarray(F.to_mont(1))))[0]) or x == 1


def test_fq_sqr_scale_and_tower_sqr():
    vals = [rand_fq() for _ in range(4)]
    out = fq_out(F.fq_sqr(fq_batch(vals)))
    assert out == [v * v % gt.q for v in vals]

    a2 = [rand_fq2() for _ in range(3)]
    s = [rand_fq() for _ in range(3)]
    scaled = T.fq2_scale(fq2_batch(a2), fq_batch(s))
    assert fq2_out(scaled) == [x * sv for x, sv in zip(a2, s)]
    assert fq2_out(T.fq2_sqr(fq2_batch(a2))) == [x.square() for x in a2]

    a6 = [rand_fq6() for _ in range(2)]
    assert fq6_out(T.fq6_sqr(fq6_batch(a6))) == [x.square() for x in a6]
    a12 = [rand_fq12() for _ in range(2)]
    assert fq12_out(T.fq12_sqr(fq12_batch(a12))) == [x.square() for x in a12]


def test_fq12_mul_line():
    """Sparse line multiply == full product with the assembled line element
    l = c_a + c_v*v + c_vw*(v*w) (the Miller-loop shape, bls_jax)."""
    zero2 = gt.Fq2(0, 0)
    f_vals = [rand_fq12() for _ in range(3)]
    c_a = [rand_fq2() for _ in range(3)]
    c_v = [rand_fq2() for _ in range(3)]
    c_vw = [rand_fq2() for _ in range(3)]
    want = [
        f * gt.Fq12(gt.Fq6(a, v, zero2), gt.Fq6(zero2, vw, zero2))
        for f, a, v, vw in zip(f_vals, c_a, c_v, c_vw)
    ]
    out = T.fq12_mul_line(fq12_batch(f_vals), fq2_batch(c_a),
                          fq2_batch(c_v), fq2_batch(c_vw))
    assert fq12_out(out) == want


def test_fq12_cyclo_sqr():
    """Granger–Scott squaring == generic squaring on cyclotomic-subgroup
    elements (staged via the easy part f^((q^6-1)(q^2+1)) on the oracle) —
    the final-exponentiation _pow_abs precondition in bls_jax.

    The 50-step chain is the regression for the value-growth bug: the
    ±2·conj passthrough must Montgomery-reduce its inputs or chained
    squarings (the BLS parameter has zero-runs up to 47) overflow the
    fq_mul value budget."""
    gs = []
    for _ in range(2):
        f = rand_fq12()
        easy = f.conj() * f.inv()
        gs.append((easy ** (gt.q ** 2)) * easy)
    assert fq12_out(T.fq12_cyclo_sqr(fq12_batch(gs))) == [g * g for g in gs]

    chained = fq12_batch(gs[:1])
    for _ in range(50):
        chained = T.fq12_cyclo_sqr(chained)
    assert fq12_out(chained) == [gs[0] ** (2 ** 50)]


def test_tower_eq_on_lazy_reps():
    """fq2/fq12 equality must see through non-canonical representations —
    this is the final pairing verdict path (bls_jax.pairing_product_is_one)."""
    import numpy as np
    qlimbs = np.asarray(F.int_to_limbs(gt.q))
    a = rand_fq12()
    x = T.fq12_to_limbs(a)
    y = x + qlimbs          # every component shifted by +q: same field value
    assert bool(np.asarray(T.fq12_eq(x[None], y[None]))[0])
    z = np.array(y)
    z[0, 0, 0] = z[0, 0, 0] + 1  # genuinely different value
    assert not bool(np.asarray(T.fq12_eq(x[None], z[None]))[0])

    b = rand_fq2()
    bx = T.fq2_to_limbs(b)
    assert bool(np.asarray(T.fq2_eq(bx[None], (bx - 3 * qlimbs)[None]))[0])
    assert bool(np.asarray(T.fq2_is_zero((qlimbs * np.int64(2))[None, None, :].repeat(2, 1)))[0])
