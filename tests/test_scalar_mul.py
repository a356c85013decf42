"""Windowed signed-digit scalar mul (ops/scalar_mul.py) vs the
double-and-add reference vs the host bignum oracle.

Three layers: host recoding algebra (exact int arithmetic), device
bit-exactness across backends/widths/batch shapes (including the pow2 pad
and point-at-infinity inputs), and the sequential-add cost model — counted
op-by-op on an unrolled eager evaluation, the way
tests/test_incremental_merkle.py asserts pair-lane counts."""
import random

import numpy as np
import pytest

import jax.numpy as jnp

from consensus_specs_tpu.crypto import bls12_381 as gt
from consensus_specs_tpu.ops import bls_jax as BJ
from consensus_specs_tpu.ops import fq as F
from consensus_specs_tpu.ops import fq_tower as T
from consensus_specs_tpu.ops import scalar_mul as SM

rng = random.Random(0x5CA1A)

SCALARS = [0, 1, 2, gt.r - 1, rng.randrange(1 << 255, 1 << 256)]


def g1_val(x, y, inf_flag, i=()):
    if bool(np.asarray(inf_flag)[i] if i != () else np.asarray(inf_flag)):
        return None
    return (F.from_mont(np.asarray(x)[i]), F.from_mont(np.asarray(y)[i]))


def g2_val(x, y, inf_flag, i=()):
    if bool(np.asarray(inf_flag)[i] if i != () else np.asarray(inf_flag)):
        return None
    return (T.fq2_from_limbs(np.asarray(x)[i]),
            T.fq2_from_limbs(np.asarray(y)[i]))


# ---------------------------------------------------------------------------
# Host recoding
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w", [2, 3, 4, 5])
def test_recode_digit_properties(w):
    """Digits odd, in-range, fixed count, top digit +1; the value identity
    itself is asserted inside recode_signed_windows in exact arithmetic."""
    for k in SCALARS + [rng.randrange(0, 1 << 256) for _ in range(8)]:
        rec = SM.recode_signed_windows(k, 256, w)
        m = SM.n_windows(256, w)
        assert rec.idx.shape == rec.sign.shape == (m,)
        assert rec.correction == (k % 2 == 0)
        assert rec.idx.min() >= 0 and rec.idx.max() < 2 ** (w - 1)
        assert set(np.unique(rec.sign)) <= {-1, 1}
        assert rec.idx[0] == 0 and rec.sign[0] == 1   # fixed-length tail
        digits = (2 * rec.idx.astype(int) + 1) * rec.sign
        value = 0
        for d in digits:
            value = (value << w) + int(d)
        assert value - (1 if rec.correction else 0) == k


def test_recode_memoized_and_readonly():
    a = SM.recode_signed_windows(12345, 256, 4)
    b = SM.recode_signed_windows(12345, 256, 4)
    assert a is b
    with pytest.raises(ValueError):
        a.idx[0] = 3
    bits = SM.scalar_bits(12345, 256)
    assert SM.scalar_bits(12345, 256) is bits
    with pytest.raises(ValueError):
        bits[0] = 1
    assert np.array_equal(
        bits, [(12345 >> (255 - i)) & 1 for i in range(256)])


# ---------------------------------------------------------------------------
# Device bit-exactness: windowed vs double-and-add vs host bignum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w", [2, 4, 5])
def test_windowed_g1_matches_oracle(w):
    """All SCALARS at one batch shape per width (one compile per w; the
    width sweep 2–5 splits across G1 here and G2 below, every width
    differential-tested against the double-and-add path and the bignum
    oracle)."""
    pts = [gt.ec_mul(gt.G1_GEN, 3 * i + 2) for i in range(2)]
    arr = np.stack([BJ.g1_to_limbs(p) for p in pts])
    x, y = jnp.asarray(arr[:, 0]), jnp.asarray(arr[:, 1])
    for k in SCALARS:
        rec = SM.recode_signed_windows(k, 256, w)
        gx, gy, ginf = BJ._g1_scalar_mul_win(
            x, y, jnp.asarray(rec.idx), jnp.asarray(rec.sign),
            jnp.asarray(np.bool_(rec.correction)), w=w)
        da_x, da_y, da_inf = BJ._g1_scalar_mul(
            x, y, jnp.asarray(SM.scalar_bits(k, 256)))
        for i, p in enumerate(pts):
            want = gt.ec_mul(p, k)
            assert g1_val(gx, gy, ginf, i) == want, (k, w, i)
            assert g1_val(da_x, da_y, da_inf, i) == want, (k, i)


@pytest.mark.parametrize("w", [3])
def test_windowed_g2_matches_oracle(w):
    pts = [gt.ec_mul(gt.G2_GEN, 5 * i + 7) for i in range(2)]
    arr = np.stack([BJ.g2_to_limbs(p) for p in pts])
    x, y = jnp.asarray(arr[:, 0]), jnp.asarray(arr[:, 1])
    for k in SCALARS:
        rec = SM.recode_signed_windows(k, 256, w)
        gx, gy, ginf = BJ._g2_scalar_mul_win(
            x, y, jnp.asarray(rec.idx), jnp.asarray(rec.sign),
            jnp.asarray(np.bool_(rec.correction)), w=w)
        for i, p in enumerate(pts):
            assert g2_val(gx, gy, ginf, i) == gt.ec_mul(p, k), (k, w, i)


def test_windowed_cofactor_fixed_scalar():
    """The ~509-bit fixed-scalar path: module-load digits, G2 batch (8
    points — the same program shape hash_to_g2_batch's pow2 pad hits, so
    the compile is shared with those tests)."""
    nbits = gt.G2_COFACTOR.bit_length()
    pts = [gt.hash_to_g2_candidate(bytes([m]) * 32, 1) for m in range(1, 9)]
    arr = np.stack([BJ.g2_to_limbs(p) for p in pts])
    x, y, inf = BJ.g2_scalar_mul(jnp.asarray(arr[:, 0]),
                                 jnp.asarray(arr[:, 1]),
                                 gt.G2_COFACTOR, nbits=nbits)
    for i, p in enumerate(pts):
        assert g2_val(x, y, inf, i) == gt.ec_mul(p, gt.G2_COFACTOR), i


def test_point_at_infinity_inputs():
    """Batch mixing finite points with flagged infinity inputs: infinity
    propagates through table build + loop on BOTH backends; finite lanes
    are unaffected. 24-bit scalar: the windowed side runs eagerly
    unrolled, the double-and-add side compiles one small program."""
    nbits, w = 24, 3
    k = rng.randrange(1, 1 << nbits)
    p = gt.ec_mul(gt.G1_GEN, 5)
    arr = np.stack([BJ.g1_to_limbs(p), BJ.g1_to_limbs(p)])
    x, y = jnp.asarray(arr[:, 0]), jnp.asarray(arr[:, 1])
    inf = jnp.asarray(np.array([False, True]))
    rec = SM.recode_signed_windows(k, nbits, w)
    win = SM.windowed_scalar_mul(
        BJ.G1_OPS, (x, y), rec.idx, rec.sign, rec.correction, w=w,
        inf=inf, unroll=True)
    da = SM.jac_scalar_mul(BJ.G1_OPS, (x, y),
                           jnp.asarray(SM.scalar_bits(k, nbits)), inf=inf)
    for pt in (win, da):
        ax, ay, ainf = BJ.jac_to_affine(BJ.G1_OPS, pt)
        assert g1_val(ax, ay, ainf, 0) == gt.ec_mul(p, k)
        assert g1_val(ax, ay, ainf, 1) is None   # O stays O


def test_batch_crossing_pow2_pad():
    """hash_to_g2_batch pads 5 -> 8: every unpadded lane must still equal
    the host oracle, on both backends."""
    reqs = [(bytes([m]) * 32, 3) for m in range(5)]
    want = [gt.hash_to_g2(mh, d) for mh, d in reqs]
    for backend in ("window", "double_add"):
        SM.set_scalar_mul_backend(backend)
        try:
            assert BJ.hash_to_g2_batch(reqs) == want, backend
        finally:
            SM.set_scalar_mul_backend(None)


def test_backend_knob():
    """Env knob + override semantics mirror CSTPU_MERKLE_BACKEND."""
    assert SM.scalar_mul_backend_name() == "window"   # default
    SM.set_scalar_mul_backend("double_add")
    try:
        assert SM.scalar_mul_backend_name() == "double_add"
    finally:
        SM.set_scalar_mul_backend(None)
    with pytest.raises(AssertionError):
        SM.set_scalar_mul_backend("bogus")


def test_backend_env_validation(monkeypatch):
    monkeypatch.setenv("CSTPU_SCALAR_MUL", "nope")
    with pytest.raises(ValueError):
        SM.scalar_mul_backend_name()
    monkeypatch.setenv("CSTPU_SCALAR_MUL", "double_add")
    assert SM.scalar_mul_backend_name() == "double_add"
    monkeypatch.setenv("CSTPU_SCALAR_WINDOW", "0")
    with pytest.raises(ValueError):
        SM.scalar_mul_window()
    monkeypatch.setenv("CSTPU_SCALAR_WINDOW", "5")
    assert SM.scalar_mul_window() == 5


def test_sign_privtopub_parity_both_backends():
    """The spec-facing surface stays byte-identical to the bignum oracle
    under either scalar-mul backend."""
    py, jx = gt.PythonBackend(), BJ.JaxBackend()
    msg = b"\x5a" * 32
    for backend in ("window", "double_add"):
        SM.set_scalar_mul_backend(backend)
        try:
            assert jx.privtopub(0xBEEF) == gt.privtopub(0xBEEF), backend
            assert jx.sign(msg, 77, 2) == py.sign(msg, 77, 2), backend
        finally:
            SM.set_scalar_mul_backend(None)


# ---------------------------------------------------------------------------
# Sequential-add cost model (the acceptance bound)
# ---------------------------------------------------------------------------
# The jac_add/jac_double counter this section hand-rolled through PR 8
# now lives in the shared tracer library (tools/analysis/trace/tracer.py
# `counted_point_ops`) and the count itself is a committed kernel
# contract (ops.scalar_mul.windowed_chain) — the test asserts the chain
# THROUGH the contract engine, so the op model the ratchet enforces and
# the one the tests pin are the same object.


def test_sequential_add_count_measured_through_contract():
    """The windowed_chain contract: an unrolled eager windowed evaluation
    counted op-by-op (every call one dependent step at batch ()), pinned
    exactly to the analytic model (scalar_mul.sequential_adds) — measured by the
    contract engine, value-checked against the host oracle here."""
    from tools.analysis.trace import engine as trace_engine
    contracts = [c for c in trace_engine.discover()
                 if c["name"] == "ops.scalar_mul.windowed_chain"]
    assert len(contracts) == 1
    report = trace_engine.run_contracts(contracts)
    assert report.findings == [], [f.message for f in report.findings]
    (res,) = report.results
    nbits, w = 24, 3
    assert res.measured["seq_adds"] == SM.sequential_adds("window", nbits, w)
    assert res.measured["seq_doubles"] == SM.sequential_doubles(
        "window", nbits, w)
    # the shared counter itself, exercised directly at a tiny shape and
    # value-checked against the bignum oracle (the big-shape eager run
    # already happened once, inside the engine)
    from tools.analysis.trace import tracer
    nbits, w = 8, 2
    k = 0b10110100   # even: exercises the fixup add
    rec = SM.recode_signed_windows(k, nbits, w)
    arr = BJ.g1_to_limbs(gt.ec_mul(gt.G1_GEN, 9))
    with tracer.counted_point_ops() as counts:
        pt = SM.windowed_scalar_mul(
            BJ.G1_OPS, (jnp.asarray(arr[0]), jnp.asarray(arr[1])),
            rec.idx, rec.sign, rec.correction, w=w, unroll=True)
    assert counts["jac_add"] == SM.sequential_adds("window", nbits, w)
    # every jac_add internally evaluates one jac_double (the branch-free
    # P1 == P2 fallback), so the raw double count carries one extra per add
    assert (counts["jac_double"] - counts["jac_add"]
            == SM.sequential_doubles("window", nbits, w))
    x, y, inf = BJ.jac_to_affine(BJ.G1_OPS, pt)
    assert g1_val(x, y, inf) == gt.ec_mul(gt.ec_mul(gt.G1_GEN, 9), k)


def test_sequential_add_bound():
    """The acceptance criterion: ≥2.5x fewer dependent adds than
    double-and-add on BOTH hot shapes at the default width."""
    w = SM.scalar_mul_window()
    for nbits in (256, gt.G2_COFACTOR.bit_length()):
        da = SM.sequential_adds("double_add", nbits)
        win = SM.sequential_adds("window", nbits, w)
        assert da >= 2.5 * win, (nbits, da, win)
        # doublings must not regress past the window-rounding slack
        assert SM.sequential_doubles("window", nbits, w) <= nbits + w
