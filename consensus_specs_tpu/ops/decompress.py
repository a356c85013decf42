"""Batched G1 point decompression on device.

The remaining py_ecc-shaped cost in the verify path was host staging:
`decompress_g1` does a 381-bit modular square root in Python bignums PER
PUBKEY (crypto/bls12_381.py:368-386) — at a 4,096-member committee that is
seconds of host time per attestation, exactly the cost this framework
exists to remove. Here the byte-parse is
vectorized numpy and the field math — Montgomery lift, y^2 = x^3 + 4, the
(q+1)/4 square-root exponentiation, the sign select — runs batched on the
TPU: one program, N points, ~570 field multiplies of depth regardless of N.

Wire/flag semantics are bit-compatible with the bignum oracle
(bls_signature.md:36-64: c/b/a flags, x mod 2^381, a_flag = y*2//q) and
differentially tested against it, including every malformed-encoding
class (tests/test_decompress.py).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from . import fq as F
from . import intmath  # noqa: F401  (x64 on)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# flag bits live in the top byte of the 48-byte big-endian encoding
_FLAG_A = 0x20
_FLAG_B = 0x40
_FLAG_C = 0x80

_HALF_Q_NP = F.int_to_limbs((F.Q - 1) // 2)        # y > (q-1)/2 <=> a_flag 1
_R2_NP = F.int_to_limbs(F.R2_MONT)
_ONE_RAW_NP = F.int_to_limbs(1)                    # Montgomery-mul by this = mont -> raw
_FOUR_MONT_NP = np.asarray(F.to_mont(4), dtype=np.int64)


# ---------------------------------------------------------------------------
# Host: vectorized byte parsing (no per-point Python ints)
# ---------------------------------------------------------------------------

def parse_g1_bytes(data: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                              np.ndarray, np.ndarray]:
    """[N, 48] uint8 big-endian compressed points ->
    (x_limbs [N, L] int64 raw (non-Montgomery), a_flag [N] bool,
     is_infinity [N] bool, wellformed [N] bool).

    wellformed covers the flag grammar ONLY (c set; infinity iff b with
    a=0 and x=0); the x < q range check and on-curve check need field math
    and happen on device."""
    data = np.asarray(data, dtype=np.uint8)
    n = data.shape[0]
    top = data[:, 0]
    c_flag = (top & _FLAG_C) != 0
    b_flag = (top & _FLAG_B) != 0
    a_flag = (top & _FLAG_A) != 0

    stripped = data.copy()
    stripped[:, 0] &= 0x1F                        # x = z mod 2^381

    # big-endian bytes -> little-endian u64 words -> 29-bit limbs
    le = stripped[:, ::-1].copy()                 # byte 0 = LSB
    words = le.view("<u8").reshape(n, 6)          # w[j] = bits [64j, 64j+64)
    limbs = np.zeros((n, F.L), dtype=np.int64)
    for i in range(F.L):
        bit = F.B * i
        j, off = bit // 64, bit % 64
        lo = words[:, j] >> np.uint64(off)
        if off > 64 - F.B and j + 1 < 6:
            lo = lo | (words[:, j + 1] << np.uint64(64 - off))
        limbs[:, i] = (lo & np.uint64(F.MASK)).astype(np.int64)

    x_is_zero = ~np.any(limbs, axis=1)
    is_infinity = b_flag
    wellformed = c_flag & (~b_flag | (~a_flag & x_is_zero))
    return limbs, a_flag, is_infinity, wellformed


# ---------------------------------------------------------------------------
# Device: batched lift + sqrt + sign
# ---------------------------------------------------------------------------

def _fq_gt(a_canon, b_limbs_np: np.ndarray):
    """canonical limbs a > constant b, lexicographic from the top limb."""
    b = jnp.asarray(b_limbs_np)
    gt = jnp.zeros(a_canon.shape[:-1], dtype=bool)
    eq = jnp.ones(a_canon.shape[:-1], dtype=bool)
    for i in range(F.L - 1, -1, -1):
        ai = a_canon[..., i]
        gt = gt | (eq & (ai > b[i]))
        eq = eq & (ai == b[i])
    return gt


def _g1_decompress_traced(x_raw, a_flag):
    """x_raw [N, L] int64 raw limbs, a_flag [N] bool ->
    (x_mont, y_mont [N, L], valid [N] bool).

    valid = x < q AND x on curve. Infinity/flag grammar is the host's job
    (parse_g1_bytes); a point failing `valid` must be rejected by the
    caller exactly as the oracle's asserts reject it."""
    # range check x < q: canonical subtraction sign
    d = F._carry_rounds(x_raw - jnp.asarray(F._Q_NP), F.NORM_FULL)
    x_lt_q = d[..., -1] < 0

    x = F.fq_mul(x_raw, jnp.asarray(_R2_NP))      # Montgomery lift
    y2 = F.fq_add(F.fq_mul(F.fq_sqr(x), x), jnp.asarray(_FOUR_MONT_NP))
    y = F.fq_sqrt_candidate(y2)
    on_curve = F.fq_is_zero(F.fq_sqr(y) - y2)

    y_canon = F.fq_canon(F.fq_mul(y, jnp.asarray(_ONE_RAW_NP)))
    flip = _fq_gt(y_canon, _HALF_Q_NP) != a_flag
    y = F.fq_select(flip, F.fq_neg(y), y)
    return x, y, x_lt_q & on_curve


_g1_decompress_jit = jax.jit(_g1_decompress_traced)


# ---------------------------------------------------------------------------
# G2: Fq2 square root + sign per the oracle's modular_squareroot
# (crypto/bls12_381.py:430-441, spec bls_signature.md:96-109)
# ---------------------------------------------------------------------------

def _fq2_mont(v) -> np.ndarray:
    from . import fq_tower as T
    return np.asarray(T.fq2_to_limbs(v), dtype=np.int64)


def _g2_constants():
    """Host-precomputed Fq2 constants for the sqrt ladder: the 4 even
    eighth-roots of unity, the inverses of their square roots (the fourth
    roots the candidate divides by), and G2_B."""
    from ..crypto import bls12_381 as gt
    even_roots = [gt._EIGHTH_ROOTS[k] for k in (0, 2, 4, 6)]
    fourth_inv = [gt.FQ2_ONE / gt._EIGHTH_ROOTS[k] for k in (0, 1, 2, 3)]
    return (np.stack([_fq2_mont(r) for r in even_roots]),
            np.stack([_fq2_mont(r) for r in fourth_inv]),
            _fq2_mont(gt.G2_B))


_SQRT2_EXP_BITS = None   # lazy: bits of (q^2 + 7) // 16


def _fq2_pow_static(a, bits_np: np.ndarray):
    from . import fq_tower as T
    bits = jnp.asarray(bits_np.astype(np.uint8))
    n = int(bits_np.shape[0])

    def body(i, acc):
        acc = T.fq2_sqr(acc)
        mul = T.fq2_mul(acc, a)
        return T.fq2_select(bits[i] == 1, mul, acc)

    one = jnp.broadcast_to(T.fq2_ones(()), a.shape)
    return jax.lax.fori_loop(0, n, body, one)


def _fq2_sign_flip(y, a_flag):
    """Whether to negate `y` so the result equals the oracle's
    modular_squareroot-then-a_flag composition (bls12_381.py:436-441,
    417-418). For c1 != 0 the flag condition alone pins the root: final
    (c1 > (q-1)/2) == a_flag. For c1 == 0 the flag is insensitive (both
    roots have c1 == 0), so the max-(c1, c0) pick survives and the flip
    applies on top: final (c0 > (q-1)/2) == NOT a_flag."""
    raw = F.fq_mul(y, jnp.asarray(_ONE_RAW_NP))
    c0 = F.fq_canon(raw[..., 0, :])
    c1 = F.fq_canon(raw[..., 1, :])
    c1_zero = ~jnp.any(c1 != 0, axis=-1)
    c0_gt = _fq_gt(c0, _HALF_Q_NP)
    c1_gt = _fq_gt(c1, _HALF_Q_NP)
    return jnp.where(c1_zero, c0_gt == a_flag, c1_gt != a_flag)


def _g2_decompress_traced(x_raw, a_flag):
    """x_raw [N, 2, L] raw limbs (c0, c1), a_flag [N] bool ->
    (x_mont, y_mont [N, 2, L], valid [N] bool)."""
    from ..crypto import bls12_381 as gt
    from . import fq_tower as T

    # deliberate: idempotent trace-time memo of a pure host constant
    # (same value every trace), read only as a compile-time unroll bound.
    # Re-reviewed under the interprocedural pass: every cross-module
    # caller reaches this def through the same jit context, so the memo
    # still fills exactly once per process regardless of entry path.
    global _SQRT2_EXP_BITS  # csa: ignore[CSA302]
    if _SQRT2_EXP_BITS is None:
        _SQRT2_EXP_BITS = F._exp_bits((gt.q ** 2 + 7) // 16)
    even_roots, fourth_inv, g2_b = _g2_constants()

    # range check both coordinates < q
    d0 = F._carry_rounds(x_raw[:, 0] - jnp.asarray(F._Q_NP), F.NORM_FULL)
    d1 = F._carry_rounds(x_raw[:, 1] - jnp.asarray(F._Q_NP), F.NORM_FULL)
    x_lt_q = (d0[..., -1] < 0) & (d1[..., -1] < 0)

    r2 = jnp.asarray(_R2_NP)
    x = T.fq2(F.fq_mul(x_raw[:, 0], r2), F.fq_mul(x_raw[:, 1], r2))
    y2 = T.fq2_add(T.fq2_mul(T.fq2_sqr(x), x), jnp.asarray(g2_b))

    cand = _fq2_pow_static(y2, _SQRT2_EXP_BITS)      # y2^((q^2+7)/16)
    check = T.fq2_mul(T.fq2_sqr(cand), T.fq2_inv(y2))

    # which even eighth-root the check equals (if any) selects the fourth
    # root to divide out; no match = not a square = off curve
    y = jnp.zeros_like(cand)
    matched = jnp.zeros(cand.shape[0], dtype=bool)
    for k in range(4):
        hit = T.fq2_eq(check, jnp.asarray(even_roots[k]))
        yk = T.fq2_mul(cand, jnp.asarray(fourth_inv[k]))
        y = T.fq2_select(hit & ~matched, yk, y)
        matched = matched | hit

    y = T.fq2_select(_fq2_sign_flip(y, a_flag), T.fq2_neg(y), y)
    return x, y, x_lt_q & matched


_g2_decompress_jit = jax.jit(_g2_decompress_traced)


def parse_g2_bytes(data: np.ndarray):
    """[N, 96] uint8 -> (x_limbs [N, 2, L] raw (c0, c1), a_flag1 [N] bool,
    is_infinity [N] bool, wellformed [N] bool). The encoding is
    z1 (flags | x.c1) || z2 (x.c0) — imaginary part first on the wire."""
    data = np.asarray(data, dtype=np.uint8)
    c1_limbs, a_flag1, b_flag1, wf1 = parse_g1_bytes(data[:, :48])
    z2_top_clear = (data[:, 48] & 0xE0) == 0
    c0_limbs, _, _, _ = parse_g1_bytes(
        np.concatenate([data[:, 48:49] & 0x1F, data[:, 49:]], axis=1))
    c0_zero = ~np.any(c0_limbs, axis=1)
    is_inf = b_flag1
    wellformed = wf1 & z2_top_clear & (~b_flag1 | c0_zero)
    x = np.stack([c0_limbs, c1_limbs], axis=1)
    return x, a_flag1, is_inf, wellformed


def g2_decompress_batch(data: np.ndarray):
    """[N, 96] uint8 -> (x_mont [N, 2, L], y_mont [N, 2, L], valid [N],
    is_infinity [N]) with the same accept/reject set as the bignum
    oracle's decompress_g2."""
    x_raw, a_flag, is_inf, wellformed = parse_g2_bytes(data)
    x, y, valid = _g2_decompress_jit(x_raw, jnp.asarray(a_flag))
    valid = np.asarray(valid) & wellformed & ~is_inf
    valid = valid | (wellformed & is_inf)
    return x, y, valid, is_inf


def g1_decompress_batch(data: np.ndarray):
    """[N, 48] uint8 -> (x_mont [N, L], y_mont [N, L], valid [N] bool,
    is_infinity [N] bool).

    valid is False for any malformed encoding (bad flags, x >= q, x not on
    curve); infinity points report valid=True with is_infinity set. The
    (x_mont, y_mont) pair feeds straight into the pairing's affine inputs
    (ops/bls_jax.py point layout)."""
    limbs, a_flag, is_inf, wellformed = parse_g1_bytes(data)
    x, y, valid = _g1_decompress_jit(limbs, jnp.asarray(a_flag))
    valid = np.asarray(valid) & wellformed & ~is_inf
    valid = valid | (wellformed & is_inf)
    return x, y, valid, is_inf
