"""Pallas TPU kernel for the Merkle pair-hash (sha256 of 64-byte messages).

The XLA form (ops/sha256.py) already fuses well; this kernel is the
hand-scheduled Pallas counterpart of its hottest entry point,
`sha256_pairs`, for the tree levels that dominate the bulk state root
(reference hot path: the per-slot full-state hash_tree_root,
/root/reference specs/core/0_beacon-chain.md:1232-1245, Merkle loop at
test_libs/pyspec/eth2spec/utils/merkle_minimal.py:47-54).

Layout is deliberately transposed vs the XLA entry point: lanes live on
the LAST axis ([16, N] words in, [8, N] digests out) so each of the 16
message words is a [block_lanes]-wide VPU vector with the lane axis on
the TPU's native 128-wide dimension — the sublane axis (16, then 8) is a
multiple of the 8-row uint32 tile. Each grid step owns a [16, block_lanes]
tile in VMEM; all 64 rounds of both compressions run unrolled over it with
a rotating 16-word schedule window, so carries never leave registers/VMEM.

The second compression's message is the constant 64-byte-length padding
block, whose 64-entry schedule is data-independent — it is precomputed on
the host once (_PAD_SCHED) and folded into the round chain as immediates,
removing the entire schedule recurrence from half the work.

Correctness: bit-identical to ops/sha256.sha256_pairs. The Mosaic body
(`_sha256_pairs_kernel`) is compiled for a described v5e in
tests/test_tpu_compile.py and run on the chip by chip_smoke.py's
pair_hash_pallas phase; tests/test_sha256_pallas.py runs the interpreter's
fori-loop body on the CPU by passing interpret=True itself. The production
Merkle path keeps the XLA kernel as default until an on-chip A/B shows the
Pallas form ahead; both share this module's contract.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .sha256 import H0, K, _PAD_64, _rotr

_LANE = 128          # TPU lane width: block_lanes must be a multiple


def _schedule_np(block_words: np.ndarray) -> np.ndarray:
    """Host: the full 64-word message schedule of one constant block."""
    w = list(block_words.astype(np.uint64))
    for i in range(16, 64):
        x, y = w[i - 15], w[i - 2]

        def rotr(v, n):
            return ((v >> n) | (v << (32 - n))) & 0xFFFFFFFF

        s0 = rotr(x, 7) ^ rotr(x, 18) ^ (x >> 3)
        s1 = rotr(y, 17) ^ rotr(y, 19) ^ (y >> 10)
        w.append((w[i - 16] + s0 + w[i - 7] + s1) & 0xFFFFFFFF)
    return np.array(w, dtype=np.uint32)


_PAD_SCHED = _schedule_np(_PAD_64)


def _round(state, wi, k: np.uint32):
    a, b, c, d, e, f, g, h = state
    S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
    ch = (e & f) ^ ((e ^ np.uint32(0xFFFFFFFF)) & g)
    t1 = h + S1 + ch + k + wi
    S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
    maj = (a & b) ^ (a & c) ^ (b & c)
    return (t1 + S0 + maj, a, b, c, d + t1, e, f, g)


def _sha256_pairs_kernel(w_ref, out_ref):
    """One VMEM tile: w_ref [16, BN] uint32 -> out_ref [8, BN] uint32."""
    w = [w_ref[i, :] for i in range(16)]
    lanes = w[0].shape
    state = tuple(jnp.full(lanes, np.uint32(H0[i])) for i in range(8))

    # Compression 1: the 64-byte message, rolling 16-word schedule window.
    s = state
    for i in range(64):
        if i < 16:
            wi = w[i]
        else:
            x = w[(i - 15) % 16]
            y = w[(i - 2) % 16]
            s0 = _rotr(x, 7) ^ _rotr(x, 18) ^ (x >> np.uint32(3))
            s1 = _rotr(y, 17) ^ _rotr(y, 19) ^ (y >> np.uint32(10))
            wi = w[i % 16] + s0 + w[(i - 7) % 16] + s1
            w[i % 16] = wi
        s = _round(s, wi, np.uint32(K[i]))
    mid = tuple(h0 + si for h0, si in zip(state, s))

    # Compression 2: the constant padding block — schedule is immediate.
    s = mid
    for i in range(64):
        s = _round(s, np.uint32(_PAD_SCHED[i]), np.uint32(K[i]))
    for i in range(8):
        out_ref[i, :] = mid[i] + s[i]


def _sha256_pairs_kernel_fori(w_ref, k_ref, pad_ref, out_ref):
    """fori-loop form of _sha256_pairs_kernel for the interpreter: the
    interpret path still compiles the kernel body through XLA:CPU, whose
    algebraic simplifier loops forever on 128 unrolled rotate rounds (same
    bug ops/sha256.py pins its CPU path around); rolled loops compile fine.
    The K and pad-schedule tables arrive as inputs (kernels cannot capture
    array constants). Bit-identical output — the tests run both forms
    against each other."""
    block = w_ref[:, :]                           # [16, BN]
    lanes = block.shape[1:]
    w = jnp.zeros((64,) + lanes, jnp.uint32).at[:16].set(block)

    def sched_body(i, w):
        x = w[i - 15]
        y = w[i - 2]
        s0 = _rotr(x, 7) ^ _rotr(x, 18) ^ (x >> np.uint32(3))
        s1 = _rotr(y, 17) ^ _rotr(y, 19) ^ (y >> np.uint32(10))
        return w.at[i].set(w[i - 16] + s0 + w[i - 7] + s1)

    w = jax.lax.fori_loop(16, 64, sched_body, w)
    k_arr = k_ref[:]
    state = tuple(jnp.full(lanes, np.uint32(H0[i])) for i in range(8))
    s = jax.lax.fori_loop(
        0, 64, lambda i, st: _round(st, w[i], k_arr[i]), state)
    mid = tuple(h0 + si for h0, si in zip(state, s))
    pad_sched = pad_ref[:]
    s = jax.lax.fori_loop(
        0, 64, lambda i, st: _round(st, pad_sched[i], k_arr[i]), mid)
    out_ref[:, :] = jnp.stack([mi + si for mi, si in zip(mid, s)])


def _lane_tile(i):
    """Index map of a [rows, block_lanes] tile: all rows, lane block i.
    The row index is an int32 like the grid index beside it — the serving
    process runs with 64-bit types on (ops/intmath.py), where a literal 0
    traces as an i64 and Mosaic cannot legalize the mixed (i64, i32)
    return (seen on the v5e, jax 0.9.0)."""
    return (np.int32(0), i)


def _whole_table(i):
    return (np.int32(0),)


def _pairs_transposed(wt: jnp.ndarray, block_lanes: int, interpret: bool):
    n = wt.shape[1]
    n_pad = -(-n // block_lanes) * block_lanes
    wt = jnp.pad(wt, ((0, 0), (0, n_pad - n)))
    grid = (n_pad // block_lanes,)
    w_spec = pl.BlockSpec((16, block_lanes), _lane_tile)
    out_spec = pl.BlockSpec((8, block_lanes), _lane_tile)
    out_shape = jax.ShapeDtypeStruct((8, n_pad), jnp.uint32)
    if interpret:
        table = pl.BlockSpec((64,), _whole_table)
        return pl.pallas_call(
            _sha256_pairs_kernel_fori, grid=grid,
            in_specs=[w_spec, table, table],
            out_specs=out_spec, out_shape=out_shape, interpret=True,
        )(wt, jnp.asarray(K), jnp.asarray(_PAD_SCHED))[:, :n]
    return pl.pallas_call(
        _sha256_pairs_kernel, grid=grid,
        in_specs=[w_spec], out_specs=out_spec, out_shape=out_shape,
    )(wt)[:, :n]


# jit ONLY the real-hardware path: under interpret=True a jit would inline
# the 128 unrolled rotate rounds into one XLA:CPU program, which trips the
# XLA:CPU algebraic-simplifier rewrite loop documented in ops/sha256.py
# (compile never returns); the eager interpreter dispatches per-op instead.
_pairs_transposed_jit = jax.jit(
    _pairs_transposed, static_argnames=("block_lanes", "interpret"))


def vmem_block_model(block_lanes: int = 512):
    """(shape, dtype) rows of one grid step's VMEM residency, built
    from the SAME BlockSpecs `_pairs_transposed` hands pallas_call (the
    [16, BN] message tile, the [8, BN] digest tile, and the interpret
    path's two [64] schedule tables — the superset, so the bound covers
    both kernel forms). The memory tier's CSA1604 contract multiplies
    these by the pipeline's double buffering against the 16 MiB/core
    budget; reading `.block_shape` off real BlockSpec objects keeps the
    bound tracking the kernel, not a transcription of it."""
    w_spec = pl.BlockSpec((16, block_lanes), _lane_tile)
    out_spec = pl.BlockSpec((8, block_lanes), _lane_tile)
    table = pl.BlockSpec((64,), _whole_table)
    return [(tuple(s.block_shape), "uint32")
            for s in (w_spec, out_spec, table, table)]


# ---------------------------------------------------------------------------
# Memory contract (tools/analysis/memory/, `make memory`)
# ---------------------------------------------------------------------------
# The VMEM footprint of the default block_lanes=512 tile under the
# double-buffered grid pipeline: (16 + 8) x 512 x 4 B tiles plus the
# two 64-entry schedule tables, x2 buffering — ~97 KiB of the 16 MiB
# core, leaving the headroom the ROADMAP item-3 REDC kernel will share.
# A block_lanes bump (or a dtype widening in the tile) that escapes the
# budget fails here before Mosaic ever sees it.

MEM_CONTRACTS = [
    dict(
        name="ops.sha256_pallas.pairs_vmem",
        vmem=dict(blocks=vmem_block_model, buffering=2),
    ),
]


def sha256_pairs_pallas(words: jnp.ndarray, *, block_lanes: int = 512,
                        interpret: bool = False) -> jnp.ndarray:
    """[N, 16] uint32 big-endian words -> [N, 8] digests; == sha256_pairs.

    The default is the Mosaic kernel, whatever the device: Mosaic lowers
    for TPUs only, so off-TPU this call FAILS rather than quietly running
    something else. interpret=True is the Pallas interpreter (the
    fori-loop body) and is the caller's explicit choice — the CPU tests
    and the trace/memory contracts pass it themselves.
    """
    assert block_lanes % _LANE == 0, "block_lanes must be lane-aligned"
    wt = jnp.transpose(jnp.asarray(words, jnp.uint32), (1, 0))
    run = _pairs_transposed if interpret else _pairs_transposed_jit
    return jnp.transpose(run(wt, block_lanes, interpret), (1, 0))
