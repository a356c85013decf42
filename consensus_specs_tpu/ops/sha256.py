"""Batched SHA-256 as JAX uint32 array code.

The reference hashes one 64-byte block at a time through OpenSSL
(/root/reference test_libs/pyspec/eth2spec/utils/hash_function.py:1-29) and
Merkleizes level-by-level with a Python loop
(/root/reference test_libs/pyspec/eth2spec/utils/merkle_minimal.py:47-54).
Here the unit of work is a *batch*: an [N, 16] uint32 array of message blocks
compressed in one traced program, so a whole Merkle tree level (or all 90
shuffle-round hashes for every index at once) is a single XLA op stream on the
VPU. All lanes run the same 64 unrolled rounds — no data-dependent control
flow, fixed shapes, uint32 throughout (TPU-native word size).

Laid out so the hot entry points are jit-cached by shape:
  - sha256_blocks(state [*, 8], block [*, 16])  — one compression, any batch shape
  - sha256_pairs(words [N, 16]) -> [N, 8]       — hash N 64-byte messages (Merkle level)
  - sha256_single_block(words [*, 16])          — hash messages <= 55 bytes already
                                                  padded into one block (shuffle path)
  - merkle_root_from_leaves_device(leaves)      — full tree reduction on device

Host bridging helpers convert bytes <-> big-endian uint32 word arrays.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# Round constants: fractional parts of cube roots of the first 64 primes.
K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2,
], dtype=np.uint32)

# Initial hash state: fractional parts of square roots of the first 8 primes.
H0 = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19,
], dtype=np.uint32)


def _rotr(x: jnp.ndarray, n: int) -> jnp.ndarray:
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _unroll_for(lanes: int) -> bool:
    """Pick the round structure for a compression over `lanes` lanes.

    True = 64 statically-unrolled rounds (fastest on TPU: 3.9x, the whole
    chain fuses, carries never touch HBM). False = lax.fori_loop rounds
    (graph ~64x smaller). XLA:CPU is pinned to the fori form: its algebraic
    simplifier falls into a circular rewrite loop on the unrolled rotate
    chains (observed "ran for 50 runs on computation main", compile never
    returns — with both or-of-shifts and add-of-shifts rotations), so
    unrolling is reserved for the TPU, and only where the batch is wide
    enough to pay for the bigger program.
    """
    return lanes >= _UNROLL_MIN_LANES and jax.default_backend() != "cpu"


def sha256_blocks(state: jnp.ndarray, block: jnp.ndarray,
                  unroll: Optional[bool] = None) -> jnp.ndarray:
    """One SHA-256 compression. state: [..., 8] uint32, block: [..., 16] uint32.

    unroll=True statically unrolls the 64 rounds with a rotating 16-word
    schedule window: no [64, batch] schedule array is ever materialized and
    XLA fuses the whole round chain, so the carries live in registers
    instead of round-tripping HBM every round — measured 3.9x faster at 4M
    lanes on the v5e (64 ms vs 249 ms). unroll=False keeps the fori_loop
    form whose traced graph is ~64x smaller. Default None = _unroll_for:
    unrolled on TPU for wide batches, fori on CPU (XLA:CPU simplifier bug)
    and for narrow levels that can't saturate the VPU anyway.
    """
    if unroll is None:
        unroll = _unroll_for(int(np.prod(block.shape[:-1])))
    if unroll:
        return _sha256_blocks_unrolled(state, block)
    batch = block.shape[:-1]
    w = jnp.zeros((64,) + batch, dtype=jnp.uint32)
    w = w.at[:16].set(jnp.moveaxis(block, -1, 0))

    def sched_body(i, w):
        x = w[i - 15]
        y = w[i - 2]
        s0 = _rotr(x, 7) ^ _rotr(x, 18) ^ (x >> np.uint32(3))
        s1 = _rotr(y, 17) ^ _rotr(y, 19) ^ (y >> np.uint32(10))
        return w.at[i].set(w[i - 16] + s0 + w[i - 7] + s1)

    w = jax.lax.fori_loop(16, 64, sched_body, w)
    k_arr = jnp.asarray(K)

    def round_body(i, carry):
        a, b, c, d, e, f, g, h = carry
        S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        temp1 = h + S1 + ch + k_arr[i] + w[i]
        S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        temp2 = S0 + maj
        return (temp1 + temp2, a, b, c, d + temp1, e, f, g)

    init = tuple(state[..., i] for i in range(8))
    out = jax.lax.fori_loop(0, 64, round_body, init)
    return state + jnp.stack(out, axis=-1)


def _sha256_blocks_unrolled(state: jnp.ndarray, block: jnp.ndarray) -> jnp.ndarray:
    """Unrolled compression: rotating 16-word schedule window, 64 static
    rounds — one fused kernel, minimal HBM traffic.

    The rounds run WORD-MAJOR: block and state are transposed to
    [16, ...lanes] / [8, ...lanes] once, so every schedule word and
    carry is a whole row with the lanes on the minor (128-wide) axis.
    Slicing `block[..., i]` out of the lane-major [N, 16] form instead
    leaves XLA:TPU with [N, 1] intermediates that tile as (8, 128) —
    128x padding each, 1.9 GB per word at 4M lanes — and the 1M-validator
    registry leaf program then needs 24.9 GB of HBM (v5e compiler,
    jax 0.9.0)."""
    bt = jnp.moveaxis(block, -1, 0)
    st = jnp.moveaxis(state, -1, 0)
    w = [bt[i] for i in range(16)]
    a, b, c, d, e, f, g, h = (st[i] for i in range(8))
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
        S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + S1 + ch + np.uint32(K[i]) + wi
        S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        a, b, c, d, e, f, g, h = t1 + S0 + maj, a, b, c, d + t1, e, f, g
    out = st + jnp.stack([a, b, c, d, e, f, g, h], axis=0)
    return jnp.moveaxis(out, 0, -1)


def _padding_block_for_length(message_bytes: int) -> np.ndarray:
    """The final all-padding block for a message that exactly fills prior blocks."""
    assert message_bytes % 64 == 0
    blk = np.zeros(16, dtype=np.uint32)
    blk[0] = 0x80000000
    bitlen = message_bytes * 8
    blk[14] = (bitlen >> 32) & 0xFFFFFFFF
    blk[15] = bitlen & 0xFFFFFFFF
    return blk

_PAD_64 = _padding_block_for_length(64)  # padding block for 64-byte messages


def sha256_pairs_inner(words: jnp.ndarray, unroll=None) -> jnp.ndarray:
    """Hash N 64-byte messages given as [N, 16] uint32 (big-endian words) -> [N, 8].

    This is the Merkle work-horse: each lane is `sha256(left ‖ right)`.
    Two compressions: the data block, then the constant padding block.
    Un-jitted so larger traced programs (merkle_reduce_words, the bulk
    state-root) can inline it; sha256_pairs is the jitted entry point.
    """
    n = words.shape[0]
    state = jnp.broadcast_to(jnp.asarray(H0), (n, 8))
    state = sha256_blocks(state, words, unroll=unroll)
    pad = jnp.broadcast_to(jnp.asarray(_PAD_64), (n, 16))
    return sha256_blocks(state, pad, unroll=unroll)


sha256_pairs = jax.jit(sha256_pairs_inner, static_argnames=("unroll",))

# below this many lanes a compression cannot saturate the VPU, so the
# graph-compact fori form is used there to bound trace/compile time
# (the wide unrolled levels dominate runtime anyway)
_UNROLL_MIN_LANES = 4096


@jax.jit
def sha256_single_block(words: jnp.ndarray) -> jnp.ndarray:
    """Hash messages that (with padding) fit one block: [..., 16] uint32 -> [..., 8].

    Caller must have already placed 0x80 terminator + bit length into the words
    (see pad_to_single_block). Used by the shuffle kernel (33/37-byte inputs).
    """
    state = jnp.broadcast_to(jnp.asarray(H0), words.shape[:-1] + (8,))
    return sha256_blocks(state, words)


def pad_to_single_block(data: np.ndarray, message_bytes: int) -> np.ndarray:
    """Pad [..., message_bytes] uint8 arrays (<=55 bytes) into [..., 16] uint32 blocks."""
    assert message_bytes <= 55
    padded = np.zeros(data.shape[:-1] + (64,), dtype=np.uint8)
    padded[..., :message_bytes] = data
    padded[..., message_bytes] = 0x80
    bitlen = message_bytes * 8
    padded[..., 62] = (bitlen >> 8) & 0xFF
    padded[..., 63] = bitlen & 0xFF
    return bytes_to_words(padded)


# ---------------------------------------------------------------------------
# bytes <-> big-endian uint32 word bridging
# ---------------------------------------------------------------------------

def bytes_to_words(data: np.ndarray) -> np.ndarray:
    """[..., 4k] uint8 -> [..., k] uint32 big-endian words."""
    assert data.dtype == np.uint8 and data.shape[-1] % 4 == 0
    return data.reshape(data.shape[:-1] + (-1, 4)).astype(np.uint32) @ np.array(
        [1 << 24, 1 << 16, 1 << 8, 1], dtype=np.uint32)


def words_to_bytes(words: np.ndarray) -> np.ndarray:
    """[..., k] uint32 -> [..., 4k] uint8 big-endian."""
    words = np.asarray(words, dtype=np.uint32)
    out = np.empty(words.shape + (4,), dtype=np.uint8)
    out[..., 0] = words >> 24
    out[..., 1] = (words >> 16) & 0xFF
    out[..., 2] = (words >> 8) & 0xFF
    out[..., 3] = words & 0xFF
    return out.reshape(words.shape[:-1] + (-1,))


def sha256_many(messages: np.ndarray) -> np.ndarray:
    """Hash a batch of equal-length byte messages on device.

    messages: [N, L] uint8. Returns [N, 32] uint8. Handles arbitrary L by
    building the standard padded multi-block layout and compressing each block
    in sequence (block count is static — derived from L).
    """
    n, length = messages.shape
    n_blocks = (length + 9 + 63) // 64
    padded = np.zeros((n, n_blocks * 64), dtype=np.uint8)
    padded[:, :length] = messages
    padded[:, length] = 0x80
    bitlen = length * 8
    bl = np.frombuffer(bitlen.to_bytes(8, "big"), dtype=np.uint8)
    padded[:, -8:] = bl
    words = bytes_to_words(padded).reshape(n, n_blocks, 16)
    state = _sha256_multiblock(jnp.asarray(words))
    return words_to_bytes(np.asarray(state))


@jax.jit
def _sha256_multiblock(words: jnp.ndarray) -> jnp.ndarray:
    n, n_blocks, _ = words.shape
    state = jnp.broadcast_to(jnp.asarray(H0), (n, 8))
    # block count is static (fixed by shape), but the rounds inside each
    # block only unroll for short messages: a long message would multiply
    # 64 unrolled rounds by n_blocks and explode trace/compile time
    unroll = _unroll_for(n) if n_blocks <= 4 else False
    for i in range(n_blocks):
        state = sha256_blocks(state, words[:, i, :], unroll=unroll)
    return state


# ---------------------------------------------------------------------------
# Device-side Merkle reduction
# ---------------------------------------------------------------------------

def zerohash_words(depth: int) -> np.ndarray:
    """[8] uint32 big-endian words of the depth-`depth` zero-subtree root."""
    from ..utils.hash import zerohashes  # local import to avoid cycle
    return bytes_to_words(np.frombuffer(zerohashes[depth], dtype=np.uint8))


_zerohash_words = zerohash_words  # internal alias (pre-export name)


def merkle_reduce_words(chunks: jnp.ndarray) -> jnp.ndarray:
    """[N, 8]-word chunk rows -> [8] root words, entirely on device.

    Trace-time Python loop over levels (static unroll, log2(N) iterations);
    odd levels are padded with the zero-subtree hash of that depth, which
    is exactly SSZ merkleize's virtual zero-chunk padding
    (specs/simple-serialize.md:139-147, merkle_minimal.py:47-54) without
    materializing a power-of-two tree. Designed to be called INSIDE a jit:
    the whole reduction — every level of a 1M-leaf tree — is one compiled
    program, one transfer in, 32 bytes out. (The per-level host loop in
    merkle_root_device round-trips device<->host each level.)
    """
    level = chunks
    depth = 0
    while level.shape[0] > 1:
        if level.shape[0] % 2 == 1:
            pad = jnp.asarray(_zerohash_words(depth))[None, :]
            level = jnp.concatenate([level, pad], axis=0)
        pairs = level.reshape(-1, 16)
        level = sha256_pairs_inner(pairs, unroll=_unroll_for(pairs.shape[0]))
        depth += 1
    return level[0]


def subtree_roots_words(leaves: jnp.ndarray, unroll=None) -> jnp.ndarray:
    """[V, P, 8]-word per-element subtrees -> [V, 8] roots, on device.

    P must be a power of two; all V subtrees descend one level per
    compression call, each level one (V*P/2)-lane batch. Composable inside
    jit (the bulk state-root program inlines this). `unroll` as in
    sha256_blocks; None chooses by the level's lanes."""
    V, P, _ = leaves.shape
    assert P & (P - 1) == 0, "pad element chunk count to a power of two"
    level = leaves
    while level.shape[1] > 1:
        pairs = level.reshape(-1, 16)
        level = sha256_pairs_inner(
            pairs, unroll=_unroll_for(pairs.shape[0]) if unroll is None
            else unroll).reshape(V, level.shape[1] // 2, 8)
    return level[:, 0, :]


def merkle_root_device(leaves: jnp.ndarray, depth: int) -> jnp.ndarray:
    """Root of a power-of-two tree over [N, 8]-word leaves, N == 2**depth.

    Host loop over levels; each level is one call into the jitted pair hash,
    so level shapes compile once and are shared across all trees of a size.
    """
    level = leaves
    for _ in range(depth):
        blocks = level.reshape(level.shape[0] // 2, 16)
        level = sha256_pairs(blocks)
    return level[0]


def merkle_root_from_leaves_device(leaves_bytes: Sequence[bytes], pad_to: int) -> bytes:
    """Host entry: Merkle root of 32-byte leaves, zero-padded to pad_to (pow2)."""
    from ..utils.hash import zerohashes  # local import to avoid cycle
    n = len(leaves_bytes)
    assert pad_to >= 1 and (pad_to & (pad_to - 1)) == 0
    depth = (pad_to - 1).bit_length()
    if n == 0:
        return zerohashes[depth]
    arr = np.zeros((pad_to, 32), dtype=np.uint8)
    for i, leaf in enumerate(leaves_bytes):
        arr[i] = np.frombuffer(leaf, dtype=np.uint8)
    words = jnp.asarray(bytes_to_words(arr))
    root = merkle_root_device(words, depth)
    return words_to_bytes(np.asarray(root)).tobytes()


# ---------------------------------------------------------------------------
# Selectable Merkle pair-hash backend: the XLA kernel vs the Pallas kernel.
#
# sha256_pairs_pallas (ops/sha256_pallas.py) has always promised an on-chip
# A/B against the XLA form; this switch is what actually selects it. The
# host-orchestrated Merkle paths — bulk.hash_pairs_array and the incremental
# forest (utils/ssz/incremental.py) — route every level through
# pair_hash_words, so CSTPU_MERKLE_BACKEND=pallas swaps the kernel under
# them without touching call sites. The one-program traced reductions
# (merkle_reduce_words et al.) keep the inlined XLA form: they are compiled
# as a single fused program where the kernel choice is part of the trace.
# ---------------------------------------------------------------------------

_PAIR_BACKENDS = ("xla", "pallas")
_pair_backend_override: Optional[str] = None


def set_merkle_pair_backend(name: Optional[str]) -> None:
    """Pin the pair-hash backend ("xla"/"pallas"); None returns control to
    the CSTPU_MERKLE_BACKEND environment variable (default "xla")."""
    global _pair_backend_override
    assert name is None or name in _PAIR_BACKENDS, name
    _pair_backend_override = name


def merkle_pair_backend_name() -> str:
    import os
    name = _pair_backend_override or os.environ.get(
        "CSTPU_MERKLE_BACKEND", "xla")
    if name not in _PAIR_BACKENDS:
        raise ValueError(
            f"CSTPU_MERKLE_BACKEND must be one of {_PAIR_BACKENDS}, "
            f"got {name!r}")
    return name


def pair_hash_words(words: jnp.ndarray) -> jnp.ndarray:
    """[N, 16] uint32 words -> [N, 8] digests via the selected backend.

    Host-orchestration entry point (called OUTSIDE jit, once per Merkle
    level); both backends are bit-identical (tests/test_sha256_pallas.py,
    tests/test_incremental_merkle.py)."""
    if merkle_pair_backend_name() == "pallas":
        from .sha256_pallas import sha256_pairs_pallas
        return sha256_pairs_pallas(words)
    return sha256_pairs(words)


# ---------------------------------------------------------------------------
# Pluggable pair-hasher backend for utils.hash (host bytes in/out)
# ---------------------------------------------------------------------------

_DEVICE_MIN_BATCH = 256  # below this, OpenSSL beats the dispatch overhead


def jax_pair_hasher(blocks: List[bytes]) -> List[bytes]:
    """Drop-in for utils.hash.hash_pairs: batch 64-byte inputs onto the device."""
    if len(blocks) < _DEVICE_MIN_BATCH:
        from ..utils.hash import _host_hash_pairs
        return _host_hash_pairs(blocks)
    arr = np.frombuffer(b"".join(blocks), dtype=np.uint8).reshape(len(blocks), 64)
    digests = sha256_pairs(jnp.asarray(bytes_to_words(arr)))
    out = words_to_bytes(np.asarray(digests))
    return [out[i].tobytes() for i in range(len(blocks))]


def install_device_hasher() -> None:
    from ..utils.hash import set_pair_hasher
    set_pair_hasher(jax_pair_hasher)


# ---------------------------------------------------------------------------
# Trace-tier kernel contract (tools/analysis/trace/, `make contracts`)
# ---------------------------------------------------------------------------
# One Merkle pair-hash level at a canonical 8-lane batch: the graph-size
# ratchet guards the 2x64-round compression structure (a silently
# doubled round count or a dead extra compression shows up as an eqn
# jump), and the hygiene scans keep the bulk Merkleizer's inner loop
# free of f64 upcasts, host callbacks, and staged transfers.

TRACE_CONTRACTS = [
    dict(
        name="ops.sha256.pair_hash_level",
        build=lambda: dict(
            fn=lambda w: sha256_pairs_inner(w),
            args=(jnp.zeros((8, 16), jnp.uint32),)),
        budgets={"jaxpr_eqns": 3_000},
        forbid=("f64", "callback", "device_put"),
    ),
]


# ---------------------------------------------------------------------------
# Value-range contract (tools/analysis/ranges/, `make ranges`)
# ---------------------------------------------------------------------------
# SHA-256 is DEFINED over uint32 modular arithmetic: every add/rotate in
# the 64-round compression wraps mod 2^32 by design. The contract
# declares exactly that (`wrap_ok=("uint32",)`), so the interpreter
# walks the real fori-form rounds without flagging a single intentional
# wrap — while the declaration documents the wrap surface and any OTHER
# dtype creeping into the compression (an int64 index, an f32 upcast)
# would still be checked against ITS range.

RANGE_CONTRACTS = [
    dict(
        name="ops.sha256.single_block_mod32",
        build=lambda: dict(
            fn=lambda w: sha256_single_block(w),
            args=(jnp.zeros((4, 16), jnp.uint32),),
            ranges=({"lo": 0, "hi": (1 << 32) - 1},)),
        wrap_ok=("uint32",),
        output={"lo": 0, "hi": (1 << 32) - 1},
    ),
]
