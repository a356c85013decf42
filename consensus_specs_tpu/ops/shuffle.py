"""Swap-or-not shuffle as a batched, gather-free JAX kernel.

The reference evaluates the permutation one index at a time — 90 rounds × 2
hashes per index (/root/reference specs/core/0_beacon-chain.md:860-882) — and
calls it per committee slot (:884-891). Here the *whole* permutation for
(seed, n) is one traced program.

TPU-native formulation: evaluating the per-index point function on all indices
at once needs a random gather per round (bits indexed by the evolving index
values), which XLA lowers catastrophically on TPU. Instead the kernel uses the
*positional* form of the network: each round is an involution on positions,
  f_r(p) = flip(p) = (pivot_r - p) mod n   iff bit_r(max(p, flip(p))),
and `A[flip(p)]` over all p is `roll(reverse(A), pivot+1)` — contiguous memory
movement, no gather. Composing contents C[p] <- C[f(p)] with rounds applied in
REVERSE order yields C_final[p] = (f_{R-1} ∘ … ∘ f_0)(p) = get_shuffled_index(p)
directly (for involutions, reverse-order content evolution composes the
forward permutation). Per round: two reverse+rolls and two selects over [n] —
~90 × O(n) streaming traffic, zero random access.

All `rounds × ceil(n/256)` position-block digests come from one batched
SHA-256 dispatch; per-round pivots (64-bit modular reduction of 33-byte
hashes) are computed host-side where bignum mod is free.

Index dtype is int32: n is asserted < 2**30 (the spec bound is 2**40, but a
validator registry is millions, not billions; the one-point oracle
`get_shuffled_index` retains full-range semantics). The int32 choice is
MACHINE-AUDITED at the ceiling: the value-range contract below
(`make ranges`) walks all 90 rounds at n = 2**30 - 1 and proves every
index intermediate — `pivot - pos` in (-(n-1), n-1), the `flip + n`
renormalization peaking at 2n - 1 = 2**31 - 1, the roll/slice starts (the
wrapped flip's `pivot + 1 + n` among them) — stays inside int32, and the
stored contents inside [0, capacity - 1] (the permutation's inside
[0, n-1]: the traced count's padding holds its own positions); any
widening of `_MAX_N` past 2**30 (where `flip + n` would genuinely wrap)
trips CSA1401 before it can ship.
"""
from __future__ import annotations

import hashlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .sha256 import bytes_to_words, sha256_single_block

_MAX_N = 1 << 30


def _round_bits(seed_words: jnp.ndarray, n: int, rounds: int,
                dtype) -> jnp.ndarray:
    """[rounds, n] per-position decision bits — the consensus-critical
    digest grammar (seed ‖ round ‖ block_index single-block SHA-256,
    spec :860-882) in ONE place, shared by both kernel variants.

    Message layout (big-endian words): w0..w7 = seed; byte32 = round,
    bytes 33..36 = block index little-endian, byte 37 = 0x80 terminator,
    w15 = bit length (37*8). All R*B digests come from one batched
    compression; the host ships 32 bytes, not megabytes."""
    n_blocks = (n + 255) // 256
    blk = jnp.arange(n_blocks, dtype=jnp.uint32)[None, :]            # [1, B]
    rnd = jnp.arange(rounds, dtype=jnp.uint32)[:, None]              # [R, 1]
    w8 = (rnd << 24) | ((blk & 0xFF) << 16) | (((blk >> 8) & 0xFF) << 8) | ((blk >> 16) & 0xFF)
    w9 = jnp.broadcast_to((((blk >> 24) & 0xFF) << 24) | jnp.uint32(0x80 << 16),
                          (rounds, n_blocks))
    zeros = jnp.zeros((rounds, n_blocks), dtype=jnp.uint32)
    w15 = jnp.full((rounds, n_blocks), 37 * 8, dtype=jnp.uint32)
    seed_bcast = [jnp.broadcast_to(seed_words[i], (rounds, n_blocks)) for i in range(8)]
    source_words = jnp.stack(
        seed_bcast + [w8, w9, zeros, zeros, zeros, zeros, zeros, w15], axis=-1)
    digests = sha256_single_block(source_words)
    # Expand to per-position bits [R, n]: byte j of a digest is word j//4,
    # big-endian within the word; bit k of byte j decides position 8j+k.
    shifts = (24 - 8 * (np.arange(32, dtype=np.uint32) // 8 % 4)  # byte shift
              + np.arange(32, dtype=np.uint32) % 8)               # bit shift
    bits = (digests[..., :, None] >> shifts.astype(jnp.uint32)) & jnp.uint32(1)
    return bits.reshape(rounds, n_blocks * 256)[:, :n].astype(dtype)


def host_pivots(seed: bytes, n: int, rounds: int) -> np.ndarray:
    """Per-round pivots (64-bit modular reduction of the round hash) —
    tiny host work where bignum mod is free."""
    pivots = np.empty(rounds, dtype=np.int32)
    for r in range(rounds):
        digest = hashlib.sha256(seed + bytes([r])).digest()
        pivots[r] = int.from_bytes(digest[:8], "little") % n
    return pivots


def shuffle_capacity(n: int) -> int:
    """The length the shuffle's program runs at for `n` positions: `n`
    rounded up to a multiple of a sixteenth of its power of two, so a
    count that moves a little (an active set that loses its churn every
    epoch) stays at one shape, and at most a sixteenth of the positions
    are padding."""
    step = 1 << max(int(n).bit_length() - 5, 0)
    return -(-int(n) // step) * step


@partial(jax.jit, static_argnames=("capacity", "rounds"))
def _shuffle_rounds(seed_words: jnp.ndarray, pivots: jnp.ndarray,
                    n: jnp.ndarray, capacity: int, rounds: int) -> jnp.ndarray:
    """seed_words: [8] uint32 (big-endian seed), pivots: [R] int32 (< n),
    n: int32 scalar, TRACED, 0 < n <= capacity (static): every count of a
    capacity shares one program.

    Returns [capacity] int32 whose first n entries are perm[p] = image of
    index p under the shuffle of n; what follows them is padding.
    """
    N = capacity
    with jax.named_scope("shuffle_round_bits"):
        bits = _round_bits(seed_words, N, rounds, jnp.bool_)
    pos = jnp.arange(N, dtype=jnp.int32)
    C0 = pos

    def flipped(X, pivot, low):
        # X[flip(p)] for all p < n. Over the N stored positions,
        # X[pivot - p] is roll(reverse(X), pivot + 1) and X[pivot + n - p]
        # is roll(reverse(X), pivot + 1 + n): the first serves p <= pivot,
        # the second the positions above the pivot, whose flip wraps at n
        # and not at N. Both read positions below n only.
        rev = X[::-1]
        return jnp.where(low, jnp.roll(rev, pivot + 1),
                         jnp.roll(rev, pivot + 1 + n))

    def body(k, C):
        r = rounds - 1 - k  # reverse round order -> forward permutation
        pivot = pivots[r]
        flip = pivot - pos
        low = flip >= 0
        flip = jnp.where(low, flip, flip + n)
        C_flip = flipped(C, pivot, low)
        bits_r = bits[r]
        bits_flip = flipped(bits_r, pivot, low)
        # decision bit lives at max(p, flip(p))
        bit_at_max = jnp.where(pos >= flip, bits_r, bits_flip)
        return jnp.where(bit_at_max, C_flip, C)

    with jax.named_scope("shuffle_rounds"):
        return jax.lax.fori_loop(0, rounds, body, C0)


@partial(jax.jit, static_argnames=("n", "rounds"))
def _shuffle_rounds_stacked(seed_words: jnp.ndarray, pivots: jnp.ndarray,
                            n: int, rounds: int) -> jnp.ndarray:
    """A/B variant of _shuffle_rounds: the contents C and the round's
    decision bits ride ONE [2, n] int32 array, so each round's
    reverse+roll is a single data movement (one kernel, shared shift)
    instead of two. Bytes moved rise slightly (bits as int32, not bool);
    kernel-launch/fusion-boundary count halves. Which effect wins on the
    chip is an empirical question nothing has timed yet; bit-equality is
    pinned in tests/test_shuffle_kernel.py.
    """
    bits = _round_bits(seed_words, n, rounds, jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)

    def body(k, C):
        r = rounds - 1 - k
        pivot = pivots[r]
        flip = pivot - pos
        flip = jnp.where(flip < 0, flip + n, flip)
        X = jnp.stack([C, bits[r]])                    # [2, n]
        X_flip = jnp.roll(X[:, ::-1], pivot + 1, axis=1)
        bit_at_max = jnp.where(pos >= flip, X[1], X_flip[1])
        return jnp.where(bit_at_max == 1, X_flip[0], C)

    return jax.lax.fori_loop(0, rounds, body, pos)


def _padded_permutation(seed: bytes, index_count: int, rounds: int) -> jnp.ndarray:
    """`_shuffle_rounds` for (seed, n): [shuffle_capacity(n)] int32 on the
    device, the permutation in its first n entries."""
    n = int(index_count)
    assert 0 < n < _MAX_N
    seed_words = jnp.asarray(bytes_to_words(np.frombuffer(seed, dtype=np.uint8)))
    return _shuffle_rounds(
        seed_words, jnp.asarray(host_pivots(seed, n, rounds)),
        np.int32(n), shuffle_capacity(n), rounds)


def shuffle_permutation_on_device(seed: bytes, index_count: int, rounds: int) -> jnp.ndarray:
    """perm[i] == get_shuffled_index(i, index_count, seed), as a DEVICE array.

    The device-resident entry point for jitted pipelines (committee slicing,
    epoch processing): nothing but the 32-byte seed and 90 pivots crosses the
    host↔device boundary. The cut to index_count entries is a slice
    program of its own a count; shuffle_permutation_device, which serves
    the spec's hook, cuts on the host and compiles nothing a count.
    """
    return _padded_permutation(seed, index_count, rounds)[:int(index_count)]


def shuffle_permutation_device(seed: bytes, index_count: int, rounds: int) -> np.ndarray:
    """Host-facing wrapper: same permutation, materialized as numpy int64."""
    padded = np.asarray(_padded_permutation(seed, index_count, rounds))
    return padded[:int(index_count)].astype(np.int64)


# ---------------------------------------------------------------------------
# Value-range contract (tools/analysis/ranges/, `make ranges`)
# ---------------------------------------------------------------------------
# The swap-or-not round arithmetic at the maximum validator count: all
# 90 rounds traced at n = _MAX_N - 1 (ShapeDtypeStruct — nothing
# allocates), digest words declared intentionally mod-2^32
# (`wrap_ok=("uint32",)`, the SHA-256 grammar), and the int32 index
# math proven wrap-free, with the permutation contents pinned inside
# [0, n-1]. This is the audit the module docstring cites.

def _shuffle_ranges_build():
    import jax as _jax
    n, rounds = _MAX_N - 1, 90
    return dict(
        fn=lambda s, p, count: _shuffle_rounds(
            s, p, count, capacity=shuffle_capacity(n), rounds=rounds),
        args=(_jax.ShapeDtypeStruct((8,), jnp.uint32),
              _jax.ShapeDtypeStruct((rounds,), jnp.int32),
              _jax.ShapeDtypeStruct((), jnp.int32)),
        ranges=({"lo": 0, "hi": (1 << 32) - 1},      # seed words
                {"lo": 0, "hi": _MAX_N - 2},         # host pivots < n
                {"lo": 1, "hi": n}))                 # the traced count


RANGE_CONTRACTS = [
    dict(
        name="ops.shuffle.swap_or_not_ceiling",
        build=_shuffle_ranges_build,
        wrap_ok=("uint32",),
        # perm values < n in the first n entries; the padding behind them
        # holds positions < the capacity, which at this n is _MAX_N
        output={"lo": 0, "hi": _MAX_N - 1},
    ),
]


def install_device_shuffler(min_n: int = 1 << 13) -> None:
    """Route the spec's batched-permutation hook to the device kernel.

    Below min_n the host numpy path wins (dispatch overhead dominates);
    above it, the device runs all rounds in one program.
    """
    from ..models.phase0 import helpers

    def backend(seed: bytes, index_count: int, rounds: int):
        if index_count < min_n or index_count >= _MAX_N:
            return None  # fall back to host path (small n, or beyond int32 range)
        return shuffle_permutation_device(seed, index_count, rounds)

    helpers.set_shuffle_backend(backend)
