"""Persistent, device-resident incremental Merkle forest.

The per-slot full-state `hash_tree_root` (reference hot path,
/root/reference specs/core/0_beacon-chain.md:1232-1245, Merkle loop at
test_libs/pyspec/eth2spec/utils/merkle_minimal.py:47-54) pays O(V)
compressions per root even when a block touched a handful of validators:
every device path so far (bulk.merkleize_chunk_array, merkle_reduce_words)
recomputes the whole tree from its leaves. This module keeps EVERY level of
a tree resident as `[n_level, 8]` uint32 word arrays and re-hashes only the
root paths of updated leaves — one batched pair-hash launch per level, so an
update costs O(dirty * log V) compressions instead of O(V).

Semantics are exactly SSZ merkleize (specs/simple-serialize.md:139-147):
the leaf count pads to the next power of two with zero chunks. A tree has a
CAPACITY (the rows of level 0, fixed when it is built) and a LOGICAL leaf
count `n` <= capacity: rows from `n` to the capacity are materialized zero
chunks, rows beyond the capacity are virtual (stored level `d` holds
ceil(capacity / 2**d) rows, the rest equal `zerohashes[d]`). A list that
grows takes its new leaves by `update` / `update_bucket` at rows from `n` on
with the new `logical_n`: no level changes shape, so no program compiles.
The root of the `n` logical leaves is the first node of level
`tree_depth(n)` (`root_level`), which is the top one while more than half of
the padded capacity is in use. A tree whose capacity is passed is built anew
by its owner (models/phase0/resident.py re-lays its whole core out).

Level scatters donate the old level buffer (`donate_argnums`), so a dirty
update rewrites rows in place instead of copying registry-scale arrays.
Dirty index sets pad to the next power of two (duplicating the last index —
duplicate scatters write identical values) so the jit cache sees log-many
shapes per level, not one per dirty count.

The pair hash routes through ops.sha256.pair_hash_words, making the forest
A/B-switchable between the XLA kernel and the Pallas kernel
(CSTPU_MERKLE_BACKEND=pallas|xla). `last_pairs_per_level` records the lanes
dispatched by the most recent operation so tests (and benches) can assert
the O(dirty * log V) work bound instead of trusting wall-clock.
"""
from __future__ import annotations

from typing import List

import numpy as np

import jax
import jax.numpy as jnp

from ..donation import platform_donated_jit
from ..hash import ZERO_BYTES32
from ..merkle import next_power_of_two, tree_depth
from ...ops.sha256 import (_unroll_for, bytes_to_words, merkle_pair_backend_name,
                           pair_hash_words, sha256_pairs_inner, words_to_bytes,
                           zerohash_words)
from ...telemetry import counter as _tele_counter

# Process-wide forest accounting in the telemetry registry; the
# per-instance attributes (`last_pairs_per_level`, `total_pairs_hashed`,
# `builds`) stay as the per-tree view tests and benches assert on.
_PAIR_LANES = _tele_counter("merkle.forest.pair_lanes")
_PAIR_LAUNCHES = _tele_counter("merkle.forest.launches")
_FOREST_BUILDS = _tele_counter("merkle.forest.builds")


def _scatter_rows_traced(level: jnp.ndarray, idx: jnp.ndarray,
                         rows: jnp.ndarray) -> jnp.ndarray:
    return level.at[idx].set(rows)


# level.at[idx].set(rows) with the old buffer donated on accelerator
# backends: the update rewrites the resident level in place instead of
# copying O(n) rows. XLA:CPU keeps the undonated (copying) form — CPU
# executables deserialized from the persistent compilation cache have
# been observed to violate donated input/output aliasing (see
# utils/donation.py), and tests differential on CPU.
_scatter_rows_pd = platform_donated_jit(_scatter_rows_traced,
                                        donate_argnums=(0,))


def _scatter_rows(level: jnp.ndarray, idx: jnp.ndarray,
                  rows: jnp.ndarray) -> jnp.ndarray:
    return _scatter_rows_pd(level, idx, rows)


def _zero_rows(depth: int, k: int) -> jnp.ndarray:
    """[k, 8] words, every row the depth-`depth` zero-subtree root."""
    return jnp.broadcast_to(jnp.asarray(zerohash_words(depth)), (k, 8))


@jax.jit
def _build_levels(leaf_words: jnp.ndarray):
    """Every level of the tree in ONE traced program — the full build (the
    epoch-boundary degenerate case) must cost what the fused one-shot root
    programs cost, not a per-level dispatch chain. Same per-level zerohash
    padding as merkle_reduce_words; jit-cached per leaf count (a resident
    deployment has one)."""
    levels = [leaf_words]
    level = leaf_words
    depth = 0
    while level.shape[0] > 1:
        if level.shape[0] % 2:
            level = jnp.concatenate([level, _zero_rows(depth, 1)])
        pairs = level.reshape(-1, 16)
        with jax.named_scope(f"forest_level_{depth}"):
            level = sha256_pairs_inner(pairs,
                                       unroll=_unroll_for(pairs.shape[0]))
        levels.append(level)
        depth += 1
    return tuple(levels)


def _update_paths_traced(levels, rows, idx: np.ndarray):
    """Pure single-program twin of `update()` with a STATIC dirty set:
    the scatter plus every level's path re-hash in one traceable
    function (the instance method interleaves host bookkeeping and
    per-level launches; this form exists so the memory tier can model
    the whole update's liveness and O(dirty * log V) byte order over
    one jaxpr). Same gather/zerohash/scatter sequence as
    `_rehash_paths`, minus the lane accounting."""
    levels = list(levels)
    idx = np.asarray(idx, np.int32).reshape(-1)
    levels[0] = _scatter_rows_traced(levels[0], jnp.asarray(idx), rows)
    dirty = np.unique(idx)
    for d in range(len(levels) - 1):
        parents = np.unique(dirty >> 1)
        lanes = _pad_pow2_indices(parents)
        level = levels[d]
        n_d = level.shape[0]
        left = level[jnp.asarray(lanes * 2)]
        ri = lanes * 2 + 1
        right = level[jnp.asarray(np.minimum(ri, n_d - 1))]
        virtual = ri >= n_d
        if virtual.any():
            right = jnp.where(jnp.asarray(virtual)[:, None],
                              _zero_rows(d, 1), right)
        digests = pair_hash_words(jnp.concatenate([left, right], axis=1))
        levels[d + 1] = _scatter_rows_traced(levels[d + 1],
                                             jnp.asarray(lanes), digests)
        dirty = parents
    return tuple(levels)


def _update_bucket_traced(levels, idx, rows, unroll: bool):
    """`update()` with a TRACED dirty set: the leaf scatter plus every
    level's path re-hash for the `[k]` leaf indices `idx`, in one program
    whose shapes are the tree's and k alone, so what the dirty leaves are
    changes no shape and a serving loop compiles one program a tree and a
    bucket size k. Every level hashes k lanes, lane j the parent on leaf
    j's path (lanes that share a parent hash and scatter the same value).

    The levels depend on each other through the dirty nodes only, so the
    program reads every node's stored sibling first (one gather a level,
    none waits for another), then walks the levels in a `lax.scan` whose
    body holds ONE pair hash: a lane's own child is the digest it carried
    up, its sibling the digest of the lane that carried it if the sibling
    is dirty too (a k x k match), else the stored one. The digests go
    into their levels afterwards, one scatter a level. A step is a few
    small kernels, where a pair hash a level in line would be the depth
    times the hash to compile or, rolled, 224 loop turns a level to run.
    Returns the new levels."""
    depth = len(levels) - 1
    idx = idx.astype(jnp.int32)
    leaves = levels[0].at[idx].set(rows)
    if depth == 0:
        return (leaves,)
    # a stored sibling is read only where the sibling is not dirty itself,
    # so the levels as they came serve (the new leaves change dirty rows)
    stored = []
    for d in range(depth):
        level = levels[d]
        n_d = level.shape[0]
        sibling = (idx >> d) ^ 1
        rows_d = level[jnp.minimum(sibling, n_d - 1)]
        if n_d % 2:     # an odd tail's sibling is the virtual zerohash
            rows_d = jnp.where((sibling >= n_d)[:, None], _zero_rows(d, 1),
                               rows_d)
        stored.append(rows_d)

    def step(carried, xs):
        d, stored_d = xs
        nodes = idx >> d
        sibling = nodes ^ 1
        match = sibling[:, None] == nodes[None, :]
        other = jnp.where(match.any(axis=1)[:, None],
                          carried[jnp.argmax(match, axis=1)], stored_d)
        odd = ((nodes & 1) == 1)[:, None]
        pairs = jnp.concatenate([jnp.where(odd, other, carried),
                                 jnp.where(odd, carried, other)], axis=1)
        digests = sha256_pairs_inner(pairs, unroll=unroll)
        return digests, digests

    with jax.named_scope("forest_paths"):
        _, digests = jax.lax.scan(
            step, rows, (jnp.arange(depth, dtype=jnp.int32),
                         jnp.stack(stored)))
    return (leaves,) + tuple(
        levels[d + 1].at[idx >> (d + 1)].set(digests[d]) for d in range(depth))


# the levels donated on accelerator backends, like the level scatters
_update_bucket_pd = platform_donated_jit(
    _update_bucket_traced, donate_argnums=(0,), static_argnames=("unroll",))


def bucket_indices(idx: np.ndarray, floor: int = 32) -> np.ndarray:
    """`idx` as int32, padded by repeating its last entry to the bucket a
    serving loop updates a forest at: the next power of two that holds it,
    `floor` at least, so that the dirty sets of a block (16 exits, a few
    slashings) all meet one program."""
    idx = np.asarray(idx, np.int32).reshape(-1)
    m = max(next_power_of_two(idx.shape[0]), floor)
    return np.concatenate([idx, np.full(m - idx.shape[0], idx[-1], np.int32)])


def _pad_pow2_indices(idx: np.ndarray) -> np.ndarray:
    """Pad an index vector to the next power of two by repeating its last
    entry (bounds jit-cache shapes; duplicates are harmless for gather and
    for scatters that write identical values)."""
    m = next_power_of_two(idx.shape[0])
    if m == idx.shape[0]:
        return idx
    return np.concatenate([idx, np.full(m - idx.shape[0], idx[-1], idx.dtype)])


class IncrementalMerkleTree:
    """All levels of one pow2-padded SSZ Merkle tree, device-resident.

    build:  IncrementalMerkleTree(leaf_words)   [capacity, 8] uint32 big-endian words
            (`logical_n` <= capacity: the rows from it on are zero chunks)
    update: tree.update(leaf_idx, rows_words)   O(dirty * log n) compressions
            (`logical_n=`: the list's new length, when rows from `n` on are written)
    root:   tree.root() -> 32 bytes             (the only device download)

    List-kind callers mix the length in themselves (impl.mix_in_length), the
    same contract as bulk.merkleize_chunk_array.

    The tree takes OWNERSHIP of device-array arguments: level buffers are
    donated back into scatters on update, so a jnp `leaf_words`/`rows_words`
    must not be reused by the caller afterwards (numpy inputs are copied on
    upload and stay valid).
    """

    def __init__(self, leaf_words, pair_fn=None, logical_n: int = None):
        leaf_words = jnp.asarray(leaf_words, jnp.uint32)
        assert leaf_words.ndim == 2 and leaf_words.shape[1] == 8, \
            leaf_words.shape
        self._set_logical_n(int(leaf_words.shape[0]) if logical_n is None
                            else logical_n, int(leaf_words.shape[0]))
        self._pair_fn = pair_fn          # None = ops.sha256.pair_hash_words
        self.last_pairs_per_level: List[int] = []
        self.total_pairs_hashed = 0
        self.builds = 0
        self.levels: List[jnp.ndarray] = [leaf_words]
        self._build()

    @property
    def n(self) -> int:
        """The logical leaf count (the list's length)."""
        return self._n

    @property
    def capacity(self) -> int:
        return int(self.levels[0].shape[0])

    @property
    def depth(self) -> int:
        return len(self.levels) - 1

    def _set_logical_n(self, n: int, capacity: int) -> None:
        assert 0 <= n <= capacity, \
            f"logical length {n} outside a capacity of {capacity} rows"
        self._n = int(n)

    def _take_logical_n(self, idx: np.ndarray, logical_n) -> None:
        """An update's range check: the written rows lie inside the
        logical length, the new one where the update gives it."""
        n = self._n if logical_n is None else int(logical_n)
        assert n >= self._n, "a list's tree never shrinks"
        assert 0 <= idx.min() and idx.max() < n, \
            f"leaf index out of range (n={n}, capacity={self.capacity})"
        self._set_logical_n(n, self.capacity)

    def _hash(self, pairs: jnp.ndarray) -> jnp.ndarray:
        fn = self._pair_fn if self._pair_fn is not None else pair_hash_words
        return fn(pairs)

    def _count(self, depth: int, lanes: int) -> None:
        while len(self.last_pairs_per_level) <= depth:
            self.last_pairs_per_level.append(0)
        self.last_pairs_per_level[depth] += lanes
        self.total_pairs_hashed += lanes
        _PAIR_LANES.inc(lanes)
        _PAIR_LAUNCHES.inc()

    # -- full build (the epoch-boundary degenerate case) --------------------

    def _build(self) -> None:
        self.builds += 1
        _FOREST_BUILDS.inc()
        self.last_pairs_per_level = []
        level = self.levels[0]
        del self.levels[1:]
        depth = tree_depth(level.shape[0])
        if depth == 0:
            return
        if self._pair_fn is None and merkle_pair_backend_name() == "xla":
            # default kernel: the whole build is one traced program
            self.levels = list(_build_levels(level))
            for d in range(depth):
                self._count(d, (self.levels[d].shape[0] + 1) // 2)
            return
        # explicit/Pallas backends keep the per-level host loop (the A/B
        # boundary lives at the per-launch pair hash)
        for d in range(depth):
            if level.shape[0] % 2:
                level = jnp.concatenate([level, _zero_rows(d, 1)])
            pairs = level.reshape(-1, 16)
            level = self._hash(pairs)
            self._count(d, pairs.shape[0])
            self.levels.append(level)

    # -- incremental paths --------------------------------------------------

    def update(self, leaf_idx, rows_words, logical_n: int = None) -> None:
        """Overwrite leaves and re-hash only their root paths.

        leaf_idx: [k] unique in-range ints; rows_words: [k, 8] uint32;
        `logical_n`: the list's new length where leaves from `n` on are
        among them (inside the capacity)."""
        idx = np.asarray(leaf_idx, dtype=np.int32).reshape(-1)
        rows = jnp.asarray(rows_words, jnp.uint32).reshape(-1, 8)
        assert idx.shape[0] == rows.shape[0], (idx.shape, rows.shape)
        if idx.shape[0] == 0:
            self.last_pairs_per_level = []
            return
        dirty = np.unique(idx)
        assert dirty.shape[0] == idx.shape[0], "duplicate leaf indices"
        self._take_logical_n(dirty, logical_n)
        self.levels[0] = _scatter_rows(self.levels[0], jnp.asarray(idx), rows)
        self.last_pairs_per_level = []
        self._rehash_paths(dirty)

    def update_bucket(self, leaf_idx, rows_words,
                      logical_n: int = None) -> None:
        """`update` for a serving loop: `leaf_idx` is a `bucket_indices`
        bucket (in-range, repeats allowed where the rows repeat with
        them), `rows_words` the `[k, 8]` device leaves at them; the
        scatter and all the path levels are ONE dispatched program
        (`_update_bucket_traced`), k lanes a level, nothing comes back to
        the host. The levels keep their placement, and leaves appended
        inside the capacity (`logical_n`) meet the same program."""
        idx = np.asarray(leaf_idx, np.int32).reshape(-1)
        self._take_logical_n(idx, logical_n)
        idx = jnp.asarray(idx)
        rows = jnp.asarray(rows_words, jnp.uint32).reshape(-1, 8)
        assert idx.shape[0] == rows.shape[0], (idx.shape, rows.shape)
        # the pair hash's TPU form off the CPU (sha256._unroll_for's reason)
        self.levels = list(self._update_bucket_fn()(
            tuple(self.levels), idx, rows,
            unroll=jax.default_backend() != "cpu"))
        self.last_pairs_per_level = []
        for d in range(self.depth):
            self._count(d, int(idx.shape[0]))

    def _update_bucket_fn(self):
        return _update_bucket_pd

    def _rehash_paths(self, dirty: np.ndarray) -> None:
        """Re-hash the ancestor rows of `dirty` leaves, one batched pair-hash
        launch per level (dirty set padded to pow2 to bound jit shapes)."""
        for d in range(self.depth):
            parents = np.unique(dirty >> 1)
            lanes = _pad_pow2_indices(parents)
            level = self.levels[d]
            n_d = level.shape[0]
            left = level[jnp.asarray(lanes * 2)]
            ri = lanes * 2 + 1
            right = level[jnp.asarray(np.minimum(ri, n_d - 1))]
            virtual = ri >= n_d            # odd tail: right child is zerohash
            if virtual.any():
                right = jnp.where(jnp.asarray(virtual)[:, None],
                                  _zero_rows(d, 1), right)
            digests = self._hash(jnp.concatenate([left, right], axis=1))
            self.levels[d + 1] = _scatter_rows(
                self.levels[d + 1], jnp.asarray(lanes), digests)
            self._count(d, int(lanes.shape[0]))
            dirty = parents

    # -- root ---------------------------------------------------------------

    def root_level(self) -> jnp.ndarray:
        """The level whose first node is the root of the `n` logical
        leaves: fetched whole by a serving loop (a row sliced on the
        device is a program a level shape), one row while more than half
        of the padded capacity is in use."""
        return self.levels[tree_depth(self._n)]

    def root(self) -> bytes:
        """The pow2-padded merkleize root of the logical leaves,
        bit-identical to bulk.merkleize_chunk_array over the equivalent
        chunk matrix."""
        if self.n == 0:
            return ZERO_BYTES32
        return words_to_bytes(np.asarray(self.root_level())[0]).tobytes()


class ShardedIncrementalMerkleTree(IncrementalMerkleTree):
    """The forest under a validator-axis ServingMesh (ROADMAP item 1):
    per-shard subtree levels stay RESIDENT ON THEIR SHARD, a tiny
    replicated cap tree joins the per-shard roots, and updates scatter
    only into the owning shard (a scatter with replicated updates into a
    sharded operand keeps the operand's placement — each device rewrites
    its own rows).

    The single-device tree's contract (a capacity, a logical `n`, zero
    chunks between them) with one more condition: jax pins shard sizes at
    placement time, so the capacity is a power of two (a multiple of the
    mesh size by construction, both being powers of two) and no level has
    a virtual tail. A level shards over "v" while its row count divides
    the mesh and replicates above that (the cap). Every stored node that
    both layouts hold, and the root, is bit-identical to the
    single-device tree's (tests/test_multichip.py).

    `placement` is a parallel.sharding.ServingMesh (duck-typed: needs
    row_sharding / forest_build_jit / size).
    """

    def __init__(self, leaf_words, placement, pair_fn=None,
                 logical_n: int = None):
        import jax.numpy as jnp
        self._placement = placement
        leaf_words = jnp.asarray(leaf_words, jnp.uint32)
        assert leaf_words.ndim == 2 and leaf_words.shape[1] == 8, \
            leaf_words.shape
        rows = int(leaf_words.shape[0])
        if logical_n is None:
            # raw leaves: pad to pow2 here (zero rows == zerohash level 0)
            logical_n = rows
            cap = next_power_of_two(max(rows, 1))
            if cap > rows:
                leaf_words = jnp.concatenate(
                    [leaf_words, jnp.zeros((cap - rows, 8), jnp.uint32)])
        else:
            assert rows == next_power_of_two(rows), (rows, logical_n)
        self._set_logical_n(logical_n, int(leaf_words.shape[0]))
        level0 = jax.device_put(
            leaf_words, placement.row_sharding(int(leaf_words.shape[0])))
        self._pair_fn = pair_fn
        self.last_pairs_per_level = []
        self.total_pairs_hashed = 0
        self.builds = 0
        self.levels = [level0]
        self._build()

    def _build(self) -> None:
        self.builds += 1
        _FOREST_BUILDS.inc()
        self.last_pairs_per_level = []
        level = self.levels[0]
        del self.levels[1:]
        depth = tree_depth(int(level.shape[0]))
        if depth == 0:
            return
        if self._pair_fn is None and merkle_pair_backend_name() == "xla":
            # one traced program, every level placed per row_sharding
            fn = self._placement.forest_build_jit(int(level.shape[0]))
            self.levels = list(fn(level))
            for d in range(depth):
                self._count(d, self.levels[d].shape[0] // 2)
            return
        for d in range(depth):
            pairs = level.reshape(-1, 16)
            level = jax.device_put(
                self._hash(pairs),
                self._placement.row_sharding(pairs.shape[0]))
            self._count(d, pairs.shape[0])
            self.levels.append(level)

    def _update_bucket_fn(self):
        """The bucket update with every level pinned to the placement it
        has (a level sharded by row stays so, the cap stays replicated)."""
        key = tuple(int(l.shape[0]) for l in self.levels)
        fn = getattr(self, "_bucket_fn", None)
        if fn is None or fn[0] != key:
            pdj = platform_donated_jit
            fn = (key, pdj(
                _update_bucket_traced, donate_argnums=(0,),
                static_argnames=("unroll",),
                out_shardings=tuple(l.sharding for l in self.levels)))
            self._bucket_fn = fn
        return fn[1]

    # update() is inherited verbatim: with pow2-materialized levels the
    # odd-tail/virtual-row branches of _rehash_paths never trigger and the
    # level scatters preserve each level's placement.


def tree_from_chunks(chunks: np.ndarray,
                     pair_fn=None) -> IncrementalMerkleTree:
    """[n, 32] uint8 chunk matrix -> forest (byte-level convenience)."""
    chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
    assert chunks.ndim == 2 and chunks.shape[1] == 32, chunks.shape
    words = (np.zeros((0, 8), np.uint32) if chunks.shape[0] == 0
             else bytes_to_words(chunks))   # reshape of 0 rows is ill-defined
    return IncrementalMerkleTree(words, pair_fn=pair_fn)


# ---------------------------------------------------------------------------
# Trace-tier kernel contract (tools/analysis/trace/, `make contracts`)
# ---------------------------------------------------------------------------
# PR 3's O(dirty * log V) invariant as exact pair-lane pins at a
# canonical shape: a 64-leaf forest costs exactly n-1 = 63 pair lanes to
# build, and a 2-dirty update re-hashes only the two root paths (11
# lanes here — they merge two levels below the root). A kernel change
# that silently rebuilds a level (or the whole forest) on update shows
# up as a lane jump long before the benchmark's `forest_build_ms` moves.

def _forest_lane_measure():
    leaves = np.arange(64 * 8, dtype=np.uint32).reshape(64, 8)
    tree = IncrementalMerkleTree(leaves)
    build_lanes = sum(tree.last_pairs_per_level)
    tree.update(np.array([3, 40]), np.zeros((2, 8), np.uint32))
    update_lanes = sum(tree.last_pairs_per_level)
    return {"build_pair_lanes": build_lanes,
            "update_pair_lanes": update_lanes}


TRACE_CONTRACTS = [
    dict(
        name="utils.ssz.incremental.forest_pair_lanes",
        measure=_forest_lane_measure,
        budgets={"build_pair_lanes": 63, "update_pair_lanes": 11},
        exact=("build_pair_lanes", "update_pair_lanes"),
    ),
]


# ---------------------------------------------------------------------------
# Memory contracts (tools/analysis/memory/, `make memory`)
# ---------------------------------------------------------------------------
# The 2^20-leaf forest (a 1M-validator registry's chunk tree): the full
# build's peak is every level live at once (Sum n/2^d = 2n rows of 32 B)
# plus the pair-hash transients — O(V), pinned by the capacity probes —
# and an update's bytes beyond the donated-and-aliased level buffers
# (counted ONCE, the donation the class performs through
# platform_donated_jit) are the gathered children, the schedule windows
# and the digests of the dirty root paths: O(dirty * log V), pinned by
# the dirty-count probes at a fixed 2^16 capacity. A kernel change that
# re-hashes a whole level on update (the regression the trace tier's
# lane pin also guards) breaks the scaling fit, not just the ratchet.

def _forest_build_mem_build(v: int = 1 << 20):
    import jax as _jax
    return dict(fn=_build_levels,
                args=(_jax.ShapeDtypeStruct((v, 8), jnp.uint32),))


def _forest_update_mem_build(v: int = 1 << 20, dirty: int = 64):
    import jax as _jax
    S = _jax.ShapeDtypeStruct
    levels = tuple(S((max(v >> d, 1), 8), jnp.uint32)
                   for d in range(tree_depth(v) + 1))
    rng = np.random.default_rng(7)
    idx = np.sort(rng.choice(v, size=dirty, replace=False)).astype(np.int32)
    return dict(
        fn=lambda lv, rows: _update_paths_traced(lv, rows, idx),
        args=(levels, S((dirty, 8), jnp.uint32)),
        donate_argnums=(0,))


MEM_CONTRACTS = [
    dict(
        name="utils.ssz.incremental.forest_build_1m",
        build=_forest_build_mem_build,
        # all levels live at once (2n rows) plus the leaf level's sha256
        # schedule windows, which the no-fusion model counts at full
        # width and one level at a time, in program order. The compiled
        # tolerance is wider than the default for what jax 0.9.0's
        # XLA:CPU holds beyond that (compiled/model = 1.55 at the probe
        # shape, buffer assignment read in PR 30): the second SHA block
        # of a pair hash is the constant padding block, so every level's
        # [64, n_d] window over it depends on no input, and the
        # scheduler extends all of them at program start and keeps them
        # until their level runs: 64 * 4 * (n - 1) B live at once
        # (1,048,320 B at n = 2^12) where the walk holds level 0's
        # 524,288 B alone. It is a constant factor of the same O(n).
        budget_bytes=384 << 20,
        scaling=dict(ns=[1 << 14, 1 << 17, 1 << 20],
                     build=_forest_build_mem_build,
                     metric="peak_bytes", max_order=1.0),
        compiled=dict(build=lambda: _forest_build_mem_build(1 << 12),
                      tol=1.6),
    ),
    dict(
        name="utils.ssz.incremental.forest_update_dirty",
        build=_forest_update_mem_build,
        scaling=dict(ns=[8, 64, 512],
                     build=lambda d: _forest_update_mem_build(1 << 16, d),
                     metric="temp_bytes", max_order=1.0, tol=0.2),
        compiled=dict(build=lambda: _forest_update_mem_build(1 << 12, 16)),
    ),
]
