"""Incremental Merkle forest == full recompute, bit for bit, under
adversarial dirty patterns — and in O(dirty·log V) pair-hash lanes.

The forest (utils/ssz/incremental.py) keeps every tree level resident and
re-hashes only dirty root paths; every root here is checked against the
full-recompute oracle bulk.merkleize_chunk_array (itself pinned to the
recursive object-model Merkleizer in tests/test_bulk_htr.py). Patterns:
single leaf, dense stripes, repeated updates to the same leaf, a list that
grows inside its tree's capacity (powers of two crossed), and the all-dirty
epoch-boundary shape —
on both pair-hash backends (CSTPU_MERKLE_BACKEND=xla|pallas; the Pallas
kernel lowers for TPUs only, so the fixture swaps in its interpreter form
and the scenario stays compact).

The work bound is asserted by counting hashed pairs per level, not by
wall-clock: a ≤k-leaf update on an n-leaf tree must dispatch at most
2·k·depth lanes (the pow2 index padding at worst doubles), far below the
~2n lanes of a full rebuild.
"""
import numpy as np
import pytest

from consensus_specs_tpu.ops import sha256 as S
from consensus_specs_tpu.ops.sha256 import bytes_to_words
from consensus_specs_tpu.utils.merkle import tree_depth
from consensus_specs_tpu.utils.ssz import bulk
from consensus_specs_tpu.utils.ssz.incremental import (
    IncrementalMerkleTree, tree_from_chunks)


@pytest.fixture(params=["xla", "pallas"])
def backend(request, monkeypatch):
    if request.param == "pallas":
        # the backend switch resolves the kernel at call time; on the CPU
        # the test picks the interpreter itself (the default is Mosaic)
        from functools import partial
        from consensus_specs_tpu.ops import sha256_pallas
        monkeypatch.setattr(
            sha256_pallas, "sha256_pairs_pallas",
            partial(sha256_pallas.sha256_pairs_pallas, interpret=True))
    S.set_merkle_pair_backend(request.param)
    yield request.param
    S.set_merkle_pair_backend(None)


def _rand_chunks(rng, n):
    return rng.integers(0, 256, (n, 32), dtype=np.uint8)


def _check(tree, chunks, context=""):
    assert tree.root() == bulk.merkleize_chunk_array(chunks), context


# ---------------------------------------------------------------------------
# Full battery (XLA backend — the default production kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [0, 1, 2, 3, 5, 8, 9, 31, 32, 33, 100, 257])
def test_build_matches_full_recompute(n):
    chunks = _rand_chunks(np.random.default_rng(n), n)
    _check(tree_from_chunks(chunks), chunks, n)


def test_single_leaf_updates():
    rng = np.random.default_rng(1)
    chunks = _rand_chunks(rng, 97)
    tree = tree_from_chunks(chunks)
    for leaf in (0, 1, 50, 95, 96):          # both edges incl. the odd tail
        row = _rand_chunks(rng, 1)
        chunks[leaf] = row
        tree.update([leaf], bytes_to_words(row))
        _check(tree, chunks, leaf)


def test_dense_stripes():
    rng = np.random.default_rng(2)
    chunks = _rand_chunks(rng, 300)
    tree = tree_from_chunks(chunks)
    for start, width in ((0, 64), (100, 37), (250, 50), (0, 300)):
        idx = np.arange(start, start + width)
        rows = _rand_chunks(rng, width)
        chunks[idx] = rows
        tree.update(idx, bytes_to_words(rows))
        _check(tree, chunks, (start, width))


def test_repeated_updates_to_same_leaf():
    rng = np.random.default_rng(3)
    chunks = _rand_chunks(rng, 64)
    tree = tree_from_chunks(chunks)
    for _ in range(10):
        row = _rand_chunks(rng, 1)
        chunks[17] = row
        tree.update([17], bytes_to_words(row))
        _check(tree, chunks)
    # ... and restoring the original content reproduces the original root
    original = tree_from_chunks(chunks).root()
    assert tree.root() == original


def _with_room(chunks, capacity):
    """A tree of `capacity` rows whose logical leaves are `chunks`: zero
    chunks from there on, as a list with room to grow is laid out."""
    rows = np.zeros((capacity, 32), np.uint8)
    rows[:len(chunks)] = chunks
    return IncrementalMerkleTree(bytes_to_words(rows), logical_n=len(chunks))


@pytest.mark.parametrize("capacity", [271, 300, 512, 1000])
def test_a_list_grows_inside_its_capacity_crossing_powers_of_two(capacity):
    """Leaves appended by `update` at the rows from `n` on, with the new
    logical length: no level changes shape, and the root is the first node
    of the level that spans the logical leaves, the SSZ root of the list
    as long as it now is."""
    rng = np.random.default_rng(4)
    chunks = _rand_chunks(rng, 5)
    tree = _with_room(chunks, capacity)
    shapes = [level.shape for level in tree.levels]
    _check(tree, chunks, "built")
    for k in (2, 1, 4, 9, 50, 200):          # crosses 8, 16, 64, 256
        rows = _rand_chunks(rng, k)
        n = chunks.shape[0]
        chunks = np.concatenate([chunks, rows])
        tree.update(np.arange(n, n + k), bytes_to_words(rows), logical_n=n + k)
        _check(tree, chunks, k)
        assert tree.n == n + k and tree.capacity == capacity
        assert tree.root_level().shape[0] \
            == -(-capacity // (1 << tree_depth(n + k)))
    assert [level.shape for level in tree.levels] == shapes
    assert tree.builds == 1
    # interleave: update old leaves after several growth steps
    idx = np.array([0, 6, 7, 8, 100, chunks.shape[0] - 1])
    rows = _rand_chunks(rng, idx.shape[0])
    chunks[idx] = rows
    tree.update(idx, bytes_to_words(rows))
    _check(tree, chunks)


def test_a_list_grows_from_empty_and_never_past_its_capacity():
    rng = np.random.default_rng(5)
    tree = _with_room(np.zeros((0, 32), np.uint8), 4)
    assert tree.root() == bulk.merkleize_chunk_array(np.zeros((0, 32), np.uint8))
    chunks = _rand_chunks(rng, 3)
    tree.update(np.arange(3), bytes_to_words(chunks), logical_n=3)
    _check(tree, chunks)
    with pytest.raises(AssertionError, match="capacity"):
        tree.update([4], bytes_to_words(_rand_chunks(rng, 1)), logical_n=5)
    with pytest.raises(AssertionError, match="out of range"):
        tree.update([3], bytes_to_words(_rand_chunks(rng, 1)))  # no new length
    with pytest.raises(AssertionError, match="never shrinks"):
        tree.update([0], bytes_to_words(_rand_chunks(rng, 1)), logical_n=2)
    _check(tree, chunks)


@pytest.mark.parametrize("n,capacity", [(5, 64), (100, 128), (97, 1000), (1000, 1024)])
def test_bucket_update_appends_inside_the_capacity(n, capacity):
    """The serving loop's program takes new leaves like dirty ones: rows
    from `n` on in the bucket, the new logical length beside them, every
    stored level what a from-scratch build of the longer list gives."""
    from consensus_specs_tpu.utils.ssz.incremental import bucket_indices
    rng = np.random.default_rng(capacity)
    chunks = _rand_chunks(rng, n)
    tree = _with_room(chunks, capacity)
    for k in (12, 1, 16):
        if n + k > capacity:
            break
        dirty = np.unique(rng.choice(n, 4, replace=False))
        new = _rand_chunks(rng, k)
        chunks[dirty] = _rand_chunks(rng, len(dirty))
        chunks = np.concatenate([chunks, new])
        idx = bucket_indices(np.concatenate([dirty, np.arange(n, n + k)]))
        n += k
        tree.update_bucket(idx, bytes_to_words(chunks[idx]), logical_n=n)
        _check(tree, chunks, k)
        rebuilt = _with_room(chunks, capacity)
        for got, want in zip(tree.levels, rebuilt.levels):
            assert (np.asarray(got) == np.asarray(want)).all(), (n, k)
    assert tree.builds == 1


def test_all_dirty_epoch_boundary_shape():
    rng = np.random.default_rng(6)
    chunks = _rand_chunks(rng, 130)
    tree = tree_from_chunks(chunks)
    rows = _rand_chunks(rng, 130)
    tree.update(np.arange(130), bytes_to_words(rows))
    _check(tree, rows)


def test_randomized_mixed_patterns():
    rng = np.random.default_rng(7)
    chunks = _rand_chunks(rng, 41)
    tree = tree_from_chunks(chunks)
    tree = _with_room(chunks, 128)
    for trial in range(30):
        if rng.random() < 0.25:              # grow
            k = int(rng.integers(1, 8))
            rows = _rand_chunks(rng, k)
            n = chunks.shape[0]
            chunks = np.concatenate([chunks, rows])
            tree.update(np.arange(n, n + k), bytes_to_words(rows),
                        logical_n=n + k)
        else:                                # scattered dirty set
            k = int(rng.integers(1, min(16, chunks.shape[0]) + 1))
            idx = rng.choice(chunks.shape[0], k, replace=False)
            rows = _rand_chunks(rng, k)
            chunks[idx] = rows
            tree.update(idx, bytes_to_words(rows))
        _check(tree, chunks, trial)


def test_update_rejects_bad_indices():
    rng = np.random.default_rng(8)
    chunks = _rand_chunks(rng, 16)
    tree = tree_from_chunks(chunks)
    with pytest.raises(AssertionError):
        tree.update([16], bytes_to_words(_rand_chunks(rng, 1)))  # out of range
    with pytest.raises(AssertionError):
        tree.update([3, 3], bytes_to_words(_rand_chunks(rng, 2)))  # duplicate


# ---------------------------------------------------------------------------
# Work bound: O(dirty·log V) pair-hash lanes, counted — not wall-clocked
# ---------------------------------------------------------------------------

def test_update_work_is_dirty_log_v():
    rng = np.random.default_rng(9)
    n = 4096
    tree = IncrementalMerkleTree(
        rng.integers(0, 2 ** 32, (n, 8), dtype=np.uint32))
    full_lanes = sum(tree.last_pairs_per_level)
    assert full_lanes >= n - 1                   # the build really is O(n)
    for k in (1, 64, 16):
        idx = rng.choice(n, k, replace=False)
        tree.update(idx, rng.integers(0, 2 ** 32, (k, 8), dtype=np.uint32))
        lanes = tree.last_pairs_per_level
        assert len(lanes) == tree.depth          # one batched launch per level
        # pow2 padding at worst doubles the dirty set at each level
        assert sum(lanes) <= 2 * k * tree.depth, (k, lanes)
        assert all(lane <= 2 * k for lane in lanes), (k, lanes)
    # 16 dirty leaves of 4096: an order of magnitude under the full rebuild
    # even at this small scale
    assert sum(tree.last_pairs_per_level) * 10 < full_lanes


# ---------------------------------------------------------------------------
# Both backends (the Pallas form interprets eagerly here: keep it compact)
# ---------------------------------------------------------------------------

def test_backend_scenario_bit_exact(backend):
    """One build + scattered update + same-leaf rewrite + pow2-crossing
    growth per backend, each against the full-recompute oracle (the oracle
    itself hashes through the selected backend only above its device
    threshold, so this also cross-checks pallas against hashlib)."""
    rng = np.random.default_rng(10)
    chunks = _rand_chunks(rng, 6)
    tree = _with_room(chunks, 12)
    _check(tree, chunks, backend)
    idx = np.array([0, 3, 5])
    rows = _rand_chunks(rng, 3)
    chunks[idx] = rows
    tree.update(idx, bytes_to_words(rows))
    _check(tree, chunks, backend)
    row = _rand_chunks(rng, 1)                  # repeated same-leaf rewrite
    chunks[3] = row
    tree.update([3], bytes_to_words(row))
    _check(tree, chunks, backend)
    rows = _rand_chunks(rng, 4)                 # 6 -> 10 crosses 8
    chunks = np.concatenate([chunks, rows])
    tree.update(np.arange(6, 10), bytes_to_words(rows), logical_n=10)
    _check(tree, chunks, backend)


def test_backend_selection_plumbing(monkeypatch):
    monkeypatch.setenv("CSTPU_MERKLE_BACKEND", "pallas")
    assert S.merkle_pair_backend_name() == "pallas"
    S.set_merkle_pair_backend("xla")             # explicit pin beats the env
    try:
        assert S.merkle_pair_backend_name() == "xla"
    finally:
        S.set_merkle_pair_backend(None)
    monkeypatch.setenv("CSTPU_MERKLE_BACKEND", "mosaic")
    with pytest.raises(ValueError):
        S.merkle_pair_backend_name()


# ---------------------------------------------------------------------------
# Tree-handle API (bulk.py): memo coherence with forest invalidation
# ---------------------------------------------------------------------------

def test_chunk_tree_handle_matches_oracle():
    rng = np.random.default_rng(11)
    chunks = _rand_chunks(rng, 200)
    handle = bulk.build_chunk_tree(chunks)
    assert handle.root() == bulk.merkleize_chunk_array(chunks)
    idx = [7, 100, 199]
    rows = _rand_chunks(rng, 3)
    handle.update(idx, rows)
    chunks[idx] = rows
    assert handle.root() == bulk.merkleize_chunk_array(chunks)


def test_handle_owns_its_chunks():
    """The handle copies the chunk matrix at build: scribbling on the
    caller's array must not desynchronize the forest from its memo key."""
    rng = np.random.default_rng(12)
    chunks = _rand_chunks(rng, 128)
    handle = bulk.build_chunk_tree(chunks)
    want = handle.root()
    chunks[:] = 0
    assert handle.root() == want


def test_forest_invalidation_evicts_memo_entries():
    """Forest invalidation and the byte memo move together: the entry a
    handle's root() inserted comes OUT when the handle updates, so the memo
    never carries entries for content the forest has superseded."""
    rng = np.random.default_rng(13)
    chunks = _rand_chunks(rng, 256)
    handle = bulk.build_chunk_tree(chunks)
    r0 = handle.root()
    key = ("mca", chunks.tobytes())
    assert bulk._memo.get(key) == r0             # root() memoized its content
    bytes_before = bulk._memo_bytes
    row = _rand_chunks(rng, 1)
    handle.update([11], row)
    assert key not in bulk._memo                 # evicted, not lingering
    assert bulk._memo_bytes < bytes_before       # accounting followed
    # the old content still roots correctly through the normal path ...
    assert bulk.merkleize_chunk_array(chunks) == r0
    # ... and the new content is served fresh, not from a stale entry
    chunks[11] = row
    assert handle.root() == bulk.merkleize_chunk_array(chunks) != r0


# ---------------------------------------------------------------------------
# The serving loop's bucket update: one program a tree, a traced dirty set
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 5, 64, 97, 1000, 4097])
def test_bucket_update_equals_a_rebuild_level_for_level(n):
    """`update_bucket` (the leaf scatter and every path level in ONE
    program, the dirty set padded to a bucket of 32) leaves every stored
    level as a from-scratch build of the new leaves leaves it: odd tails,
    dirty siblings, repeated indices, a bucket larger than the tree."""
    from consensus_specs_tpu.utils.ssz.incremental import bucket_indices
    rng = np.random.default_rng(n)
    leaves = rng.integers(0, 2 ** 32, (n, 8), dtype=np.uint32)
    tree = IncrementalMerkleTree(leaves.copy())
    for k in (1, 3, 16, 21, 40):
        idx = np.sort(rng.choice(n, min(k, n), replace=False))
        if n > 2:
            idx[-1] = n - 1                 # the tail, odd or even
        idx = np.unique(idx)
        leaves[idx] = rng.integers(0, 2 ** 32, (len(idx), 8), dtype=np.uint32)
        bucket = bucket_indices(idx)
        assert len(bucket) == max(32, 1 << (len(idx) - 1).bit_length())
        assert (bucket[:len(idx)] == idx).all() and (bucket[len(idx):] == idx[-1]).all()
        tree.update_bucket(bucket, leaves[bucket])
        assert tree.last_pairs_per_level == [len(bucket)] * tree.depth
        rebuilt = IncrementalMerkleTree(leaves.copy())
        assert tree.root() == rebuilt.root(), (n, k)
        for got, want in zip(tree.levels, rebuilt.levels):
            assert (np.asarray(got) == np.asarray(want)).all(), (n, k)
    assert tree.builds == 1


def test_bucket_updates_of_one_tree_share_one_program():
    """What the dirty leaves are changes no shape: every block's dirty set
    (16 exits, 17 with a slashing, 21 with a double vote) meets the
    program the first one compiled."""
    from consensus_specs_tpu.utils.ssz.incremental import (_update_bucket_pd,
                                                          bucket_indices)
    rng = np.random.default_rng(36)
    leaves = rng.integers(0, 2 ** 32, (3000, 8), dtype=np.uint32)
    tree = IncrementalMerkleTree(leaves.copy())
    tree.update_bucket(bucket_indices(np.array([7])), leaves[[7] * 32])
    programs = _update_bucket_pd.resolve()._cache_size()
    for k in (16, 17, 21, 1, 32):
        idx = np.sort(rng.choice(3000, k, replace=False))
        leaves[idx] = rng.integers(0, 2 ** 32, (k, 8), dtype=np.uint32)
        bucket = bucket_indices(idx)
        tree.update_bucket(bucket, leaves[bucket])
        assert tree.root() == IncrementalMerkleTree(leaves.copy()).root()
    assert _update_bucket_pd.resolve()._cache_size() == programs
