"""Persistent host trees == the one-shot Merkleizers, bit for bit.

utils/ssz/host_tree.py keeps a field's tree on the host from one state
root to the next and re-hashes only what was written. Every root here is
checked against merkle.merkleize_chunks (the chunk tree) or
impl.hash_tree_root of a PLAIN copy (the bound series trees), under random
update / append / rebuild sequences, every `list` mutator on a tracked
vector, and the ways a resident state's field can stop being the object a
tree was built on: a wholesale assignment, a deep copy, writes made while
the core is suspended. The work is asserted by the counters the slot root
notes (pairs hashed, leaves updated, trees rebuilt), not by wall clock.
"""
from copy import deepcopy

import numpy as np
import pytest

from consensus_specs_tpu import telemetry
from consensus_specs_tpu.utils.merkle import merkleize_chunks, tree_depth
from consensus_specs_tpu.utils.ssz import bulk, host_tree, impl
from consensus_specs_tpu.utils.ssz.host_tree import (
    AppendOnlyListTree, HostChunkTree, TrackedList, TrackedSeriesTree)
from consensus_specs_tpu.utils.ssz.typing import (
    Bytes32, Container, List, Vector, uint8, uint64)


class Link(Container):
    shard: uint64
    start_epoch: uint64
    end_epoch: uint64
    parent_root: Bytes32
    data_root: Bytes32


class Pending(Container):
    bits: bytes
    link: Link
    delay: uint64


@pytest.fixture(autouse=True)
def counting():
    """The host counters count only while telemetry is on."""
    telemetry.set_enabled(True)
    yield
    telemetry.set_enabled(None)


class Work:
    """Deltas of the five host Merkle counters over a `with` block."""
    COUNTERS = {"pairs_hashed": bulk.HOST_PAIRS_HASHED,
                "pairs_zero_filled": bulk.HOST_PAIRS_ZERO_FILLED,
                "leaves_updated": host_tree.LEAVES_UPDATED,
                "trees_rebuilt": host_tree.TREE_REBUILDS,
                "plan_elements": bulk.PLAN_ELEMENTS}

    def __enter__(self):
        self._before = {k: c.value for k, c in self.COUNTERS.items()}
        return self

    def __exit__(self, *exc):
        for k, c in self.COUNTERS.items():
            setattr(self, k, c.value - self._before[k])


def _rand_chunks(rng, n):
    return rng.integers(0, 256, (n, 32), dtype=np.uint8)


def _oracle(chunks):
    return merkleize_chunks([bytes(row) for row in chunks])


# -- the chunk tree ------------------------------------------------------------

@pytest.mark.parametrize("n", [0, 1, 2, 3, 5, 8, 9, 31, 32, 33, 100, 257])
def test_build_matches_merkleize_chunks(n):
    chunks = _rand_chunks(np.random.default_rng(n), n)
    tree = HostChunkTree(chunks)
    assert tree.n == n and tree.root() == _oracle(chunks)
    assert len(tree.levels) == tree_depth(n) + 1


@pytest.mark.parametrize("fill", ["zero_suffix", "identical", "all_zero"])
def test_build_keeps_the_level_pass_shortcuts(fill):
    """A mostly-zero vector is filled from the zero-hash table and a vector
    of one repeated root hashes one pair a level while the level holds 64
    pairs or more, as the one-shot path."""
    n = 1024
    chunks = np.zeros((n, 32), np.uint8)
    if fill == "zero_suffix":
        chunks[:5] = _rand_chunks(np.random.default_rng(7), 5)
    elif fill == "identical":
        chunks[:] = _rand_chunks(np.random.default_rng(8), 1)
    with Work() as work:
        tree = HostChunkTree(chunks)
    assert tree.root() == _oracle(chunks)
    assert work.pairs_hashed + work.pairs_zero_filled <= n - 1
    assert work.pairs_hashed <= {"zero_suffix": 3 + 2 + 8,
                                 "identical": 4 + 63, "all_zero": 0}[fill]
    assert work.trees_rebuilt == 1


@pytest.mark.parametrize("seed", range(8))
def test_random_update_append_rebuild_sequences(seed):
    rng = np.random.default_rng(seed)
    chunks = _rand_chunks(rng, int(rng.integers(0, 40)))
    tree = HostChunkTree(chunks)
    for step in range(60):
        op = rng.integers(0, 10)
        n = chunks.shape[0]
        if op < 5 and n:
            k = int(rng.integers(1, min(n, 9) + 1))
            idx = rng.choice(n, size=k, replace=False)
            rows = _rand_chunks(rng, k)
            if rng.integers(0, 4) == 0:
                rows[0] = 0            # a leaf going back to the zero chunk
            chunks[idx] = rows
            tree.update([int(i) for i in idx], rows.tobytes())
        elif op < 9:
            rows = _rand_chunks(rng, int(rng.integers(0, 20)))
            chunks = np.concatenate([chunks, rows])
            tree.append(rows.tobytes())
        else:
            tree = HostChunkTree(chunks)
        assert tree.n == chunks.shape[0]
        assert tree.root() == _oracle(chunks), (seed, step)


@pytest.mark.parametrize("n0,k", [(0, 1), (1, 1), (2, 1), (4, 1), (7, 2),
                                  (8, 9), (31, 2), (32, 1), (33, 64)])
def test_append_crosses_powers_of_two(n0, k):
    rng = np.random.default_rng(n0 * 100 + k)
    chunks = _rand_chunks(rng, n0 + k)
    tree = HostChunkTree(chunks[:n0])
    tree.append(chunks[n0:].tobytes())
    assert tree.root() == _oracle(chunks)
    assert len(tree.levels) == tree_depth(n0 + k) + 1


def test_one_leaf_of_8192_costs_its_root_path():
    chunks = _rand_chunks(np.random.default_rng(1), 8192)
    tree = HostChunkTree(chunks)
    chunks[4097] = 9
    with Work() as work:
        tree.update([4097], chunks[4097].tobytes())
    assert tree.root() == _oracle(chunks)
    assert (work.pairs_hashed, work.leaves_updated, work.trees_rebuilt) \
        == (13, 1, 0)
    with Work() as work:                # adjacent leaves share their path
        tree.append(_rand_chunks(np.random.default_rng(2), 16).tobytes())
    assert work.leaves_updated == 16 and work.pairs_hashed <= 8 + 4 + 2 + 13


def test_update_rejects_what_it_cannot_place():
    tree = HostChunkTree(np.zeros((4, 32), np.uint8))
    with pytest.raises(IndexError):
        tree.update([4], bytes(32))
    with pytest.raises(ValueError):
        tree.update([1], bytes(31))
    with pytest.raises(ValueError):
        tree.append(bytes(33))


# -- the tracked list ----------------------------------------------------------

def _roots(rng, n):
    return [Bytes32(bytes(row)) for row in _rand_chunks(rng, n)]


MUTATORS = {
    "setitem": lambda v, x: v.__setitem__(3, x),
    "setitem_negative": lambda v, x: v.__setitem__(-2, x),
    "setitem_same_index_twice": lambda v, x: (v.__setitem__(5, x),
                                              v.__setitem__(5, v[0])),
    "slice_assignment": lambda v, x: v.__setitem__(slice(1, 3), [x, x]),
    "append": lambda v, x: v.append(x),
    "extend": lambda v, x: v.extend([x, x]),
    "insert": lambda v, x: v.insert(2, x),
    "pop": lambda v, x: v.pop(),
    "remove": lambda v, x: v.remove(v[4]),
    "delitem": lambda v, x: v.__delitem__(1),
    "iadd": lambda v, x: v.__iadd__([x]),
    "imul": lambda v, x: v.__imul__(2),
    "sort": lambda v, x: v.sort(),
    "reverse": lambda v, x: v.reverse(),
    "clear": lambda v, x: v.clear(),
}


@pytest.mark.parametrize("name", sorted(MUTATORS))
def test_every_list_mutator_is_seen(name):
    """After any mutator the tree's root is that of a plain copy; an
    integer `__setitem__` re-hashes one path, anything else builds anew."""
    rng = np.random.default_rng(3)
    typ = List[Bytes32]
    vec = TrackedList(_roots(rng, 12))
    tree = TrackedSeriesTree(vec, typ)
    assert tree.root() == impl.hash_tree_root(list(vec), typ)
    with Work() as work:
        MUTATORS[name](vec, _roots(rng, 1)[0])
        got = tree.root()
    assert got == impl.hash_tree_root(list(vec), typ)
    if name.startswith("setitem"):
        assert (work.trees_rebuilt, work.leaves_updated) == (0, 1)
    else:
        assert work.trees_rebuilt == 1
    with Work() as work:                # and the record starts again empty
        assert tree.root() == got
    assert (work.pairs_hashed, work.trees_rebuilt) == (0, 0)


def test_a_tracked_list_is_a_list_to_everyone_else():
    rng = np.random.default_rng(4)
    plain = _roots(rng, 8)
    vec = TrackedList(plain)
    assert vec == plain and isinstance(vec, list) and len(vec) == 8
    typ = Vector[Bytes32, 8]
    assert impl.serialize(typ(vec), typ) == impl.serialize(typ(plain), typ)
    assert impl.hash_tree_root(vec, List[Bytes32]) \
        == impl.hash_tree_root(plain, List[Bytes32])
    vec.take_written()
    for copied in (deepcopy(vec), type(vec)(vec)):
        assert copied == plain and copied.take_written() is None
    assert vec.take_written() == set()


@pytest.mark.parametrize("elem,make", [
    (Bytes32, lambda rng, i: Bytes32(bytes(_rand_chunks(rng, 1)[0]))),
    (uint64, lambda rng, i: int(rng.integers(0, 2**63)) * 2 + (i & 1)),
    (uint8, lambda rng, i: int(rng.integers(0, 256))),
    (bool, lambda rng, i: bool(rng.integers(0, 2))),
    (Link, lambda rng, i: Link(shard=i, end_epoch=int(rng.integers(0, 99)),
                               data_root=bytes(_rand_chunks(rng, 1)[0]))),
], ids=["Bytes32", "uint64", "uint8", "bool", "container"])
@pytest.mark.parametrize("n", [1, 7, 70, 256])
def test_tracked_series_of_each_element_kind(elem, make, n):
    """Packed basics (several to a chunk), 32-byte values and containers,
    as a vector and as a list (length mixed in), under scattered writes."""
    rng = np.random.default_rng(n)
    for typ in (Vector[elem, n], List[elem]):
        vec = TrackedList(make(rng, i) for i in range(n))
        tree = TrackedSeriesTree(vec, typ)
        for _ in range(4):
            for i in rng.choice(n, size=min(n, 5), replace=False):
                vec[int(i)] = make(rng, int(i))
            with Work() as work:
                assert tree.root() == impl.hash_tree_root(list(vec), typ)
            assert work.trees_rebuilt == 0 and 1 <= work.leaves_updated <= 5


def test_two_trees_over_one_list_do_not_split_its_record():
    """The written-index record belongs to the tree that took it last;
    another reader is told "any of them" and builds anew."""
    typ = List[Bytes32]
    vec = TrackedList(_roots(np.random.default_rng(11), 9))
    one, two = TrackedSeriesTree(vec, typ), TrackedSeriesTree(vec, typ)
    for i, tree in enumerate((one, two, two, one, two)):
        vec[i] = Bytes32(bytes([i + 1]) * 32)
        assert tree.root() == impl.hash_tree_root(list(vec), typ)
    with Work() as work:
        two.root()
    assert work.trees_rebuilt == 0


def test_a_failed_root_does_not_lose_the_record():
    vec = TrackedList(_roots(np.random.default_rng(5), 8))
    tree = TrackedSeriesTree(vec, List[Bytes32])
    tree.root()
    vec[2] = b"short"
    with pytest.raises(ValueError):
        tree.root()
    vec[2] = Bytes32(b"\x07" * 32)
    assert tree.root() == impl.hash_tree_root(list(vec), List[Bytes32])


# -- the append-only list ------------------------------------------------------

def _pending(rng, i):
    return Pending(bits=bytes(rng.integers(0, 256, 122, dtype=np.uint8)),
                   link=Link(shard=i, parent_root=bytes(_rand_chunks(rng, 1)[0])),
                   delay=i)


def test_append_only_list_hashes_only_its_new_tail():
    rng = np.random.default_rng(6)
    typ = List[Pending]
    atts = []
    tree = AppendOnlyListTree(atts, typ)
    assert tree.root() == impl.mix_in_length(bytes(32), 0)
    per_element = None
    for slot in range(6):
        atts.extend(_pending(rng, 16 * slot + i) for i in range(16))
        with Work() as work:
            assert tree.root() == impl.hash_tree_root(list(atts), typ)
        assert (work.leaves_updated, work.trees_rebuilt) == (16, 0)
        # 16 element roots + 16 adjacent leaves' paths; flat as the list grows
        per_element = per_element or work.pairs_hashed
        assert work.pairs_hashed <= per_element + 8
    with Work() as work:                # nothing new: nothing hashed
        tree.root()
    assert (work.pairs_hashed, work.leaves_updated) == (0, 0)


@pytest.mark.parametrize("k,bitfield_bytes,pairs", [
    (16, 122, [303, 304, 305, 305]),        # a replay slot's committees
    (128, 16, [2047, 2048, 2049, 2049])])   # a full block's aggregates
def test_new_attestations_go_through_the_root_plan(k, bitfield_bytes, pairs):
    """k new PendingAttestations a root: k elements through the type's
    root plan, and exactly the pairs the recursive path hashed for the
    same appends (`pairs`: read off the parent of PR 35, whose _leaf_rows
    sent each element through bulk.hash_tree_root_bulk's field walk:
    k x (15 + the bitfield's 3 or 0) element pairs + the new leaves' paths)."""
    from consensus_specs_tpu.models import phase0
    spec = phase0.get_spec("mainnet")
    rng = np.random.default_rng(k)

    def root32():
        return bytes(_rand_chunks(rng, 1)[0])

    def pending(i):
        return spec.PendingAttestation(
            aggregation_bitfield=bytes(
                rng.integers(0, 256, bitfield_bytes, dtype=np.uint8)),
            data=spec.AttestationData(
                beacon_block_root=root32(), source_epoch=i,
                source_root=root32(), target_epoch=i + 1, target_root=root32(),
                crosslink=spec.Crosslink(
                    shard=i % 1024, start_epoch=i, end_epoch=i + 1,
                    parent_root=root32(), data_root=root32())),
            inclusion_delay=4, proposer_index=7 * i)

    typ = List[spec.PendingAttestation]
    atts = []
    tree = AppendOnlyListTree(atts, typ)
    for slot, want in enumerate(pairs):
        atts.extend(pending(k * slot + i) for i in range(k))
        with Work() as work:
            assert tree.root() == impl.hash_tree_root(list(atts), typ)
        assert (work.plan_elements, work.leaves_updated, work.trees_rebuilt,
                work.pairs_hashed) == (k, k, 0, want)


@pytest.mark.parametrize("change", ["replaced", "shorter", "reordered"])
def test_append_only_list_rebuilds_on_a_changed_prefix(change):
    rng = np.random.default_rng(9)
    typ = List[Pending]
    atts = [_pending(rng, i) for i in range(10)]
    tree = AppendOnlyListTree(atts, typ)
    tree.root()
    if change == "replaced":
        atts[4] = _pending(rng, 99)
    elif change == "shorter":
        del atts[7:]
    else:
        atts[0], atts[1] = atts[1], atts[0]
    atts.append(_pending(rng, 100))
    with Work() as work:
        assert tree.root() == impl.hash_tree_root(list(atts), typ)
    assert work.trees_rebuilt == 1


def test_small_containers_cost_their_hashes():
    """bulk's dispatcher takes a container of a few chunks by plain
    hashlib: same root as the recursive oracle, and every pair counted."""
    att = _pending(np.random.default_rng(10), 3)
    with Work() as work:
        assert bulk.hash_tree_root_bulk(att, Pending) \
            == impl.hash_tree_root(att, Pending)
    # bits: 4 chunks (3 pairs; its length mix is not a pair of the tree),
    # link: 5 fields (7), the container's 3 fields (2)
    assert work.pairs_hashed == 3 + 7 + 2


# -- fields of a resident state that stop being the object a tree was built on -

@pytest.fixture
def core():
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    from consensus_specs_tpu.testing import factories
    spec = phase0.get_spec("minimal")
    bls.bls_active = False
    spec.clear_caches()
    state = factories.seed_genesis_state(spec, 4 * spec.SLOTS_PER_EPOCH)
    factories.advance_slots(spec, state, 3)
    core = ResidentCore(spec, state, mesh=None)
    yield core
    core._uninstall()
    spec.clear_caches()


def _root_and_work(core):
    with Work() as work:
        root = core._state_root(core.state)
    # the object registry is as entered (no boundary ran), so the recursive
    # oracle on a plain deep copy is the whole state's root
    with core.suspended():
        assert root == impl.hash_tree_root(deepcopy(core.state))
    return work


def test_trees_carry_over_from_root_to_root(core):
    from consensus_specs_tpu.models.phase0.resident import _TREE_KINDS
    assert _root_and_work(core).trees_rebuilt == len(_TREE_KINDS) == 10
    work = _root_and_work(core)
    assert (work.trees_rebuilt, work.leaves_updated) == (0, 0)
    state = core.state
    assert type(state.latest_block_roots.items) is TrackedList
    state.latest_block_roots[5] = b"\x11" * 32
    state.latest_randao_mixes[1] = b"\x22" * 32         # block.py's write
    state.latest_slashed_balances[2] += 7               # helpers.py's write
    state.current_crosslinks[3] = core.spec.Crosslink(shard=3, end_epoch=9)
    work = _root_and_work(core)
    assert (work.trees_rebuilt, work.leaves_updated) == (0, 4)


@pytest.mark.parametrize("field", ["latest_slashed_balances",
                                   "previous_crosslinks",
                                   "latest_randao_mixes",
                                   "current_epoch_attestations"])
def test_a_wholesale_assignment_rebuilds_that_tree(core, field):
    _root_and_work(core)
    state = core.state
    value = list(getattr(state, field))
    if field == "latest_slashed_balances":
        value[1] = 12345
    elif field == "current_epoch_attestations":
        value = [core.spec.PendingAttestation(inclusion_delay=4)]
    else:
        value[1] = deepcopy(value[2])
    setattr(state, field, value)        # a plain list where a vector stood
    work = _root_and_work(core)
    assert work.trees_rebuilt == 1
    assert _root_and_work(core).trees_rebuilt == 0      # wrapped, bound again


def test_a_deep_copy_of_a_field_or_of_the_state_rebuilds(core):
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    _root_and_work(core)
    state = core.state
    state.latest_state_roots = deepcopy(state.latest_state_roots)
    state.latest_state_roots[0] = b"\x33" * 32
    assert _root_and_work(core).trees_rebuilt == 1
    # the whole state copied: a tracked state copies, hashes and serialises
    # as a plain one, and a core entered on the copy builds every tree once
    copied = deepcopy(state)
    with core.suspended():
        assert impl.hash_tree_root(copied) == impl.hash_tree_root(state)
        assert impl.serialize(copied) == impl.serialize(state)
    copied.latest_block_roots[9] = b"\x44" * 32
    core._uninstall()
    other = ResidentCore(core.spec, copied, mesh=None)
    try:
        assert _root_and_work(other).trees_rebuilt == 10
        assert _root_and_work(other).trees_rebuilt == 0
    finally:
        other._uninstall()
        core._install()


def test_a_suspended_round_trip_is_seen(core):
    """While the core is suspended the spec's own code may write the
    resident state; tracked writes are still recorded, and a field that is
    assigned anew in there is built anew."""
    _root_and_work(core)
    state = core.state
    with core.suspended():
        state.latest_active_index_roots[2] = b"\x55" * 32
        state.previous_crosslinks = [c for c in state.current_crosslinks]
    work = _root_and_work(core)
    assert (work.trees_rebuilt, work.leaves_updated) == (1, 1)
    assert _root_and_work(core).trees_rebuilt == 0
