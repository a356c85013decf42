"""Persistent host-side Merkle trees for the state's list and vector fields.

A slot changes two 32-byte leaves of the history vectors and appends a few
attestations; the one-shot roots of bulk.py walk every element of every
field to find that out. Here a field's tree stays on the host from one
root to the next, and a root re-hashes only the root paths of the leaves
written since the last one: the same SHA-256 tree, minus the pairs whose
inputs did not move.

  * `HostChunkTree`: the levels of one tree over 32-byte chunks. `build`
    by bulk.py's own level pass (zero-pair fill and the all-identical
    shortcut kept), `update` / `append` in O(dirty * log N) hashlib calls.
    Not bulk.ChunkTreeHandle: that one keeps its levels on the device and
    pays a launch a level a call.
  * `TrackedList`: a `list` that records which indices were written.
  * `TrackedSeriesTree` / `AppendOnlyListTree`: the tree of one SSZ list or
    vector value, bound to the list OBJECT it was built on. The first
    learns what changed from the tracked list's record, the second from
    the identity of the elements it has already taken.

What a tree hashes anew is an element's own root first (`_leaf_rows`):
a container whose type has a root plan (root_plan.py: every field a uint,
a bool, a BytesN, `bytes` or such a container; PendingAttestation with its
AttestationData and Crosslink) through the plan's batch form, 64 or more
`container_list_is_fast` elements at once (a vector of crosslinks assigned
anew) through bulk's numpy columns, a Bytes32 as it is.

Differential gate: tests/test_host_tree.py (against merkle.merkleize_chunks
and impl.hash_tree_root).
"""
from __future__ import annotations

import hashlib
import itertools
import operator
from typing import Any, Iterable, Optional, Sequence

import numpy as np

from ..hash import ZERO_BYTES32, zerohashes
from ...telemetry import counter as _tele_counter
from . import bulk, impl
from .root_plan import plan_for
from .typing import is_bytesn_type, is_list_kind

# Leaves re-hashed through a tree (update or append) and trees built from
# content: a slot root that finds its trees as it left them reads 0 builds.
LEAVES_UPDATED = _tele_counter("merkle.host.leaves_updated")
TREE_REBUILDS = _tele_counter("merkle.host.tree_rebuilds")


class HostChunkTree:
    """Every level of the Merkle tree over n 32-byte chunks, as bytearrays:
    level 0 the chunks, level d+1 the ceil(n_d / 2) parents (an odd level's
    last node pairs with the zero subtree of its depth), the top one node.
    Same root as merkle.merkleize_chunks at every n, appends across a power
    of two included (the tree grows a level)."""

    __slots__ = ("levels",)

    def __init__(self, chunks: np.ndarray):
        """Build from an [n, 32] uint8 matrix by bulk.next_level."""
        TREE_REBUILDS.inc(1)
        level = np.ascontiguousarray(chunks, dtype=np.uint8).reshape(-1, 32)
        self.levels = [bytearray(level.tobytes())]
        depth = zero_filled = 0
        while level.shape[0] > 1:
            level, filled = bulk.next_level(level, depth)
            zero_filled += filled
            depth += 1
            self.levels.append(bytearray(level.tobytes()))
        bulk.HOST_PAIRS_ZERO_FILLED.inc(zero_filled)

    @property
    def n(self) -> int:
        return len(self.levels[0]) // 32

    def root(self) -> bytes:
        return bytes(self.levels[-1]) if self.levels[0] else ZERO_BYTES32

    def update(self, idx: Sequence[int], rows: bytes) -> None:
        """Overwrite the chunks at `idx` (distinct, in range) with the
        32-byte rows of `rows`, and re-hash their root paths."""
        n, leaves = self.n, self.levels[0]
        if len(rows) != 32 * len(idx):
            raise ValueError(f"{len(idx)} leaves need {32 * len(idx)} bytes, "
                             f"got {len(rows)}")
        for k, i in enumerate(idx):
            if not 0 <= i < n:
                raise IndexError(f"leaf {i} outside a tree of {n}")
            leaves[32 * i:32 * i + 32] = rows[32 * k:32 * k + 32]
        LEAVES_UPDATED.inc(len(idx))
        self._rehash({i >> 1 for i in idx})

    def append(self, rows: bytes) -> None:
        """Grow by the 32-byte rows of `rows`."""
        if len(rows) % 32:
            raise ValueError(f"{len(rows)} bytes are not whole chunks")
        if not rows:
            return
        n0 = self.n
        self.levels[0] += rows
        n_d, d = self.n, 0
        while n_d > 1:      # room for the new nodes, new levels included
            n_d = (n_d + 1) >> 1
            d += 1
            if d == len(self.levels):
                self.levels.append(bytearray())
            self.levels[d] += bytes(32 * n_d - len(self.levels[d]))
        LEAVES_UPDATED.inc(self.n - n0)
        self._rehash(range(n0 >> 1, ((self.n - 1) >> 1) + 1))

    def _rehash(self, parents: Iterable[int]) -> None:
        """Recompute the nodes `parents` of level 1 and all their ancestors."""
        sha, hashed = hashlib.sha256, 0
        for d in range(len(self.levels) - 1):
            level, above = self.levels[d], self.levels[d + 1]
            parents = sorted(parents)
            for j in parents:
                pair = level[64 * j:64 * j + 64]
                if len(pair) == 32:
                    pair += zerohashes[d]
                above[32 * j:32 * j + 32] = sha(pair).digest()
            hashed += len(parents)
            parents = {j >> 1 for j in parents}
        bulk.HOST_PAIRS_HASHED.inc(hashed)


class TrackedList(list):
    """A `list` that records which indices were written since the last
    `take_written()`. `lst[i] = v` with an integer records i; every other
    mutator marks the whole list written, and so does a copy (copy and
    pickle rebuild a list subclass through `append` / `extend`, and an
    instance that never ran `__init__` reads the class's `_whole`).

    It knows that an element was REPLACED, not that one was mutated in
    place: what is stored in a tracked vector of containers is replaced,
    never edited (the spec's own code does so for Crosslinks: epoch.py
    process_crosslinks, epoch_soa.process_crosslinks_vectorized)."""

    _whole = True
    _taker = None

    def __init__(self, *args):
        super().__init__(*args)
        self._written: set = set()
        self._whole = True

    def take_written(self, taker=None) -> Optional[set]:
        """Indices written since the last call, or None for "any of them";
        the record starts again empty. The record is one reader's: a
        `taker` (any token) other than the last one is told "any of
        them", so two trees over one list never split a record."""
        whole = self._whole or self._taker != taker
        written = None if whole else self._written
        self._written, self._whole, self._taker = set(), False, taker
        return written

    def __setitem__(self, i, v):
        super().__setitem__(i, v)
        if self._whole:
            return
        if isinstance(i, slice):
            self._whole = True
        else:
            i = operator.index(i)
            self._written.add(i if i >= 0 else i + len(self))


def _marks_whole(name: str):
    method = getattr(list, name)

    def mutator(self, *args, **kwargs):
        self._whole = True
        return method(self, *args, **kwargs)
    mutator.__name__ = name
    return mutator


for _name in ("__delitem__", "__iadd__", "__imul__", "append", "extend",
              "insert", "pop", "remove", "sort", "reverse", "clear"):
    setattr(TrackedList, _name, _marks_whole(_name))
del _name


def _leaf_rows(values: Sequence[Any], elem_type: Any) -> bytes:
    """The tree leaves of composite or BytesN elements, 32 bytes a value.
    Bytes32 values are their own leaves. `container_list_is_fast` elements
    (Crosslink, Eth1Data) from `_MEMO_MIN_CHUNKS` values up take bulk's
    numpy columns, where the width pays for them (a vector of 1,024
    crosslinks assigned anew). Any other container whose type has a root
    plan (root_plan.py: PendingAttestation, and the few crosslinks an
    epoch writes) takes the plan's batch form, one call for all of them;
    an element type without a plan takes bulk's dispatcher value by value."""
    if is_bytesn_type(elem_type) and elem_type.length == 32:
        rows = b"".join(values)
        if len(rows) != 32 * len(values):
            raise ValueError("a Bytes32 series holds a value of another length")
        return rows
    if (len(values) >= bulk._MEMO_MIN_CHUNKS
            and bulk.container_list_is_fast(elem_type)):
        return bulk.container_list_roots(values, elem_type).tobytes()
    plan = plan_for(elem_type)
    if plan is not None:
        return bulk.plan_roots(plan, values)
    return b"".join(bulk.hash_tree_root_bulk(v, elem_type) for v in values)


def _chunk_matrix(rows: bytes) -> np.ndarray:
    return np.frombuffer(rows, np.uint8).reshape(-1, 32)


class TrackedSeriesTree:
    """The tree of one list or vector value held in a `TrackedList`, bound
    to that object: `root()` re-hashes the root paths of the indices the
    list recorded, or builds anew when the list says "any of them" (a
    length-changing or bulk mutator, a copy). Basic elements pack several
    to a chunk, everything else is one leaf an element."""

    _tokens = itertools.count(1)

    def __init__(self, lst: TrackedList, typ: Any):
        self.bound = lst
        self._token = next(self._tokens)    # names this reader to the list
        self._list_kind = is_list_kind(typ)
        self._elem = elem = typ.elem_type
        # basic elements to a chunk; 0: composite, a leaf an element
        self._per_chunk = (32 // impl.fixed_byte_size(elem)
                           if impl.is_basic_type(elem) else 0)
        self._build()

    def _build(self) -> None:
        lst = self.bound
        lst.take_written(self._token)
        self.tree = HostChunkTree(
            bulk.pack_basic_list_chunks(lst, self._elem) if self._per_chunk
            else _chunk_matrix(_leaf_rows(lst, self._elem)))

    def root(self) -> bytes:
        lst, per = self.bound, self._per_chunk
        written = lst.take_written(self._token)
        try:
            if written is None:
                self._build()
            elif written and per:
                idx = sorted({i // per for i in written})
                self.tree.update(idx, b"".join(
                    bulk.pack_basic_list_chunks(
                        lst[c * per:(c + 1) * per], self._elem).tobytes()
                    for c in idx))
            elif written:
                idx = sorted(written)
                self.tree.update(
                    idx, _leaf_rows([lst[i] for i in idx], self._elem))
        except BaseException:
            lst._whole = True   # the record is spent and the tree did not follow
            raise
        root = self.tree.root()
        return impl.mix_in_length(root, len(lst)) if self._list_kind else root


class AppendOnlyListTree:
    """The tree of a list of composite elements that only ever grows at
    its end between rebuilds (the pending-attestation lists: process_
    attestation appends, final updates rotate the list OBJECTS), bound to
    the list object. A root checks that the elements already taken are
    still the first ones, object for object, hashes only the new tail and
    appends it; a shorter list or a changed prefix builds anew. Like the
    identity memo this replaces, it knows an element was replaced, not
    mutated in place: a stored PendingAttestation is never edited."""

    def __init__(self, lst: list, typ: Any):
        self.bound = lst
        self._elem = typ.elem_type
        self._build()

    def _build(self) -> None:
        self._taken = list(self.bound)
        self.tree = HostChunkTree(
            _chunk_matrix(_leaf_rows(self._taken, self._elem)))

    def root(self) -> bytes:
        lst, taken = self.bound, self._taken
        if len(lst) < len(taken) or not all(map(operator.is_, taken, lst)):
            self._build()
        elif len(lst) > len(taken):
            new = lst[len(taken):]
            self.tree.append(_leaf_rows(new, self._elem))
            taken.extend(new)
        return impl.mix_in_length(self.tree.root(), len(lst))
