"""Bulk (device-batched) hash_tree_root for the big state vectors.

The recursive object-model Merkleizer (impl.hash_tree_root) walks every
element through Python — at 1M validators that is minutes of host work for
a root the protocol needs every slot (/root/reference
specs/core/0_beacon-chain.md:1232-1245 hashes the full state per slot;
Merkleization contract: specs/simple-serialize.md:139-158 and
test_libs/pyspec/eth2spec/utils/ssz/ssz_impl.py:144-155 +
merkle_minimal.py:47-54).

This module computes the same roots from *columns*:

  - a List[Container] whose fields are all fixed-size basics/BytesN becomes
    a [V, P, 32] chunk tensor built with numpy column ops (no per-element
    recursion), reduced level-by-level on the device — every level of every
    element's subtree is ONE batched sha256_pairs launch over the whole
    registry;
  - basic lists/vectors (balances, slashed-balance tables) pack straight
    into [C, 32] chunk matrices via dtype views;
  - Bytes32 vectors (block/state/randao roots) are already chunk matrices.

`hash_tree_root_bulk` mirrors impl.hash_tree_root's dispatch, routing any
shape it cannot vectorize back through the recursive oracle, so it is safe
to call on arbitrary objects and bit-identical by construction (asserted in
tests/test_bulk_htr.py). `state_root_bulk` is the BeaconState entry point.
A single small container (a PendingAttestation, a header) is no column at
all: it is rooted by its type's root plan (root_plan.py, `plan_roots`).
"""
from __future__ import annotations

import hashlib
from typing import Any, Dict, Optional, Sequence

import numpy as np

from ..hash import ZERO_BYTES32, zerohashes
from ...telemetry import counter as _tele_counter
from . import impl
from .root_plan import Plan, plan_for
from .typing import (
    is_bool_type, is_bytesn_type, is_container_type, is_list_kind,
    is_list_type, is_uint_type, is_vector_type, read_elem_type,
    uint_byte_size)

# below this many 64-byte pair inputs, OpenSSL beats device dispatch —
# set high because this host-orchestrated path pays a dispatch and a
# host round trip PER LEVEL; the chatty-free
# alternative for production roots is the one-program device path below
_DEVICE_MIN_PAIRS = 1 << 15

# The host Merkleizer's work: SHA-256 pair hashes that hashlib really ran
# (a level filled with one pair runs one) and pairs whose parent came from
# the zero-hash table. Bumped once a call, never once a pair.
HOST_PAIRS_HASHED = _tele_counter("merkle.host.pairs_hashed")
HOST_PAIRS_ZERO_FILLED = _tele_counter("merkle.host.pairs_zero_filled")
# Container values rooted through their type's root plan (root_plan.py).
PLAN_ELEMENTS = _tele_counter("merkle.host.plan_elements")


# ---------------------------------------------------------------------------
# Array-level hashing primitives
# ---------------------------------------------------------------------------

def hash_pairs_array(pairs: np.ndarray) -> np.ndarray:
    """[N, 64] uint8 -> [N, 32] uint8 SHA-256, device-batched when large.

    Device batches are zero-padded up to the next power of two so the jit
    cache sees log-many shapes total (a Merkle reduction otherwise presents
    a fresh shape per level per tree size and pays a compile each)."""
    n = pairs.shape[0]
    if n >= _DEVICE_MIN_PAIRS:
        import jax.numpy as jnp
        from ...ops.sha256 import (bytes_to_words, pair_hash_words,
                                   words_to_bytes)
        m = 1
        while m < n:
            m *= 2
        padded = np.zeros((m, 64), dtype=np.uint8)
        padded[:n] = pairs
        # pair_hash_words is the CSTPU_MERKLE_BACKEND switch (XLA vs Pallas)
        digests = pair_hash_words(jnp.asarray(bytes_to_words(padded)))
        return words_to_bytes(np.asarray(digests))[:n]
    sha = hashlib.sha256
    # an all-identical level (a vector filled with one root, e.g. the
    # genesis active-index roots) hashes once — O(n) check, no sort
    if n >= 64 and (pairs == pairs[0]).all():
        HOST_PAIRS_HASHED.inc(1)
        row = np.frombuffer(sha(pairs[0].tobytes()).digest(), np.uint8)
        out = np.empty((n, 32), dtype=np.uint8)
        out[:] = row
        return out
    HOST_PAIRS_HASHED.inc(n)
    buf = pairs.tobytes()
    digests = b"".join(sha(buf[64 * i:64 * i + 64]).digest()
                       for i in range(n))
    return np.frombuffer(digests, np.uint8).reshape(n, 32)


# Content-keyed merkleization memo. sha256 trees are pure functions of
# their input bytes, so (kind, raw bytes) -> result is sound. The per-slot
# full-state root (the reference's hottest loop, 0_beacon-chain.md:1232-1245)
# recomputes every field subtree while process_slot changed only a handful
# of entries; the memo turns each unchanged subtree into one ~µs/32KB key
# build plus a dict hit. Bounded by accumulated key bytes and cleared
# wholesale when exceeded (the next state root repopulates the live set).
_MEMO_MAX_BYTES = 96 * 1024 * 1024
_MEMO_MAX_KEY = _MEMO_MAX_BYTES // 16   # one entry must never dominate the cap
_MEMO_MIN_CHUNKS = 64         # below this, hashing is cheaper than keying
_memo: dict = {}
_memo_bytes = 0


def _memo_put(kind, key: bytes, value) -> None:
    global _memo_bytes
    if _memo_bytes > _MEMO_MAX_BYTES:
        _memo.clear()
        _memo_bytes = 0
    _memo[(kind, key)] = value
    _memo_bytes += len(key) + len(value) + 64


def _memo_evict(kind, key: bytes) -> None:
    """Drop one memo entry (mirror of _memo_put's accounting). Used by the
    incremental tree handles: when a forest invalidates a leaf range, the
    entries it inserted for the superseded content come out immediately
    instead of lingering until the wholesale cap clear."""
    global _memo_bytes
    value = _memo.pop((kind, key), None)
    if value is not None:
        _memo_bytes = max(0, _memo_bytes - (len(key) + len(value) + 64))


def _zero_chunk_rows(n: int, depth: int) -> np.ndarray:
    row = np.frombuffer(zerohashes[depth], dtype=np.uint8)
    return np.broadcast_to(row, (n, 32))


def next_level(level: np.ndarray, depth: int) -> tuple:
    """One level up from the [n, 32] nodes at `depth`: ([ceil(n/2), 32]
    parents, pairs filled from the zero-hash table). An odd level takes the
    zero subtree of its depth as its last sibling. Shared by the one-shot
    root below and the persistent host tree's build (host_tree.py), so a
    build costs what a root costs."""
    if level.shape[0] % 2 == 1:
        level = np.concatenate([level, _zero_chunk_rows(1, depth)])
    pairs = level.reshape(-1, 64)
    zero_pair = np.frombuffer(zerohashes[depth] * 2, dtype=np.uint8)
    nonzero = ~np.all(pairs == zero_pair, axis=1)
    nxt = np.empty((pairs.shape[0], 32), dtype=np.uint8)
    nxt[:] = np.frombuffer(zerohashes[depth + 1], np.uint8)
    to_hash = int(np.count_nonzero(nonzero))
    if to_hash:
        nxt[nonzero] = hash_pairs_array(pairs[nonzero])
    return nxt, pairs.shape[0] - to_hash


def merkleize_chunk_array(chunks: np.ndarray) -> bytes:
    """Root over an [N, 32] uint8 chunk matrix (next-pow2 zero padding),
    identical to merkle.merkleize_chunks on the equivalent byte list.

    Pairs of zero-subtree roots hash to the next zero-subtree root by
    definition, so they are filled from the precomputed zerohash table
    instead of hashed — the big state vectors (block/state/randao roots,
    8,192 entries each) are mostly zero-suffixed, and a per-slot state root
    must not pay full-vector hashing for them."""
    n = chunks.shape[0]
    if n == 0:
        return ZERO_BYTES32
    key = None
    if _MEMO_MIN_CHUNKS <= n and n * 32 <= _MEMO_MAX_KEY:
        key = chunks.tobytes()
        hit = _memo.get(("mca", key))
        if hit is not None:
            return hit
    level = np.ascontiguousarray(chunks)
    depth = 0
    zero_filled = 0
    while level.shape[0] > 1:
        level, filled = next_level(level, depth)
        zero_filled += filled
        depth += 1
    HOST_PAIRS_ZERO_FILLED.inc(zero_filled)
    root = level[0].tobytes()
    if key is not None:
        _memo_put("mca", key, root)
    return root


def merkleize_few(chunks: list) -> bytes:
    """merkle.merkleize_chunks for a handful of 32-byte chunks: one hashlib
    call a pair and nothing else, counted like the array path. A container
    of a few fields or a short byte string costs its hashes here; the numpy
    level pass above costs a dozen array operations a level whatever the
    width, and only pays for itself from `_MEMO_MIN_CHUNKS` chunks up."""
    if not chunks:
        return ZERO_BYTES32
    sha = hashlib.sha256
    level, depth, hashed = chunks, 0, 0
    while len(level) > 1:
        if len(level) % 2:
            level = level + [zerohashes[depth]]
        level = [sha(level[i] + level[i + 1]).digest()
                 for i in range(0, len(level), 2)]
        hashed += len(level)
        depth += 1
    HOST_PAIRS_HASHED.inc(hashed)
    return level[0]


def subtree_roots_batch(leaves: np.ndarray) -> np.ndarray:
    """[V, P, 32] uint8 (P a power of two) -> [V, 32] subtree roots.

    All V subtrees descend one level per hash call: the [V, P/2, 64] tensor
    flattens into one (V*P/2)-lane batch — the device sees registry-sized
    batches even though each element's tree is tiny."""
    V, P, _ = leaves.shape
    assert P & (P - 1) == 0, "pad element chunk count to a power of two"
    key = None
    if _MEMO_MIN_CHUNKS <= V * P and V * P * 32 <= _MEMO_MAX_KEY:
        key = leaves.tobytes()
        hit = _memo.get((("srb", P), key))
        if hit is not None:
            return np.frombuffer(hit, np.uint8).reshape(V, 32).copy()
    level = leaves
    while level.shape[1] > 1:
        level = hash_pairs_array(
            level.reshape(-1, 64)).reshape(V, level.shape[1] // 2, 32)
    roots = level[:, 0, :]
    if key is not None:
        _memo_put(("srb", P), key, np.ascontiguousarray(roots).tobytes())
    return roots


# ---------------------------------------------------------------------------
# Tree-handle API: build -> update(leaf_idx, rows) -> root
#
# merkleize_chunk_array answers one-shot roots; callers that OWN a chunk
# matrix and mutate it a few rows at a time (per-slot state roots between
# epoch boundaries) get a persistent handle instead: the incremental forest
# (utils/ssz/incremental.py) keeps every tree level resident and re-hashes
# only the dirty root paths — O(dirty * log N) instead of O(N) per root.
# ---------------------------------------------------------------------------

class ChunkTreeHandle:
    """Incremental root over an [N, 32] uint8 chunk matrix.

    Keeps a host mirror of the chunks (updates are host-initiated) so the
    content-keyed byte memo stays coherent: `root()` inserts its result
    under the current content key exactly like merkleize_chunk_array, and
    any invalidation (update/append) EVICTS the entries this handle put
    there — forest invalidation and memo eviction move together, so a stale
    root can never be served for superseded content, and dead keys do not
    sit in the cap's accounting until the wholesale clear.
    """

    def __init__(self, chunks: np.ndarray):
        from .incremental import tree_from_chunks
        self._chunks = np.array(chunks, dtype=np.uint8)   # owned host mirror
        assert self._chunks.ndim == 2 and self._chunks.shape[1] == 32
        self.tree = tree_from_chunks(self._chunks)
        self._memo_keys: list = []
        self._memo_stale = True   # content not yet offered to the memo

    @property
    def n(self) -> int:
        return self._chunks.shape[0]

    def root(self) -> bytes:
        root = self.tree.root()
        n = self.n
        # offer the root to the shared memo ONCE per content generation —
        # the O(N) tobytes key build must not recur on every steady-state
        # root (that would reintroduce the linear host cost the tree avoids)
        if (self._memo_stale and _MEMO_MIN_CHUNKS <= n
                and n * 32 <= _MEMO_MAX_KEY):
            key = self._chunks.tobytes()
            if ("mca", key) not in _memo:
                _memo_put("mca", key, root)
                self._memo_keys.append(("mca", key))
            self._memo_stale = False
        return root

    def update(self, leaf_idx, rows: np.ndarray) -> None:
        """Overwrite chunk rows; O(len(leaf_idx) * log N) re-hash."""
        from ...ops.sha256 import bytes_to_words
        rows = np.asarray(rows, np.uint8).reshape(-1, 32)
        self.invalidate_memo()
        # the tree validates (unique, in-range) BEFORE mutating anything:
        # a rejected update must leave mirror and tree consistent, or the
        # next root() would memoize the old root under the new content key
        self.tree.update(leaf_idx, bytes_to_words(rows) if rows.shape[0]
                         else np.zeros((0, 8), np.uint32))
        self._chunks[np.asarray(leaf_idx, np.int64)] = rows

    def invalidate_memo(self) -> None:
        """Evict every memo entry this handle inserted (its content is about
        to be superseded)."""
        for kind, key in self._memo_keys:
            _memo_evict(kind, key)
        self._memo_keys.clear()
        self._memo_stale = True


def build_chunk_tree(chunks: np.ndarray) -> ChunkTreeHandle:
    """Tree-handle entry point (`build` of build -> update -> root)."""
    return ChunkTreeHandle(chunks)


# ---------------------------------------------------------------------------
# Column -> chunk builders (numpy, no per-element Python)
# ---------------------------------------------------------------------------

def uint_column_chunks(values: Sequence[int], byte_len: int) -> np.ndarray:
    """[V] ints -> [V, 32] one-chunk-per-value little-endian leaves."""
    v = len(values)
    out = np.zeros((v, 32), dtype=np.uint8)
    if byte_len <= 8:
        col = np.asarray(values, dtype=np.uint64)
        out[:, :8] = col.astype("<u8").view(np.uint8).reshape(v, 8)
    else:
        for i, x in enumerate(values):  # uint128/uint256 columns are rare
            out[i, :byte_len] = np.frombuffer(
                int(x).to_bytes(byte_len, "little"), np.uint8)
    return out


def bool_column_chunks(values: Sequence[bool]) -> np.ndarray:
    v = len(values)
    out = np.zeros((v, 32), dtype=np.uint8)
    out[:, 0] = np.asarray(values, dtype=np.uint8)
    return out


def bytes_column_matrix(values: Sequence[bytes], length: int) -> np.ndarray:
    """[V] equal-length byte strings -> [V, length] uint8."""
    joined = b"".join(values)
    return np.frombuffer(joined, dtype=np.uint8).reshape(len(values), length)


def bytesn_column_leaves(values: Sequence[bytes], length: int) -> np.ndarray:
    """[V] Bytes[N] values -> [V, 32] hash_tree_root leaves (pre-hashing the
    mini-tree for N > 32 on device: Bytes48 -> 1 level, Bytes96 -> 2)."""
    mat = bytes_column_matrix(values, length)
    v = mat.shape[0]
    n_chunks = (length + 31) // 32
    if n_chunks == 1:
        out = np.zeros((v, 32), dtype=np.uint8)
        out[:, :length] = mat
        return out
    pad = 1
    while pad < n_chunks:
        pad *= 2
    chunks = np.zeros((v, pad, 32), dtype=np.uint8)
    flat = chunks.reshape(v, pad * 32)
    flat[:, :length] = mat
    return subtree_roots_batch(chunks)


def pack_basic_list_chunks(values: Sequence[Any], elem_type: Any) -> np.ndarray:
    """Pack a basic-element series into its [C, 32] chunk matrix (SSZ pack,
    specs/simple-serialize.md:139-147)."""
    if isinstance(values, bytes):
        data = np.frombuffer(values, dtype=np.uint8)
    elif is_bool_type(elem_type):
        data = np.asarray(values, dtype=np.uint8)
    else:
        size = uint_byte_size(elem_type)
        if size == 8:
            data = np.asarray(values, dtype=np.uint64).astype("<u8").view(np.uint8)
        else:
            data = np.frombuffer(
                b"".join(int(x).to_bytes(size, "little") for x in values), np.uint8)
    n = data.shape[0]
    c = max(1, (n + 31) // 32)
    out = np.zeros((c, 32), dtype=np.uint8)
    out.reshape(-1)[:n] = data
    return out


# ---------------------------------------------------------------------------
# Container-list fast path
# ---------------------------------------------------------------------------

def _is_fast_field(typ: Any) -> bool:
    return is_uint_type(typ) or is_bool_type(typ) or is_bytesn_type(typ)


def container_list_is_fast(elem_type: Any) -> bool:
    return is_container_type(elem_type) and all(
        _is_fast_field(t) for t in elem_type.get_field_types())


def container_column_leaves(columns: Dict[str, Any], elem_type: Any,
                            count: int) -> np.ndarray:
    """Columns (field name -> [V] sequence) -> [V, P, 32] leaf tensor."""
    fields = elem_type.get_fields()
    pad = 1
    while pad < len(fields):
        pad *= 2
    leaves = np.zeros((count, pad, 32), dtype=np.uint8)
    for k, (name, ftyp) in enumerate(fields):
        col = columns[name]
        if is_uint_type(ftyp):
            leaves[:, k, :] = uint_column_chunks(col, uint_byte_size(ftyp))
        elif is_bool_type(ftyp):
            leaves[:, k, :] = bool_column_chunks(col)
        elif is_bytesn_type(ftyp):
            leaves[:, k, :] = bytesn_column_leaves(col, ftyp.length)
        else:
            raise TypeError(f"not a fast column field: {ftyp}")
    return leaves


def container_list_roots(objs: Sequence[Any], elem_type: Any) -> np.ndarray:
    """[V] container objects -> [V, 32] element hash_tree_roots (bulk)."""
    columns = {
        name: [getattr(o, name) for o in objs]
        for name, _ in elem_type.get_fields()
    }
    leaves = container_column_leaves(columns, elem_type, len(objs))
    return subtree_roots_batch(leaves)


# ---------------------------------------------------------------------------
# Generic bulk dispatcher
# ---------------------------------------------------------------------------

def _plan_rows(plan: Plan, values: Sequence[Any]) -> list:
    """The roots of k containers of one type through that type's root plan
    (root_plan.plan_for), one by one. Every pair is hashed by hashlib from
    the values as they are now; the pairs and the elements are counted
    once a call."""
    rows, hashed = [], 0
    for v in values:
        root, pairs = plan(v)
        rows.append(root)
        hashed += pairs
    HOST_PAIRS_HASHED.inc(hashed)
    PLAN_ELEMENTS.inc(len(rows))
    return rows


def plan_roots(plan: Plan, values: Sequence[Any]) -> bytes:
    """The batch form of a container's root: `_plan_rows` as 32 k bytes."""
    return b"".join(_plan_rows(plan, values))


def hash_tree_root_bulk(obj: Any, typ: Any = None) -> bytes:
    """Same value as impl.hash_tree_root, with device-batched fast paths for
    big homogeneous collections. Falls back to the recursive oracle for
    anything it can't vectorize.

    A container whose type has a root plan (root_plan.py: every field a
    uint, a bool, a BytesN, `bytes` or such a container: Fork, Eth1Data,
    Crosslink, AttestationData, PendingAttestation, Attestation,
    BeaconBlockHeader, Validator and the like) is rooted by the plan. One
    with a list or vector field (BeaconState, BeaconBlockBody,
    HistoricalBatch, IndexedAttestation) is walked field by field here, so
    that its wide fields reach the column paths. A list or vector follows
    its element type, never its length: `container_list_is_fast` elements
    (Validator, VoluntaryExit, a vector's Crosslinks) go through numpy
    columns (container_list_roots), so that a registry never costs a
    Python call an element; elements that are not but have a plan
    (Attestation, PendingAttestation, ProposerSlashing) through the plan
    as one batch and a tree hashed pair by pair, nothing kept from one
    call to the next; any other composite element (AttesterSlashing,
    Deposit) comes back here one by one.

    Entered by the resident core's state root (ResidentCore._field_root)
    and by `spec.hash_tree_root` / `spec.signing_root` for every container
    value but a BeaconState (helpers.hash_tree_root): impl.hash_tree_root
    is the oracle the tests compare with and this function's fall-back,
    not a served path."""
    if typ is None:
        return impl.hash_tree_root(obj)

    if impl.is_bottom_layer_kind(typ) and not impl.is_basic_type(typ):
        elem = read_elem_type(typ)
        if len(obj) * impl.fixed_byte_size(elem) < _MEMO_MIN_CHUNKS * 32:
            root = merkleize_few(impl.chunkify(impl.pack(obj, elem)))
        else:
            root = merkleize_chunk_array(pack_basic_list_chunks(obj, elem))
        return impl.mix_in_length(root, len(obj)) if is_list_kind(typ) else root

    if is_list_type(typ) or is_vector_type(typ):
        elem = typ.elem_type
        n = len(obj)
        if n == 0:
            leaves: Optional[np.ndarray] = np.zeros((0, 32), dtype=np.uint8)
        elif container_list_is_fast(elem):
            leaves = container_list_roots(list(obj), elem)
        elif is_bytesn_type(elem):
            leaves = bytesn_column_leaves([bytes(x) for x in obj], elem.length)
        elif plan_for(elem) is not None:
            # one batch, and its tree pair by pair: no content memo stands
            # between a block's attestations and their list's root
            root = merkleize_few(_plan_rows(plan_for(elem), obj))
            return impl.mix_in_length(root, n) if is_list_kind(typ) else root
        else:
            leaves = np.stack([
                np.frombuffer(hash_tree_root_bulk(v, elem), np.uint8)
                for v in obj])
        root = merkleize_chunk_array(leaves)
        return impl.mix_in_length(root, n) if is_list_kind(typ) else root

    if is_container_type(typ):
        plan = plan_for(typ)
        if plan is not None:
            return plan_roots(plan, (obj,))
        roots = [hash_tree_root_bulk(v, t) for v, t in obj.get_typed_values()]
        if len(roots) < _MEMO_MIN_CHUNKS:
            return merkleize_few(roots)
        return merkleize_chunk_array(np.stack(
            [np.frombuffer(r, np.uint8) for r in roots]))

    return impl.hash_tree_root(obj, typ)


def signing_root_bulk(obj: Any) -> bytes:
    """Same value as impl.signing_root: the root of container `obj` over
    every field but its last (the signature), each field's root taken as
    hash_tree_root_bulk takes it."""
    return merkleize_few([hash_tree_root_bulk(v, t)
                          for v, t in obj.get_typed_values()[:-1]])


def state_root_bulk(state: Any) -> bytes:
    """BeaconState hash_tree_root via the bulk paths (registry + balances +
    root vectors dominate; everything else is tiny)."""
    return hash_tree_root_bulk(state, state.__class__)


# ---------------------------------------------------------------------------
# SoA direct path (no object extraction at all — bench/production shape)
# ---------------------------------------------------------------------------

def validator_leaf_chunks(
        pubkeys: np.ndarray, withdrawal_credentials: np.ndarray,
        activation_eligibility_epoch: np.ndarray, activation_epoch: np.ndarray,
        exit_epoch: np.ndarray, withdrawable_epoch: np.ndarray,
        slashed: np.ndarray, effective_balance: np.ndarray) -> np.ndarray:
    """[V, 8, 32] per-validator field-chunk subtrees from SoA arrays —
    subtree_roots_batch of the result gives each Validator's hash_tree_root.
    Shared by the full registry root below and the incremental forest's
    dirty-leaf recompute (resident.py patches only touched validators)."""
    V = pubkeys.shape[0]
    leaves = np.zeros((V, 8, 32), dtype=np.uint8)
    pk = np.zeros((V, 2, 32), dtype=np.uint8)
    pk.reshape(V, 64)[:, :48] = pubkeys
    leaves[:, 0, :] = subtree_roots_batch(pk)
    leaves[:, 1, :] = withdrawal_credentials
    for k, col in ((2, activation_eligibility_epoch), (3, activation_epoch),
                   (4, exit_epoch), (5, withdrawable_epoch)):
        leaves[:, k, :8] = np.asarray(col, dtype=np.uint64).astype(
            "<u8").view(np.uint8).reshape(V, 8)
    leaves[:, 6, 0] = np.asarray(slashed, dtype=np.uint8)
    leaves[:, 7, :8] = np.asarray(effective_balance, dtype=np.uint64).astype(
        "<u8").view(np.uint8).reshape(V, 8)
    return leaves


def validator_registry_root_from_columns(
        pubkeys: np.ndarray, withdrawal_credentials: np.ndarray,
        activation_eligibility_epoch: np.ndarray, activation_epoch: np.ndarray,
        exit_epoch: np.ndarray, withdrawable_epoch: np.ndarray,
        slashed: np.ndarray, effective_balance: np.ndarray) -> bytes:
    """List[Validator] root straight from SoA arrays (pubkeys [V,48] uint8,
    withdrawal_credentials [V,32] uint8, epochs/balances [V] uint64,
    slashed [V] bool) — zero per-validator Python. Field order matches
    containers.Validator (spec: 0_beacon-chain.md:278-298)."""
    V = pubkeys.shape[0]
    leaves = validator_leaf_chunks(
        pubkeys, withdrawal_credentials, activation_eligibility_epoch,
        activation_epoch, exit_epoch, withdrawable_epoch, slashed,
        effective_balance)
    roots = subtree_roots_batch(leaves)
    return impl.mix_in_length(merkleize_chunk_array(roots), V)


def uint64_list_root_from_column(values: np.ndarray) -> bytes:
    """List[uint64] root straight from a [V] uint64 array (balances)."""
    v = np.asarray(values, dtype=np.uint64)
    n = v.shape[0]
    c = max(1, (n * 8 + 31) // 32)
    out = np.zeros((c, 32), dtype=np.uint8)
    out.reshape(-1)[:n * 8] = v.astype("<u8").view(np.uint8)
    return impl.mix_in_length(merkleize_chunk_array(out), n)


# ---------------------------------------------------------------------------
# Fully device-resident path (ONE program, one upload, 32 bytes down)
#
# The numpy paths above batch each hash LEVEL onto the device but bounce the
# intermediate level through the host, once per level.
# These entry points instead trace leaf construction + every Merkle level
# into one jit: columns go up once, the root comes down. They are the
# production shape: the SoA epoch state already lives on device, so in a
# real pipeline the upload amortizes to zero. Bit-equality with the numpy
# path (and thus with the recursive object-model oracle) is asserted in
# tests/test_bulk_htr.py.
# ---------------------------------------------------------------------------

def _bswap32(x):
    """uint32 byte swap (little-endian value bytes -> big-endian SHA word)."""
    import jax.numpy as jnp
    x = x.astype(jnp.uint32)
    return ((x & 0xFF) << 24) | ((x & 0xFF00) << 8) \
        | ((x >> 8) & 0xFF00) | (x >> 24)


def _u64_col_words(col):
    """[V] uint64 -> [V, 8] words of each value's one-chunk leaf
    (little-endian bytes 0..7, zero bytes 8..31)."""
    import jax.numpy as jnp
    col = col.astype(jnp.uint64)
    w0 = _bswap32((col & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
    w1 = _bswap32((col >> jnp.uint64(32)).astype(jnp.uint32))
    zero = jnp.zeros_like(w0)
    return jnp.stack([w0, w1] + [zero] * 6, axis=-1)


def _u8_mat_words(mat):
    """[..., 4k] uint8 -> [..., k] big-endian uint32 words (device)."""
    import jax.numpy as jnp
    m = mat.astype(jnp.uint32).reshape(mat.shape[:-1] + (-1, 4))
    return (m[..., 0] << 24) | (m[..., 1] << 16) | (m[..., 2] << 8) | m[..., 3]


def _length_chunk_words(n: int) -> np.ndarray:
    """[1, 8] words of SSZ mix_in_length's little-endian length chunk."""
    from ...ops.sha256 import bytes_to_words
    chunk = np.zeros(32, dtype=np.uint8)
    chunk[:8] = np.frombuffer(int(n).to_bytes(8, "little"), np.uint8)
    return bytes_to_words(chunk)[None, :]


def _registry_leaf_words(pubkeys, wc, act_elig, act, exit_ep, withdrawable,
                         slashed, eff_balance, unroll=None):
    """Traced body: SoA validator columns -> [V, 8] per-validator root words
    (the leaves of the registry list tree — the incremental forest builds
    its level 0 from exactly these). `unroll` as in sha256_blocks: None
    chooses by the lanes, a few dirty rows name their own."""
    import jax
    import jax.numpy as jnp

    from ...ops.sha256 import sha256_pairs_inner, subtree_roots_words

    V = pubkeys.shape[0]
    with jax.named_scope("registry_leaves"):
        # pubkey: Bytes48 -> two chunks -> one pair-hash
        pk_padded = jnp.concatenate(
            [pubkeys, jnp.zeros((V, 16), dtype=pubkeys.dtype)], axis=1)
        pk_root = sha256_pairs_inner(_u8_mat_words(pk_padded),
                                     unroll=unroll)               # [V, 8]
        leaves = jnp.stack([
            pk_root,
            _u8_mat_words(wc),
            _u64_col_words(act_elig),
            _u64_col_words(act),
            _u64_col_words(exit_ep),
            _u64_col_words(withdrawable),
            _u64_col_words(slashed.astype(jnp.uint64)),  # bool: byte0 = 0/1
            _u64_col_words(eff_balance),
        ], axis=1)                                                # [V, 8, 8]
        return subtree_roots_words(leaves, unroll=unroll)         # [V, 8]


def _registry_root_words(pubkeys, wc, act_elig, act, exit_ep, withdrawable,
                         slashed, eff_balance):
    """Traced body: SoA validator columns -> List[Validator] root words."""
    import jax.numpy as jnp

    from ...ops.sha256 import merkle_reduce_words, sha256_pairs_inner

    V = pubkeys.shape[0]
    roots = _registry_leaf_words(pubkeys, wc, act_elig, act, exit_ep,
                                 withdrawable, slashed, eff_balance)
    list_root = merkle_reduce_words(roots)                        # [8]
    mixed = jnp.concatenate([list_root[None, :],
                             jnp.asarray(_length_chunk_words(V))], axis=1)
    return sha256_pairs_inner(mixed)[0]


def _balances_chunk_words(balances):
    """Traced body: [V] uint64 -> [C, 8] SSZ pack chunk words (4 values per
    32-byte chunk) — level 0 of the balances list tree."""
    import jax
    import jax.numpy as jnp

    V = balances.shape[0]
    pad = (-V) % 4
    with jax.named_scope("balances_chunks"):
        col = balances.astype(jnp.uint64)
        if pad:
            col = jnp.concatenate([col, jnp.zeros(pad, dtype=jnp.uint64)])
        w0 = _bswap32((col & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
        w1 = _bswap32((col >> jnp.uint64(32)).astype(jnp.uint32))
        return jnp.stack([w0, w1], axis=-1).reshape(-1, 8)        # [C, 8]


def _balances_root_words(balances):
    """Traced body: [V] uint64 -> List[uint64] root words (4 values/chunk)."""
    import jax.numpy as jnp

    from ...ops.sha256 import merkle_reduce_words, sha256_pairs_inner

    V = balances.shape[0]
    chunks = _balances_chunk_words(balances)
    list_root = merkle_reduce_words(chunks)
    mixed = jnp.concatenate([list_root[None, :],
                             jnp.asarray(_length_chunk_words(V))], axis=1)
    return sha256_pairs_inner(mixed)[0]


_device_root_jits: Dict[str, Any] = {}


def _get_root_jit(name: str, fn):
    if name not in _device_root_jits:
        from ...ops import intmath  # noqa: F401  (enables jax_enable_x64)
        import jax
        _device_root_jits[name] = jax.jit(fn)
    return _device_root_jits[name]


def registry_and_balances_roots_device(
        pubkeys, withdrawal_credentials, activation_eligibility_epoch,
        activation_epoch, exit_epoch, withdrawable_epoch, slashed,
        effective_balance, balances):
    """(registry_root, balances_root) as 32-byte strings — both roots in a
    single device program. Accepts numpy or already-device-resident jnp
    columns; per-slot production use keeps the columns on device so the
    only transfer is the 64 bytes of roots coming back."""
    import jax

    from ...ops.sha256 import words_to_bytes

    n_balances = balances.shape[0] if hasattr(balances, "shape") else len(balances)
    if pubkeys.shape[0] == 0 or n_balances == 0:  # metadata only: no device download
        # empty columns are zero-subtree roots; the traced path would hit a
        # degenerate (0, 8) reduction — match the numpy oracle directly
        r1 = validator_registry_root_from_columns(
            np.asarray(pubkeys), np.asarray(withdrawal_credentials),
            _as_u64(activation_eligibility_epoch), _as_u64(activation_epoch),
            _as_u64(exit_epoch), _as_u64(withdrawable_epoch),
            np.asarray(slashed, dtype=bool), _as_u64(effective_balance))
        r2 = uint64_list_root_from_column(np.asarray(balances, np.uint64))
        return r1, r2

    def both(pk, wc, a, b, c, d, s, eb, bal):
        return (_registry_root_words(pk, wc, a, b, c, d, s, eb),
                _balances_root_words(bal))

    fn = _get_root_jit("both", both)
    r1, r2 = jax.block_until_ready(fn(
        pubkeys, withdrawal_credentials,
        _as_u64(activation_eligibility_epoch), _as_u64(activation_epoch),
        _as_u64(exit_epoch), _as_u64(withdrawable_epoch),
        np.asarray(slashed, dtype=bool) if isinstance(slashed, np.ndarray)
        else slashed,
        _as_u64(effective_balance), _as_u64(balances)))
    return (words_to_bytes(np.asarray(r1)).tobytes(),
            words_to_bytes(np.asarray(r2)).tobytes())


def _as_u64(col):
    return np.asarray(col, dtype=np.uint64) if isinstance(
        col, (np.ndarray, list, tuple)) else col


def registry_leaf_words_device(pubkeys, withdrawal_credentials,
                               activation_eligibility_epoch, activation_epoch,
                               exit_epoch, withdrawable_epoch, slashed,
                               effective_balance):
    """[V, 8] device words of every validator's hash_tree_root — level 0 of
    the registry's incremental forest (resident.py builds the forest from
    these at an epoch boundary; one traced program, nothing downloads)."""
    fn = _get_root_jit("reg_leaves", _registry_leaf_words)
    return fn(pubkeys, withdrawal_credentials,
              _as_u64(activation_eligibility_epoch), _as_u64(activation_epoch),
              _as_u64(exit_epoch), _as_u64(withdrawable_epoch),
              np.asarray(slashed, dtype=bool) if isinstance(slashed, np.ndarray)
              else slashed,
              _as_u64(effective_balance))


def balances_chunk_words_device(balances):
    """[C, 8] device words of the balances list's SSZ pack chunks — level 0
    of the balances incremental forest."""
    fn = _get_root_jit("bal_chunks", _balances_chunk_words)
    return fn(_as_u64(balances))
