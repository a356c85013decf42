"""Phase-0 helper functions (bound as methods of Phase0Spec).

Semantics per /root/reference specs/core/0_beacon-chain.md:580-1155. Every
function takes the spec object first (giving access to constants, types, the
BLS boundary, and caches) and is attached to Phase0Spec at build time.

Performance redesign vs the reference: the committee path does not point-call
`get_shuffled_index` per output slot (:884-891). Instead the *whole* swap-or-not
permutation for (seed, n) is materialized once per epoch by a batched backend
(numpy host path here; the JAX kernel in ops/shuffle.py drops into the same
hook) and committees become array slices. `get_shuffled_index` remains as the
one-point spec semantics and as the oracle the batched path is tested against.
"""
from __future__ import annotations

import hashlib
from typing import Any, List, Optional, Sequence

import numpy as np

from ... import telemetry
from ...utils import merkle
from ...utils.ssz import bulk
from ...utils.ssz.impl import hash_tree_root as ssz_hash_tree_root
from ...utils.ssz.typing import Container


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------

def xor(spec, bytes1: bytes, bytes2: bytes) -> bytes:
    return bytes(a ^ b for a, b in zip(bytes1, bytes2))


def hash(spec, data: bytes) -> bytes:  # noqa: A001 - spec name
    cached = spec._hash_cache.get(data)
    if cached is None:
        cached = hashlib.sha256(data).digest()
        spec._hash_cache[data] = cached
    return cached


_state_root_backend = None


def set_state_root_backend(backend) -> None:
    """Install a full-BeaconState Merkleizer: fn(state) -> bytes|None.

    The per-slot `hash_tree_root(state)` is the reference's hottest loop
    (0_beacon-chain.md:1232-1245); this hook routes it through the bulk
    device Merkleizer (utils/ssz/bulk.py) the same way set_shuffle_backend
    routes committee permutations. Returning None falls back to the
    recursive oracle, so a backend can decline small states.
    """
    global _state_root_backend
    _state_root_backend = backend


def install_bulk_state_root(min_validators: int = 0) -> None:
    """Route spec.hash_tree_root(state) through bulk.state_root_bulk.

    Installed by production/bench entry points; tests install it explicitly
    and differential-check against the recursive path. Below min_validators
    the recursive oracle (with its hash cache) is kept.
    """
    def backend(state):
        if len(state.validator_registry) < min_validators:
            return None
        return bulk.state_root_bulk(state)

    set_state_root_backend(backend)


def hash_tree_root(spec, obj: Any, typ: Any = None) -> bytes:
    """The spec's `hash_tree_root`, keyed by the value's type alone.

    A container value goes to bulk.hash_tree_root_bulk: its type's root
    plan where it has one (root_plan.py: a Crosslink, an Attestation, a
    header), the field walk where a field is a list or a vector (a
    BeaconBlockBody, whose attestation list then reaches the plans as one
    batch; a HistoricalBatch). A BeaconState keeps the path it had: the
    installed backend, else the recursive oracle. A basic value, and
    anything asked with an explicit `typ`, goes to the oracle as before.
    impl.hash_tree_root is what the tests compare with
    (tests/test_root_plans.py)."""
    if typ is not None or not isinstance(obj, Container):
        return ssz_hash_tree_root(obj, typ)
    if obj.__class__ is not getattr(spec, "BeaconState", None):
        return bulk.hash_tree_root_bulk(obj, obj.__class__)
    if _state_root_backend is not None:
        root = _state_root_backend(obj)
        if root is not None:
            return root
    return ssz_hash_tree_root(obj)


def signing_root(spec, obj: Any) -> bytes:
    """The root of a container without its last field, the fields' roots
    taken as `hash_tree_root` above takes a container's."""
    return bulk.signing_root_bulk(obj)


def int_to_bytes(spec, integer: int, length: int) -> bytes:
    return int(integer).to_bytes(length, "little")


def bytes_to_int(spec, data: bytes) -> int:
    return int.from_bytes(data, "little")


def bls_domain(spec, domain_type: int, fork_version: bytes = b"\x00\x00\x00\x00") -> int:
    return int.from_bytes(int(domain_type).to_bytes(4, "little") + fork_version, "little")


def integer_squareroot(spec, n: int) -> int:
    assert n >= 0
    x, y = n, (n + 1) // 2
    while y < x:
        x, y = y, (y + n // y) // 2
    return x


# ---------------------------------------------------------------------------
# Time math
# ---------------------------------------------------------------------------

def slot_to_epoch(spec, slot: int) -> int:
    return slot // spec.SLOTS_PER_EPOCH


def get_current_epoch(spec, state) -> int:
    return spec.slot_to_epoch(state.slot)


def get_previous_epoch(spec, state) -> int:
    current_epoch = spec.get_current_epoch(state)
    return spec.GENESIS_EPOCH if current_epoch == spec.GENESIS_EPOCH else current_epoch - 1


def get_epoch_start_slot(spec, epoch: int) -> int:
    return epoch * spec.SLOTS_PER_EPOCH


def get_delayed_activation_exit_epoch(spec, epoch: int) -> int:
    return epoch + 1 + spec.ACTIVATION_EXIT_DELAY


# ---------------------------------------------------------------------------
# Validator predicates and balances
# ---------------------------------------------------------------------------

def is_active_validator(spec, validator, epoch: int) -> bool:
    return validator.activation_epoch <= epoch < validator.exit_epoch


def is_slashable_validator(spec, validator, epoch: int) -> bool:
    return (not validator.slashed) and (validator.activation_epoch <= epoch < validator.withdrawable_epoch)


def get_active_validator_indices(spec, state, epoch: int) -> List[int]:
    """Indices active at `epoch` (reference 0_beacon-chain.md:678-685).
    The predicate is inlined: the committee machinery calls this dozens
    of times per transition, and a per-element is_active_validator frame
    dominates the scan at registry scale."""
    return [i for i, v in enumerate(state.validator_registry)
            if v.activation_epoch <= epoch < v.exit_epoch]


def compute_active_index_root(spec, state, epoch: int) -> bytes:
    """hash_tree_root(get_active_validator_indices(state, epoch),
    List[uint64]): what process_final_updates writes into
    latest_active_index_roots every epoch. Through the vectorized
    uint64-list Merkleizer (equality-gated against the recursive path in
    tests/test_bulk_htr.py, which is seconds per call at registry scale);
    accepts the object helper's list and an ndarray alike. An explicit
    spec method so that the resident pipeline, which holds the registry's
    columns on the device, can build this tree there
    (models/phase0/resident.py)."""
    from ...utils.ssz.bulk import uint64_list_root_from_column
    return uint64_list_root_from_column(np.asarray(
        spec.get_active_validator_indices(state, epoch), dtype=np.uint64))


def increase_balance(spec, state, index: int, delta: int) -> None:
    spec.registry_view(state).increase_balance(index, delta)


def decrease_balance(spec, state, index: int, delta: int) -> None:
    spec.registry_view(state).decrease_balance(index, delta)


def effective_balance_of(spec, state, index: int) -> int:
    """Single-validator effective-balance read. An explicit spec method so
    the resident pipeline (models/phase0/resident.py) can redirect it to
    device-refreshed mirrors without cloning its callers (proposer
    rejection sampling)."""
    return state.validator_registry[index].effective_balance


def get_total_balance(spec, state, indices: Sequence[int]) -> int:
    return max(sum(state.validator_registry[i].effective_balance for i in indices), 1)


class ObjectRegistry:
    """The registry as block processing reads and writes it
    (`registry_view`), answered by an object state's validator list and
    balances: how many validators there are; one validator's `slashed`
    flag, pubkey, epochs and effective balance; the pubkeys of an index
    set; the row that holds a pubkey; the exit queue; and the writes of an
    exit, a slashing, a balance move and a deposit's new validator.

    `pubkey_index` is the spec's one-entry memo of the last object
    registry a deposit looked a key up in ([the list, pubkey -> first
    row, how many rows are indexed]): the index is built once a registry
    and caught up with rows appended since, so a deposit costs its own
    row where the spec's text scans the registry."""

    __slots__ = ("state", "far", "pubkey_index")

    def __init__(self, state, far: int = 2 ** 64 - 1,
                 pubkey_index: Optional[list] = None):
        self.state = state
        self.far = far
        self.pubkey_index = [None, {}, 0] if pubkey_index is None \
            else pubkey_index

    def __len__(self) -> int:
        return len(self.state.validator_registry)

    def slashed(self, index: int) -> bool:
        return bool(self.state.validator_registry[index].slashed)

    def pubkey(self, index: int) -> bytes:
        return self.state.validator_registry[index].pubkey

    def pubkeys(self, indices: Sequence[int]) -> List[bytes]:
        registry = self.state.validator_registry
        return [registry[i].pubkey for i in indices]

    def index_of_pubkey(self, pubkey) -> Optional[int]:
        """The first row whose validator has `pubkey`, None if none has."""
        registry = self.state.validator_registry
        memo = self.pubkey_index
        if memo[0] is not registry or memo[2] > len(registry):
            memo[:] = [registry, {}, 0]
        for row in range(memo[2], len(registry)):
            memo[1].setdefault(bytes(registry[row].pubkey), row)
        memo[2] = len(registry)
        return memo[1].get(bytes(pubkey))

    def append(self, validator, amount: int) -> None:
        self.state.validator_registry.append(validator)
        self.state.balances.append(amount)

    def activation_epoch(self, index: int) -> int:
        return self.state.validator_registry[index].activation_epoch

    def exit_epoch(self, index: int) -> int:
        return self.state.validator_registry[index].exit_epoch

    def withdrawable_epoch(self, index: int) -> int:
        return self.state.validator_registry[index].withdrawable_epoch

    def effective_balance(self, index: int) -> int:
        return self.state.validator_registry[index].effective_balance

    def exit_queue(self, floor_epoch: int) -> tuple:
        """(the exit queue's head epoch, how many validators exit in it):
        the later of `floor_epoch` and the last exit epoch any validator
        has, by the spec's two scans of the registry."""
        registry = self.state.validator_registry
        exit_epochs = [v.exit_epoch for v in registry if v.exit_epoch != self.far]
        head = max(exit_epochs + [floor_epoch])
        return head, sum(1 for v in registry if v.exit_epoch == head)

    def initiate_exit(self, index: int, exit_epoch: int, withdrawable_epoch: int) -> None:
        validator = self.state.validator_registry[index]
        validator.exit_epoch = exit_epoch
        validator.withdrawable_epoch = withdrawable_epoch

    def slash(self, index: int, withdrawable_epoch: int) -> None:
        validator = self.state.validator_registry[index]
        validator.slashed = True
        validator.withdrawable_epoch = withdrawable_epoch

    def increase_balance(self, index: int, delta: int) -> None:
        self.state.balances[index] += delta

    def decrease_balance(self, index: int, delta: int) -> None:
        balances = self.state.balances
        balances[index] = 0 if delta > balances[index] else balances[index] - delta


def registry_view(spec, state):
    """Who answers block processing's reads and writes of `state`'s
    registry: the view a resident core has registered for its own state
    (its host mirrors, resident pubkeys and device columns;
    models/phase0/resident.py), else the state's own validator list. Every
    state is asked the same way, so the block path has one implementation
    whether the registry lives as objects or as columns."""
    view = spec._registry_views.get(id(state))
    if view is not None and view.state is state:
        return view
    return ObjectRegistry(state, spec.FAR_FUTURE_EPOCH, spec._pubkey_index)


def get_churn_limit(spec, state) -> int:
    active = len(spec.get_active_validator_indices(state, spec.get_current_epoch(state)))
    return max(spec.MIN_PER_EPOCH_CHURN_LIMIT, active // spec.CHURN_LIMIT_QUOTIENT)


# ---------------------------------------------------------------------------
# Committee counting and shard layout
# ---------------------------------------------------------------------------

def get_epoch_committee_count(spec, state, epoch: int) -> int:
    active = len(spec.get_active_validator_indices(state, epoch))
    return max(
        1,
        min(
            spec.SHARD_COUNT // spec.SLOTS_PER_EPOCH,
            active // spec.SLOTS_PER_EPOCH // spec.TARGET_COMMITTEE_SIZE,
        ),
    ) * spec.SLOTS_PER_EPOCH


def get_shard_delta(spec, state, epoch: int) -> int:
    return min(
        spec.get_epoch_committee_count(state, epoch),
        spec.SHARD_COUNT - spec.SHARD_COUNT // spec.SLOTS_PER_EPOCH,
    )


def get_epoch_start_shard(spec, state, epoch: int) -> int:
    assert epoch <= spec.get_current_epoch(state) + 1
    check_epoch = spec.get_current_epoch(state) + 1
    shard = (state.latest_start_shard + spec.get_shard_delta(state, spec.get_current_epoch(state))) % spec.SHARD_COUNT
    while check_epoch > epoch:
        check_epoch -= 1
        shard = (shard + spec.SHARD_COUNT - spec.get_shard_delta(state, check_epoch)) % spec.SHARD_COUNT
    return shard


def get_attestation_data_slot(spec, state, data) -> int:
    committee_count = spec.get_epoch_committee_count(state, data.target_epoch)
    offset = (data.crosslink.shard + spec.SHARD_COUNT
              - spec.get_epoch_start_shard(state, data.target_epoch)) % spec.SHARD_COUNT
    return spec.get_epoch_start_slot(data.target_epoch) + offset // (committee_count // spec.SLOTS_PER_EPOCH)


# ---------------------------------------------------------------------------
# Roots, mixes, seeds
# ---------------------------------------------------------------------------

def get_block_root_at_slot(spec, state, slot: int) -> bytes:
    assert slot < state.slot <= slot + spec.SLOTS_PER_HISTORICAL_ROOT
    return state.latest_block_roots[slot % spec.SLOTS_PER_HISTORICAL_ROOT]


def get_block_root(spec, state, epoch: int) -> bytes:
    return spec.get_block_root_at_slot(state, spec.get_epoch_start_slot(epoch))


def get_randao_mix(spec, state, epoch: int) -> bytes:
    return state.latest_randao_mixes[epoch % spec.LATEST_RANDAO_MIXES_LENGTH]


def get_active_index_root(spec, state, epoch: int) -> bytes:
    return state.latest_active_index_roots[epoch % spec.LATEST_ACTIVE_INDEX_ROOTS_LENGTH]


def generate_seed(spec, state, epoch: int) -> bytes:
    return spec.hash(
        spec.get_randao_mix(state, epoch + spec.LATEST_RANDAO_MIXES_LENGTH - spec.MIN_SEED_LOOKAHEAD)
        + spec.get_active_index_root(state, epoch)
        + spec.int_to_bytes(epoch, length=32)
    )


# ---------------------------------------------------------------------------
# Swap-or-not shuffling
# ---------------------------------------------------------------------------

def get_shuffled_index(spec, index: int, index_count: int, seed: bytes) -> int:
    """One-point swap-or-not image (reference 0_beacon-chain.md:860-882)."""
    assert index < index_count
    assert index_count <= 2 ** 40
    for current_round in range(spec.SHUFFLE_ROUND_COUNT):
        round_byte = spec.int_to_bytes(current_round, length=1)
        pivot = spec.bytes_to_int(spec.hash(seed + round_byte)[0:8]) % index_count
        flip = (pivot + index_count - index) % index_count
        position = max(index, flip)
        source = spec.hash(seed + round_byte + spec.int_to_bytes(position // 256, length=4))
        bit = (source[(position % 256) // 8] >> (position % 8)) % 2
        index = flip if bit else index
    return index


_shuffle_backend = None
# Permutations really computed: bumped on every miss of `spec._perm_cache`,
# whichever backend then serves it.
PERMUTATIONS_COMPUTED = telemetry.counter("shuffle.permutations_computed")


def set_shuffle_backend(backend) -> None:
    """Install a batched permutation backend: fn(seed, n, rounds) -> perm|None.

    Returning None falls back to the numpy host path (e.g. for small n where
    device dispatch overhead dominates). ops/shuffle.py installs the JAX
    kernel here.
    """
    global _shuffle_backend
    _shuffle_backend = backend


def get_shuffle_permutation(spec, index_count: int, seed: bytes) -> np.ndarray:
    """perm[i] == get_shuffled_index(i, index_count, seed) for all i, batched.

    All rounds vectorized over the full index range; per round only the
    ceil(n/256) distinct position-block hashes are computed. Cached per
    (seed, n) — committees for a whole epoch reuse one permutation.
    """
    if index_count == 0:
        return np.empty(0, dtype=np.int64)
    key = (bytes(seed), index_count)
    cached = spec._perm_cache.get(key)
    if cached is not None:
        return cached
    PERMUTATIONS_COMPUTED.inc()
    perm = None
    if _shuffle_backend is not None:
        perm = _shuffle_backend(bytes(seed), index_count, spec.SHUFFLE_ROUND_COUNT)
    if perm is not None:
        return _cache_permutation(spec, key, perm)
    n = index_count
    idx = np.arange(n, dtype=np.int64)
    n_blocks = (n + 255) // 256
    for current_round in range(spec.SHUFFLE_ROUND_COUNT):
        round_byte = bytes([current_round])
        pivot = int.from_bytes(hashlib.sha256(seed + round_byte).digest()[:8], "little") % n
        flip = (pivot + n - idx) % n
        position = np.maximum(idx, flip)
        source = np.frombuffer(
            b"".join(hashlib.sha256(seed + round_byte + int(b).to_bytes(4, "little")).digest()
                     for b in range(n_blocks)),
            dtype=np.uint8,
        ).reshape(n_blocks, 32)
        byte = source[position // 256, (position % 256) // 8]
        bit = (byte >> (position % 8).astype(np.uint8)) & 1
        idx = np.where(bit.astype(bool), flip, idx)
    return _cache_permutation(spec, key, idx)


def _cache_permutation(spec, key, perm: np.ndarray) -> np.ndarray:
    if len(spec._perm_cache) > 64:
        spec._perm_cache.clear()
    spec._perm_cache[key] = perm
    return perm


def compute_committee(spec, indices: Sequence[int], seed: bytes, index: int, count: int) -> List[int]:
    start = (len(indices) * index) // count
    end = (len(indices) * (index + 1)) // count
    perm = spec.get_shuffle_permutation(len(indices), seed)
    return [indices[perm[i]] for i in range(start, end)]


def get_crosslink_committee(spec, state, epoch: int, shard: int) -> List[int]:
    return spec.compute_committee(
        indices=spec.get_active_validator_indices(state, epoch),
        seed=spec.generate_seed(state, epoch),
        index=(shard + spec.SHARD_COUNT - spec.get_epoch_start_shard(state, epoch)) % spec.SHARD_COUNT,
        count=spec.get_epoch_committee_count(state, epoch),
    )


def get_crosslink_committee_array(spec, state, epoch: int, shard: int) -> np.ndarray:
    """`get_crosslink_committee` as the int64 array its members are sliced
    from: the attestation family works by the member (a bit a member, an
    index a set bit), which is array work. The active indices are whatever
    `get_active_validator_indices` answers for this state, the object
    model's list or a resident core's array."""
    indices = spec.get_active_validator_indices(state, epoch)
    count = spec.get_epoch_committee_count(state, epoch)
    index = (shard + spec.SHARD_COUNT - spec.get_epoch_start_shard(state, epoch)) % spec.SHARD_COUNT
    start = (len(indices) * index) // count
    end = (len(indices) * (index + 1)) // count
    members = spec.get_shuffle_permutation(len(indices), spec.generate_seed(state, epoch))[start:end]
    if isinstance(indices, np.ndarray):
        return indices[members].astype(np.int64, copy=False)
    return np.fromiter((indices[i] for i in members), np.int64, count=end - start)


def get_beacon_proposer_index(spec, state) -> int:
    """Balance-weighted rejection sampling over the first committee of the slot
    (reference 0_beacon-chain.md:819-841).

    A block's attestation family calls this once per attestation (up to
    128x, 0_beacon-chain.md:1703-1718) with an identical result — inside
    that loop the only state mutations are PendingAttestation appends.
    block.process_attestations_batched pins the answer on the state for
    exactly that scope (cleared in its finally); the (slot, registry
    length) key is belt-and-suspenders. Mirrors the reference epilogue's
    committee memo (scripts/build_spec.py:78-91)."""
    memo = getattr(state, "_proposer_memo", None)
    if memo is not None and memo[0] == (int(state.slot),
                                        len(spec.registry_view(state))):
        return memo[1]
    return _compute_beacon_proposer_index(spec, state)


def _compute_beacon_proposer_index(spec, state) -> int:
    epoch = spec.get_current_epoch(state)
    committees_per_slot = spec.get_epoch_committee_count(state, epoch) // spec.SLOTS_PER_EPOCH
    offset = committees_per_slot * (state.slot % spec.SLOTS_PER_EPOCH)
    shard = (spec.get_epoch_start_shard(state, epoch) + offset) % spec.SHARD_COUNT
    first_committee = spec.get_crosslink_committee(state, epoch, shard)
    max_random_byte = 2 ** 8 - 1
    seed = spec.generate_seed(state, epoch)
    i = 0
    while True:
        candidate_index = first_committee[(epoch + i) % len(first_committee)]
        random_byte = spec.hash(seed + spec.int_to_bytes(i // 32, length=8))[i % 32]
        effective_balance = spec.effective_balance_of(state, candidate_index)
        if effective_balance * max_random_byte >= spec.MAX_EFFECTIVE_BALANCE * random_byte:
            return candidate_index
        i += 1


# ---------------------------------------------------------------------------
# Bitfields and attestations
# ---------------------------------------------------------------------------

def get_bitfield_bit(spec, bitfield: bytes, i: int) -> int:
    return (bitfield[i // 8] >> (i % 8)) % 2


def verify_bitfield(spec, bitfield: bytes, committee_size: int) -> bool:
    if len(bitfield) != (committee_size + 7) // 8:
        return False
    for i in range(committee_size, len(bitfield) * 8):
        if spec.get_bitfield_bit(bitfield, i) == 0b1:
            return False
    return True


def _set_bits(bitfield: bytes, size: int) -> np.ndarray:
    """Positions of the set bits among the first `size` of a bitfield that
    `verify_bitfield` has passed."""
    bits = np.unpackbits(np.frombuffer(bytes(bitfield), np.uint8), bitorder="little")
    return np.flatnonzero(bits[:size])


def _attesting_members(spec, committee: np.ndarray, bitfield: bytes) -> np.ndarray:
    """The members of `committee` whose bit is set, ascending."""
    assert spec.verify_bitfield(bitfield, len(committee))
    return np.sort(committee[_set_bits(bitfield, len(committee))])


def get_attesting_indices(spec, state, attestation_data, bitfield: bytes) -> List[int]:
    committee = spec.get_crosslink_committee_array(
        state, attestation_data.target_epoch, attestation_data.crosslink.shard)
    return _attesting_members(spec, committee, bitfield).tolist()


def convert_to_indexed(spec, state, attestation):
    # one committee for both bitfields, each checked and read as an array
    committee = spec.get_crosslink_committee_array(
        state, attestation.data.target_epoch, attestation.data.crosslink.shard)
    attesting_indices = _attesting_members(spec, committee, attestation.aggregation_bitfield)
    custody_bit_1_indices = _attesting_members(spec, committee, attestation.custody_bitfield)
    custody_bit_0_indices = attesting_indices[~np.isin(attesting_indices, custody_bit_1_indices)]
    return spec.IndexedAttestation(
        custody_bit_0_indices=custody_bit_0_indices.tolist(),
        custody_bit_1_indices=custody_bit_1_indices.tolist(),
        data=attestation.data,
        signature=attestation.signature,
    )


def _ascending(indices: np.ndarray) -> bool:
    return bool((indices[1:] >= indices[:-1]).all())


def validate_indexed_attestation(spec, state, indexed_attestation) -> None:
    bit_0_indices = np.asarray(indexed_attestation.custody_bit_0_indices, dtype=np.uint64)
    bit_1_indices = np.asarray(indexed_attestation.custody_bit_1_indices, dtype=np.uint64)

    # No custody bits set yet [phase 0], bounded size, disjoint, sorted.
    assert len(bit_1_indices) == 0
    assert len(bit_0_indices) + len(bit_1_indices) <= spec.MAX_INDICES_PER_ATTESTATION
    assert np.intersect1d(bit_0_indices, bit_1_indices).size == 0
    assert _ascending(bit_0_indices) and _ascending(bit_1_indices)
    # every index names a validator: the object model's list access raises
    # here, and so does this, whether or not a signature makes it look
    registry = spec.registry_view(state)
    for indices in (bit_0_indices, bit_1_indices):
        if indices.size and int(indices[-1]) >= len(registry):
            raise IndexError(f"validator index {int(indices[-1])} outside a registry of {len(registry)}")
    if not spec.bls.bls_active:
        # nothing reads the pubkey sets or the message hashes: every
        # verify answers True unread (crypto/bls.py)
        return
    check = spec.attestation_signature_check(
        state, indexed_attestation.data, bit_0_indices, bit_1_indices,
        indexed_attestation.signature)
    sink = spec._att_verify_sink
    if sink is not None:
        # Deferred: process_operations collects the whole block's checks
        # into one grouped device pipeline (block.py) — the verdict is
        # asserted there, with identical failure semantics.
        sink.append(check)
        return
    pubkey_sets, message_hashes, _, domain = check
    assert spec.bls.bls_verify_multiple(
        pubkeys=[spec.bls.bls_aggregate_pubkeys(s) for s in pubkey_sets],
        message_hashes=message_hashes,
        signature=indexed_attestation.signature,
        domain=domain,
    )


def attestation_signature_check(spec, state, data, bit_0_indices: np.ndarray,
                                bit_1_indices: np.ndarray, signature) -> tuple:
    """What the verify of an indexed attestation reads, as the deferred
    sink holds it (block.process_attestations_batched): the pubkey sets
    of the two custody bits, their two message hashes, the signature and
    the domain."""
    registry = spec.registry_view(state)
    pubkey_sets = [registry.pubkeys(bit_0_indices.tolist()), registry.pubkeys(bit_1_indices.tolist())]
    message_hashes = [
        spec.hash_tree_root(spec.AttestationDataAndCustodyBit(data=data, custody_bit=False)),
        spec.hash_tree_root(spec.AttestationDataAndCustodyBit(data=data, custody_bit=True)),
    ]
    domain = spec.get_domain(state, spec.DOMAIN_ATTESTATION, data.target_epoch)
    return pubkey_sets, message_hashes, bytes(signature), domain


def is_slashable_attestation_data(spec, data_1, data_2) -> bool:
    return (
        # Double vote
        (data_1 != data_2 and data_1.target_epoch == data_2.target_epoch)
        # Surround vote
        or (data_1.source_epoch < data_2.source_epoch and data_2.target_epoch < data_1.target_epoch)
    )


# ---------------------------------------------------------------------------
# Domains and Merkle branches
# ---------------------------------------------------------------------------

def get_domain(spec, state, domain_type: int, message_epoch: Optional[int] = None) -> int:
    epoch = spec.get_current_epoch(state) if message_epoch is None else message_epoch
    fork_version = state.fork.previous_version if epoch < state.fork.epoch else state.fork.current_version
    return spec.bls_domain(domain_type, bytes(fork_version))


def verify_merkle_branch(spec, leaf: bytes, proof: Sequence[bytes], depth: int, index: int, root: bytes) -> bool:
    return merkle.verify_merkle_branch(leaf, proof, depth, index, root)


# ---------------------------------------------------------------------------
# Validator status mutations
# ---------------------------------------------------------------------------

def is_slashable_index(spec, state, index: int, epoch: int) -> bool:
    """`is_slashable_validator` of the validator at `index`, read through
    the state's registry view."""
    registry = spec.registry_view(state)
    return (not registry.slashed(index)) and (
        registry.activation_epoch(index) <= epoch < registry.withdrawable_epoch(index))


def initiate_validator_exit(spec, state, index: int) -> None:
    registry = spec.registry_view(state)
    if registry.exit_epoch(index) != spec.FAR_FUTURE_EPOCH:
        return

    # the spec's two scans of the registry are the view's to answer
    exit_queue_epoch, exit_queue_churn = registry.exit_queue(
        spec.get_delayed_activation_exit_epoch(spec.get_current_epoch(state)))
    if exit_queue_churn >= spec.get_churn_limit(state):
        exit_queue_epoch += 1

    registry.initiate_exit(index, exit_queue_epoch,
                           exit_queue_epoch + spec.MIN_VALIDATOR_WITHDRAWABILITY_DELAY)


def slash_validator(spec, state, slashed_index: int, whistleblower_index: Optional[int] = None) -> None:
    registry = spec.registry_view(state)
    current_epoch = spec.get_current_epoch(state)
    spec.initiate_validator_exit(state, slashed_index)
    registry.slash(slashed_index, current_epoch + spec.LATEST_SLASHED_EXIT_LENGTH)
    slashed_balance = registry.effective_balance(slashed_index)
    state.latest_slashed_balances[current_epoch % spec.LATEST_SLASHED_EXIT_LENGTH] += slashed_balance

    proposer_index = spec.get_beacon_proposer_index(state)
    if whistleblower_index is None:
        whistleblower_index = proposer_index
    whistleblowing_reward = slashed_balance // spec.WHISTLEBLOWING_REWARD_QUOTIENT
    proposer_reward = whistleblowing_reward // spec.PROPOSER_REWARD_QUOTIENT
    spec.increase_balance(state, proposer_index, proposer_reward)
    spec.increase_balance(state, whistleblower_index, whistleblowing_reward - proposer_reward)
    spec.decrease_balance(state, slashed_index, whistleblowing_reward)
