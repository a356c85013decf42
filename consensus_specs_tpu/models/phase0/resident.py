"""Device-resident multi-epoch pipeline.

`process_epoch_soa` is a one-shot bridge: every call walks the object
registry into columns (seconds at 1M validators), runs the device epoch
program, and writes the columns back. Production does not need the round
trip — the registry and balances can stay device-resident across slots,
blocks, and epoch boundaries, with the object state carrying only the
small byte-rooted fields. This module makes that story real:

  * `ResidentCore(spec, state)` uploads the SoA columns + identity columns
    (pubkeys, withdrawal credentials) once, keeps small host numpy MIRRORS
    of the columns the host-side spec logic reads (activation/exit epochs,
    effective balance, slashed), and installs spec-method overrides that
    redirect those reads to the mirrors — `get_active_validator_indices`,
    `compute_committee` (vectorized), `get_beacon_proposer_index`,
    `get_total_balance` — so the UNMODIFIED process_block /
    process_attestation code runs against stale object numerics without
    ever touching them.
  * per-slot state roots combine the registry/balances roots of two
    device-resident INCREMENTAL Merkle forests (utils/ssz/incremental.py:
    every tree level stays on device, invalidation is per leaf) with the
    roots of every other field, the wide ones (history and crosslink
    vectors, attestation lists) kept as persistent HOST trees that take
    only the leaves a slot wrote (utils/ssz/host_tree.py) — the object
    registry is never materialized for a root, and a registry-mutating
    block re-hashes only the validators it touched (O(dirty * log V))
    instead of forcing the old all-or-nothing registry-scale rebuild.
  * at an epoch boundary the existing distillation machinery
    (build_epoch_context / process_crosslinks_vectorized /
    build_epoch_inputs) runs straight off the mirrors — the object-walk
    term (columns_np_from_state) disappears, and the shuffle permutations
    computed during the epoch's block processing are reused through the
    spec's permutation cache. The device program then runs
    on the ALREADY-RESIDENT columns; only the distilled participation
    facts upload, and only the three mirror columns (+ 2x32-byte roots)
    come back. The boundary's active-index root, the one registry-scale
    tree besides the two forests, is a third device build by the balances
    forest's own programs over the zero-filled index column
    (`_active_index_root`): one upload, one node down, no pair of it
    hashed on the host.
  * a block whose operations are attestations, deposits, voluntary exits
    and proposer and attester slashings (everything but transfers) is
    processed on the resident state (`process_block`): what the spec's
    block code reads and writes of the registry (the proposer's `slashed`
    flag and pubkey, the pubkey sets of indexed attestations, the
    registry's length; a validator's epochs and effective balance, the
    exit queue's head; an exit's and a slashing's rows, a slashing's
    balance moves; a deposit's lookup of its pubkey, its top-up or its
    new row) goes through the view the core registers for its state
    (helpers.registry_view), so a checkpoint-resumed (light) core, which
    has no Validator objects, takes such a chain like an object-entered
    one. An operation costs its own rows: the host mirrors take a write at
    once, the device columns take the block's dirty and appended rows when
    its last operation has passed (`resident.registry_write`), both
    forests their dirty and new leaves' paths (`resident.forests.update`:
    one program a forest), and the next slot's root reads the forests'
    roots as they then are. A block the spec rejects leaves the registry's
    length, columns, mirrors, pubkey index and forests as they were.
  * the registry has a CAPACITY: `capacity` rows of storage (an argument
    of both entries; none given, it is the registry's length V and every
    shape is V's) for the seven device columns, the identity matrices, the
    host mirrors and identity copies, and both forests. The rows from V to
    the capacity are inert in the columns (epoch_soa.inert_column_tail:
    what the mesh's padding is) and zero chunks in the forests; V is a
    host integer, mixed into the lists' roots on the host, and a traced
    scalar where a device program needs it, never a shape. So a deposit
    that appends a validator inside the capacity changes no shape and
    compiles nothing; one that would pass it re-lays the core out at the
    next power of two (`resident.registry.capacity_grown`: one re-layout
    and its compiles).
  * blocks carrying transfers take the fallback (a light core refuses
    them, having no objects to fall back to): exit residency (one
    writeback), process the block through the untouched object path,
    re-enter INCREMENTALLY — the re-entry diffs the columns against the
    pre-block snapshot, scatters only the changed rows back to device, and
    updates the forests at leaf granularity. Correctness is the object
    path's by construction; the re-Merkleization cost is proportional to
    the block, not the registry.

Reference semantics covered: per-slot root caching (0_beacon-chain.md
:1173-1191), process_epoch ordering (:1251-1262), final updates
(:1526-1564). Differential gate: tests/test_resident.py drives multiple
epochs with attestation-carrying blocks and asserts byte-identical
serialized states and per-slot roots vs the object model.
"""
from __future__ import annotations

import itertools
import time
from typing import Dict, Optional

import numpy as np

import jax

from ... import telemetry
from ...ops.sha256 import words_to_bytes
from ...resilience.errors import (CheckpointCorrupt, DispatchError,
                                  FatalDispatchError)
from ...telemetry import watchdog as _watchdog
from ...utils.donation import platform_donated_jit
from ...utils.merkle import next_power_of_two, tree_depth
from ...utils.ssz import bulk, host_tree
from ...utils.ssz import impl as ssz_impl
from ...utils.ssz.incremental import (IncrementalMerkleTree,
                                      ShardedIncrementalMerkleTree,
                                      bucket_indices)
from ...utils.ssz.typing import Vector
from . import block as block_mod
from . import helpers as helpers_mod
from .epoch_soa import (REPLICATED_INPUT_FIELDS, EpochConfig,
                        ValidatorColumns, build_epoch_context,
                        build_epoch_inputs_np,
                        columns_np_from_state, inert_column_tail,
                        pad_epoch_inputs, pad_validator_columns,
                        process_crosslinks_vectorized, scalars_from_state,
                        scalars_np_from_state, _apply_justification,
                        _apply_validator_columns, _epoch_transition_jit)

# Mirror columns the host-side spec logic reads between boundaries.
_MIRROR_FIELDS = ("activation_epoch", "exit_epoch", "effective_balance",
                  "slashed")
_ALL_FIELDS = ValidatorColumns._fields

# The state root's fields by the span that times them (_state_root): the
# two forests and the two attestation lists have paths of their own, the
# history vectors are the widest host fields, the rest is small.
_ATTESTATION_FIELDS = ("previous_epoch_attestations",
                       "current_epoch_attestations")
_HISTORY_FIELDS = ("latest_block_roots", "latest_state_roots",
                   "latest_randao_mixes", "latest_active_index_roots",
                   "latest_slashed_balances", "historical_roots")
_CROSSLINK_FIELDS = ("current_crosslinks", "previous_crosslinks")
# The fields whose root is kept as a persistent host tree (host_tree.py),
# by how the tree learns what changed: the attestation lists grow at their
# end and rotate as objects, the vectors are written an index at a time.
_TREE_KINDS = {
    **dict.fromkeys(_ATTESTATION_FIELDS, host_tree.AppendOnlyListTree),
    **dict.fromkeys(_HISTORY_FIELDS + _CROSSLINK_FIELDS,
                    host_tree.TrackedSeriesTree)}
_FOREST_PAIR_LANES = telemetry.counter("merkle.forest.pair_lanes")
# The host Merkleizer's work that `resident.slot_root` notes on its record.
_SLOT_ROOT_NOTES = {"pairs_hashed": bulk.HOST_PAIRS_HASHED,
                    "pairs_zero_filled": bulk.HOST_PAIRS_ZERO_FILLED,
                    "leaves_updated": host_tree.LEAVES_UPDATED,
                    "trees_rebuilt": host_tree.TREE_REBUILDS,
                    "plan_elements": bulk.PLAN_ELEMENTS}

# Blocks that left the served path for the object model (_fallback_block).
_BLOCK_FALLBACKS = telemetry.counter("resident.block.fallbacks", always=True)
# Attestation families that left the one pass for the per-attestation loop
# (`resident.block.attestations.sequential`, always on): the family counts
# them where it chooses (block.process_attestations_batched).
_FAMILY_LOOPS = block_mod.SEQUENTIAL_FAMILIES
# The lists of a block's body that the resident state does not serve: a
# transfer is cut from the preset (MAX_TRANSFERS 0). A block that carries
# one takes the object model on an object-entered core; a
# checkpoint-resumed core, which has no objects to fall back to, refuses
# it. Deposits, exits and both kinds of slashing are served (process_block).
_UNSERVED_OPERATIONS = ("transfers",)
# Re-layouts at a larger capacity (_grow_capacity): each is a new shape for
# every program of the serving path.
_CAPACITY_GROWN = telemetry.counter("resident.registry.capacity_grown",
                                    always=True)
# A block's appended rows go to the device in one bucket of this size
# (MAX_DEPOSITS of both presets), so how many a block appended is no shape.
_APPEND_BUCKET = 16

# Per-core watchdog key prefix: layout fingerprints must not leak between
# cores (a mesh core and a single-device core in one test process would
# otherwise trip false re-layout events against each other's placements).
_CORE_SEQ = itertools.count()


def light_state_from_bytes(spec, data: bytes):
    """Serialized BeaconState -> a BeaconState with every field
    deserialized EXCEPT validator_registry/balances (left empty — in a
    checkpoint-resumed resident pipeline those live as device columns,
    and materializing a million Validator objects is the distill floor
    this path exists to avoid)."""
    from ...utils.ssz.columns import container_field_spans
    from ...utils.ssz.impl import deserialize

    spans = container_field_spans(data, spec.BeaconState)
    state = spec.BeaconState()
    for name, typ in zip(spec.BeaconState.get_field_names(),
                         spec.BeaconState.get_field_types()):
        if name in ("validator_registry", "balances"):
            continue
        lo, hi = spans[name]
        setattr(state, name, deserialize(bytes(data[lo:hi]), typ))
    return state


class _BlockWrites:
    """What the operations of one served block have written through the
    registry view so far: the `withdrawable_epoch` an exit or a slashing
    gave each validator it touched (its keys are the block's dirty
    registry rows: the mirrors have their `exit_epoch` and `slashed`
    already, the device columns get all three when the block's last
    operation has passed), the balance moves in the order they were made,
    the rows its deposits appended (the registry's length, the mirrors,
    the host identity copies and the pubkey index have them already; the
    device columns and the forests get them with the dirty rows), and
    what it takes to put the length, the mirrors and the exit queue back
    if the block is rejected."""

    __slots__ = ("withdrawable", "balance_moves", "undo", "exit_queue",
                 "v0", "appended")

    def __init__(self, exit_queue, v0: int):
        self.withdrawable: dict = {}     # validator -> withdrawable_epoch
        self.balance_moves: list = []    # (index, up, down)
        self.undo: list = []             # (mirror, index, the value it had)
        self.exit_queue = None if exit_queue is None else list(exit_queue)
        self.v0 = v0                     # the registry's length at the open
        self.appended: list = []         # the new rows' first balances

    @property
    def dirty(self) -> bool:
        return bool(self.balance_moves or self.withdrawable or self.appended)


class _ColumnsRegistry:
    """`helpers.registry_view` for a resident core's own state: the reads
    and writes block processing makes of the registry, answered by the
    host mirrors, the core's host copy of the resident pubkeys (an identity
    row never changes while resident; a deposit appends one) and, for what
    has no mirror (a
    validator's `withdrawable_epoch`, the balances), the device columns a
    few rows at a time, on one device and on a mesh alike. A light core's
    state has no validator list to answer them. Every read and write
    costs its own rows, never the registry (`exit_queue`: the queue's
    head epoch and count are kept, `ResidentCore._exit_queue_head`)."""

    __slots__ = ("_core",)

    def __init__(self, core):
        self._core = core

    @property
    def state(self):
        return self._core.state

    def __len__(self) -> int:
        return self._core._v

    def _row(self, index) -> int:
        index = int(index)
        if not 0 <= index < self._core._v:
            raise IndexError(f"validator index {index} outside a registry "
                             f"of {self._core._v}")
        return index

    def slashed(self, index: int) -> bool:
        return bool(self._core.mirrors["slashed"][self._row(index)])

    def pubkey(self, index: int) -> bytes:
        return self._core._pk_np[self._row(index)].tobytes()

    def pubkeys(self, indices) -> list:
        rows = self._core._pk_np[np.asarray(indices, np.int64)]
        return [row.tobytes() for row in rows]

    def index_of_pubkey(self, pubkey) -> Optional[int]:
        return self._core._pubkey_lookup().get(bytes(pubkey))

    def append(self, validator, amount: int) -> None:
        self._core._append_row(validator, int(amount))

    def activation_epoch(self, index: int) -> int:
        return int(self._core.mirrors["activation_epoch"][self._row(index)])

    def exit_epoch(self, index: int) -> int:
        return int(self._core.mirrors["exit_epoch"][self._row(index)])

    def effective_balance(self, index: int) -> int:
        return int(self._core.mirrors["effective_balance"][self._row(index)])

    def withdrawable_epoch(self, index: int) -> int:
        return self._core._withdrawable_epoch(self._row(index))

    def exit_queue(self, floor_epoch: int) -> tuple:
        return self._core._exit_queue_head(int(floor_epoch))

    def initiate_exit(self, index: int, exit_epoch: int,
                      withdrawable_epoch: int) -> None:
        self._core._write_exit(self._row(index), int(exit_epoch),
                               int(withdrawable_epoch))

    def slash(self, index: int, withdrawable_epoch: int) -> None:
        self._core._write_slash(self._row(index), int(withdrawable_epoch))

    def increase_balance(self, index: int, delta: int) -> None:
        self._core._move_balance(self._row(index), int(delta), 0)

    def decrease_balance(self, index: int, delta: int) -> None:
        self._core._move_balance(self._row(index), 0, int(delta))


def _rows_at_traced(column, idx):
    return column[idx]


def _leaves_at_traced(pk_rows, wc_rows, elig, act, exit_ep, withdrawable,
                      slashed, eff, idx, unroll):
    """[k, 8] registry leaves (validator roots) of the rows `idx`: their
    identity bytes as the host holds them (a gather from the [V, 48]
    pubkey column costs the device a copy of it), the rest from the device
    columns as they stand."""
    return bulk._registry_leaf_words(
        pk_rows, wc_rows, elig[idx], act[idx], exit_ep[idx],
        withdrawable[idx], slashed[idx], eff[idx], unroll=unroll)


def _masked_leaves_traced(pk, wc, elig, act, exit_ep, withdrawable, slashed,
                          eff, count):
    """Level 0 of the registry forest of a core with room to grow: the
    validator roots of the rows below `count` (the logical length, traced),
    zero chunks from there to the capacity: an inert row's root is a hash,
    the SSZ list's padding is zero."""
    import jax.numpy as jnp
    leaves = bulk._registry_leaf_words(pk, wc, elig, act, exit_ep,
                                       withdrawable, slashed, eff)
    rows = jnp.arange(leaves.shape[0], dtype=jnp.int32)[:, None]
    return jnp.where(rows < count, leaves, jnp.uint32(0))


def _pending_activations_traced(elig, act, far):
    """Rows with an eligibility epoch and no activation epoch: the
    activation queue as a boundary finds it."""
    import jax.numpy as jnp
    return jnp.sum(((elig != far) & (act == far)).astype(jnp.int32))


def _append_rows_traced(pk, wc, cols, idx, pk_rows, wc_rows, eff_rows,
                        balance_rows, far):
    """A block's appended validators into the free rows `idx` (a bucket of
    _APPEND_BUCKET: the last repeated) of the identity matrices and the
    seven columns, one dispatch: never eligible, active, exiting or
    withdrawable yet, not slashed, the deposit's effective balance and
    amount."""
    import jax.numpy as jnp
    epochs = jnp.full(idx.shape, far, dtype=cols.exit_epoch.dtype)
    return pk.at[idx].set(pk_rows), wc.at[idx].set(wc_rows), ValidatorColumns(
        activation_eligibility_epoch=cols.activation_eligibility_epoch
        .at[idx].set(epochs),
        activation_epoch=cols.activation_epoch.at[idx].set(epochs),
        exit_epoch=cols.exit_epoch.at[idx].set(epochs),
        withdrawable_epoch=cols.withdrawable_epoch.at[idx].set(epochs),
        slashed=cols.slashed.at[idx].set(False),
        effective_balance=cols.effective_balance.at[idx].set(eff_rows),
        balance=cols.balance.at[idx].set(balance_rows))


def _write_rows_traced(exit_ep, withdrawable, slashed, idx, exit_rows,
                       withdrawable_rows, slashed_rows):
    """A block's dirty registry rows into the three columns its operations
    write, one dispatch."""
    return (exit_ep.at[idx].set(exit_rows),
            withdrawable.at[idx].set(withdrawable_rows),
            slashed.at[idx].set(slashed_rows))


def _balance_chunks_at_traced(balance, chunks, count):
    """[k, 8] words of the balances list's pack chunks `chunks`, from the
    device column as it stands; positions from `count` (the logical length)
    on are the pack's zero padding."""
    import jax.numpy as jnp
    pos = chunks[:, None] * 4 + jnp.arange(4, dtype=chunks.dtype)[None, :]
    vals = jnp.where(pos < count,
                     balance[jnp.minimum(pos, balance.shape[0] - 1)],
                     jnp.zeros((), dtype=balance.dtype))
    return bulk._balances_chunk_words(vals.reshape(-1))


def _move_balance_traced(balance, index, up, down):
    """increase_balance by `up`, then decrease_balance by `down` (the
    spec's: to zero where the balance is smaller), of one row."""
    import jax.numpy as jnp
    b = balance[index] + up
    return balance.at[index].set(jnp.where(down > b, jnp.zeros_like(b),
                                           b - down))


_rows_at = jax.jit(_rows_at_traced)
_leaves_at = jax.jit(_leaves_at_traced, static_argnames=("unroll",))
_write_rows = jax.jit(_write_rows_traced)
_balance_chunks_at = jax.jit(_balance_chunks_at_traced)
_move_balance = jax.jit(_move_balance_traced)
_masked_leaves = jax.jit(_masked_leaves_traced)
_pending_activations = jax.jit(_pending_activations_traced)
# the identity matrices are donated off the CPU (80 MB at 1M, rewritten in
# place), the columns are not (`_write_rows`' reason: 8 MB a column)
_append_rows = platform_donated_jit(_append_rows_traced, donate_argnums=(0, 1))


def _serving_mesh(mesh):
    """Resolve the `mesh` ctor argument: "env" consults CSTPU_SERVING_MESH
    (parallel.sharding.ServingMesh.from_env), None forces single-device,
    anything else is used as the ServingMesh itself."""
    if mesh == "env":
        from ...parallel.sharding import ServingMesh
        return ServingMesh.from_env()
    return mesh


class ResidentCore:
    """Holds the registry/balances on device across slots and epochs.

    With `mesh` (a parallel.sharding.ServingMesh, or CSTPU_SERVING_MESH
    set), the whole serving path runs under the validator-axis
    NamedSharding: columns and participation facts shard over "v" (padded
    to a mesh multiple with inert rows — epoch_soa.pad_validator_columns),
    the incremental forests keep per-shard subtree levels on their shard
    with a replicated cap tree, and every jitted program dispatches with
    matched in/out shardings so chained slot and epoch steps never
    re-lay-out. Roots and serialized states stay bit-identical to the
    single-device core (tests/test_resident.py).

    `capacity` is the rows of storage the registry is laid out with (the
    module docstring's CAPACITY): at least its length, which it is when
    none is given."""

    def __init__(self, spec, state, mesh="env", capacity: int = None):
        if spec._insert_after_registry_updates or spec._insert_after_final_updates:
            raise NotImplementedError(
                "resident mode covers the phase-0 fused epoch program; "
                "phase-1 insert hooks take process_epoch_soa_staged")
        self._mesh = _serving_mesh(mesh)
        self._tkey = f"resident{next(_CORE_SEQ)}"
        self.spec = spec
        self.cfg = EpochConfig.from_spec(spec)
        self.state = state
        self._saved_methods: Dict[str, object] = {}
        self._saved_root_backend = None
        self._active_idx_memo: Dict[int, np.ndarray] = {}
        self._host_trees: Dict[tuple, object] = {}
        self._light = False
        self._writes: Optional[_BlockWrites] = None
        self._capacity = int(capacity or 0)
        self._enter(state)

    # -- residency lifecycle ------------------------------------------------

    @classmethod
    def from_checkpoint(cls, spec, state_bytes: bytes, mesh="env",
                        capacity: int = None) -> "ResidentCore":
        """Resume a serialized BeaconState straight into residency without
        materializing the registry: the big fields parse as strided-view
        columns (utils/ssz/columns.py), everything else deserializes into
        a LIGHT state whose validator_registry/balances stay empty — the
        device columns are the authority. This is the production resume
        path (checkpoint bytes in, resident pipeline out); the object-walk
        entry (`ResidentCore(spec, state)`) exists for states that already
        live as objects.

        A light-resident core drives slots, epoch boundaries and blocks
        whose operations are anything but transfers: attestations,
        deposits, voluntary exits and proposer and attester slashings
        (state_transition / process_block: the registry is read and
        written through the core's view: the mirrors, the resident
        pubkeys, the device columns' dirty and appended rows); a block
        that carries a transfer, and exit(), need the object registry and
        are the standard entry's job.

        `capacity` is the rows of storage the registry is laid out with:
        a deposit that appends a validator inside it changes no shape and
        compiles nothing, one that would pass it re-lays the core out at
        the next power of two. None given, it is the checkpoint's own
        length, and the first new validator is such a re-layout. A core
        resumed from `checkpoint_bytes()` with the live core's capacity
        gives the live core's roots.

        Truncated or garbage bytes raise the TYPED `CheckpointCorrupt`
        (resilience/errors.py) up front — never an opaque struct/index
        error from deep inside the offset-grammar walkers — so the
        checkpoint store's generation fallback can branch on type."""
        with telemetry.span("resident.restore"):
            return cls._from_checkpoint(spec, state_bytes, mesh, capacity)

    @classmethod
    def _from_checkpoint(cls, spec, state_bytes, mesh,
                         capacity) -> "ResidentCore":
        if spec._insert_after_registry_updates or spec._insert_after_final_updates:
            raise NotImplementedError(
                "resident mode covers the phase-0 fused epoch program; "
                "phase-1 insert hooks take process_epoch_soa_staged")
        from ...utils.ssz.columns import state_columns_from_bytes
        from ...utils.ssz.impl import fixed_byte_size, is_fixed_size
        if not isinstance(state_bytes, (bytes, bytearray, memoryview)):
            raise CheckpointCorrupt(
                f"checkpoint payload must be bytes, got "
                f"{type(state_bytes).__name__}")
        # length floor BEFORE any parsing: every fixed field plus one
        # 4-byte offset per variable field must fit
        floor = sum(
            fixed_byte_size(t) if is_fixed_size(t) else 4
            for t in spec.BeaconState.get_field_types())
        if len(state_bytes) < floor:
            raise CheckpointCorrupt(
                f"checkpoint truncated: {len(state_bytes)} bytes < the "
                f"{floor}-byte BeaconState fixed-part floor")
        try:
            with telemetry.span("resident.restore.decode"):
                np_cols = state_columns_from_bytes(state_bytes, spec)
                state = light_state_from_bytes(spec, state_bytes)
        except CheckpointCorrupt:
            raise
        except Exception as exc:
            # the SSZ walkers reject garbage with Assertion/Index/Value/
            # struct errors at whatever depth the framing first breaks;
            # surface ONE typed class with the cause chained
            raise CheckpointCorrupt(
                f"checkpoint bytes do not parse as a serialized "
                f"BeaconState: {type(exc).__name__}: {exc}") from exc
        core = cls.__new__(cls)
        core._mesh = _serving_mesh(mesh)
        core._tkey = f"resident{next(_CORE_SEQ)}"
        core.spec = spec
        core.cfg = EpochConfig.from_spec(spec)
        core._saved_methods = {}
        core._saved_root_backend = None
        core._active_idx_memo = {}
        core._host_trees = {}
        core._light = True
        core._writes = None
        core._capacity = int(capacity or 0)
        with telemetry.span("resident.restore.upload") as sp:
            core._enter(state, np_cols=np_cols)
            sp.fence(core.cols, core.pk_dev)    # the uploads have landed
        return core

    def _enter(self, state, np_cols: Optional[dict] = None) -> None:
        self.state = state
        if np_cols is None:
            np_cols = dict(columns_np_from_state(state))
            n = len(state.validator_registry)
            pk = np.zeros((n, 48), np.uint8)
            wc = np.zeros((n, 32), np.uint8)
            for i, v in enumerate(state.validator_registry):
                pk[i] = np.frombuffer(bytes(v.pubkey), np.uint8)
                wc[i] = np.frombuffer(bytes(v.withdrawal_credentials), np.uint8)
            np_cols["pubkey"] = pk
            np_cols["withdrawal_credentials"] = wc
        # _v is the LOGICAL validator count; the storage has _capacity rows
        # (the entry's argument, V at least), inert from V on
        self._v = int(np_cols["balance"].shape[0])
        self._capacity = max(self._capacity, self._v)
        self._place_host(np_cols)
        self._upload(np_cols)
        # pubkey -> row, built when a deposit first asks (_pubkey_lookup)
        self._pubkey_index: Optional[dict] = None
        self._big_roots: Optional[tuple] = None
        # Per-column incremental Merkle forests (utils/ssz/incremental.py),
        # built lazily on the first root request; a fresh entry cannot reuse
        # old trees (unknown provenance of the new columns)
        self._reg_forest: Optional[IncrementalMerkleTree] = None
        self._bal_forest: Optional[IncrementalMerkleTree] = None
        self._active_idx_memo.clear()
        # [the last exit epoch any validator has, how many have it]; None
        # until an exit asks (_exit_queue_head) and whenever something
        # other than a served exit may have moved it
        self._exit_queue: Optional[list] = None
        self._install()

    def _padded(self, field: str, rows: np.ndarray, total: int) -> np.ndarray:
        """`rows` (the logical ones of one column or identity matrix)
        with the inert tail up to `total` rows; itself when it has them."""
        k = total - rows.shape[0]
        if k == 0:
            return rows
        tail = (np.zeros((k,) + rows.shape[1:], rows.dtype)
                if field in ("pubkey", "withdrawal_credentials")
                else inert_column_tail(field, k,
                                       int(self.spec.FAR_FUTURE_EPOCH)))
        return np.concatenate([rows, tail.astype(rows.dtype)])

    def _place_host(self, np_cols: Dict[str, np.ndarray]) -> None:
        """The host's part of a layout, `_capacity` rows each: the mirrors
        of the columns the spec's host logic reads, and the identity
        copies (they serve the checkpoint WRITE path and the block path's
        pubkey reads alongside the device uploads)."""
        self.mirrors: Dict[str, np.ndarray] = {
            f: self._padded(f, np_cols[f], self._capacity).copy()
            for f in _MIRROR_FIELDS}
        self._pk_np = self._padded("pubkey", np.asarray(np_cols["pubkey"]),
                                   self._capacity)
        self._wc_np = self._padded(
            "withdrawal_credentials",
            np.asarray(np_cols["withdrawal_credentials"]), self._capacity)

    def _device_rows(self) -> int:
        """The rows the device's columns have: the capacity, under a
        serving mesh up to the next mesh multiple."""
        return (self._capacity if self._mesh is None
                else self._mesh.pad_rows(self._capacity))

    def _put(self, tree):
        """Arrays of `_device_rows` rows onto the device, where the
        layout has them: the default device, or sharded by row."""
        import jax.numpy as jnp
        if self._mesh is None:
            return jax.tree_util.tree_map(jnp.asarray, tree)
        return jax.device_put(tree, self._mesh.shard_v)

    def _upload(self, np_cols: Dict[str, np.ndarray]) -> None:
        """The device's part of a layout: the seven columns and the
        identity matrices at `_device_rows`, inert from the logical rows
        on."""
        rows = self._device_rows()
        self.cols = self._put(ValidatorColumns(
            **{f: self._padded(f, np_cols[f], rows) for f in _ALL_FIELDS}))
        self.pk_dev = self._put(self._padded("pubkey", self._pk_np, rows))
        self.wc_dev = self._put(self._padded("withdrawal_credentials",
                                             self._wc_np, rows))

    def exit(self):
        """Materialize the device columns back into the object state and
        restore the spec; returns the (now fully concrete) state.

        The spec overrides come off even when the device is gone (a
        device lost mid-run must not leave the cached spec singleton
        monkey-patched for later host-only stages)."""
        if self._light:
            # refuse BEFORE touching the teardown: a refused exit must not
            # strip the residency overrides as a side effect (a caller that
            # catches this and keeps driving would otherwise run against
            # the EMPTY light registry) — use checkpoint_bytes() instead
            raise NotImplementedError(
                "a checkpoint-resumed (light) resident state has no object "
                "registry to materialize into; serialize via "
                "checkpoint_bytes() instead")
        try:
            self._write_back(self._materialize_np_cols())
        finally:
            self._uninstall()
        return self.state

    def _write_back(self, np_cols: Dict[str, np.ndarray]) -> None:
        """The columns into the object state's registry and balances: a
        Validator for every row that served deposits appended, then the
        numbers. `_apply_validator_columns` leaves `slashed` out (the
        epoch program never writes it); a served slashing does, on the
        columns."""
        registry = self.state.validator_registry
        for i in range(len(registry), self._v):
            registry.append(self.spec.Validator(
                pubkey=self._pk_np[i].tobytes(),
                withdrawal_credentials=self._wc_np[i].tobytes()))
        _apply_validator_columns(self.state, ValidatorColumns(**np_cols))
        for i in np.nonzero(np_cols["slashed"])[0]:
            self.state.validator_registry[int(i)].slashed = True

    def _materialize_np_cols(self) -> Dict[str, np.ndarray]:
        """One download of the device columns as a host dict (sliced back
        to the logical validator count — the inert rows of the capacity
        and of the sharded layout never reach host consumers)."""
        cols = jax.device_get(self.cols)
        return {f: np.asarray(getattr(cols, f))[:self._v]
                for f in _ALL_FIELDS}

    def checkpoint_bytes(self) -> bytes:
        """Serialize the resident state WITHOUT materializing the registry:
        the device columns come down once and assemble vectorized into the
        `List[Validator]`/balances payloads; the small fields serialize
        from the (light or object) host state. Works in both entry modes;
        with from_checkpoint this round-trips the original bytes when no
        transition ran."""
        from ...utils.ssz.columns import state_bytes_from_columns
        with telemetry.span("resident.checkpoint_write"):
            with telemetry.span("resident.checkpoint_write.download"):
                np_cols = self._materialize_np_cols()
            with telemetry.span("resident.checkpoint_write.assemble"):
                np_cols["pubkey"] = self._pk_np[:self._v]
                np_cols["withdrawal_credentials"] = self._wc_np[:self._v]
                return state_bytes_from_columns(self.state, np_cols,
                                                self.spec)

    def suspended(self):
        """Context manager: temporarily restore the unpatched spec (e.g.
        to run an independent object-model state while resident)."""
        import contextlib

        @contextlib.contextmanager
        def _cm():
            self._uninstall()
            try:
                yield
            finally:
                self._install()
        return _cm()

    def _fallback_block(self, state, block) -> None:
        """Exit -> unmodified object-path block -> INCREMENTAL re-enter.

        Correctness stays the object path's by construction; the cost no
        longer includes a full re-Merkleization. Re-entry diffs the columns
        the block changed against the pre-block snapshot, scatters only
        those rows into the device columns, and re-hashes only the touched
        validators' root paths in the incremental forests — a transfer
        that moves three balances costs O(dirty * log V) compressions,
        not the ~2M-leaf rebuild the old all-or-nothing `_big_roots`
        cache forced."""
        _BLOCK_FALLBACKS.inc()
        old_np = self._materialize_np_cols()
        try:
            self._write_back(old_np)
        finally:
            self._uninstall()
        self.spec.process_block(state, block)
        self._reenter_incremental(state, old_np)

    def _reenter_incremental(self, state, old_np: Dict[str, np.ndarray]) -> None:
        """Resume residency after an object-path block by diffing columns
        against the pre-block snapshot: changed rows scatter into the device
        columns and the forests invalidate at leaf granularity. The block
        carried transfers and no deposit the object path could take for a
        new validator (those are served), so the registry is as long as it
        was."""
        import jax.numpy as jnp
        self.state = state
        np_cols = dict(columns_np_from_state(state))
        assert np_cols["balance"].shape[0] == self._v, \
            "an object-path block changed the registry's length"
        dirty: Dict[str, np.ndarray] = {}
        new_cols = {}
        for f in _ALL_FIELDS:
            idx = np.nonzero(np_cols[f] != old_np[f])[0]
            dirty[f] = idx
            dev = getattr(self.cols, f)
            if idx.size:
                dev = dev.at[jnp.asarray(idx.astype(np.int32))].set(
                    jnp.asarray(np_cols[f][idx]))
            new_cols[f] = dev
        self.cols = ValidatorColumns(**new_cols)
        for f in _MIRROR_FIELDS:
            self.mirrors[f] = self._padded(f, np_cols[f], self._capacity).copy()
        self._active_idx_memo.clear()
        self._exit_queue = None
        self._update_forest_paths(
            np.unique(np.concatenate([dirty[f] for f in self._LEAF_FIELDS])),
            np.unique(dirty["balance"] // 4))
        self._big_roots = None
        self._install()

    # registry-leaf fields: everything the Validator container Merkleizes
    # except the separate balances list (pubkey/wc never change in place)
    _LEAF_FIELDS = ("activation_eligibility_epoch", "activation_epoch",
                    "exit_epoch", "withdrawable_epoch", "slashed",
                    "effective_balance")

    def _update_forest_paths(self, rows: np.ndarray,
                             chunks: np.ndarray) -> None:
        """The per-slot dirty path of both forests: the registry leaves of
        the validators `rows` and the balances chunks `chunks`, computed on
        the device from the columns as they stand, scattered into level 0,
        and their root paths re-hashed, ONE program a forest
        (IncrementalMerkleTree.update_bucket). Each dirty set is padded to
        a bucket (`bucket_indices`), so whatever a block dirties meets the
        programs the first block compiled; rows a block's deposits
        appended are dirty leaves like any other (every index is below
        the registry's length as it now is, which the forests take as
        their lists' new lengths). Nothing comes back: the next root
        request fetches the roots as they then are."""
        unroll = jax.default_backend() != "cpu"     # sha256._unroll_for's reason
        if self._reg_forest is not None and len(rows):
            idx = bucket_indices(rows)
            c = self.cols
            self._reg_forest.update_bucket(idx, _leaves_at(
                self._pk_np[idx], self._wc_np[idx],
                c.activation_eligibility_epoch, c.activation_epoch,
                c.exit_epoch, c.withdrawable_epoch, c.slashed,
                c.effective_balance, idx, unroll=unroll), logical_n=self._v)
            self._big_roots = None
        if self._bal_forest is not None and len(chunks):
            idx = bucket_indices(chunks)
            self._bal_forest.update_bucket(idx, _balance_chunks_at(
                self.cols.balance, idx, np.int32(self._v)),
                logical_n=-(-self._v // 4))
            self._big_roots = None

    # -- the registry view's writes (a served block's operations) -------------

    def _open_writes(self) -> _BlockWrites:
        """The open block's record: the view's writes are a block's, made
        inside `process_block`, which commits them or rolls them back."""
        if self._writes is None:
            raise RuntimeError(
                "a registry write through a resident core's view outside "
                "process_block: nothing would commit it to the device "
                "columns and the forests")
        return self._writes

    def _writable_mirror(self, field: str) -> np.ndarray:
        mirror = self.mirrors[field]
        if not mirror.flags.writeable:      # a column as device_get left it
            mirror = self.mirrors[field] = mirror.copy()
        return mirror

    def _mirror_set(self, writes: _BlockWrites, field: str, index: int,
                    value) -> None:
        mirror = self._writable_mirror(field)
        writes.undo.append((field, index, mirror[index]))
        mirror[index] = value

    def _withdrawable_epoch(self, index: int) -> int:
        """One validator's `withdrawable_epoch`: what the open block wrote,
        else the device column's row (it has no mirror: only a slashing's
        `is_slashable_validator` reads it)."""
        written = self._writes.withdrawable.get(index) \
            if self._writes is not None else None
        if written is not None:
            return written
        return int(jax.device_get(_rows_at(
            self.cols.withdrawable_epoch, np.array([index], np.int32)))[0])

    def _exit_queue_head(self, floor_epoch: int) -> tuple:
        """initiate_validator_exit's two scans of the registry: (the later
        of `floor_epoch` and the last exit epoch any validator has, how
        many validators exit in it). The last exit epoch and its count are
        found by two reductions over the mirror when nothing is known (the
        first exit after an entry or a boundary, whose ejections may have
        moved the queue) and kept from exit to exit."""
        queue = self._exit_queue
        if queue is None:
            exit_epoch = self.mirrors["exit_epoch"]
            known = exit_epoch[exit_epoch
                               != np.uint64(int(self.spec.FAR_FUTURE_EPOCH))]
            head = int(known.max()) if known.size else -1
            queue = self._exit_queue = [
                head, int(np.count_nonzero(exit_epoch == np.uint64(head)))
                if known.size else 0]
        return (floor_epoch, 0) if floor_epoch > queue[0] else tuple(queue)

    def _write_exit(self, index: int, exit_epoch: int,
                    withdrawable_epoch: int) -> None:
        writes = self._open_writes()
        self._mirror_set(writes, "exit_epoch", index, exit_epoch)
        writes.withdrawable[index] = withdrawable_epoch
        queue = self._exit_queue
        if queue is not None:
            if exit_epoch > queue[0]:
                queue[:] = [exit_epoch, 1]
            elif exit_epoch == queue[0]:
                queue[1] += 1
        # an active set answered for an epoch the validator has now left
        for epoch in [e for e in self._active_idx_memo if e >= exit_epoch]:
            del self._active_idx_memo[epoch]

    def _write_slash(self, index: int, withdrawable_epoch: int) -> None:
        writes = self._open_writes()
        self._mirror_set(writes, "slashed", index, True)
        writes.withdrawable[index] = withdrawable_epoch

    def _move_balance(self, index: int, up: int, down: int) -> None:
        self._open_writes().balance_moves.append((index, up, down))

    def _pubkey_lookup(self) -> dict:
        """pubkey -> row (the first that holds it, as the spec's `.index`
        finds it): built when a deposit first asks, never at entry (a
        million 48-byte keys at every restore would sit inside
        `restore_s`), and kept in step by appends and rollbacks."""
        if self._pubkey_index is None:
            with telemetry.span("resident.registry.pubkey_index") as sp:
                v = self._v
                keys = np.ascontiguousarray(self._pk_np[:v]).tobytes()
                index = {keys[48 * i:48 * i + 48]: i
                         for i in range(v - 1, -1, -1)}
                self._pubkey_index = index
                sp.note(rows=v, keys=len(index))
        return self._pubkey_index

    def _append_row(self, validator, amount: int) -> None:
        """A deposit's new validator into the next free row of the host's
        part of the layout (mirrors, identity copies, pubkey index), the
        registry one longer at once: the block's next operation finds it.
        The device's part follows with the block's other writes
        (`_commit_writes`)."""
        writes = self._open_writes()
        row = self._v
        if row == self._capacity:
            self._grow_capacity(row + 1)
        pubkey = bytes(validator.pubkey)
        for field in _MIRROR_FIELDS:
            self._writable_mirror(field)[row] = getattr(validator, field)
        # (a free row is the identity copies' own: `_padded` made it)
        self._pk_np[row] = np.frombuffer(pubkey, np.uint8)
        self._wc_np[row] = np.frombuffer(
            bytes(validator.withdrawal_credentials), np.uint8)
        self._pubkey_lookup().setdefault(pubkey, row)
        writes.appended.append(amount)
        self._v = row + 1

    def _grow_capacity(self, rows: int) -> None:
        """Re-lay the core out with room for `rows`: the next power of two
        of storage, the host's part padded where it stands (it may hold an
        open block's writes), the device's columns and identity matrices
        padded on the device and put where they lie, both forests dropped
        (the next root request builds them at the new capacity, a power of
        two crossed deepening them). Every program of the serving path
        meets a new shape: one re-layout and its compiles, counted."""
        import jax.numpy as jnp
        _CAPACITY_GROWN.inc()
        self._capacity = next_power_of_two(rows)
        far = int(self.spec.FAR_FUTURE_EPOCH)
        for f in _MIRROR_FIELDS:
            self.mirrors[f] = self._padded(f, self.mirrors[f], self._capacity)
        self._pk_np = self._padded("pubkey", self._pk_np, self._capacity)
        self._wc_np = self._padded("withdrawal_credentials", self._wc_np,
                                   self._capacity)
        total = self._device_rows()
        grow = total - int(self.pk_dev.shape[0])
        self.cols = self._put(pad_validator_columns(self.cols, total, far))
        self.pk_dev = self._put(jnp.concatenate(
            [self.pk_dev, jnp.zeros((grow, 48), self.pk_dev.dtype)]))
        self.wc_dev = self._put(jnp.concatenate(
            [self.wc_dev, jnp.zeros((grow, 32), self.wc_dev.dtype)]))
        self._reg_forest = self._bal_forest = self._big_roots = None
        self._active_idx_memo.clear()
        # a deliberate re-placement, reported by its own counter
        for key in (f"{self._tkey}.epoch.cols", f"{self._tkey}.forest.reg.l0",
                    f"{self._tkey}.forest.bal.l0"):
            _watchdog.forget(key)

    def _roll_back_writes(self, writes: _BlockWrites) -> None:
        """A rejected block: the mirrors, the exit queue and the
        registry's length as they were, the rows it appended inert again
        and out of the pubkey index (nothing had reached the device
        columns or the forests)."""
        for field, index, value in reversed(writes.undo):
            self.mirrors[field][index] = value
        if self._v > writes.v0:
            far = int(self.spec.FAR_FUTURE_EPOCH)
            for row in range(writes.v0, self._v):
                key = self._pk_np[row].tobytes()
                if self._pubkey_index.get(key) == row:
                    del self._pubkey_index[key]
            k = self._v - writes.v0
            for field in _MIRROR_FIELDS:
                self.mirrors[field][writes.v0:self._v] = \
                    inert_column_tail(field, k, far)
            self._pk_np[writes.v0:self._v] = 0
            self._wc_np[writes.v0:self._v] = 0
            self._v = writes.v0
        self._exit_queue = writes.exit_queue
        self._active_idx_memo.clear()

    def _commit_writes(self, writes: _BlockWrites) -> None:
        """The block's appended and dirty rows into the device columns (the
        mirrors have them), then the new and dirty leaves and chunks into
        both forests."""
        new_rows = list(range(writes.v0, self._v))
        with telemetry.span("resident.registry_write") as sp:
            if new_rows:
                # the free rows they take, one bucket, one dispatch for the
                # seven columns and the identity matrices
                idx = bucket_indices(np.asarray(new_rows, np.int64),
                                     floor=_APPEND_BUCKET)
                amounts = np.asarray(writes.appended, np.uint64)[idx - writes.v0]
                self.pk_dev, self.wc_dev, self.cols = _append_rows(
                    self.pk_dev, self.wc_dev, self.cols, idx,
                    self._pk_np[idx], self._wc_np[idx],
                    self.mirrors["effective_balance"][idx], amounts,
                    np.uint64(int(self.spec.FAR_FUTURE_EPOCH)))
                if self._mesh is not None:      # where they lie
                    self.pk_dev, self.wc_dev, self.cols = self._put(
                        (self.pk_dev, self.wc_dev, self.cols))
            c, new = self.cols, {}
            leaf_rows = sorted(writes.withdrawable)
            if leaf_rows:
                # every row an exit or a slashing touched got a
                # withdrawable_epoch; its exit_epoch and slashed flag are
                # the mirrors'. One bucket, one dispatch for the three.
                idx = bucket_indices(np.asarray(leaf_rows, np.int64))
                new["exit_epoch"], new["withdrawable_epoch"], new["slashed"] = \
                    _write_rows(
                        c.exit_epoch, c.withdrawable_epoch, c.slashed, idx,
                        self.mirrors["exit_epoch"][idx].astype(c.exit_epoch.dtype),
                        np.array([writes.withdrawable[int(i)] for i in idx],
                                 dtype=c.withdrawable_epoch.dtype),
                        self.mirrors["slashed"][idx].astype(c.slashed.dtype))
            balance = c.balance
            for index, up, down in writes.balance_moves:
                balance = _move_balance(balance, np.int32(index),
                                        np.uint64(up), np.uint64(down))
            if writes.balance_moves:
                new["balance"] = balance
            if self._mesh is not None:      # where the columns lie
                new = {f: jax.device_put(a, self._mesh.shard_v)
                       for f, a in new.items()}
            self.cols = c._replace(**new)
            balance_rows = sorted({m[0] for m in writes.balance_moves}
                                  | set(new_rows))
            leaf_rows = sorted(set(leaf_rows) | set(new_rows))
            sp.note(rows=len(set(leaf_rows) | set(balance_rows)),
                    appended_rows=len(new_rows))
        with telemetry.span("resident.forests.update") as sp:
            lanes0 = _FOREST_PAIR_LANES.value
            self._update_forest_paths(
                np.asarray(leaf_rows, np.int64),
                np.unique(np.asarray(balance_rows, np.int64) // 4))
            sp.note(registry_leaves=len(leaf_rows),
                    appended_leaves=len(new_rows),
                    balance_chunks=len({r // 4 for r in balance_rows}),
                    pair_lanes=_FOREST_PAIR_LANES.value - lanes0)

    # -- spec-method overrides ----------------------------------------------

    def _install(self) -> None:
        spec, mirrors = self.spec, self.mirrors
        saved = self._saved_methods

        # The mirrors describe self.state ONLY — mirror the _state_root
        # guard in every override that receives a state: any other state
        # (fork choice's justified state, a differential reference copy)
        # delegates to the saved object-path original instead of silently
        # answering from the resident columns.

        def get_active_validator_indices(state, epoch):
            if state is not self.state:
                return saved["get_active_validator_indices"](state, epoch)
            memo = self._active_idx_memo.get(int(epoch))
            if memo is None:
                e = np.uint64(int(epoch))
                memo = np.nonzero((mirrors["activation_epoch"] <= e)
                                  & (e < mirrors["exit_epoch"]))[0]
                if len(self._active_idx_memo) > 8:
                    self._active_idx_memo.clear()
                self._active_idx_memo[int(epoch)] = memo
            return memo

        def compute_committee(indices, seed, index, count):
            # state-free by signature: fully determined by the caller's
            # indices/seed, so no aliasing guard is possible or needed
            n = len(indices)
            start, end = (n * index) // count, (n * (index + 1)) // count
            perm = spec.get_shuffle_permutation(n, seed)
            return np.asarray(indices)[perm[start:end]].tolist()

        def get_total_balance(state, indices):
            if state is not self.state:
                return saved["get_total_balance"](state, indices)
            # callers pass lists, sets, or arrays
            idx = np.fromiter(indices, dtype=np.int64)
            return max(int(mirrors["effective_balance"][idx].sum()), 1)

        def effective_balance_of(state, index):
            if state is not self.state:
                return saved["effective_balance_of"](state, index)
            return int(mirrors["effective_balance"][index])

        def compute_active_index_root(state, epoch):
            if state is not self.state:
                return saved["compute_active_index_root"](state, epoch)
            return self._active_index_root(
                get_active_validator_indices(state, epoch))

        # Proposer sampling and final updates need no clones: the shared
        # implementations read through get_active_validator_indices /
        # effective_balance_of / compute_active_index_root (helpers.py), all
        # of which resolve to the overrides here.
        overrides = {
            "get_active_validator_indices": get_active_validator_indices,
            "compute_active_index_root": compute_active_index_root,
            "compute_committee": compute_committee,
            "get_total_balance": get_total_balance,
            "effective_balance_of": effective_balance_of,
        }
        for name, fn in overrides.items():
            self._saved_methods[name] = getattr(spec, name)
            setattr(spec, name, fn)
        # not an override: the block path asks every state for its registry
        # view (helpers.registry_view), and this state's is the columns
        spec._registry_views[id(self.state)] = _ColumnsRegistry(self)
        self._saved_root_backend = helpers_mod._state_root_backend
        helpers_mod.set_state_root_backend(self._state_root)

    def _uninstall(self) -> None:
        for name, fn in self._saved_methods.items():
            setattr(self.spec, name, fn)
        self._saved_methods.clear()
        self.spec._registry_views.pop(id(self.state), None)
        helpers_mod.set_state_root_backend(self._saved_root_backend)
        self._saved_root_backend = None

    # -- state roots --------------------------------------------------------

    def _registry_balances_roots(self, dispatched: Optional[tuple] = None):
        """(registry_root, balances_root) from the incremental forests.

        First request after an (epoch-boundary or entry) invalidation builds
        the forests from the device columns — one traced leaf program plus a
        batched pair-hash launch per level, the same O(V) the old one-shot
        device root paid. Every request between boundaries is O(1) (cached)
        or O(dirty * log V) after a fallback block's leaf-level updates —
        never the all-or-nothing ~2M-leaf re-Merkleization.

        `resident.forests` is the span of a build, and it notes the build's
        `pair_lanes` and `ahead_ms`. A boundary's refresh dispatches the
        build first (`_dispatch_forests`, under the download and the final
        updates) and hands `dispatched` = (its pair lanes, the clock when
        the dispatch ended): the span is then the wait for the two top
        rows, and `ahead_ms` how long the build ran under other host work
        before it opened. Every other caller gets dispatch and wait in
        one place, inside the span, `ahead_ms` 0."""
        if self._big_roots is not None:
            return self._big_roots
        if dispatched is None and self._reg_forest is not None \
                and self._bal_forest is not None:
            # both stand and have taken a block's dirty paths
            # (_update_forest_paths), or were dispatched by a refresh that
            # raised before its wait: their roots as they now are;
            # nothing is built, so no `resident.forests` span
            self._big_roots = self._forest_roots()
            return self._big_roots
        with telemetry.span("resident.forests") as sp:
            opened = time.perf_counter()
            if dispatched is None:
                dispatched = (self._dispatch_forests(), opened)
            lanes, since = dispatched
            sp.note(pair_lanes=lanes, ahead_ms=(opened - since) * 1e3)
            self._big_roots = self._forest_roots()
        return self._big_roots

    def _balances_forest(self, column) -> IncrementalMerkleTree:
        """Every level of the `List[uint64]` tree over `column`, which has
        the balances column's length, dtype and placement, so that
        whatever it holds the programs that run are the balances
        forest's: the chunk program and one level build, sharded under a
        mesh (level 0 from the mesh's placed chunk program). Inert rows
        hold balance 0, the SSZ pack's own zero padding, so the chunks
        from the logical length on are zero chunks without a mask."""
        logical_n = max(1, -(-self._v // 4))
        if self._mesh is not None:
            return ShardedIncrementalMerkleTree(
                self._mesh.balances_forest_chunks(column, self._capacity),
                self._mesh, logical_n=logical_n)
        return IncrementalMerkleTree(bulk.balances_chunk_words_device(column),
                                     logical_n=logical_n)

    def _active_index_root(self, indices) -> bytes:
        """hash_tree_root(indices, List[uint64]) for the ascending active
        `indices` of the resident registry, by a device tree build: the
        indices go up once, zero-filled to the registry's length, into the
        balances column's placement, `_balances_forest` builds over them,
        and one node comes down. The zeros from n on are SSZ's own padding
        of the last chunk and of the chunks after it, so the list's root
        is the first node of the level that spans its ceil(n / 4) chunks,
        which is the top one (32 bytes down, like a forest's root) while
        more than half the chunk capacity is in use. No shape depends on
        n: a registry whose active set moves compiles nothing. The tree
        is dropped on return."""
        import jax.numpy as jnp
        n, bal = len(indices), self.cols.balance
        column = np.zeros(bal.shape[0], bal.dtype)
        column[:n] = indices
        tree = self._balances_forest(
            jnp.asarray(column) if self._mesh is None
            else jax.device_put(column, self._mesh.shard_v))
        # the whole level, as a transfer: a row sliced on the device is a
        # program a level shape, and the level has 2 V / n rows at most
        level = jax.device_get(tree.levels[tree_depth(-(-n // 4))])
        return ssz_impl.mix_in_length(words_to_bytes(level[0]).tobytes(), n)

    def _dispatch_forests(self) -> int:
        """The build behind `_registry_balances_roots`, as far as the host
        need not wait: both forests dispatched from the device columns as
        they stand (a forest that stands is kept), the leaf programs and
        the level builds queued on the device, nothing fetched, so the
        host goes on while they run (`_forest_roots` is the wait).
        Returns the pair-hash lanes launched."""
        c = self.cols
        V = self._v
        if V == 0 or self.pk_dev.shape[0] == 0:
            return 0        # degenerate: no forest (`_forest_roots`)
        lanes0 = _FOREST_PAIR_LANES.value
        if self._mesh is not None:
            # sharded forests: level 0 built by the mesh's placed leaf
            # programs (inert rows masked to the SSZ zero rows), per-shard
            # subtree levels resident on their shard
            if self._reg_forest is None:
                self._reg_forest = ShardedIncrementalMerkleTree(
                    self._mesh.registry_forest_leaves(
                        self.pk_dev, self.wc_dev,
                        c.activation_eligibility_epoch, c.activation_epoch,
                        c.exit_epoch, c.withdrawable_epoch, c.slashed,
                        c.effective_balance, v_count=V,
                        capacity=self._capacity),
                    self._mesh, logical_n=V)
        elif self._reg_forest is None:
            # a core with no room has no inert row to mask: the leaf
            # program of every validator, V a shape as it always was
            leaves = (bulk.registry_leaf_words_device if self._capacity == V
                      else lambda *cols: _masked_leaves(*cols, np.int32(V)))
            self._reg_forest = IncrementalMerkleTree(
                leaves(self.pk_dev, self.wc_dev,
                       c.activation_eligibility_epoch, c.activation_epoch,
                       c.exit_epoch, c.withdrawable_epoch, c.slashed,
                       c.effective_balance), logical_n=V)
        if self._bal_forest is None:
            self._bal_forest = self._balances_forest(c.balance)
        # re-layout watchdog on the resident forests: per-slot root
        # requests must keep every level-0 buffer's placement (a rebuild
        # at the same capacity reproduces it; only a deposit that passes
        # the capacity legitimately re-places: `_grow_capacity` reports it)
        _watchdog.layout_check(f"{self._tkey}.forest.reg.l0",
                               self._reg_forest.levels[0])
        _watchdog.layout_check(f"{self._tkey}.forest.bal.l0",
                               self._bal_forest.levels[0])
        return _FOREST_PAIR_LANES.value - lanes0

    def _forest_roots(self) -> tuple:
        """(registry_root, balances_root) of the forests as they stand:
        the wait for whatever is still queued on them, both root levels
        down in one transfer (the top rows, while the lists fill more
        than half their trees), the lists' logical length mixed in."""
        if self._reg_forest is None or self._bal_forest is None:
            # degenerate metadata-only state: the numpy oracle short-circuit
            c = self.cols
            return bulk.registry_and_balances_roots_device(
                self.pk_dev, self.wc_dev, c.activation_eligibility_epoch,
                c.activation_epoch, c.exit_epoch, c.withdrawable_epoch,
                c.slashed, c.effective_balance, c.balance)
        top = jax.device_get((self._reg_forest.root_level(),
                              self._bal_forest.root_level()))
        return tuple(
            ssz_impl.mix_in_length(words_to_bytes(t[0]).tobytes(), self._v)
            for t in top)

    def _state_root(self, state):
        """Full BeaconState root: device roots for the two registry-scale
        fields (cached until the columns change), persistent host trees
        for the history vectors, the crosslink vectors and the attestation
        lists (_field_root: only the leaves written since the last root
        are re-hashed), one-shot bulk roots for the small rest. Same leaf
        layout as impl.hash_tree_root, every pair of it hashed at some
        root from the inputs it has now. The fields are taken group by
        group, one span a group, and each root is put at its field's index.

        Declines (-> saved backend / recursive oracle) for any state other
        than the resident one: the device columns describe THIS state only,
        and spec.hash_tree_root routes every BeaconState through the
        installed backend (e.g. the object-model reference state in a
        differential test, or fork-choice side states)."""
        if state is not self.state:
            return (self._saved_root_backend(state)
                    if self._saved_root_backend is not None else None)
        names = state.get_field_names()
        typed = dict(zip(names, state.get_typed_values()))
        roots = {}
        live: Dict[tuple, object] = {}
        with telemetry.span("resident.slot_root.forests"):
            roots["validator_registry"], roots["balances"] = \
                self._registry_balances_roots()
        with telemetry.span("resident.slot_root.attestations"):
            for name in _ATTESTATION_FIELDS:
                roots[name] = self._field_root(state, name, *typed[name], live)
        with telemetry.span("resident.slot_root.history"):
            for name in _HISTORY_FIELDS:
                roots[name] = self._field_root(state, name, *typed[name], live)
        with telemetry.span("resident.slot_root.small"):
            for name in names:
                if name not in roots:
                    roots[name] = self._field_root(
                        state, name, *typed[name], live)
        self._host_trees = live     # a tree no field holds any more goes
        with telemetry.span("resident.slot_root.merkleize"):
            return bulk.merkleize_few([roots[name] for name in names])

    def _field_root(self, state, name, value, typ, live) -> bytes:
        """One field's root: through its persistent host tree where the
        field has one (_TREE_KINDS), by the one-shot bulk path otherwise.

        A tree is bound to the list OBJECT it was built on and found by
        it, so the attestation rotation (`previous = current`) hands the
        tree over with the list, and a field that holds another object
        than last slot (a wholesale assignment such as process_crosslinks'
        `previous_crosslinks = [...]`, a state decoded or copied anew)
        builds from content. A vector's list is swapped for a
        host_tree.TrackedList the first time it is seen, so that whoever
        writes it afterwards (this core, the spec's block code, a test)
        leaves a record; a caller that kept the plain list it assigned no
        longer holds the state's field."""
        kind = _TREE_KINDS.get(name)
        lst = value.items if isinstance(value, Vector) else value
        if kind is None or not isinstance(lst, list):
            return bulk.hash_tree_root_bulk(value, typ)
        if (kind is host_tree.TrackedSeriesTree
                and type(lst) is not host_tree.TrackedList):
            lst = host_tree.TrackedList(lst)
            if isinstance(value, Vector):
                value.items = lst
            else:
                setattr(state, name, lst)
        key = (id(lst), typ)
        tree = self._host_trees.get(key)
        if tree is None or tree.bound is not lst:
            tree = kind(lst, typ)
        live[key] = tree
        return tree.root()

    # -- transition drive ---------------------------------------------------

    def state_transition(self, state, block):
        """`process_slots` to the block's slot, then `process_block`: on a
        checkpoint-resumed (light) core and on an object-entered one
        alike."""
        # a block this core refuses fails loudly BEFORE process_slots
        # mutates state (matching the exit() guard)
        self._registry_operations(block)
        self.process_slots(state, block.slot)
        self.process_block(state, block)
        return state

    def _registry_operations(self, block) -> list:
        """The lists of the block's body that the resident state does not
        serve and that are not empty: transfers. None when the block is
        header, randao, eth1 vote, attestations, deposits, exits and
        slashings only, which a light core and an object-entered one
        serve alike. Any other needs the object registry
        (_fallback_block), which a checkpoint-resumed core deliberately
        never built: it is refused here by name, before anything is
        written, with no silent fallback through a million objects."""
        touched = [name for name in _UNSERVED_OPERATIONS
                   if len(getattr(block.body, name))]
        if touched and self._light:
            raise NotImplementedError(
                f"of the registry_operations a checkpoint-resumed (light) "
                f"resident core serves deposits, voluntary exits and "
                f"slashings: "
                f"the block at slot {int(block.slot)} carries "
                f"{', '.join(touched)}, which need the object registry — "
                f"resume via the standard ResidentCore entry")
        return touched

    def process_block(self, state, block) -> None:
        """The spec's `process_block` on the resident state: its four steps
        in its order, each under its span, the registry read and written
        through this core's view (helpers.registry_view). The operations
        run in the spec's order with the spec's every check: proposer and
        attester slashings (`resident.block.slashings`), attestations
        (`.attestations`), deposits (`.deposits`), voluntary exits
        (`.exits`). An exit, a slashing or a deposit's new validator
        writes the host's part at once, so that the next operation of the
        block reads it (a second exit of one validator is refused, the
        exit queue counts the first, a second deposit of a new key tops
        its row up); when the last operation has passed, the block's
        appended and dirty rows go into the device columns
        (`resident.registry_write`) and the new and dirty leaves and
        balance chunks into both forests (`resident.forests.update`), and
        the next slot's root takes the forests' roots as they then are. A
        block the spec rejects is rejected with the registry's length,
        columns, mirrors, pubkey index and forests as they were (the
        small fields it wrote before the check that failed are the
        caller's to discard, as the spec discards them). A block with a
        transfer takes `_fallback_block` on an object-entered core and is
        refused, before anything is written, by a light one."""
        if self._registry_operations(block):
            self._fallback_block(state, block)
            return
        spec, body = self.spec, block.body
        with telemetry.span("resident.block", req=int(block.slot)) as sp:
            with telemetry.span("resident.block.header") as sp_part:
                # the body's root: its attestations through their root plan
                elements = bulk.PLAN_ELEMENTS.value
                pairs = bulk.HOST_PAIRS_HASHED.value
                spec.process_block_header(state, block)
                sp_part.note(
                    plan_elements=bulk.PLAN_ELEMENTS.value - elements,
                    pairs_hashed=bulk.HOST_PAIRS_HASHED.value - pairs)
            with telemetry.span("resident.block.randao"):
                spec.process_randao(state, body)
            with telemetry.span("resident.block.eth1"):
                spec.process_eth1_data(state, body)
            writes = self._writes = _BlockWrites(self._exit_queue, self._v)
            try:
                # process_operations, list by list under the spans
                spec.check_operations(state, body)
                with telemetry.span("resident.block.slashings") as sp_part:
                    spec.process_operation_list(state, body, "proposer_slashings")
                    spec.process_operation_list(state, body, "attester_slashings")
                    sp_part.note(slashed=sum(
                        field == "slashed" for field, _, _ in writes.undo))
                with telemetry.span("resident.block.attestations") as sp_part:
                    # one parent crosslink's root a committee of the block;
                    # `sequential` 1 for a family the loop took, noted
                    # whether or not the loop then raised
                    elements = bulk.PLAN_ELEMENTS.value
                    loops = _FAMILY_LOOPS.value
                    try:
                        family = spec.process_operation_list(state, body, "attestations")
                        sp_part.note(committees=family["committees"])
                    finally:
                        sp_part.note(
                            plan_elements=bulk.PLAN_ELEMENTS.value - elements,
                            sequential=_FAMILY_LOOPS.value - loops)
                with telemetry.span("resident.block.deposits") as sp_part:
                    # each proves its branch against the state's deposit
                    # root: DEPOSIT_CONTRACT_TREE_DEPTH pairs by hashlib
                    spec.process_operation_list(state, body, "deposits")
                    new = self._v - writes.v0
                    sp_part.note(
                        new_validators=new,
                        top_ups=len(body.deposits) - new,
                        proof_pairs_hashed=len(body.deposits)
                        * int(spec.DEPOSIT_CONTRACT_TREE_DEPTH))
                with telemetry.span("resident.block.exits") as sp_part:
                    # transfers are empty here
                    spec.process_operation_list(state, body, "voluntary_exits")
                    spec.process_operation_list(state, body, "transfers")
                    sp_part.note(exits=len(body.voluntary_exits))
                spec.process_extra_operations(state, body)
            except BaseException:
                self._roll_back_writes(writes)
                raise
            finally:
                self._writes = None
            if writes.dirty:
                self._commit_writes(writes)
            # every bitfield has passed verify_bitfield: a set bit is an
            # attesting index
            sp.note(attestations=len(body.attestations),
                    attesting_indices=sum(
                        int.from_bytes(bytes(a.aggregation_bitfield),
                                       "little").bit_count()
                        for a in body.attestations))

    def process_slots(self, state, slot: int) -> None:
        assert state.slot <= slot
        while state.slot < slot:
            boundary = (state.slot + 1) % self.spec.SLOTS_PER_EPOCH == 0
            # the root span of everything this slot causes: `req` ties its
            # descendants together, and its self time is what no child holds
            with telemetry.span("resident.boundary_slot" if boundary
                                else "resident.slot", req=int(state.slot)):
                self._process_slot(state)
                if boundary:
                    self.process_epoch_resident(state)
                state.slot += 1

    def _process_slot(self, state) -> None:
        spec = self.spec
        with telemetry.span("resident.slot_root") as sp:
            before = {k: c.value for k, c in _SLOT_ROOT_NOTES.items()}
            root = self._state_root(state)
            sp.note(**{k: c.value - before[k]
                       for k, c in _SLOT_ROOT_NOTES.items()})
        state.latest_state_roots[state.slot % spec.SLOTS_PER_HISTORICAL_ROOT] = root
        if state.latest_block_header.state_root == spec.ZERO_HASH:
            state.latest_block_header.state_root = root
        state.latest_block_roots[state.slot % spec.SLOTS_PER_HISTORICAL_ROOT] = \
            spec.signing_root(state.latest_block_header)

    def degrade_to_single_device(self) -> None:
        """The degradation ladder's bottom rung (resilience/dispatch.py):
        abandon the serving mesh and re-enter single-device — one
        download of the logical columns, unsharded re-upload, forests
        invalidated (the next root request rebuilds them unsharded).
        Deliberate and reported, so the chained-column watchdog keys are
        forgotten rather than tripped: the re-placement IS the recovery
        action, not a bug. Bit-identity is PR 6's committed
        sharded==single gate. Idempotent when already single-device."""
        if self._mesh is None:
            return
        with telemetry.span("resident.degrade_single_device"):
            np_cols = self._materialize_np_cols()
            self._mesh = None
            self._upload(np_cols)
            self._reg_forest = None
            self._bal_forest = None
            self._big_roots = None
            for key in (f"{self._tkey}.epoch.cols",
                        f"{self._tkey}.forest.reg.l0",
                        f"{self._tkey}.forest.bal.l0"):
                _watchdog.forget(key)

    def _stage_epoch_inputs(self, state, inp) -> tuple:
        """(scal, inp) for the boundary's dispatch, the host facts `inp`
        (build_epoch_inputs_np) uploaded where the program takes them: on
        the default device without a mesh; under a mesh padded on the host
        to the columns' rows and put from the host straight into
        `epoch_shardings()`'s placement (no `[V]` fact is copied from chip
        to chip on its way to the program)."""
        if self._mesh is None:
            # the facts have the mirrors' rows, which are the columns'
            import jax.numpy as jnp
            return (scalars_from_state(state),
                    jax.tree_util.tree_map(jnp.asarray, inp))
        inp = pad_epoch_inputs(inp, int(self.cols.balance.shape[0]))
        return self._mesh.place_epoch_inputs(scalars_np_from_state(state),
                                             inp)

    def _epoch_dispatch(self, scal, inp):
        """The guarded boundary dispatch + the degradation ladder.

        `scal` and `inp` arrive as `_stage_epoch_inputs` placed them: on a
        mesh padded and in the program's own placement. A ladder walk can
        end at the single-device rung (`degrade_to_single_device`), where
        neither applies any more: the facts are then cut back to the
        logical rows and taken to the one device, as part of the
        recovery. The inner guard (guarded_dispatch, via
        ServingMesh.epoch_transition on the mesh path) owns retry/
        backoff/deadline/tripwires; this loop owns only the LADDER: each
        typed failure that survives its retries steps one rung — oracle
        knobs first, sharded→single last — and re-dispatches. Raises
        FatalDispatchError when the ladder is exhausted."""
        from ...resilience import dispatch as _rdispatch
        from ...resilience.integrity import (epoch_output_check,
                                             tripwires_enabled)
        check = epoch_output_check if tripwires_enabled() else None
        ladder = _rdispatch.ladder()
        while True:
            try:
                if self._mesh is not None:
                    # matched in/out shardings: this boundary's output
                    # columns are the next boundary's inputs, zero re-layout
                    return self._mesh.epoch_transition(
                        self.cfg, self.cols, scal, inp, check=check)
                # _epoch_transition_jit() donates off-CPU exactly like
                # the mesh program: same no-retry pin for post-consume
                # failures (pre-dispatch transients still retry inside
                # the guard — it tracks whether fn ever ran)
                donate = jax.default_backend() != "cpu"
                return _rdispatch.guarded_dispatch(
                    (self._tkey, "epoch", int(self.cols.balance.shape[0])),
                    _epoch_transition_jit(), self.cfg, self.cols, scal, inp,
                    check=check,
                    retries=0 if donate else _rdispatch.RETRIES_DEFAULT)
            except FatalDispatchError:
                raise
            except DispatchError as exc:
                # branch on the guard's RECORDED fact, not the exception
                # type: a transient raised DURING execution consumed the
                # donated buffers just as surely as a deadline miss did
                if (jax.default_backend() != "cpu"
                        and getattr(exc, "consumed_inputs", True)):
                    # donating backend + a failure observed AFTER the
                    # dispatch consumed the resident column buffers
                    # (deadline miss, tripwired output) — mesh-sharded
                    # or single-device alike: the arrays are gone, so
                    # in-memory recovery (including the single-device
                    # rung's materialize) is impossible — the recovery
                    # grain is the checkpoint store. Pre-dispatch
                    # transients keep their buffers and still walk the
                    # ladder below.
                    raise FatalDispatchError(
                        f"epoch dispatch failed after consuming donated "
                        f"column buffers ({exc}); restore via "
                        f"resilience.CheckpointStore.restore",
                        key=exc.key, attempts=exc.attempts) from exc
                # the ladder is GLOBAL serving-loop conservatism: rungs
                # 1-3 swap oracle kernels this particular program never
                # calls (they matter for the forest/pairing dispatch
                # sites), so for an epoch failure they are quick no-op
                # hops on the way to the rung that can help
                # (single_device) — the price of one simple invariant,
                # rung k == knobs 1..k, that /healthz can report
                on_mesh = self._mesh is not None
                ladder.register_single_device(self.degrade_to_single_device)
                try:
                    rung = ladder.degrade(reason=type(exc).__name__)
                finally:
                    ladder.unregister_single_device(
                        self.degrade_to_single_device)
                if on_mesh and self._mesh is None:
                    # staged for the mesh this core has just left
                    scal, inp = self._unstage_for_single_device(scal, inp)
                if rung is None:
                    raise FatalDispatchError(
                        f"epoch boundary dispatch failed with the "
                        f"degradation ladder exhausted: {exc}",
                        key=exc.key, attempts=exc.attempts) from exc

    def _unstage_for_single_device(self, scal, inp) -> tuple:
        """Mesh-staged (scal, inp) -> the default device, the facts cut
        back to the columns' rows there (the single-device rung's
        recovery)."""
        import jax.numpy as jnp
        scal, inp = jax.device_get((scal, inp))
        inp = inp._replace(**{f: getattr(inp, f)[:self._capacity]
                              for f in inp._fields
                              if f not in REPLICATED_INPUT_FIELDS})
        return jax.tree_util.tree_map(jnp.asarray, (scal, inp))

    def process_epoch_resident(self, state) -> None:
        """The boundary transition on resident columns, under telemetry
        spans ("resident.stage" — host distillation off the mirrors
        (".distill": the builders' own "distill.context", ".crosslinks"
        and ".inputs" with their parts (epoch_soa.py), then ".place", the
        dispatch of the facts to where the program takes them) and the
        wait for its uploads (".upload"), "resident.device" — the epoch
        program on resident columns, "resident.refresh" — the forest
        rebuild dispatched (".forests_dispatch": nothing fenced, the device
        builds while the host goes on), scalars, report and mirror columns
        down (".download": their host copies were started first, ahead of
        the forests' programs), byte-rooted final updates
        (".final_updates": its index tree queues behind the forests, so
        the fetch of its level is what first waits for them) and last the
        wait for the forests' two roots ("resident.forests", fetched
        before this call returns: no root is deferred to a later slot)).
        The span records are the one view of the boundary's times
        (telemetry.ring(), snapshot()["spans"]). The retrace and re-layout
        watchdogs cover the dispatch: the epoch program must neither
        recompile nor change the columns' placement between chained
        boundaries."""
        spec = self.spec
        with telemetry.span("resident.stage"):
            with telemetry.span("resident.stage.distill") as sp_distill:
                # the activation queue this boundary finds, counted on the
                # device (the eligibility epoch has no mirror) while the
                # host distils: read when the note is written
                pending = _pending_activations(
                    self.cols.activation_eligibility_epoch,
                    self.cols.activation_epoch,
                    np.uint64(int(spec.FAR_FUTURE_EPOCH)))
                current_epoch = spec.get_current_epoch(state)
                previous_epoch = spec.get_previous_epoch(state)
                ctx = build_epoch_context(spec, state, dict(
                    self.mirrors,
                    activation_eligibility_epoch=None,  # unused by the context
                    withdrawable_epoch=None,
                    balance=None))
                prefilled = len(ctx.cl_roots)
                process_crosslinks_vectorized(spec, state, ctx)
                facts = build_epoch_inputs_np(spec, state, ctx)
                # how hard the epoch program's proposer sum works: its
                # loop runs over this many table rows
                sp_distill.note(
                    proposer_rows=int(facts.proposer_rows),
                    # the active set the boundary ran on: it moves when
                    # blocks carry exits and slashings
                    active_validators=len(spec.get_active_validator_indices(
                        state, current_epoch)),
                    # the registry's length, which deposits move, and the
                    # rows with an eligibility epoch and no activation epoch
                    registry_rows=self._v,
                    pending_activations=int(pending),
                    # what distill's row slope is per
                    pending_rows=len(ctx.prev_atts) + len(ctx.curr_atts),
                    # Crosslink roots the three winner passes hashed one by
                    # one because the context's batch did not hold them
                    crosslink_roots_hashed_singly=(len(ctx.cl_roots)
                                                   - prefilled))
                with telemetry.span("resident.stage.distill.place"):
                    scal, inp = self._stage_epoch_inputs(state, facts)
            with telemetry.span("resident.stage.upload") as sp_up:
                sp_up.fence(scal, inp)  # uploads land in "resident.stage"

        with telemetry.span("resident.device") as sp_dev:
            # ONE layout key for the chained columns: input and output
            # fingerprints must match across boundaries (any in->out or
            # out->next-in placement change is a re-layout event)
            _watchdog.layout_check(f"{self._tkey}.epoch.cols", self.cols)
            dev_cols, dev_scal, dev_report = self._epoch_dispatch(scal, inp)
            _watchdog.layout_check(f"{self._tkey}.epoch.cols", dev_cols)
            # the layout that served: the ladder may have left the mesh
            sp_dev.note(mesh_size=1 if self._mesh is None
                        else self._mesh.size)
            sp_dev.fence(dev_cols.balance)

        with telemetry.span("resident.refresh"):
            self.cols = dev_cols
            self._big_roots = None
            # the boundary dirties every leaf (rewards touch all balances):
            # degenerate to a full forest rebuild — today's cost floor
            self._reg_forest = None
            self._bal_forest = None
            self._active_idx_memo.clear()
            self._exit_queue = None     # the program's ejections move it
            # refresh ONLY the columns host logic reads; slashed never
            # changes in the epoch program, balances stay device-only
            mirrored = ("activation_epoch", "exit_epoch", "effective_balance")
            # what the download will read, on its way to the host before
            # anything else is queued on the device
            for leaf in jax.tree_util.tree_leaves(
                    (dev_scal, dev_report,
                     [getattr(dev_cols, f) for f in mirrored])):
                leaf.copy_to_host_async()
            # the forest rebuild runs on the device under the download and
            # the final updates, which read and write nothing of it: it is
            # dispatched first and waited for last
            with telemetry.span("resident.refresh.forests_dispatch"):
                lanes = self._dispatch_forests()
            dispatched = (lanes, time.perf_counter())
            with telemetry.span("resident.refresh.download"):
                new_scal, report = jax.device_get((dev_scal, dev_report))
                # (the slice drops the sharded layout's inert padding rows)
                for f in mirrored:
                    self.mirrors[f] = np.asarray(
                        jax.device_get(getattr(dev_cols, f)))[:self._capacity]
            with telemetry.span("resident.refresh.final_updates") as sp_fin:
                lanes0 = _FOREST_PAIR_LANES.value
                hashed0 = bulk.HOST_PAIRS_HASHED.value
                _apply_justification(spec, state, new_scal, report,
                                     previous_epoch, current_epoch)
                # write the entries that moved (one an epoch): assigning
                # the vector anew would have its host tree built anew too
                slashed = state.latest_slashed_balances
                new = np.asarray(new_scal.latest_slashed_balances, np.uint64)
                for i in np.nonzero(
                        new != np.asarray(list(slashed), np.uint64))[0]:
                    slashed[int(i)] = int(new[i])
                state.latest_start_shard = int(new_scal.latest_start_shard)
                # the active-index root in it is this core's device build
                # (_install), queued behind the forests: its lanes, and
                # what the host hashed besides (a historical batch every
                # 128th epoch, else nothing)
                spec.final_updates_byte_rooted(state)
                sp_fin.note(
                    index_root_lanes=_FOREST_PAIR_LANES.value - lanes0,
                    host_pairs_hashed=bulk.HOST_PAIRS_HASHED.value - hashed0)
            # the wait, the two roots down, cached: inside this boundary
            self._registry_balances_roots(dispatched)
