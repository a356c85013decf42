"""Differential gate for the device-resident multi-epoch pipeline.

ResidentCore keeps registry/balances on device across slots, blocks, and
epoch boundaries (models/phase0/resident.py). These tests drive the SAME
block sequence through the object-model spec and through ResidentCore and
assert byte-identical outcomes:

  1. multi-epoch drive with attestation-carrying blocks — per-transition
     full-state roots agree, and the serialized states agree after exit();
  2. a registry-mutating block (proposer slashing) takes the fallback
     (exit -> object path -> re-enter) and stays bit-equal;
  3. the resident state-root backend declines foreign states (the object
     model's differential copy must not be rooted from device columns).
"""
from copy import deepcopy
from types import SimpleNamespace

import pytest

from consensus_specs_tpu.crypto import bls
from consensus_specs_tpu.models import phase0
from consensus_specs_tpu.models.phase0.resident import ResidentCore
from consensus_specs_tpu.testing import factories
from consensus_specs_tpu.utils.ssz.impl import hash_tree_root, serialize


@pytest.fixture
def spec():
    s = phase0.get_spec("minimal")
    bls.bls_active = False
    s.clear_caches()
    yield s
    s.clear_caches()


def _attestation_block(spec, ref):
    """A block at ref.slot+delay carrying a fully-participated attestation
    for ref's current slot (built on the object state; both paths apply
    the identical block)."""
    att = factories.new_attestation(spec, ref)
    block = factories.empty_block_next(spec, ref)
    block.slot = ref.slot + spec.MIN_ATTESTATION_INCLUSION_DELAY
    block.body.attestations.append(att)
    return block


def _drive(spec, ref, res, core, n_blocks, mutate=None):
    """Apply n_blocks attestation blocks to both paths, checking the full
    state root after every transition. `mutate(i, block)` can inject
    extra operations into block i."""
    for i in range(n_blocks):
        with core.suspended():
            # the reference path must run against the UNPATCHED spec —
            # otherwise mirror-derived committees/proposers/index-roots
            # would be compared against themselves
            block = _attestation_block(spec, ref)
            if mutate is not None:
                mutate(i, block)
            spec.process_slots(ref, block.slot)
            spec.process_block(ref, block)
        core.state_transition(res, block)
        assert hash_tree_root(ref) == core._state_root(res), \
            f"state root diverged after block {i} (slot {block.slot})"


def test_resident_multi_epoch_bit_equality(spec):
    state = factories.seed_genesis_state(spec, 4 * spec.SLOTS_PER_EPOCH)
    # move off genesis so attestations target a real block history
    factories.advance_slots(spec, state, 2)
    ref, res = deepcopy(state), deepcopy(state)
    core = ResidentCore(spec, res)
    try:
        # > 3 epochs of consecutive attestation-carrying blocks
        _drive(spec, ref, res, core, 3 * spec.SLOTS_PER_EPOCH + 4)
        assert spec.get_current_epoch(ref) >= 3
    finally:
        core.exit()
    assert serialize(ref, spec.BeaconState) == serialize(res, spec.BeaconState)


def test_resident_serves_a_registry_mutating_block(spec):
    """A proposer slashing mid-drive on an object-entered core: served on the
    resident state since PR 36 (no fallback), byte-identical to the object
    model through the boundary that follows."""
    state = factories.seed_genesis_state(spec, 4 * spec.SLOTS_PER_EPOCH)
    factories.advance_slots(spec, state, 2)
    ref, res = deepcopy(state), deepcopy(state)
    core = ResidentCore(spec, res)

    def mutate(i, block):
        if i == spec.SLOTS_PER_EPOCH + 1:   # mid-drive, epoch > 0
            block.body.proposer_slashings.append(
                factories.double_proposal(spec, ref))
    try:
        _drive(spec, ref, res, core, 2 * spec.SLOTS_PER_EPOCH, mutate=mutate)
        # the slashing really happened on both paths
        assert any(v.slashed for v in ref.validator_registry)
    finally:
        core.exit()
    assert serialize(ref, spec.BeaconState) == serialize(res, spec.BeaconState)


def test_a_slashing_and_a_deposit_are_served_and_a_full_core_grows(spec):
    """A registry-mutating block must NOT throw the registry-scale trees
    away: a slashing is served on the same incremental forests (one bucket
    of path lanes). A deposit is served too, with no fallback; on a core
    that was given no capacity its new validator passes the registry's
    rows of storage, and the core re-lays itself out at the next power of
    two, once (V is one here: both trees a level deeper) — roots bit-equal
    to the object model throughout."""
    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.utils.merkle import tree_depth
    fallbacks = telemetry.counter("resident.block.fallbacks", always=True)
    grown = telemetry.counter("resident.registry.capacity_grown", always=True)

    state = factories.seed_genesis_state(spec, 4 * spec.SLOTS_PER_EPOCH)
    factories.advance_slots(spec, state, 2)
    ref, res = deepcopy(state), deepcopy(state)
    core = ResidentCore(spec, res)
    try:
        core._state_root(res)                    # build the forests
        f_reg, f_bal = core._reg_forest, core._bal_forest
        V = len(ref.validator_registry)
        assert f_reg is not None and f_reg.builds == 1 and f_reg.n == V
        assert V & (V - 1) == 0, "seed V must be a power of two for the test"

        # -- slashing: dirties a handful of validators -----------------------
        with core.suspended():
            block = factories.empty_block_next(spec, ref)
            block.body.proposer_slashings.append(
                factories.double_proposal(spec, ref))
            spec.process_slots(ref, block.slot)
            spec.process_block(ref, block)
        core.state_transition(res, block)
        assert core._reg_forest is f_reg and core._bal_forest is f_bal
        assert f_reg.builds == 1                 # updated in place, no rebuild
        # the slashing is served on the resident state: its one dirty
        # registry leaf takes the per-slot path program, one bucket of 32
        # lanes a level whatever a block dirties (nothing is rebuilt)
        assert f_reg.last_pairs_per_level == [32] * f_reg.depth
        assert hash_tree_root(ref) == core._state_root(res)

        # -- deposit: grows V -> V+1 past the storage, a power of two ---------
        before = fallbacks.value, grown.value
        with core.suspended():
            # stage the deposit BEFORE building the block: it plants eth1
            # data into the state, and empty_block seals the parent header
            # with the state root as of build time
            deposit = factories.stage_deposit(
                spec, ref, V, spec.MAX_EFFECTIVE_BALANCE)
            # the planted eth1 data is pre-block chain context BOTH paths
            # need (snapshot before ref's transition can vote on it)
            res.latest_eth1_data = deepcopy(ref.latest_eth1_data)
            block = factories.empty_block_next(spec, ref)
            block.body.deposits.append(deposit)
            spec.process_slots(ref, block.slot)
            spec.process_block(ref, block)
        core.state_transition(res, block)
        assert (fallbacks.value, grown.value) == (before[0], before[1] + 1)
        assert hash_tree_root(ref) == core._state_root(res)
        assert core._v == V + 1 and core._capacity == 2 * V
        assert core._reg_forest is not f_reg and core._reg_forest.n == V + 1
        assert core._reg_forest.depth == tree_depth(V + 1) > tree_depth(V)
        # the storage grew everywhere, inert beyond the new row
        assert len(core._pk_np) == int(core.cols.balance.shape[0]) == 2 * V
        assert not core._pk_np[V + 1:].any()
    finally:
        core.exit()
    assert serialize(ref, spec.BeaconState) == serialize(res, spec.BeaconState)


def test_resident_root_backend_declines_foreign_state(spec):
    state = factories.seed_genesis_state(spec, 2 * spec.SLOTS_PER_EPOCH)
    res = deepcopy(state)
    other = deepcopy(state)
    other.slot += 123    # diverge the foreign state
    core = ResidentCore(spec, res)
    try:
        # entry parity: resident root == recursive oracle root
        assert core._state_root(res) == hash_tree_root(res)
        # the spec-level hook must route the foreign state to the oracle,
        # not to the resident device columns
        assert spec.hash_tree_root(other) == hash_tree_root(other)
    finally:
        core.exit()


def test_overrides_delegate_for_foreign_state(spec):
    """The _install overrides mirror the _state_root guard: a state other
    than the resident one (fork choice's justified state, a differential
    copy) must be answered from ITS registry via the saved object path,
    not from the resident device mirrors."""
    from consensus_specs_tpu.models.phase0.fork_choice import Store, get_head

    state = factories.seed_genesis_state(spec, 8)
    res = deepcopy(state)
    justified = deepcopy(state)
    # diverge the justified state's registry: validators 0-3 exited, and a
    # distinct effective balance on validator 4
    epoch = spec.slot_to_epoch(justified.slot)
    for i in range(4):
        justified.validator_registry[i].exit_epoch = epoch
    justified.validator_registry[4].effective_balance -= \
        spec.EFFECTIVE_BALANCE_INCREMENT

    core = ResidentCore(spec, res)
    try:
        with core.suspended():
            want_active = spec.get_active_validator_indices(justified, epoch)
            want_total = spec.get_total_balance(justified, want_active)
            want_eb = spec.effective_balance_of(justified, 4)
        # overrides installed: foreign state -> object-path answers
        assert list(spec.get_active_validator_indices(justified, epoch)) \
            == list(want_active) == [4, 5, 6, 7]
        assert spec.get_total_balance(justified, want_active) == want_total
        assert spec.effective_balance_of(justified, 4) == want_eb
        # ... while the resident state still answers from the mirrors
        assert list(spec.get_active_validator_indices(res, epoch)) \
            == list(range(8))

        # end to end through fork choice's justified-state path: votes of
        # the justified-exited validators 0-3 must not count
        store = Store()
        root_g, root_a, root_b = (bytes([9]) + bytes(31),
                                  bytes([1]) + bytes(31),
                                  bytes([2]) + bytes(31))
        store.add_block(root_g, SimpleNamespace(slot=0), None)
        store.add_block(root_a, SimpleNamespace(slot=1), root_g)
        store.add_block(root_b, SimpleNamespace(slot=1), root_g)
        store.on_attestation([0, 1, 2, 3], root_a, slot=1)   # exited
        store.on_attestation([5, 6, 7], root_b, slot=1)      # active
        assert get_head(spec, store, justified) == root_b
    finally:
        core.exit()


def test_light_core_serves_a_deposit_and_an_exit(spec):
    """A checkpoint-resumed (light) core takes blocks whose operations are
    anything but transfers (tests/test_resident_blocks.py,
    tests/test_resident_operations.py, tests/test_resident_deposits.py). A
    deposit is served on the columns, into the room the entry was given:
    the state it leaves is the object model's. An exit is served: at
    genesis the spec's own check rejects it (PERSISTENT_COMMITTEE_PERIOD),
    after process_slots has run."""
    state = factories.seed_genesis_state(spec, 2 * spec.SLOTS_PER_EPOCH)
    V = len(state.validator_registry)
    deposit = factories.stage_deposit(spec, state, V, spec.MAX_EFFECTIVE_BALANCE)
    data = serialize(state, spec.BeaconState)
    core = ResidentCore.from_checkpoint(spec, data, capacity=V + 8)
    try:
        with core.suspended():
            block = factories.empty_block_next(spec, state)
            block.body.deposits.append(deposit)
            spec.state_transition(state, block)
        core.state_transition(core.state, block)
        assert core._v == V + 1 and core._capacity == V + 8
        assert core.checkpoint_bytes() == serialize(state, spec.BeaconState)
        assert core._state_root(core.state) == hash_tree_root(state)
        data = core.checkpoint_bytes()
        with core.suspended():
            block = factories.empty_block_next(spec, state)
        block.body.voluntary_exits.append(spec.VoluntaryExit())
        with pytest.raises(AssertionError):
            core.state_transition(core.state, block)
        assert int(core.state.slot) == int(state.slot) + 1
        assert (core.mirrors["exit_epoch"]
                == int(spec.FAR_FUTURE_EPOCH)).all()
    finally:
        core._uninstall()


def test_checkpoint_resume_light_residency(spec):
    """Serialized state -> light residency (no Validator objects) -> drive
    an epoch boundary -> checkpoint_bytes == the object model's serialized
    post-state. The production resume path end to end."""
    state = factories.seed_genesis_state(spec, 4 * spec.SLOTS_PER_EPOCH)
    factories.advance_slots(spec, state, 2)
    data = serialize(state, spec.BeaconState)

    from consensus_specs_tpu.models.phase0.resident import light_state_from_bytes
    core = ResidentCore.from_checkpoint(spec, data)
    try:
        # entry round trip: no transition -> byte-identical checkpoint
        assert core.checkpoint_bytes() == data
        # entry root parity against the object-model recursive oracle
        assert core._state_root(core.state) == hash_tree_root(state)

        # drive both paths to the first slot of the next epoch
        ref = deepcopy(state)
        target = spec.get_epoch_start_slot(spec.get_current_epoch(ref) + 1)
        with core.suspended():
            spec.process_slots(ref, target)
        core.process_slots(core.state, target)
        assert core.checkpoint_bytes() == serialize(ref, spec.BeaconState)
        # light residency has no objects to exit into
        with pytest.raises(NotImplementedError):
            core.exit()
    finally:
        core._uninstall()

    # light_state_from_bytes really leaves the registry unmaterialized
    light = light_state_from_bytes(spec, data)
    assert len(light.validator_registry) == 0 and len(light.balances) == 0
    assert int(light.slot) == int(state.slot)


@pytest.fixture
def serving_mesh():
    import jax
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    if len(jax.devices()) < 8:
        pytest.skip(f"needs 8 devices, have {len(jax.devices())}")
    return ServingMesh.create(8)


def test_resident_sharded_serving_loop(spec, serving_mesh):
    """The whole serving loop under the validator-axis NamedSharding:
    multi-slot chained steps across epoch boundaries with the columns and
    forests never leaving the mesh layout, every per-transition root
    bit-equal to the object model (which the single-device suite above
    already gates bit-equal to the single-device core)."""
    mesh = serving_mesh
    state = factories.seed_genesis_state(spec, 4 * spec.SLOTS_PER_EPOCH)
    factories.advance_slots(spec, state, 2)
    ref, res = deepcopy(state), deepcopy(state)
    core = ResidentCore(spec, res, mesh=mesh)
    try:
        assert core.cols.balance.sharding.is_equivalent_to(mesh.shard_v, 1)
        _drive(spec, ref, res, core, 2 * spec.SLOTS_PER_EPOCH + 2)
        assert spec.get_current_epoch(ref) >= 2
        # chained boundaries kept the layout: columns still sharded, the
        # forests' sharded levels still on their shards, cap replicated
        assert core.cols.balance.sharding.is_equivalent_to(mesh.shard_v, 1)
        assert core._reg_forest.levels[0].sharding.is_equivalent_to(
            mesh.shard_v, 2)
        assert core._reg_forest.levels[-1].sharding.is_equivalent_to(
            mesh.replicated, 2)
    finally:
        core.exit()
    assert serialize(ref, spec.BeaconState) == serialize(res, spec.BeaconState)


def test_resident_sharded_slashing_and_deposit_growth(spec, serving_mesh):
    """Under sharding, a slashing is served where the columns lie (same
    forests, the dirty rows and paths alone, every column and level on the
    placement it had), and a deposit is served too: on a core with no room
    it re-lays the padded columns and the forests out at the next power of
    two (V 32 -> 33: columns and forest capacity 32 -> 64 rows, sharded as
    they were), all bit-equal to the object model."""
    from consensus_specs_tpu.utils.merkle import tree_depth

    mesh = serving_mesh
    state = factories.seed_genesis_state(spec, 4 * spec.SLOTS_PER_EPOCH)
    factories.advance_slots(spec, state, 2)
    ref, res = deepcopy(state), deepcopy(state)
    core = ResidentCore(spec, res, mesh=mesh)
    try:
        core._state_root(res)
        f_reg, f_bal = core._reg_forest, core._bal_forest
        V = len(ref.validator_registry)
        assert V % mesh.size == 0, "seed V must already tile the mesh"
        assert f_reg.n == V and f_reg.builds == 1
        reg_placed = [level.sharding for level in f_reg.levels]

        # -- slashing: incremental re-entry, forests survive -----------------
        with core.suspended():
            block = factories.empty_block_next(spec, ref)
            block.body.proposer_slashings.append(
                factories.double_proposal(spec, ref))
            spec.process_slots(ref, block.slot)
            spec.process_block(ref, block)
        core.state_transition(res, block)
        assert core._reg_forest is f_reg and core._bal_forest is f_bal
        assert f_reg.builds == 1
        # served on the sharded columns: one bucket of 32 lanes a level
        assert f_reg.last_pairs_per_level == [32] * f_reg.depth
        assert hash_tree_root(ref) == core._state_root(res)
        for column in (core.cols.balance, core.cols.slashed,
                       core.cols.exit_epoch, core.cols.withdrawable_epoch):
            assert column.sharding.is_equivalent_to(mesh.shard_v, 1)
        for level, was in zip(f_reg.levels, reg_placed):
            assert level.sharding.is_equivalent_to(was, level.ndim)

        # -- deposit: V -> V+1 crosses padding AND capacity ------------------
        with core.suspended():
            deposit = factories.stage_deposit(
                spec, ref, V, spec.MAX_EFFECTIVE_BALANCE)
            res.latest_eth1_data = deepcopy(ref.latest_eth1_data)
            block = factories.empty_block_next(spec, ref)
            block.body.deposits.append(deposit)
            spec.process_slots(ref, block.slot)
            spec.process_block(ref, block)
        core.state_transition(res, block)
        assert core._v == V + 1 and core._capacity == 2 * V
        assert hash_tree_root(ref) == core._state_root(res)
        # columns at the new capacity (a mesh multiple) with inert rows
        assert int(core.cols.balance.shape[0]) == mesh.pad_rows(2 * V)
        assert core.cols.balance.sharding.is_equivalent_to(mesh.shard_v, 1)
        assert core.pk_dev.sharding.is_equivalent_to(mesh.shard_v, 2)
        assert core._reg_forest is not f_reg and core._reg_forest.n == V + 1
        assert core._reg_forest.depth == tree_depth(V + 1) > tree_depth(V)
        assert core._reg_forest.levels[0].sharding.is_equivalent_to(
            mesh.shard_v, 2)
        assert len(core._pk_np) == 2 * V

        # -- and the next epoch boundary still runs sharded ------------------
        target = spec.get_epoch_start_slot(spec.get_current_epoch(ref) + 1)
        with core.suspended():
            spec.process_slots(ref, target)
        core.process_slots(res, target)
        assert hash_tree_root(ref) == core._state_root(res)
        assert core.cols.balance.sharding.is_equivalent_to(mesh.shard_v, 1)
    finally:
        core.exit()
    assert serialize(ref, spec.BeaconState) == serialize(res, spec.BeaconState)


def test_resident_serving_mesh_env_knob(spec, serving_mesh, monkeypatch):
    """CSTPU_SERVING_MESH turns the sharded serving path on without code
    changes (the production entry); unset/0 keeps single-device."""
    state = factories.seed_genesis_state(spec, 2 * spec.SLOTS_PER_EPOCH)
    monkeypatch.setenv("CSTPU_SERVING_MESH", "8")
    core = ResidentCore(spec, deepcopy(state))
    try:
        assert core._mesh is not None and core._mesh.size == 8
        assert core.cols.balance.sharding.is_equivalent_to(
            core._mesh.shard_v, 1)
        assert core._state_root(core.state) == hash_tree_root(state)
    finally:
        core.exit()
    monkeypatch.setenv("CSTPU_SERVING_MESH", "0")
    core = ResidentCore(spec, deepcopy(state))
    try:
        assert core._mesh is None
    finally:
        core.exit()


def test_from_checkpoint_rejects_phase1_hooks(spec):
    """A phase-1 spec (epoch insert hooks) must refuse BOTH entry points —
    the staged path (process_epoch_soa_staged) owns that configuration."""
    from consensus_specs_tpu.models import phase1
    p1 = phase1.get_spec("minimal")
    state = factories.seed_genesis_state(p1, 8)
    data = serialize(state, p1.BeaconState)
    with pytest.raises(NotImplementedError):
        ResidentCore.from_checkpoint(p1, data)
    with pytest.raises(NotImplementedError):
        ResidentCore(p1, state)


# -- the span tree of a slot, a boundary, a checkpoint and a resume ------------

SLOT_ROOT_GROUPS = ("forests", "attestations", "history", "small", "merkleize")


@pytest.fixture
def spans():
    """Telemetry pinned on and emptied; returns a reader of the ring."""
    from consensus_specs_tpu import telemetry
    telemetry.set_enabled(True)
    telemetry.reset()
    yield telemetry.ring
    telemetry.set_enabled(None)


def _children(records, parent):
    return [r for r in records if r["parent_id"] == parent["id"]]


def test_one_epoch_leaves_one_span_tree_a_slot(spec, spans):
    """Every slot is one root span (`req` = the slot) over exactly one slot
    root, whose five groups cover it and never exceed it; the boundary slot
    holds the slot root, stage, device and refresh; the slot root notes the
    host Merkleizer's work, equal to the counters' delta less the header's
    signing root."""
    from consensus_specs_tpu import telemetry
    spe = spec.SLOTS_PER_EPOCH
    state = factories.seed_genesis_state(spec, 4 * spe)
    core = ResidentCore(spec, state, mesh=None)
    try:
        hashed = telemetry.counter("merkle.host.pairs_hashed")
        zeroed = telemetry.counter("merkle.host.pairs_zero_filled")
        hashed0, zeroed0 = hashed.value, zeroed.value
        core.process_slots(state, spe - 1)      # slot roots and nothing else
        hashed1, zeroed1 = hashed.value, zeroed.value
        core.process_slots(state, spe)          # the boundary slot
        records = spans()
    finally:
        core.exit()
    roots = [r for r in records if r["parent_id"] == 0]
    assert [r["name"] for r in roots] == \
        ["resident.slot"] * (spe - 1) + ["resident.boundary_slot"]
    assert [r["req"] for r in roots] == list(range(spe))
    assert all(r["req"] == root["req"] for root in roots
               for r in _descendants(records, root))
    noted = {"pairs_hashed": 0, "pairs_zero_filled": 0}
    for root in roots:
        kids = _children(records, root)
        slot_roots = [k for k in kids if k["name"] == "resident.slot_root"]
        assert len(slot_roots) == 1
        groups = _children(records, slot_roots[0])
        assert sorted(g["name"] for g in groups) == sorted(
            f"resident.slot_root.{g}" for g in SLOT_ROOT_GROUPS)
        assert sum(g["dur"] for g in groups) <= slot_roots[0]["dur"]
        assert slot_roots[0]["args"]["pairs_hashed"] > 0
        if root["name"] == "resident.slot":
            assert len(kids) == 1
            for key in noted:
                noted[key] += slot_roots[0]["args"][key]
    # all a slot hashes outside its root is the header's signing root for
    # `latest_block_roots`: four fields, three pairs, through bulk since PR 37
    assert noted == {"pairs_hashed": hashed1 - hashed0 - 3 * (spe - 1),
                     "pairs_zero_filled": zeroed1 - zeroed0}

    boundary = _children(records, roots[-1])
    assert [k["name"] for k in boundary] == [
        "resident.slot_root", "resident.stage", "resident.device",
        "resident.refresh"]
    by_name = {k["name"]: k for k in boundary}
    assert [k["name"] for k in _children(records, by_name["resident.stage"])] \
        == ["resident.stage.distill", "resident.stage.upload"]
    # distill from inside: the builders' own spans, then the placement
    distill = _children(records, by_name["resident.stage"])[0]
    parts = _children(records, distill)
    assert [k["name"] for k in parts] == [
        "distill.context", "distill.crosslinks", "distill.inputs",
        "resident.stage.distill.place"]
    assert [k["name"] for k in _children(records, parts[0])] == [
        "distill.layouts", "distill.participants", "distill.crosslink_roots",
        "distill.winner_groups"]
    for parent in (distill, parts[0]):
        kids = _children(records, parent)
        assert sum(k["dur"] for k in kids) <= parent["dur"]
        assert all(parent["ts"] <= k["ts"] and k["ts"] + k["dur"]
                   <= parent["ts"] + parent["dur"] for k in kids)
    assert [k["name"] for k in _children(records, by_name["resident.refresh"])] \
        == ["resident.refresh.forests_dispatch", "resident.refresh.download",
            "resident.refresh.final_updates", "resident.forests"]
    assert sum(k["dur"] for k in boundary) <= roots[-1]["dur"]
    # the forests are built twice in the epoch: at entry, under the first
    # slot's `slot_root.forests`, build and wait in one place, and in the
    # boundary's refresh, dispatched first and waited for last: the same
    # build (the lanes are counted at the dispatch and noted at the wait),
    # which ran under the download and the final updates
    forests = [r for r in records if r["name"] == "resident.forests"]
    assert [f["parent"] for f in forests] == ["resident.slot_root.forests",
                                              "resident.refresh"]
    assert all(set(f["args"]) == {"pair_lanes", "ahead_ms"} for f in forests)
    first, waited = (f["args"] for f in forests)
    assert first["pair_lanes"] == waited["pair_lanes"] > 0
    assert first["ahead_ms"] == 0 < waited["ahead_ms"]
    dispatch, download, final_updates, _ = _children(
        records, by_name["resident.refresh"])
    assert dispatch["args"] is None             # it notes and fences nothing
    ahead_s = waited["ahead_ms"] / 1e3
    assert download["dur"] + final_updates["dur"] <= ahead_s \
        <= by_name["resident.refresh"]["dur"] - dispatch["dur"]


LAYOUTS = ["one-device", "serving-mesh"]


def _layout(request, layout):
    return None if layout == "one-device" \
        else request.getfixturevalue("serving_mesh")


def _object_forest_roots(spec, ref) -> tuple:
    from consensus_specs_tpu.utils.ssz.typing import List, uint64
    return (hash_tree_root(ref.validator_registry, List[spec.Validator]),
            hash_tree_root(ref.balances, List[uint64]))


def _forest_roots_from_scratch(core) -> tuple:
    """A root request with no forest standing: both built anew from the
    columns the core holds, dispatch and wait in one place."""
    core._reg_forest = core._bal_forest = core._big_roots = None
    return tuple(bytes(r) for r in core._registry_balances_roots())


@pytest.mark.parametrize("layout", LAYOUTS)
def test_boundary_forest_roots_equal_a_build_from_scratch(spec, spans, request,
                                                          layout):
    """The forests a boundary dispatched ahead of its download are the
    forests of the columns the epoch program returned: the two roots the
    boundary's call has fetched by the time it returns equal a build from
    scratch on the same columns and the object model's roots, two
    boundaries running."""
    spe = spec.SLOTS_PER_EPOCH
    state = factories.seed_genesis_state(spec, 4 * spe)
    ref, res = deepcopy(state), deepcopy(state)
    core = ResidentCore(spec, res, mesh=_layout(request, layout))
    try:
        for boundary in (1, 2):
            with core.suspended():
                spec.process_slots(ref, boundary * spe)
            core.process_slots(res, boundary * spe)
            # fetched inside the boundary's call: nothing is left to wait
            # for, no later slot pays for it
            assert core._big_roots is not None
            got = tuple(bytes(r) for r in core._big_roots)
            assert got == _object_forest_roots(spec, ref)
            assert got == _forest_roots_from_scratch(core)
            assert hash_tree_root(ref) == core._state_root(res)
        records = spans()
    finally:
        core.exit()
    assert serialize(ref, spec.BeaconState) == serialize(res, spec.BeaconState)
    waits = [r["args"] for r in records if r["name"] == "resident.forests"
             and r["parent"] == "resident.refresh"]
    assert len(waits) == 2
    assert all(w["pair_lanes"] > 0 and w["ahead_ms"] > 0 for w in waits)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_download_that_raises_leaves_the_next_root_correct(
        spec, spans, request, monkeypatch, layout):
    """A transfer that fails between the forests' dispatch and their wait:
    the forests stand on the new columns, and the next request for their
    roots fetches them as they are."""
    import jax
    from consensus_specs_tpu.telemetry import core as telemetry_core
    spe = spec.SLOTS_PER_EPOCH
    state = factories.seed_genesis_state(spec, 4 * spe)
    ref, res = deepcopy(state), deepcopy(state)
    core = ResidentCore(spec, res, mesh=_layout(request, layout))
    device_get, raised = jax.device_get, []

    def failing_once(tree):
        stack = telemetry_core._stack()
        if not raised and stack \
                and stack[-1].name == "resident.refresh.download":
            raised.append(True)
            raise RuntimeError("transfer failed")
        return device_get(tree)

    try:
        with core.suspended():
            spec.process_slots(ref, spe)
        monkeypatch.setattr(jax, "device_get", failing_once)
        with pytest.raises(RuntimeError, match="transfer failed"):
            core.process_slots(res, spe)
        assert raised and telemetry_core._stack() == []
        # dispatched, never waited for: nothing cached, both stand
        assert core._big_roots is None
        assert core._reg_forest is not None and core._bal_forest is not None
        got = tuple(bytes(r) for r in core._registry_balances_roots())
        assert got == _object_forest_roots(spec, ref)
        assert got == _forest_roots_from_scratch(core)
    finally:
        core._uninstall()


def test_slot_root_rehashes_only_what_was_written(spec, spans):
    """Two epochs and more of attestation-carrying blocks on a full core
    (rotation included; one block slashes a proposer, so it takes the
    fallback and writes a slashed balance through the spec's own code):
    every slot's recorded root is the object model's, and between
    boundaries a root rebuilds no host tree and re-hashes exactly the
    leaves written since the last one: the slot's state root and block
    root, and what a block wrote (a randao mix, an attestation)."""
    spe = spec.SLOTS_PER_EPOCH
    state = factories.seed_genesis_state(spec, 4 * spe)
    factories.advance_slots(spec, state, 2)
    ref, res = deepcopy(state), deepcopy(state)
    core = ResidentCore(spec, res, mesh=None)
    block_leaves = {}
    try:
        for i in range(2 * spe + 3):
            slashes = i == spe + 1
            with core.suspended():
                block = _attestation_block(spec, ref)
                if slashes:
                    block.body.proposer_slashings.append(
                        factories.double_proposal(spec, ref))
                spec.process_slots(ref, block.slot)
                spec.process_block(ref, block)
            core.state_transition(res, block)
            block_leaves[int(block.slot)] = 2 + slashes
            assert list(res.latest_state_roots) == list(ref.latest_state_roots)
        assert hash_tree_root(ref) == core._state_root(res)
        records = spans()
    finally:
        core.exit()
    assert serialize(ref, spec.BeaconState) == serialize(res, spec.BeaconState)
    assert any(v.slashed for v in ref.validator_registry)
    noted = [(r["req"], r["args"]) for r in records
             if r["name"] == "resident.slot_root"]
    assert len(noted) == int(ref.slot) - int(state.slot) > 2 * spe
    assert all(set(args) == {"pairs_hashed", "pairs_zero_filled",
                             "leaves_updated", "trees_rebuilt",
                             "plan_elements"}
               for _, args in noted)
    assert noted[0][1]["trees_rebuilt"] == 10       # the core's first root
    for slot, args in noted[1:]:
        if slot % spe == 0:
            # the boundary assigned previous_crosslinks and started a new
            # current attestation list; the old one's tree went to previous
            assert args["trees_rebuilt"] == 2
            continue
        assert args["trees_rebuilt"] == 0, slot
        assert args["leaves_updated"] == 2 + block_leaves.get(slot, 0), slot
        # through a root plan: the fork, the header and the eth1 data, and
        # the attestation a block appended
        assert args["plan_elements"] == 3 + (slot in block_leaves), slot


def test_checkpoint_of_a_tracked_state_is_the_plain_one(spec):
    """Once a root has been taken the state's vectors hold tracked lists;
    the checkpoint serialises them as the lists they are."""
    from consensus_specs_tpu.utils.ssz.host_tree import TrackedList
    state = factories.seed_genesis_state(spec, 4 * spec.SLOTS_PER_EPOCH)
    factories.advance_slots(spec, state, 2)
    ref = deepcopy(state)
    core = ResidentCore.from_checkpoint(
        spec, serialize(state, spec.BeaconState), mesh=None)
    try:
        with core.suspended():
            spec.process_slots(ref, ref.slot + 3)
        core.process_slots(core.state, core.state.slot + 3)
        assert type(core.state.latest_state_roots.items) is TrackedList
        assert type(core.state.current_crosslinks.items) is TrackedList
        assert core.checkpoint_bytes() == serialize(ref, spec.BeaconState)
    finally:
        core._uninstall()


def _descendants(records, root):
    out, frontier = [], [root]
    while frontier:
        kids = [r for p in frontier for r in _children(records, p)]
        out += kids
        frontier = kids
    return out


def test_checkpoint_round_trip_leaves_its_two_span_trees(spec, spans):
    state = factories.seed_genesis_state(spec, 4 * spec.SLOTS_PER_EPOCH)
    core = ResidentCore.from_checkpoint(
        spec, serialize(state, spec.BeaconState), mesh=None)
    try:
        data = core.checkpoint_bytes()
        root = core._state_root(core.state)     # a root without a slot
    finally:
        core._uninstall()
    assert data == serialize(state, spec.BeaconState)
    assert root == hash_tree_root(state)
    records = spans()
    roots = [r for r in records if r["parent_id"] == 0]
    assert [r["name"] for r in roots] == [
        "resident.restore", "resident.checkpoint_write",
        *(f"resident.slot_root.{g}" for g in SLOT_ROOT_GROUPS)]
    assert all(r["req"] is None for r in records)
    restore, write = roots[:2]
    assert [k["name"] for k in _children(records, restore)] == [
        "resident.restore.decode", "resident.restore.upload"]
    assert [k["name"] for k in _children(records, write)] == [
        "resident.checkpoint_write.download",
        "resident.checkpoint_write.assemble"]
    for tree in (restore, write):
        assert sum(k["dur"] for k in _children(records, tree)) <= tree["dur"]
    # the resumed core's first root request builds the forests
    (forests,) = [r for r in records if r["name"] == "resident.forests"]
    assert forests["parent"] == "resident.slot_root.forests"
    # in one place, with nothing to run under
    assert forests["args"]["pair_lanes"] > 0
    assert forests["args"]["ahead_ms"] == 0


def test_corrupt_checkpoint_closes_its_spans(spec, spans):
    from consensus_specs_tpu.resilience.errors import CheckpointCorrupt
    state = factories.seed_genesis_state(spec, 8)
    data = serialize(state, spec.BeaconState)
    with pytest.raises(CheckpointCorrupt):
        ResidentCore.from_checkpoint(spec, data[:len(data) // 2], mesh=None)
    assert [r["name"] for r in spans() if r["parent_id"] == 0] \
        == ["resident.restore"]
    from consensus_specs_tpu.telemetry import core as telemetry_core
    assert telemetry_core._stack() == []
