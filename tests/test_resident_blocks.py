"""The block path of the resident core: a checkpoint-resumed (light) core,
which holds no Validator objects, takes a chain of attestation-full blocks.

The differential chain against the unpatched object model is
tests/test_resident_block_chain.py (a file of its own, so that the workers
share the two). Here: `state_transition` is `process_slots` then
`process_block`, a block the spec rejects is rejected at the spec's place
(an unsound exit or slashing with columns, mirrors and forests as they
stood), a block with a deposit or a transfer is refused before anything is
written, the registry view answers an object state as its list does, the array form of the
attestation family equals the spec's bit-by-bit words, and a block leaves
its span tree.
"""
import sys
import traceback
from copy import deepcopy
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import spoiled_blocks  # noqa: E402
from benchmark.block_generator import BlockGenerator, eighths  # noqa: E402
from consensus_specs_tpu import telemetry  # noqa: E402
from consensus_specs_tpu.crypto import bls  # noqa: E402
from consensus_specs_tpu.models import phase0  # noqa: E402
from consensus_specs_tpu.models.phase0 import block as block_mod  # noqa: E402
from consensus_specs_tpu.models.phase0 import helpers  # noqa: E402
from consensus_specs_tpu.models.phase0.resident import ResidentCore  # noqa: E402
from consensus_specs_tpu.testing import factories  # noqa: E402
from consensus_specs_tpu.utils.ssz.impl import hash_tree_root, serialize  # noqa: E402

SEED = 2**31 + 99


@pytest.fixture
def minimal():
    bls.bls_active = False
    spec = phase0.get_spec("minimal")
    spec.clear_caches()
    state = factories.seed_genesis_state(spec, 64)
    factories.advance_slots(spec, state, 2 * int(spec.SLOTS_PER_EPOCH) + 2)
    yield spec, state, serialize(state, spec.BeaconState)
    spec.clear_caches()


def test_state_transition_is_process_slots_then_process_block(minimal):
    spec, state, data = minimal
    one = ResidentCore.from_checkpoint(spec, data, mesh=None)
    generator = BlockGenerator(spec, SEED, aggregates=8)
    blocks = []
    try:
        for _ in range(int(spec.SLOTS_PER_EPOCH) + 2):
            one.process_slots(one.state, int(one.state.slot) + 1)
            blocks.append(generator.block(one.state))
            one.process_block(one.state, blocks[-1])
        by_parts = one.checkpoint_bytes()
    finally:
        one._uninstall()
    two = ResidentCore.from_checkpoint(spec, data, mesh=None)
    try:
        for block in blocks:
            assert two.state_transition(two.state, block) is two.state
        assert two.checkpoint_bytes() == by_parts
    finally:
        two._uninstall()
    # and an object-entered core takes the same entry points
    three = ResidentCore(spec, deepcopy(state), mesh=None)
    try:
        for block in blocks:
            three.state_transition(three.state, block)
        assert three.checkpoint_bytes() == by_parts
    finally:
        three._uninstall()


# -- a block the spec rejects is rejected, at the spec's place -------------------

def _first(block):
    return block.body.attestations[0]


def _flip(root, at=0):
    out = bytearray(bytes(root))
    out[at] ^= 1
    return bytes(out)


def _wrong_slot(spec, state, block):
    block.slot += 1


def _wrong_parent_root(spec, state, block):
    block.parent_root = _flip(block.parent_root)


def _too_many_attestations(spec, state, block):
    block.body.attestations.extend(
        deepcopy(_first(block)) for _ in range(int(spec.MAX_ATTESTATIONS)))


def _included_too_early(spec, state, block):
    # the committee of the slot just before the block's
    early = BlockGenerator(spec, SEED, 8).attestations(state, int(state.slot) - 1)
    block.body.attestations[0] = early[0]


def _older_than_an_epoch(spec, state, block):
    old = BlockGenerator(spec, SEED, 8).attestations(
        state, int(state.slot) - int(spec.SLOTS_PER_EPOCH) - 1)
    block.body.attestations[0] = old[0]


def _wrong_source_epoch(spec, state, block):
    _first(block).data.source_epoch += 1


def _wrong_source_root(spec, state, block):
    _first(block).data.source_root = _flip(_first(block).data.source_root)


def _wrong_crosslink_start(spec, state, block):
    _first(block).data.crosslink.start_epoch += 1


def _wrong_crosslink_end(spec, state, block):
    _first(block).data.crosslink.end_epoch += 1


def _wrong_crosslink_parent_root(spec, state, block):
    link = _first(block).data.crosslink
    link.parent_root = _flip(link.parent_root, 7)


def _crosslink_data_root_set(spec, state, block):
    _first(block).data.crosslink.data_root = b"\x01" * 32


def _bitfield_a_byte_too_long(spec, state, block):
    _first(block).aggregation_bitfield += b"\x00"


def _bit_past_the_committees_end(spec, state, block):
    # a committee of 12: the last byte's upper four bits are padding
    att = _first(block)
    assert len(spec.get_crosslink_committee(
        state, att.data.target_epoch, att.data.crosslink.shard)) % 8
    att.aggregation_bitfield = att.aggregation_bitfield[:-1] \
        + bytes([att.aggregation_bitfield[-1] | 0x80])


def _custody_bit_set(spec, state, block):
    _first(block).custody_bitfield = _first(block).aggregation_bitfield


def _custody_bitfield_too_short(spec, state, block):
    _first(block).custody_bitfield = _first(block).custody_bitfield[:-1]


SPOILS = [_wrong_slot, _wrong_parent_root, _too_many_attestations,
          _included_too_early, _older_than_an_epoch, _wrong_source_epoch,
          _wrong_source_root, _wrong_crosslink_start, _wrong_crosslink_end,
          _wrong_crosslink_parent_root, _crosslink_data_root_set,
          _bitfield_a_byte_too_long, _bit_past_the_committees_end,
          _custody_bit_set, _custody_bitfield_too_short]


@pytest.fixture
def odd_committees():
    """Minimal preset with 96 validators: committees of 12, whose bitfields
    end in four padding bits; entry two slots into the third epoch."""
    bls.bls_active = False
    spec = phase0.get_spec("minimal")
    spec.clear_caches()
    state = factories.seed_genesis_state(spec, 96)
    factories.advance_slots(spec, state, 2 * int(spec.SLOTS_PER_EPOCH) + 2)
    yield spec, state, serialize(state, spec.BeaconState)
    spec.clear_caches()


def _where_it_raises(fn):
    """(exception type, function, line) of the innermost frame that raised."""
    try:
        fn()
    except (AssertionError, IndexError) as exc:
        frame = traceback.extract_tb(exc.__traceback__)[-1]
        return type(exc).__name__, frame.name, frame.lineno
    return None


@pytest.mark.parametrize("spoil", SPOILS, ids=lambda f: f.__name__.lstrip("_"))
def test_a_spoiled_block_raises_on_the_light_core_where_the_object_model_raises(
        odd_committees, spoil):
    spec, state, data = odd_committees
    core = ResidentCore.from_checkpoint(spec, data, mesh=None)
    res = core.state
    try:
        core.process_slots(res, int(res.slot) + 1)
        block = BlockGenerator(spec, SEED, 8).block(res)
        assert len(block.body.attestations) >= 2
        spoil(spec, res, block)
        with core.suspended():
            ref = _advanced(spec, state)
            # before the spoil the block is sound: the spoil is what fails
            spec.process_block(_advanced(spec, state),
                               BlockGenerator(spec, SEED, 8).block(ref))
            want = _where_it_raises(lambda: spec.process_block(ref, block))
        assert want is not None, "the object model takes the spoiled block"
        assert _where_it_raises(lambda: core.process_block(res, block)) == want
    finally:
        core._uninstall()


def _advanced(spec, state):
    fresh = deepcopy(state)
    spec.process_slots(fresh, int(fresh.slot) + 1)
    return fresh


@pytest.mark.parametrize("position", ["first", "middle", "last"])
@pytest.mark.parametrize("spoil", spoiled_blocks.SPOILS, ids=lambda f: f.__name__)
def test_a_spoiled_family_falls_to_the_loop_and_leaves_the_core_as_it_was(
        odd_committees, spoil, position):
    """The sync cell's four spoils, wherever in the family the spoiled
    attestation stands: the pass writes nothing, the loop raises what the
    object model raises where it raises it, the always-on counter counts
    the family, no block leaves the served path, the registry's columns,
    mirrors and forests stand as they stood, and the slot's sound block is
    then taken by the pass."""
    spec, state, data = odd_committees
    loops = telemetry.counter("resident.block.attestations.sequential", always=True)
    fallbacks = telemetry.counter("resident.block.fallbacks", always=True)
    core = ResidentCore.from_checkpoint(spec, data, mesh=None)
    res = core.state
    try:
        core.process_slots(res, int(res.slot) + 1)
        generator = BlockGenerator(spec, SEED, 8)
        n = len(generator.block(res).body.attestations)
        block = spoil(spec, generator, res,
                      {"first": 0, "middle": n // 2, "last": n - 1}[position])
        with core.suspended():
            want = _where_it_raises(
                lambda: spec.process_block(_advanced(spec, state), block))
        assert want is not None, "the object model takes the spoiled block"
        was, kept = _served_state(core), spoiled_blocks.keep(spec, res)
        before = loops.value, fallbacks.value
        assert _where_it_raises(lambda: core.process_block(res, block)) == want
        assert (loops.value, fallbacks.value) == (before[0] + 1, before[1])
        assert _same_served_state(was, _served_state(core))
        spoiled_blocks.put_back(res, *kept)
        core.process_block(res, generator.block(res))
        assert loops.value == before[0] + 1
    finally:
        core._uninstall()


# -- what the light core refuses, and what it serves ------------------------------

def test_a_block_with_a_transfer_is_refused_before_anything_is_written(minimal):
    spec, _, data = minimal
    core = ResidentCore.from_checkpoint(spec, data, mesh=None)
    try:
        block = spec.BeaconBlock(slot=int(core.state.slot) + 3)
        block.body.transfers.append(spec.Transfer())
        with pytest.raises(NotImplementedError, match="registry_operations") as exc:
            core.state_transition(core.state, block)
        assert "transfers" in str(exc.value)
        assert core.checkpoint_bytes() == data
        with pytest.raises(NotImplementedError, match="registry_operations"):
            core.process_block(core.state, block)
        assert core.checkpoint_bytes() == data
    finally:
        core._uninstall()


def test_a_block_with_a_deposit_is_served_by_the_light_core(minimal):
    """What the light core refused by name until deposits were served: the
    block is taken on the columns and leaves the object model's state."""
    spec, state, _ = minimal
    ref = deepcopy(state)
    V = len(ref.validator_registry)
    deposit = factories.stage_deposit(spec, ref, V, spec.MAX_EFFECTIVE_BALANCE)
    core = ResidentCore.from_checkpoint(
        spec, serialize(ref, spec.BeaconState), mesh=None, capacity=2 * V)
    try:
        block = factories.empty_block_next(spec, ref)
        block.body.deposits.append(deposit)
        with core.suspended():
            spec.state_transition(ref, block)
        core.state_transition(core.state, block)
        assert core._v == V + 1
        assert core.checkpoint_bytes() == serialize(ref, spec.BeaconState)
        assert core._state_root(core.state) == hash_tree_root(ref)
    finally:
        core._uninstall()


def _served_state(core) -> tuple:
    """What a rejected block must leave as it was: the device columns, the
    host mirrors, the kept exit queue and both forests' roots."""
    cols = core._materialize_np_cols()
    return ({f: a.copy() for f, a in cols.items()},
            {f: a.copy() for f, a in core.mirrors.items()},
            None if core._exit_queue is None else list(core._exit_queue),
            tuple(bytes(r) for r in core._registry_balances_roots()))


def _same_served_state(was: tuple, now: tuple) -> bool:
    return (all((was[0][f] == now[0][f]).all() for f in was[0])
            and all((was[1][f] == now[1][f]).all() for f in was[1])
            and was[2:] == now[2:])


@pytest.mark.parametrize("name,operation", [
    ("proposer_slashings", "ProposerSlashing"),
    ("attester_slashings", "AttesterSlashing"),
    ("voluntary_exits", "VoluntaryExit")])
def test_an_unsound_operation_is_rejected_by_the_spec_with_nothing_written(
        minimal, name, operation):
    """The three kinds a light core serves: the sound block of the slot
    with one default-built operation is rejected where the object model
    rejects it (equal headers; no double vote; an exit before
    PERSISTENT_COMMITTEE_PERIOD), not refused by name, and columns,
    mirrors, exit queue and forests stand as they stood."""
    spec, state, data = minimal
    core = ResidentCore.from_checkpoint(spec, data, mesh=None)
    res = core.state
    try:
        core.process_slots(res, int(res.slot) + 1)
        block = BlockGenerator(spec, SEED, 8).block(res)
        getattr(block.body, name).append(getattr(spec, operation)())
        with core.suspended():
            want = _where_it_raises(
                lambda: spec.process_block(_advanced(spec, state), block))
        assert want is not None and want[0] == "AssertionError"
        was = _served_state(core)
        assert _where_it_raises(lambda: core.process_block(res, block)) == want
        assert _same_served_state(was, _served_state(core))
    finally:
        core._uninstall()


# -- the registry view ----------------------------------------------------------

def test_the_view_of_an_object_state_answers_as_its_list_does(minimal):
    spec, state, _ = minimal
    state.validator_registry[5].slashed = True
    view = spec.registry_view(state)
    assert isinstance(view, helpers.ObjectRegistry) and view.state is state
    assert len(view) == len(state.validator_registry) == 64
    assert [view.slashed(i) for i in (4, 5)] == [False, True]
    assert view.pubkey(7) == state.validator_registry[7].pubkey
    assert view.pubkeys([3, 1, 2]) == [state.validator_registry[i].pubkey
                                       for i in (3, 1, 2)]
    with pytest.raises(IndexError):
        view.pubkey(64)


@pytest.mark.parametrize("light", [False, True], ids=["object-entered", "light"])
def test_a_resident_cores_view_answers_from_its_columns(minimal, light):
    spec, state, data = minimal
    state.validator_registry[5].slashed = True
    data = serialize(state, spec.BeaconState)
    core = (ResidentCore.from_checkpoint(spec, data, mesh=None) if light
            else ResidentCore(spec, state, mesh=None))
    try:
        view = spec.registry_view(core.state)
        assert not isinstance(view, helpers.ObjectRegistry)
        assert view.state is core.state and len(view) == 64
        assert len(core.state.validator_registry) == (0 if light else 64)
        obj = helpers.ObjectRegistry(state)
        assert [view.slashed(i) for i in range(64)] \
            == [obj.slashed(i) for i in range(64)]
        assert bytes(view.pubkey(9)) == bytes(obj.pubkey(9))
        assert [bytes(k) for k in view.pubkeys([9, 2, 63])] \
            == [bytes(k) for k in obj.pubkeys([9, 2, 63])]
        with pytest.raises(IndexError):
            view.pubkey(64)
        # another state is never answered from this core's columns
        other = deepcopy(state)
        assert isinstance(spec.registry_view(other), helpers.ObjectRegistry)
        with core.suspended():
            assert isinstance(spec.registry_view(core.state),
                              helpers.ObjectRegistry)
        assert type(spec.registry_view(core.state)) is type(view)
    finally:
        core._uninstall()
    assert spec._registry_views == {}


@pytest.mark.parametrize("family", ["loop", "pass"])
def test_the_proposer_memo_keys_on_the_views_length(minimal, family):
    """On a light core `len(state.validator_registry)` is 0; the memo's key
    takes V from the view, and is dropped when the family returns. The
    loop reads it once an attestation; the pass asks for the proposer once
    and calls no `process_attestation` at all."""
    spec, _, data = minimal
    core = ResidentCore.from_checkpoint(spec, data, mesh=None)
    res = core.state
    try:
        core.process_slots(res, int(res.slot) + 1)
        block = BlockGenerator(spec, SEED, 8).block(res)
        seen = []
        real = spec.process_attestation

        def watching(state, attestation):
            seen.append(state._proposer_memo)
            return real(state, attestation)
        spec.process_attestation = watching
        block_mod.set_attestation_batching(family == "pass")
        try:
            core.process_block(res, block)
        finally:
            block_mod.set_attestation_batching(True)
            spec.process_attestation = real
        proposer = spec.get_beacon_proposer_index(res)
        assert len(seen) == (len(block.body.attestations) if family == "loop" else 0)
        assert all(m == ((int(res.slot), 64), proposer) for m in seen)
        assert [int(p.proposer_index) for p in res.current_epoch_attestations[
            -len(block.body.attestations):]] == [proposer] * len(block.body.attestations)
        assert res._proposer_memo is None
    finally:
        core._uninstall()


# -- the array form against the spec's own words --------------------------------

def _spec_attesting_indices(spec, state, data, bitfield):
    """get_attesting_indices as v0.6 writes it, bit by bit."""
    committee = spec.get_crosslink_committee(state, data.target_epoch,
                                             data.crosslink.shard)
    assert spec.verify_bitfield(bitfield, len(committee))
    return sorted(index for i, index in enumerate(committee)
                  if spec.get_bitfield_bit(bitfield, i) == 0b1)


def test_attesting_indices_equal_the_bit_by_bit_form(odd_committees):
    spec, state, _ = odd_committees
    rng = np.random.default_rng(SEED)
    att = factories.new_attestation(spec, state, int(state.slot) - 1)
    size = len(spec.get_crosslink_committee(
        state, att.data.target_epoch, att.data.crosslink.shard))
    assert size == 12
    for _ in range(40):
        bits = np.zeros(16, np.uint8)
        bits[:size] = rng.integers(0, 2, size)
        bitfield = np.packbits(bits, bitorder="little").tobytes()
        got = spec.get_attesting_indices(state, att.data, bitfield)
        assert got == _spec_attesting_indices(spec, state, att.data, bitfield)
        assert all(type(i) is int for i in got)
    committee = spec.get_crosslink_committee_array(
        state, att.data.target_epoch, att.data.crosslink.shard)
    assert committee.dtype == np.int64 and committee.tolist() \
        == spec.get_crosslink_committee(state, att.data.target_epoch,
                                        att.data.crosslink.shard)
    for bad in (b"\xff", b"\xff\xff\x00", b"\xff\x1f"):
        with pytest.raises(AssertionError):
            spec.get_attesting_indices(state, att.data, bad)


@pytest.mark.parametrize("bit_0,bit_1,raises", [
    ([1, 2, 9], [], None), ([], [], None),
    ([1, 2], [5], AssertionError),              # a custody bit [phase 0]
    ([2, 1], [], AssertionError),               # out of order
    ([1, 1, 2], [], None),                      # list == sorted(list) holds
    ([1, 96], [], IndexError),                  # names no validator
    ([2**64 - 1], [], IndexError),
    (list(range(4097)), [], AssertionError),    # MAX_INDICES_PER_ATTESTATION
])
def test_validate_indexed_attestation_checks_on_arrays(
        odd_committees, bit_0, bit_1, raises):
    spec, state, _ = odd_committees
    indexed = spec.IndexedAttestation(custody_bit_0_indices=bit_0,
                                      custody_bit_1_indices=bit_1)
    if raises is None:
        spec.validate_indexed_attestation(state, indexed)
    else:
        with pytest.raises(raises):
            spec.validate_indexed_attestation(state, indexed)


@pytest.mark.parametrize("size,parts", [(976, 8), (977, 8), (128, 8), (12, 8),
                                        (5, 8), (1, 8), (32, 3)])
def test_eighths_partition_the_committee(size, parts):
    fields = eighths(size, parts)
    assert len(fields) == min(size, parts)
    bits = np.stack([np.unpackbits(np.frombuffer(f, np.uint8), bitorder="little")
                     for f in fields])
    assert all(len(f) == (size + 7) // 8 for f in fields)
    assert (bits.sum(axis=0)[:size] == 1).all() and not bits[:, size:].any()
    runs = bits.sum(axis=1)
    assert runs.max() - runs.min() <= 1


# -- spans and the fallback counter -----------------------------------------------

# a block that dirties nothing has no registry_write / forests.update child
BLOCK_PARTS = ("header", "randao", "eth1", "slashings", "attestations",
               "deposits", "exits")


def test_a_block_leaves_one_span_tree_and_counts_no_fallback(minimal):
    spec, state, data = minimal
    telemetry.set_enabled(True)
    telemetry.reset()
    fallbacks = telemetry.counter("resident.block.fallbacks", always=True)
    core = ResidentCore.from_checkpoint(spec, data, mesh=None)
    res = core.state
    try:
        before = fallbacks.value
        core.process_slots(res, int(res.slot) + 1)
        block = BlockGenerator(spec, SEED, 8).block(res)
        core.process_block(res, block)
        records = telemetry.ring()
        root, = [r for r in records if r["name"] == "resident.block"]
        assert root["parent_id"] == 0 and root["req"] == int(block.slot)
        children = [r for r in records if r["parent_id"] == root["id"]]
        assert [c["name"] for c in children] \
            == [f"resident.block.{part}" for part in BLOCK_PARTS]
        assert all(c["req"] == int(block.slot) for c in children)
        assert sum(c["dur"] for c in children) <= root["dur"]
        assert root["args"]["attestations"] == len(block.body.attestations) == 8
        # one committee of 8 in full: the aggregates' bits add up to it
        assert root["args"]["attesting_indices"] == 8
        assert fallbacks.value == before
    finally:
        core._uninstall()
        telemetry.set_enabled(None)


def test_a_blocks_container_roots_go_through_the_plans_and_are_hashed_every_block(
        odd_committees):
    """The header step roots the body with its attestations through their
    plan, the attestation step one parent crosslink a committee: both
    noted on their spans, hashed anew for each block, the root written
    into the header the oracle's; and the check they feed still refuses."""
    spec, state, data = odd_committees
    telemetry.set_enabled(True)
    telemetry.reset()
    core = ResidentCore.from_checkpoint(spec, data, mesh=None)
    res = core.state
    generator = BlockGenerator(spec, SEED, 8)
    try:
        blocks = []
        for _ in range(2):
            core.process_slots(res, int(res.slot) + 1)
            blocks.append(generator.block(res))
            core.process_block(res, blocks[-1])
            assert bytes(res.latest_block_header.body_root) \
                == hash_tree_root(blocks[-1].body)
        assert hash_tree_root(blocks[0].body) != hash_tree_root(blocks[1].body)
        records = telemetry.ring()
        headers = [r["args"] for r in records
                   if r["name"] == "resident.block.header"]
        families = [r["args"] for r in records
                    if r["name"] == "resident.block.attestations"]
        assert len(headers) == len(families) == 2
        for block, header, family in zip(blocks, headers, families):
            n = len(block.body.attestations)
            assert n >= 2
            # the attestations and the body's eth1 data; 18 pairs or more an
            # attestation and its list's tree above them: no answer from the
            # block before
            assert header["plan_elements"] == n + 1
            assert header["pairs_hashed"] >= 19 * n - 1
            # the family as one pass: a parent crosslink's root a committee
            # of the block, not one an attestation
            distinct = len({(a.data.target_epoch, a.data.crosslink.shard)
                            for a in block.body.attestations})
            assert 1 <= distinct < n
            assert family == {"plan_elements": distinct, "committees": distinct,
                              "sequential": 0}
        assert headers[0]["pairs_hashed"] == headers[1]["pairs_hashed"]
        # a third block whose first attestation names another parent root
        core.process_slots(res, int(res.slot) + 1)
        spoiled = generator.block(res)
        _wrong_crosslink_parent_root(spec, res, spoiled)
        assert _where_it_raises(lambda: core.process_block(res, spoiled))[:2] \
            == ("AssertionError", "process_attestation")
        # the loop took that family, and its span says so though it raised:
        # the pass's one parent and the loop's first before the check failed
        assert [r["args"] for r in telemetry.ring()
                if r["name"] == "resident.block.attestations"][-1] \
            == {"plan_elements": 2, "sequential": 1}
    finally:
        core._uninstall()
        telemetry.set_enabled(None)


def test_a_slashing_and_a_deposit_on_an_object_entered_core_are_served(
        minimal):
    """One path for an operation's write: a proposer slashing takes the
    served path on an object-entered core too (no fallback counted, the
    block's span tree gains the registry write and the forests' update),
    and so does a deposit: no block of the preset leaves for the object
    model any more."""
    spec, state, _ = minimal
    telemetry.set_enabled(True)
    telemetry.reset()
    fallbacks = telemetry.counter("resident.block.fallbacks", always=True)
    ref = deepcopy(state)
    core = ResidentCore(spec, state, mesh=None,
                        capacity=len(state.validator_registry) + 4)
    try:
        before = fallbacks.value
        with core.suspended():
            block = factories.empty_block_next(spec, ref)
            block.body.proposer_slashings.append(
                factories.double_proposal(spec, ref))
            spec.state_transition(ref, block)
        core.state_transition(state, block)
        assert fallbacks.value == before
        assert hash_tree_root(ref) == core._state_root(state)
        records = telemetry.ring()
        root, = [r for r in records if r["name"] == "resident.block"]
        children = [r for r in records if r["parent_id"] == root["id"]]
        assert [c["name"] for c in children] \
            == [f"resident.block.{part}" for part in BLOCK_PARTS] \
            + ["resident.registry_write", "resident.forests.update"]
        notes = {c["name"]: c["args"] for c in children if c["args"]}
        assert notes["resident.block.slashings"] == {"slashed": 1}
        assert notes["resident.block.exits"] == {"exits": 0}
        assert notes["resident.block.deposits"] == {
            "new_validators": 0, "top_ups": 0, "proof_pairs_hashed": 0}
        # the slashed validator and the proposer it pays
        assert notes["resident.registry_write"] == {"rows": 2, "appended_rows": 0}
        assert notes["resident.forests.update"]["registry_leaves"] == 1
        assert 1 <= notes["resident.forests.update"]["balance_chunks"] <= 2
        with core.suspended():
            deposit = factories.stage_deposit(
                spec, ref, len(ref.validator_registry), spec.MAX_EFFECTIVE_BALANCE)
            state.latest_eth1_data = deepcopy(ref.latest_eth1_data)
            block = factories.empty_block_next(spec, ref)
            block.body.deposits.append(deposit)
            spec.state_transition(ref, block)
        core.state_transition(state, block)
        assert fallbacks.value == before
        assert hash_tree_root(ref) == core._state_root(state)
        notes = {r["name"]: r["args"] for r in telemetry.ring()}
        assert notes["resident.block.deposits"] == {
            "new_validators": 1, "top_ups": 0,
            "proof_pairs_hashed": int(spec.DEPOSIT_CONTRACT_TREE_DEPTH)}
        assert notes["resident.registry_write"] == {"rows": 1, "appended_rows": 1}
        assert notes["resident.forests.update"]["appended_leaves"] == 1
    finally:
        assert serialize(core.exit(), spec.BeaconState) \
            == serialize(ref, spec.BeaconState)
        telemetry.set_enabled(None)
