"""Phase 1: custody game + shard chains on the object-model spec.

Covers /root/reference specs/core/1_custody-game.md (field-append
containers, the five operation families, epoch inserts) and
1_shard-data-chains.md (persistent committees, shard proposer, crosslink
data root, shard block validity). BLS off except where a scenario is about
signatures (mirroring the phase-0 corpus convention).
"""
from copy import deepcopy

import pytest

from consensus_specs_tpu.crypto import bls
from consensus_specs_tpu.models import phase0, phase1
from consensus_specs_tpu.testing import factories as f
from consensus_specs_tpu.utils.merkle import (
    calc_merkle_tree_from_leaves, get_merkle_proof)
from consensus_specs_tpu.utils.ssz.impl import hash_tree_root, serialize


@pytest.fixture(scope="module")
def spec():
    return phase1.get_spec("minimal")


@pytest.fixture(autouse=True)
def _bls_off():
    old = bls.bls_active
    bls.bls_active = False
    yield
    bls.bls_active = old


@pytest.fixture()
def state(spec):
    return f.seed_genesis_state(spec, spec.SLOTS_PER_EPOCH * 8)


# ---------------------------------------------------------------------------
# Containers: field-append semantics
# ---------------------------------------------------------------------------

def test_appended_fields_preserve_phase0_prefix(spec):
    p0 = phase0.get_spec("minimal")
    for name in ("Validator", "BeaconState", "BeaconBlockBody"):
        p0_fields = [fname for fname, _ in getattr(p0, name).get_fields()]
        p1_fields = [fname for fname, _ in getattr(spec, name).get_fields()]
        assert p1_fields[:len(p0_fields)] == p0_fields, name
        assert len(p1_fields) > len(p0_fields), name


def test_phase1_validator_fields(spec):
    v = spec.Validator()
    assert v.next_custody_reveal_period == 0
    assert v.max_reveal_lateness == 0


def test_phase1_state_serializes_and_roots(spec, state):
    data = serialize(state, spec.BeaconState)
    from consensus_specs_tpu.utils.ssz.impl import deserialize
    back = deserialize(data, spec.BeaconState)
    assert hash_tree_root(back, spec.BeaconState) == \
        hash_tree_root(state, spec.BeaconState)


def test_registry_holds_extended_validators(spec):
    typ = spec.BeaconState.get_fields()
    registry_type = dict(typ)["validator_registry"]
    assert registry_type.elem_type is spec.Validator


# ---------------------------------------------------------------------------
# Custody key reveals
# ---------------------------------------------------------------------------

def _mature_custody_state(spec, state, periods=2):
    state.slot = spec.SLOTS_PER_EPOCH * spec.EPOCHS_PER_CUSTODY_PERIOD * periods
    return state


def test_custody_key_reveal_success(spec, state):
    _mature_custody_state(spec, state)
    reveal = spec.CustodyKeyReveal(revealer_index=3, reveal=b"\x11" * 96)
    before = state.validator_registry[3].next_custody_reveal_period
    spec.process_custody_key_reveal(state, reveal)
    assert state.validator_registry[3].next_custody_reveal_period == before + 1


def test_custody_key_reveal_not_yet_due(spec, state):
    # current period == next_custody_reveal_period: nothing to reveal yet
    reveal = spec.CustodyKeyReveal(revealer_index=3, reveal=b"\x11" * 96)
    with pytest.raises(AssertionError):
        spec.process_custody_key_reveal(state, reveal)


def test_custody_key_reveal_in_block(spec, state):
    """e2e: a phase-1 block carrying a custody key reveal transitions."""
    _mature_custody_state(spec, state)
    block = f.empty_block_next(spec, state)
    block.body.custody_key_reveals.append(
        spec.CustodyKeyReveal(revealer_index=5, reveal=b"\x22" * 96))
    spec.state_transition(state, block)
    assert state.validator_registry[5].next_custody_reveal_period == 1


# ---------------------------------------------------------------------------
# Early derived secret reveals
# ---------------------------------------------------------------------------

def _edsr(spec, state, epoch_ahead, revealed_index=2, masker_index=9):
    return spec.EarlyDerivedSecretReveal(
        revealed_index=revealed_index,
        epoch=spec.get_current_epoch(state) + epoch_ahead,
        reveal=b"\x33" * 96,
        masker_index=masker_index,
        mask=b"\x44" * 32,
    )


def test_early_reveal_inside_custody_window_slashes(spec, state):
    reveal = _edsr(spec, state, spec.CUSTODY_PERIOD_TO_RANDAO_PADDING)
    spec.process_early_derived_secret_reveal(state, reveal)
    assert state.validator_registry[reveal.revealed_index].slashed


def test_early_reveal_outside_window_penalizes_only(spec, state):
    reveal = _edsr(spec, state, spec.RANDAO_PENALTY_EPOCHS)
    pre_balance = state.balances[reveal.revealed_index]
    spec.process_early_derived_secret_reveal(state, reveal)
    assert not state.validator_registry[reveal.revealed_index].slashed
    assert state.balances[reveal.revealed_index] < pre_balance
    slot_index = reveal.epoch % spec.EARLY_DERIVED_SECRET_PENALTY_MAX_FUTURE_EPOCHS
    assert reveal.revealed_index in list(state.exposed_derived_secrets[slot_index])


def test_early_reveal_duplicate_rejected(spec, state):
    reveal = _edsr(spec, state, spec.RANDAO_PENALTY_EPOCHS)
    spec.process_early_derived_secret_reveal(state, reveal)
    with pytest.raises(AssertionError):
        spec.process_early_derived_secret_reveal(state, deepcopy(reveal))


def test_early_reveal_too_late_rejected(spec, state):
    reveal = _edsr(spec, state, 0)   # current epoch: not early at all
    with pytest.raises(AssertionError):
        spec.process_early_derived_secret_reveal(state, reveal)


# ---------------------------------------------------------------------------
# Chunk challenges + responses
# ---------------------------------------------------------------------------

def _challengeable_attestation(spec, state, chunk_count, data_root):
    """An includable attestation whose crosslink spans >=1 epoch and commits
    to `data_root` (challenge paths don't re-check phase-0 data_root rules)."""
    f.advance_epoch(spec, state)
    f.transition_with_empty_block(spec, state)
    att = f.new_attestation(spec, state)
    att.data.crosslink.data_root = data_root
    if chunk_count:
        att.data.crosslink.end_epoch = att.data.crosslink.start_epoch + 1
    return att


def test_chunk_challenge_and_response(spec, state):
    chunk = b"\x07" * spec.BYTES_PER_CUSTODY_CHUNK
    # crosslink spans one epoch -> real chunk tree; commit to a tree whose
    # leaf 0 is our chunk so the response's Merkle branch verifies
    att = _challengeable_attestation(spec, state, 1, spec.ZERO_HASH)
    chunk_count = spec.get_custody_chunk_count(att.data.crosslink)
    depth = spec.ceillog2(chunk_count)
    leaves = [hash_tree_root(chunk)] + [spec.ZERO_HASH] * (chunk_count - 1)
    tree = calc_merkle_tree_from_leaves(leaves, depth)
    att.data.crosslink.data_root = tree[-1][0]

    responder = spec.get_attesting_indices(
        state, att.data, att.aggregation_bitfield)[0]
    challenge = spec.CustodyChunkChallenge(
        responder_index=responder, attestation=att, chunk_index=0)
    spec.process_chunk_challenge(state, challenge)

    records = [r for r in state.custody_chunk_challenge_records
               if r != spec.CustodyChunkChallengeRecord()]
    assert len(records) == 1
    record = records[0]
    assert record.responder_index == responder
    assert record.depth == depth
    assert state.validator_registry[responder].withdrawable_epoch == spec.FAR_FUTURE_EPOCH

    # duplicate challenge on the same (data_root, chunk) must be rejected
    with pytest.raises(AssertionError):
        spec.process_chunk_challenge(state, deepcopy(challenge))

    # answer it after the minimum delay
    state.slot += spec.SLOTS_PER_EPOCH * (spec.ACTIVATION_EXIT_DELAY + 1)
    response = spec.CustodyResponse(
        challenge_index=record.challenge_index,
        chunk_index=0,
        chunk=chunk,
        data_branch=get_merkle_proof(tree, 0),
        chunk_bits_branch=[],
        chunk_bits_leaf=spec.ZERO_HASH,
    )
    spec.process_custody_response(state, response)
    assert all(r == spec.CustodyChunkChallengeRecord()
               for r in state.custody_chunk_challenge_records)


def test_chunk_challenge_wrong_responder_rejected(spec, state):
    att = _challengeable_attestation(spec, state, 0, spec.ZERO_HASH)
    outsiders = [i for i in range(len(state.validator_registry))
                 if i not in spec.get_attesting_indices(
                     state, att.data, att.aggregation_bitfield)]
    challenge = spec.CustodyChunkChallenge(
        responder_index=outsiders[0], attestation=att, chunk_index=0)
    with pytest.raises(AssertionError):
        spec.process_chunk_challenge(state, challenge)


def test_challenge_deadline_slashes_responder(spec, state):
    att = _challengeable_attestation(spec, state, 0, spec.ZERO_HASH)
    responder = spec.get_attesting_indices(
        state, att.data, att.aggregation_bitfield)[0]
    spec.process_chunk_challenge(state, spec.CustodyChunkChallenge(
        responder_index=responder, attestation=att, chunk_index=0))
    state.slot += spec.SLOTS_PER_EPOCH * (spec.CUSTODY_RESPONSE_DEADLINE + 2)
    spec.process_challenge_deadlines(state)
    assert state.validator_registry[responder].slashed
    assert all(r == spec.CustodyChunkChallengeRecord()
               for r in state.custody_chunk_challenge_records)


# ---------------------------------------------------------------------------
# Bit challenges
# ---------------------------------------------------------------------------

def test_bit_challenge_opens_record(spec, state):
    att = _challengeable_attestation(spec, state, 1, spec.ZERO_HASH)
    # a bit challenge targets an attestation from a custody period the
    # responder has already passed: age the state by two full periods
    state.slot += spec.SLOTS_PER_EPOCH * spec.EPOCHS_PER_CUSTODY_PERIOD * 2
    attesters = spec.get_attesting_indices(state, att.data, att.aggregation_bitfield)
    responder = attesters[0]
    challenger = [i for i in range(len(state.validator_registry))
                  if i not in attesters][0]
    chunk_count = spec.get_custody_chunk_count(att.data.crosslink)
    assert chunk_count > 0

    # find chunk bits whose folded-hash first bit is 1 (custody bit is 0)
    width = (chunk_count + 7) // 8
    chunk_bits = None
    for probe in range(256):
        candidate = bytes([probe]) + b"\x00" * (width - 1)
        if spec.get_bitfield_bit(spec.get_chunk_bits_root(candidate), 0) == 1:
            chunk_bits = candidate
            break
    assert chunk_bits is not None

    challenge = spec.CustodyBitChallenge(
        responder_index=responder,
        attestation=att,
        challenger_index=challenger,
        responder_key=b"\x55" * 96,
        chunk_bits=chunk_bits,
        signature=b"\x66" * 96,
    )
    spec.process_bit_challenge(state, challenge)
    records = [r for r in state.custody_bit_challenge_records
               if r != spec.CustodyBitChallengeRecord()]
    assert len(records) == 1
    assert records[0].chunk_count == chunk_count

    # one challenger, one open challenge at a time
    with pytest.raises(AssertionError):
        spec.process_bit_challenge(state, deepcopy(challenge))


# ---------------------------------------------------------------------------
# Epoch inserts
# ---------------------------------------------------------------------------

def test_reveal_deadline_slashes_laggards(spec, state):
    periods_late = spec.CUSTODY_RESPONSE_DEADLINE // spec.EPOCHS_PER_CUSTODY_PERIOD + 2
    _mature_custody_state(spec, state, periods=periods_late)
    spec.process_reveal_deadlines(state)
    assert all(v.slashed for v in state.validator_registry)


def test_final_updates_cleans_exposed_secrets_and_unfreezes(spec, state):
    reveal = _edsr(spec, state, spec.RANDAO_PENALTY_EPOCHS)
    spec.process_early_derived_secret_reveal(state, reveal)
    slot_index = reveal.epoch % spec.EARLY_DERIVED_SECRET_PENALTY_MAX_FUTURE_EPOCHS

    # a frozen-withdrawability exited validator with no open challenge
    leaver = 7
    state.validator_registry[leaver].exit_epoch = spec.get_current_epoch(state)
    state.validator_registry[leaver].withdrawable_epoch = spec.FAR_FUTURE_EPOCH

    # roll current_epoch onto the reveal's storage slot, then clean up
    state.slot = reveal.epoch * spec.SLOTS_PER_EPOCH
    spec.after_process_final_updates(state)
    assert list(state.exposed_derived_secrets[slot_index]) == []
    assert state.validator_registry[leaver].withdrawable_epoch != spec.FAR_FUTURE_EPOCH


def test_phase1_epoch_transition_runs_inserts(spec, state):
    """Full process_slots across an epoch boundary with the phase-1 hooks
    registered must execute without error."""
    f.advance_epoch(spec, state)
    assert spec.get_current_epoch(state) == 1


# ---------------------------------------------------------------------------
# Shard chains
# ---------------------------------------------------------------------------

def test_persistent_committee_deterministic(spec, state):
    a = spec.get_persistent_committee(state, 0, state.slot)
    b = spec.get_persistent_committee(state, 0, state.slot)
    assert a == b
    assert a == sorted(a)
    assert all(0 <= i < len(state.validator_registry) for i in a)


def test_shard_proposer_is_active_member(spec, state):
    committee = spec.get_persistent_committee(state, 1, state.slot)
    proposer = spec.get_shard_proposer_index(state, 1, state.slot)
    if committee:
        assert proposer in committee
        assert spec.is_active_validator(
            state.validator_registry[proposer], spec.get_current_epoch(state))


def test_crosslink_data_root_deterministic_and_sensitive(spec, state):
    body = spec.ShardBlockBody(data=b"\x01" * spec.BYTES_PER_SHARD_BLOCK_BODY)
    blk = spec.ShardBlock(slot=0, shard=0, data=body)
    root1 = spec.compute_crosslink_data_root([blk])
    assert root1 == spec.compute_crosslink_data_root([deepcopy(blk)])
    blk2 = deepcopy(blk)
    blk2.data = spec.ShardBlockBody(data=b"\x02" * spec.BYTES_PER_SHARD_BLOCK_BODY)
    assert spec.compute_crosslink_data_root([blk2]) != root1
    assert spec.compute_crosslink_data_root([]) != root1


def test_shard_block_validity_happy_path(spec, state):
    """A fork-slot shard block anchored to a real beacon block validates."""
    beacon_block = f.empty_block(spec, state)
    beacon_blocks = [beacon_block] * (spec.SLOTS_PER_EPOCH * 2)
    candidate = spec.ShardBlock(
        slot=spec.PHASE_1_FORK_SLOT,
        shard=1,
        beacon_chain_root=spec.signing_root(beacon_block),
        parent_root=spec.ZERO_HASH,
        data=spec.ShardBlockBody(data=b"\x00" * spec.BYTES_PER_SHARD_BLOCK_BODY),
        state_root=spec.ZERO_HASH,
    )
    assert spec.is_valid_shard_block(beacon_blocks, state, [], candidate)


def test_shard_block_wrong_beacon_root_rejected(spec, state):
    beacon_block = f.empty_block(spec, state)
    beacon_blocks = [beacon_block] * spec.SLOTS_PER_EPOCH
    candidate = spec.ShardBlock(
        slot=spec.PHASE_1_FORK_SLOT,
        shard=1,
        beacon_chain_root=b"\x13" * 32,
        parent_root=spec.ZERO_HASH,
        data=spec.ShardBlockBody(data=b"\x00" * spec.BYTES_PER_SHARD_BLOCK_BODY),
        state_root=spec.ZERO_HASH,
    )
    with pytest.raises(AssertionError):
        spec.is_valid_shard_block(beacon_blocks, state, [], candidate)


# ---------------------------------------------------------------------------
# Device epoch path with insert hooks
# ---------------------------------------------------------------------------

def _diff_epoch_paths(spec, state):
    """process_epoch vs process_epoch_soa on copies; returns (ref, soa)."""
    from consensus_specs_tpu.models.phase0.epoch_soa import process_epoch_soa
    if (state.slot + 1) % spec.SLOTS_PER_EPOCH != 0:
        state.slot += (spec.SLOTS_PER_EPOCH - 1
                       - state.slot % spec.SLOTS_PER_EPOCH)
    ref, soa = deepcopy(state), deepcopy(state)
    spec.process_epoch(ref)
    out = process_epoch_soa(spec, soa)
    assert out is not None, "staged device path must run, not fall back"
    assert hash_tree_root(ref) == hash_tree_root(soa)
    return ref, soa


def test_phase1_device_epoch_matches_object_model(spec, state):
    """Attested phase-1 epoch: the staged device path (stage A -> hooks ->
    stage B) must equal Phase1Spec.process_epoch bit-for-bit."""
    from consensus_specs_tpu.testing.cases.finality import attested_epoch
    f.advance_epoch(spec, state)
    f.transition_with_empty_block(spec, state)
    _, _, state = attested_epoch(spec, state, current=True, previous=True)
    _diff_epoch_paths(spec, state)


def test_phase1_hook_slashing_lands_between_stages(spec, state):
    """An overdue custody challenge makes @process_challenge_deadlines slash
    BETWEEN the two device stages; stage B must see the new slashed flag and
    slashed-balance table exactly like the object model's sequential run."""
    att = _challengeable_attestation(spec, state, 0, spec.ZERO_HASH)
    responder = spec.get_attesting_indices(
        state, att.data, att.aggregation_bitfield)[0]
    spec.process_chunk_challenge(state, spec.CustodyChunkChallenge(
        responder_index=responder, attestation=att, chunk_index=0))
    state.previous_epoch_attestations = []
    state.current_epoch_attestations = []
    state.slot += spec.SLOTS_PER_EPOCH * (spec.CUSTODY_RESPONSE_DEADLINE + 2)
    ref, soa = _diff_epoch_paths(spec, state)
    assert soa.validator_registry[responder].slashed
