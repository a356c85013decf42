"""Whole pre-built states at a chosen validator count, for the tests that
drive the epoch and block paths end to end (tests/test_state_to_state.py,
tests/test_block_batch.py, tests/test_streaming.py).

Where factories.py builds one valid protocol object on a state that a
genesis helper made, these write a full registry and a full epoch of
attestations directly, with the committee layout taken from the vectorized
distillation (`epoch_soa._epoch_layout`): the per-committee spec helper
rebuilds the O(V) active list on every call.
"""
from __future__ import annotations

from ..crypto import bls12_381 as gt
from ..crypto.bls import get_backend
from ..models.phase0.epoch_soa import _epoch_layout, columns_np_from_state
from ..utils.ssz.impl import hash_tree_root
from ..utils.ssz.typing import List as SSZList, uint64


def _active_registry(spec, V, pubkey_of) -> list:
    """V validators active since genesis at the maximum effective balance."""
    return [
        spec.Validator(
            pubkey=pubkey_of(i),
            withdrawal_credentials=b"\x00" * 32,
            activation_eligibility_epoch=spec.GENESIS_EPOCH,
            activation_epoch=spec.GENESIS_EPOCH,
            exit_epoch=spec.FAR_FUTURE_EPOCH,
            withdrawable_epoch=spec.FAR_FUTURE_EPOCH,
            effective_balance=spec.MAX_EFFECTIVE_BALANCE,
        )
        for i in range(V)
    ]


def _full_bitfield(size: int) -> bytes:
    """Full participation, excess bits zero (verify_bitfield :355-361)."""
    bitfield = bytearray(b"\xff" * (size // 8))
    if size % 8:
        bitfield.append((1 << (size % 8)) - 1)
    return bytes(bitfield)


def build_baseline_state(spec, V):
    """Pre-epoch-boundary object-model state with a full epoch of
    attestations (genesis-zero block roots keep everything consistent)."""
    state = spec.BeaconState(genesis_time=0, deposit_index=V)
    state.balances = [spec.MAX_EFFECTIVE_BALANCE] * V
    state.validator_registry = _active_registry(
        spec, V, lambda i: i.to_bytes(48, "little"))
    root = hash_tree_root(list(range(V)), SSZList[uint64])
    for i in range(spec.LATEST_ACTIVE_INDEX_ROOTS_LENGTH):
        state.latest_active_index_roots[i] = root
    state.slot = 3 * spec.SLOTS_PER_EPOCH - 1
    np_cols = columns_np_from_state(state)
    prev_epoch = spec.get_previous_epoch(state)
    for epoch, store in (
        (prev_epoch, state.previous_epoch_attestations),
        (spec.get_current_epoch(state), state.current_epoch_attestations),
    ):
        lay = _epoch_layout(spec, state, np_cols, epoch)
        committee_count, start_shard = lay.count, lay.start_shard
        for offset in range(committee_count):
            shard = (start_shard + offset) % spec.SHARD_COUNT
            committee = lay.shuffled[lay.bounds[offset]:lay.bounds[offset + 1]]
            slot = spec.get_epoch_start_slot(epoch) + offset // (committee_count // spec.SLOTS_PER_EPOCH)
            if slot >= state.slot:
                continue
            data = spec.AttestationData(
                beacon_block_root=spec.get_block_root_at_slot(state, slot),
                source_epoch=state.current_justified_epoch,
                source_root=state.current_justified_root,
                target_epoch=epoch,
                target_root=spec.get_block_root(state, epoch),
                crosslink=spec.Crosslink(
                    shard=shard,
                    parent_root=spec.hash_tree_root(state.current_crosslinks[shard]),
                    end_epoch=min(epoch, spec.MAX_EPOCHS_PER_CROSSLINK),
                ),
            )
            store.append(spec.PendingAttestation(
                aggregation_bitfield=_full_bitfield(len(committee)),
                data=data,
                inclusion_delay=spec.MIN_ATTESTATION_INCLUSION_DELAY,
                proposer_index=int(committee[0]),
            ))
    return state


def build_config3_state_and_block(spec, V, n_attestations, n_keys=64):
    """A state at an epoch boundary + a valid block carrying
    `n_attestations` previous-epoch attestations with REAL aggregate
    signatures over FULL committees (BASELINE config 3).

    Staging trick (verifier work unchanged): validator i's keypair is
    privkey (i % n_keys) + 1, so a committee's aggregate signature over the
    shared message is ONE sign with the sum of member privkeys mod r. The
    verifier still decompresses + aggregates every member pubkey and runs
    the full grouped pairing — only the attester-side signing (not the
    node's measured work) is shortcut."""
    backend = get_backend()
    keypub = [gt.privtopub(k + 1) for k in range(n_keys)]
    state = spec.BeaconState(
        genesis_time=0, deposit_index=V,
        latest_eth1_data=spec.Eth1Data(deposit_count=V))
    state.balances = [spec.MAX_EFFECTIVE_BALANCE] * V
    state.validator_registry = _active_registry(
        spec, V, lambda i: keypub[i % n_keys])
    # First slot of epoch 2: every prev-epoch attestation slot s satisfies
    # s + MIN_ATTESTATION_INCLUSION_DELAY <= slot <= s + SLOTS_PER_EPOCH
    state.slot = 2 * spec.SLOTS_PER_EPOCH
    prev = spec.get_previous_epoch(state)
    lay = _epoch_layout(spec, state, columns_np_from_state(state), prev)
    assert n_attestations <= lay.count, \
        f"only {lay.count} committees at V={V}; raise V for {n_attestations}"
    domain = spec.get_domain(state, spec.DOMAIN_ATTESTATION, prev)

    attestations = []
    for offset in range(n_attestations):
        shard = (lay.start_shard + offset) % spec.SHARD_COUNT
        committee = lay.shuffled[lay.bounds[offset]:lay.bounds[offset + 1]]
        att_slot = (spec.get_epoch_start_slot(prev)
                    + offset // (lay.count // spec.SLOTS_PER_EPOCH))
        parent = state.previous_crosslinks[shard]
        data = spec.AttestationData(
            beacon_block_root=spec.get_block_root_at_slot(state, att_slot),
            source_epoch=state.previous_justified_epoch,
            source_root=state.previous_justified_root,
            target_epoch=prev,
            target_root=spec.get_block_root(state, prev),
            crosslink=spec.Crosslink(
                shard=shard,
                parent_root=spec.hash_tree_root(parent),
                end_epoch=min(prev, parent.end_epoch + spec.MAX_EPOCHS_PER_CROSSLINK),
            ),
        )
        bitfield = _full_bitfield(len(committee))
        msg = spec.hash_tree_root(
            spec.AttestationDataAndCustodyBit(data=data, custody_bit=False))
        k_agg = sum((int(i) % n_keys) + 1 for i in committee) % gt.r
        attestations.append(spec.Attestation(
            aggregation_bitfield=bitfield,
            data=data,
            custody_bitfield=bytes(len(bitfield)),
            signature=backend.sign(msg, k_agg, domain),
        ))

    block = spec.BeaconBlock()
    block.slot = state.slot
    block.parent_root = spec.signing_root(state.latest_block_header)
    block.body.eth1_data.deposit_count = state.deposit_index
    block.body.attestations = attestations
    proposer_key = (spec.get_beacon_proposer_index(state) % n_keys) + 1
    epoch = spec.get_current_epoch(state)
    block.body.randao_reveal = backend.sign(
        spec.hash_tree_root(epoch), proposer_key,
        spec.get_domain(state, spec.DOMAIN_RANDAO, epoch))
    block.signature = backend.sign(
        spec.signing_root(block), proposer_key,
        spec.get_domain(state, spec.DOMAIN_BEACON_PROPOSER))
    return state, block
