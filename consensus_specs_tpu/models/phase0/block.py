"""Phase-0 block processing (bound as methods of Phase0Spec).

Semantics per /root/reference specs/core/0_beacon-chain.md:1566-1832:
header, randao, eth1 data, then the six operation types in fixed order with
per-type max counts.
"""
from __future__ import annotations

import numpy as np

from ... import telemetry
from ...utils.ssz import bulk
from ...utils.ssz.root_plan import plan_for


def process_block(spec, state, block) -> None:
    spec.process_block_header(state, block)
    spec.process_randao(state, block.body)
    spec.process_eth1_data(state, block.body)
    spec.process_operations(state, block.body)


def process_block_header(spec, state, block) -> None:
    # Slot and parent linkage
    assert block.slot == state.slot
    assert block.parent_root == spec.signing_root(state.latest_block_header)
    state.latest_block_header = spec.BeaconBlockHeader(
        slot=block.slot,
        parent_root=block.parent_root,
        body_root=spec.hash_tree_root(block.body),
    )
    # Proposer must not be slashed, and must have signed the block
    registry = spec.registry_view(state)
    proposer_index = spec.get_beacon_proposer_index(state)
    assert not registry.slashed(proposer_index)
    # the message is the block's root, a second pass over the whole body:
    # computed only for a verify that reads it
    assert not spec.bls.bls_active or spec.bls.bls_verify(
        registry.pubkey(proposer_index), spec.signing_root(block), block.signature,
        spec.get_domain(state, spec.DOMAIN_BEACON_PROPOSER))


def process_randao(spec, state, body) -> None:
    proposer_pubkey = spec.registry_view(state).pubkey(spec.get_beacon_proposer_index(state))
    current_epoch = spec.get_current_epoch(state)
    assert spec.bls.bls_verify(
        proposer_pubkey,
        spec.hash_tree_root(current_epoch),
        body.randao_reveal,
        spec.get_domain(state, spec.DOMAIN_RANDAO),
    )
    state.latest_randao_mixes[current_epoch % spec.LATEST_RANDAO_MIXES_LENGTH] = spec.xor(
        spec.get_randao_mix(state, current_epoch), spec.hash(bytes(body.randao_reveal)))


def process_eth1_data(spec, state, body) -> None:
    state.eth1_data_votes.append(body.eth1_data)
    # field by field: Container.__eq__ compares hash_tree_roots, two a vote
    # over a list that holds up to SLOTS_PER_ETH1_VOTING_PERIOD of them
    vote = body.eth1_data.get_field_values()
    if sum(1 for v in state.eth1_data_votes if v.get_field_values() == vote) * 2 > spec.SLOTS_PER_ETH1_VOTING_PERIOD:
        state.latest_eth1_data = body.eth1_data


# The six operation lists of a block's body in the spec's fixed order: the
# body's list -> (the preset's per-block maximum, the per-operation handler).
# The attestation list is processed as a family (process_attestations_batched:
# one pass over the block's attestations, and with BLS on their signature
# checks as one device pipeline); `process_attestation` is the spec's handler
# for one, and the family's loop.
OPERATIONS = {
    "proposer_slashings": ("MAX_PROPOSER_SLASHINGS", "process_proposer_slashing"),
    "attester_slashings": ("MAX_ATTESTER_SLASHINGS", "process_attester_slashing"),
    "attestations": ("MAX_ATTESTATIONS", "process_attestation"),
    "deposits": ("MAX_DEPOSITS", "process_deposit"),
    "voluntary_exits": ("MAX_VOLUNTARY_EXITS", "process_voluntary_exit"),
    "transfers": ("MAX_TRANSFERS", "process_transfer"),
}


def check_operations(spec, state, body) -> None:
    """What `process_operations` asserts of the body before any operation."""
    # Outstanding deposits must be processed up to the per-block maximum
    assert len(body.deposits) == min(spec.MAX_DEPOSITS,
                                     state.latest_eth1_data.deposit_count - state.deposit_index)
    # No duplicate transfers
    assert len(body.transfers) == len(set(body.transfers))


def process_operation_list(spec, state, body, name: str):
    """One of the body's six lists (`OPERATIONS`), whole: its per-block
    maximum, then every operation through its handler; the attestations as
    a family, whose account of what it did is handed back."""
    max_name, handler = OPERATIONS[name]
    operations = getattr(body, name)
    assert len(operations) <= getattr(spec, max_name)
    if name == "attestations":
        return process_attestations_batched(spec, state, operations)
    handle = getattr(spec, handler)
    for operation in operations:
        handle(state, operation)


def process_extra_operations(spec, state, body) -> None:
    # Later phases append operation families after all phase-0 ops (the
    # reference appends them via spec-doc ordering, 1_custody-game.md:330+)
    for body_attr, max_operations, handler in spec._extra_block_operations:
        operations = getattr(body, body_attr)
        assert len(operations) <= max_operations
        for operation in operations:
            handler(state, operation)


def process_operations(spec, state, body) -> None:
    spec.check_operations(state, body)
    for name in OPERATIONS:
        spec.process_operation_list(state, body, name)
    spec.process_extra_operations(state, body)


_batching_enabled = True


def set_attestation_batching(enabled: bool) -> None:
    """Test hook: force the sequential per-attestation loop."""
    global _batching_enabled
    _batching_enabled = enabled


# Families that ran the per-attestation loop (`process_attestation` an
# attestation) and not the one pass: a family with a failing check, which
# the loop then rejects at the spec's place; the test hook above; BLS on a
# backend without a batch verify. Counted where the choice is made, before
# the loop can raise, whichever state the block meets: a deployment's blocks
# meet a resident core's, whose `resident.block.attestations` span notes what
# its own family added here as `sequential`.
SEQUENTIAL_FAMILIES = telemetry.counter(
    "resident.block.attestations.sequential", always=True)


class _Committee:
    """What a block's attestations of one (target epoch, shard) share: the
    slot and the committee they name, the FFG triple and the crosslink
    lineage the state demands of them, the state's list they join, and
    their places in the block. Each check that reads nothing else of an
    attestation is made here, once."""
    __slots__ = ("slot", "members", "ffg", "parent", "end_epoch",
                 "parent_root", "pending", "rows")

    def __init__(self, spec, state, data):
        epoch, shard = data.target_epoch, data.crosslink.shard
        current_epoch = spec.get_current_epoch(state)
        assert epoch in (spec.get_previous_epoch(state), current_epoch)
        self.slot = spec.get_attestation_data_slot(state, data)
        assert self.slot + spec.MIN_ATTESTATION_INCLUSION_DELAY <= state.slot \
            <= self.slot + spec.SLOTS_PER_EPOCH
        self.members = spec.get_crosslink_committee_array(state, epoch, shard)
        if epoch == current_epoch:
            self.ffg = (state.current_justified_epoch, state.current_justified_root, epoch)
            self.parent = state.current_crosslinks[shard]
            self.pending = state.current_epoch_attestations
        else:
            self.ffg = (state.previous_justified_epoch, state.previous_justified_root, epoch)
            self.parent = state.previous_crosslinks[shard]
            self.pending = state.previous_epoch_attestations
        self.end_epoch = min(epoch, self.parent.end_epoch + spec.MAX_EPOCHS_PER_CROSSLINK)
        self.parent_root = None     # hashed with the block's other parents
        self.rows = []


def _checked_family(spec, state, attestations):
    """Every check `process_attestation` makes, of every attestation of a
    block, with nothing written. What the attestations of one committee
    share is resolved and checked once a committee (`_Committee`: inside
    the family the only state writes are PendingAttestation appends, so
    eight aggregates of one committee meet one state, as `_proposer_memo`
    has it); the parent crosslinks are rooted from the state as it stands,
    one batch of the Crosslink root plan, nothing kept past the call; the
    bitfields are checked as one array a committee size. The members of a
    committee are distinct and `convert_to_indexed` sorts the attesting
    ones, with no custody bit beside them, so the indexed attestation's
    sortedness and disjointness hold by construction and none is built.

    Returns the number of committees and, in list order, each
    attestation's committee and (with `bls_active`) its sorted attesting
    indices. A failing check raises AssertionError or
    IndexError; WHICH attestation fails first, and with what, is the
    loop's to say (process_attestations_batched)."""
    committees, of = {}, []
    for row, attestation in enumerate(attestations):
        data = attestation.data
        key = (data.target_epoch, data.crosslink.shard)
        committee = committees.get(key)
        if committee is None:
            committee = committees[key] = _Committee(spec, state, data)
        committee.rows.append(row)
        of.append(committee)
    if committees:
        roots = bulk.plan_roots(plan_for(spec.Crosslink),
                                [c.parent for c in committees.values()])
        for i, committee in enumerate(committees.values()):
            committee.parent_root = roots[32 * i:32 * i + 32]

    # once an attestation, scalars only
    for attestation, committee in zip(attestations, of):
        data = attestation.data
        link = data.crosslink
        assert committee.ffg == (data.source_epoch, data.source_root, data.target_epoch)
        assert link.start_epoch == committee.parent.end_epoch
        assert link.end_epoch == committee.end_epoch
        assert link.parent_root == committee.parent_root
        assert link.data_root == spec.ZERO_HASH  # [to be removed in phase 1]

    # the bitfields, one array a committee size
    registry_size = len(spec.registry_view(state))
    indices = [None] * len(of)
    by_size = {}
    for committee in committees.values():
        by_size.setdefault(len(committee.members), []).append(committee)
    for size, same in by_size.items():
        rows = [row for committee in same for row in committee.rows]
        n_bytes = (size + 7) // 8
        no_custody_bit = bytes(n_bytes)
        fields = []
        for row in rows:
            # verify_bitfield's length for both; of the custody bits none
            # may be set [phase 0], padding or not
            assert attestations[row].custody_bitfield == no_custody_bit
            fields.append(attestations[row].aggregation_bitfield)
            assert len(fields[-1]) == n_bytes
        bits = np.unpackbits(
            np.frombuffer(b"".join(fields), np.uint8).reshape(len(rows), n_bytes),
            axis=1, bitorder="little").view(bool)
        assert not bits[:, size:].any()
        bits = bits[:, :size]
        counts = bits.sum(axis=1)
        assert counts.max() <= spec.MAX_INDICES_PER_ATTESTATION
        # an attestation's bits are its committee's members, and each names
        # a validator (validate_indexed_attestation's IndexError)
        members = np.stack([committee.members for committee in same])
        of_row = np.repeat(np.arange(len(same)), [len(c.rows) for c in same])
        at_row, at_bit = np.nonzero(bits)
        attesting = members[of_row[at_row], at_bit]
        if attesting.size and int(attesting.max()) >= registry_size:
            raise IndexError(f"validator index {int(attesting.max())} outside "
                             f"a registry of {registry_size}")
        if spec.bls.bls_active:
            for row, part in zip(rows, np.split(attesting, np.cumsum(counts)[:-1])):
                indices[row] = np.sort(part)
    return len(committees), of, indices


def process_attestations_batched(spec, state, attestations) -> dict:
    """The block's attestation family, all or nothing: ONE pass over the
    family (`_checked_family`) makes every check of the spec's
    `process_attestation` (0_beacon-chain.md:1692-1727) of every
    attestation, once a committee what the attestations of a committee
    share, and only when all have passed are the PendingAttestations built
    and appended, in list order. A family with a failing check has written
    nothing by then and is run again by the loop, `process_attestation` an
    attestation, so that the first failing attestation raises what the
    spec raises, at the spec's place, over the state half written as the
    spec leaves it. Which of the two runs is read off the block, not off a
    switch; `set_attestation_batching(False)` forces the loop for tests.

    With BLS on, the signature checks collapse into ONE grouped device
    pipeline (BASELINE config 3; :1625-1645): the pass, or in the loop
    validate_indexed_attestation, puts each attestation's check into a
    sink (helpers.attestation_signature_check), and the collected block
    is then verified by the backend's verify_indexed_batch: batched G1
    aggregation, G2 decompression, hash_to_G2, and one grouped pairing
    program. A failed verdict raises the same AssertionError the inline
    verify raises (the reference discards half-mutated state on failure
    either way, :1204-1219). A backend without batch support (the bignum
    oracle) verifies inline, an attestation at a time, which is the loop.

    Returns what it did: `committees`, the committee resolutions made (one
    a distinct (target epoch, shard) in the pass, one an attestation in
    the loop), and `sequential`, 1 when the loop ran."""
    batch = (getattr(spec.bls.get_backend(), "verify_indexed_batch", None)
             if spec.bls.bls_active and _batching_enabled else None)
    # streaming firehose (ISSUE 15): when a StreamingVerifier is
    # installed on the spec, the sink's verdicts come from its queue —
    # attestations the gossip firehose already verified are served from
    # the verdict cache, misses ride the same cross-slot batching
    # pipeline. Verdicts are bit-identical to verify_indexed_batch
    # (tests/test_streaming.py), so failure semantics are unchanged.
    streaming = (getattr(spec, "_streaming_verifier", None)
                 if batch is not None else None)
    # Within the family the only state mutations are PendingAttestation
    # appends, so the slot's proposer index is invariant: pin it for the
    # scope (each process_attestation of the loop consults it; up to 128
    # rejection-sampling recomputations collapse to one)
    if len(attestations) > 1:
        state._proposer_memo = (
            (int(state.slot), len(spec.registry_view(state))),
            spec.get_beacon_proposer_index(state))
    outer = spec._att_verify_sink
    # where the signature checks go: a sink already installed, else this
    # family's own when the backend verifies a batch, else nowhere (BLS
    # off, or each verified inline)
    sink = outer if outer is not None else [] if batch is not None else None
    try:
        family = None
        if _batching_enabled and not (spec.bls.bls_active and sink is None):
            try:
                family = _checked_family(spec, state, attestations)
            except (AssertionError, IndexError):
                pass
        if family is None:
            SEQUENTIAL_FAMILIES.inc()
            spec._att_verify_sink = sink
            try:
                for attestation in attestations:
                    spec.process_attestation(state, attestation)
            finally:
                spec._att_verify_sink = outer
            work = {"committees": len(attestations), "sequential": 1}
        else:
            committees, of, indices = family
            proposer_index = spec.get_beacon_proposer_index(state)
            for attestation, committee, attesting in zip(attestations, of, indices):
                committee.pending.append(spec.PendingAttestation(
                    data=attestation.data,
                    aggregation_bitfield=attestation.aggregation_bitfield,
                    inclusion_delay=state.slot - committee.slot,
                    proposer_index=proposer_index,
                ))
                if attesting is not None:
                    sink.append(spec.attestation_signature_check(
                        state, attestation.data, attesting, attesting[:0],
                        attestation.signature))
            work = {"committees": committees, "sequential": 0}
        if sink and outer is None:
            if streaming is not None:
                assert all(streaming.verdicts_for(sink))
            else:
                assert all(batch(sink))
        return work
    finally:
        if len(attestations) > 1:
            state._proposer_memo = None


def process_proposer_slashing(spec, state, proposer_slashing) -> None:
    registry = spec.registry_view(state)
    index = proposer_slashing.proposer_index
    # Same epoch, different headers, slashable proposer, both signatures valid
    assert spec.slot_to_epoch(proposer_slashing.header_1.slot) == \
        spec.slot_to_epoch(proposer_slashing.header_2.slot)
    assert proposer_slashing.header_1 != proposer_slashing.header_2
    assert spec.is_slashable_index(state, index, spec.get_current_epoch(state))
    if spec.bls.bls_active:
        # the messages are roots of both headers: computed only for a
        # verify that reads them
        pubkey = registry.pubkey(index)
        for header in (proposer_slashing.header_1, proposer_slashing.header_2):
            domain = spec.get_domain(state, spec.DOMAIN_BEACON_PROPOSER, spec.slot_to_epoch(header.slot))
            assert spec.bls.bls_verify(pubkey, spec.signing_root(header), header.signature, domain)

    spec.slash_validator(state, index)


def process_attester_slashing(spec, state, attester_slashing) -> None:
    attestation_1 = attester_slashing.attestation_1
    attestation_2 = attester_slashing.attestation_2
    assert spec.is_slashable_attestation_data(attestation_1.data, attestation_2.data)
    spec.validate_indexed_attestation(state, attestation_1)
    spec.validate_indexed_attestation(state, attestation_2)

    slashed_any = False
    attesting_indices_1 = list(attestation_1.custody_bit_0_indices) + list(attestation_1.custody_bit_1_indices)
    attesting_indices_2 = list(attestation_2.custody_bit_0_indices) + list(attestation_2.custody_bit_1_indices)
    for index in sorted(set(attesting_indices_1) & set(attesting_indices_2)):
        if spec.is_slashable_index(state, index, spec.get_current_epoch(state)):
            spec.slash_validator(state, index)
            slashed_any = True
    assert slashed_any


def process_attestation(spec, state, attestation) -> None:
    data = attestation.data
    attestation_slot = spec.get_attestation_data_slot(state, data)
    assert attestation_slot + spec.MIN_ATTESTATION_INCLUSION_DELAY <= state.slot \
        <= attestation_slot + spec.SLOTS_PER_EPOCH

    pending_attestation = spec.PendingAttestation(
        data=data,
        aggregation_bitfield=attestation.aggregation_bitfield,
        inclusion_delay=state.slot - attestation_slot,
        proposer_index=spec.get_beacon_proposer_index(state),
    )

    assert data.target_epoch in (spec.get_previous_epoch(state), spec.get_current_epoch(state))
    if data.target_epoch == spec.get_current_epoch(state):
        ffg_data = (state.current_justified_epoch, state.current_justified_root, spec.get_current_epoch(state))
        parent_crosslink = state.current_crosslinks[data.crosslink.shard]
        state.current_epoch_attestations.append(pending_attestation)
    else:
        ffg_data = (state.previous_justified_epoch, state.previous_justified_root, spec.get_previous_epoch(state))
        parent_crosslink = state.previous_crosslinks[data.crosslink.shard]
        state.previous_epoch_attestations.append(pending_attestation)

    # FFG vote, crosslink linkage, and aggregate signature must all check out
    assert ffg_data == (data.source_epoch, data.source_root, data.target_epoch)
    assert data.crosslink.start_epoch == parent_crosslink.end_epoch
    assert data.crosslink.end_epoch == min(data.target_epoch,
                                           parent_crosslink.end_epoch + spec.MAX_EPOCHS_PER_CROSSLINK)
    assert data.crosslink.parent_root == spec.hash_tree_root(parent_crosslink)
    assert data.crosslink.data_root == spec.ZERO_HASH  # [to be removed in phase 1]
    spec.validate_indexed_attestation(state, spec.convert_to_indexed(state, attestation))


def process_deposit(spec, state, deposit) -> None:
    """Register a validator or top up its balance from an Eth1 deposit."""
    assert spec.verify_merkle_branch(
        leaf=spec.hash_tree_root(deposit.data),
        proof=deposit.proof,
        depth=spec.DEPOSIT_CONTRACT_TREE_DEPTH,
        index=state.deposit_index,
        root=state.latest_eth1_data.deposit_root,
    )

    # Deposits must be processed in order
    state.deposit_index += 1

    pubkey = deposit.data.pubkey
    amount = deposit.data.amount
    # the registry through the state's view: a lookup of one key and an
    # append or a top-up, each costing its own rows, never a scan
    registry = spec.registry_view(state)
    index = registry.index_of_pubkey(pubkey)
    if index is None:
        # New validator: the deposit signature (proof of possession) must be
        # valid — but an invalid one just skips the deposit (the contract
        # can't filter them), it does not invalidate the block.
        if not spec.bls.bls_verify(pubkey, spec.signing_root(deposit.data), deposit.data.signature,
                                   spec.bls_domain(spec.DOMAIN_DEPOSIT)):
            return

        registry.append(spec.Validator(
            pubkey=pubkey,
            withdrawal_credentials=deposit.data.withdrawal_credentials,
            activation_eligibility_epoch=spec.FAR_FUTURE_EPOCH,
            activation_epoch=spec.FAR_FUTURE_EPOCH,
            exit_epoch=spec.FAR_FUTURE_EPOCH,
            withdrawable_epoch=spec.FAR_FUTURE_EPOCH,
            effective_balance=min(amount - amount % spec.EFFECTIVE_BALANCE_INCREMENT,
                                  spec.MAX_EFFECTIVE_BALANCE),
        ), amount)
    else:
        registry.increase_balance(index, amount)


def process_voluntary_exit(spec, state, exit) -> None:
    registry = spec.registry_view(state)
    index = exit.validator_index
    current_epoch = spec.get_current_epoch(state)
    activation_epoch = registry.activation_epoch(index)
    exit_epoch = registry.exit_epoch(index)
    # Active, not yet exited, exit epoch reached, active long enough, signed
    assert activation_epoch <= current_epoch < exit_epoch
    assert exit_epoch == spec.FAR_FUTURE_EPOCH
    assert current_epoch >= exit.epoch
    assert current_epoch >= activation_epoch + spec.PERSISTENT_COMMITTEE_PERIOD
    # the message is the exit's root: computed only for a verify that reads it
    assert not spec.bls.bls_active or spec.bls.bls_verify(
        registry.pubkey(index), spec.signing_root(exit), exit.signature,
        spec.get_domain(state, spec.DOMAIN_VOLUNTARY_EXIT, exit.epoch))

    spec.initiate_validator_exit(state, index)


def process_transfer(spec, state, transfer) -> None:
    # Anti-overflow: amount and fee individually covered
    assert state.balances[transfer.sender] >= max(transfer.amount, transfer.fee)
    # Valid in exactly one slot
    assert state.slot == transfer.slot
    # Sender not yet activation-eligible, withdrawn, or keeps MAX_EFFECTIVE_BALANCE
    assert (
        state.validator_registry[transfer.sender].activation_eligibility_epoch == spec.FAR_FUTURE_EPOCH
        or spec.get_current_epoch(state) >= state.validator_registry[transfer.sender].withdrawable_epoch
        or transfer.amount + transfer.fee + spec.MAX_EFFECTIVE_BALANCE <= state.balances[transfer.sender]
    )
    # Withdrawal credentials must commit to the provided pubkey
    assert (bytes(state.validator_registry[transfer.sender].withdrawal_credentials)
            == spec.int_to_bytes(spec.BLS_WITHDRAWAL_PREFIX, length=1) + spec.hash(bytes(transfer.pubkey))[1:])
    assert spec.bls.bls_verify(transfer.pubkey, spec.signing_root(transfer), transfer.signature,
                               spec.get_domain(state, spec.DOMAIN_TRANSFER))

    spec.decrease_balance(state, transfer.sender, transfer.amount + transfer.fee)
    spec.increase_balance(state, transfer.recipient, transfer.amount)
    spec.increase_balance(state, spec.get_beacon_proposer_index(state), transfer.fee)
    # No dust balances
    assert not (0 < state.balances[transfer.sender] < spec.MIN_DEPOSIT_AMOUNT)
    assert not (0 < state.balances[transfer.recipient] < spec.MIN_DEPOSIT_AMOUNT)
