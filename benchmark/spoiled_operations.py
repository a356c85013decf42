"""Blocks the spec rejects for an operation that changes the registry, for
the dirty-slots mix's last comparison: the valid block of the state's slot
(`ops_generator.py`) with one operation spoiled, one check of guarantee 6
a spoil. Each must be refused by the served path and by the plain reference
alike, and the device's columns must stand as they stood. `keep` and
`put_back` restore the small fields `process_block` writes before it
raises (`spoiled_blocks`' and, for a block whose sound slashings ran before
the spoiled operation, the epoch's entry of `latest_slashed_balances`)."""
from __future__ import annotations

from benchmark import spoiled_blocks


def exit_of_an_exiting_validator(spec, generator, state, seed: int):
    """The block's first exit once more at its end: by then the validator
    is exiting."""
    block = generator.block(state)
    exits = block.body.voluntary_exits
    exits[-1] = exits[0].copy()
    return block


def exit_dated_in_the_future(spec, generator, state, seed: int):
    block = generator.block(state)
    exits = block.body.voluntary_exits
    exits[seed % len(exits)].epoch = int(spec.get_current_epoch(state)) + 1
    return block


def proposer_slashing_of_equal_headers(spec, generator, state, seed: int):
    block = generator.block(state)
    slashing = generator.proposer_slashing(
        int(state.slot), index=int(block.body.voluntary_exits[0].validator_index))
    slashing.header_2 = slashing.header_1.copy()
    block.body.proposer_slashings.append(slashing)
    return block


def attester_slashing_that_is_no_double_vote_and_no_surround(
        spec, generator, state, seed: int):
    """Two indexed attestations of one data: nothing to slash for."""
    block = generator.block(state)
    slashing = generator.attester_slashing(
        state, block.body,
        indices=[int(block.body.voluntary_exits[0].validator_index)])
    slashing.attestation_2.data = slashing.attestation_1.data.copy()
    del block.body.attester_slashings[:]
    block.body.attester_slashings.append(slashing)
    return block


SPOILS = (exit_of_an_exiting_validator, exit_dated_in_the_future,
          proposer_slashing_of_equal_headers,
          attester_slashing_that_is_no_double_vote_and_no_surround)


def keep(spec, state) -> tuple:
    at = int(spec.get_current_epoch(state)) % len(state.latest_slashed_balances)
    return (spoiled_blocks.keep(spec, state), at,
            int(state.latest_slashed_balances[at]))


def put_back(state, kept, at, slashed_balance) -> None:
    spoiled_blocks.put_back(state, *kept)
    if int(state.latest_slashed_balances[at]) != slashed_balance:
        state.latest_slashed_balances[at] = slashed_balance
