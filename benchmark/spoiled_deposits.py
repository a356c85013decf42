"""Blocks the spec rejects for a deposit, for the deposit-queue mix's last
comparison: the valid block of the state's slot (`deposit_generator.py`)
with its deposit list spoiled, one check of the deposits' guarantee a
spoil. Each must be refused by the served path and by the plain reference
alike, and the registry's length, the device's columns, the mirrors, the
pubkey index and both forests must stand as they stood. `keep` and
`put_back` restore the small fields `process_block` writes before it
raises: `spoiled_blocks`' and `deposit_index`, which the sound deposits
ahead of the spoiled one moved."""
from __future__ import annotations

from benchmark import spoiled_blocks


def deposit_with_one_proof_node_flipped(spec, generator, state, seed: int):
    """One bit of one sibling of one deposit's branch, a new validator's
    among the block's sound ones: those before it have appended their rows
    by the time it is refused."""
    block = generator.block(state)
    deposits = block.body.deposits
    deposit = deposits[5 + seed % (len(deposits) - 5)]
    node = seed % len(deposit.proof)
    sibling = bytearray(bytes(deposit.proof[node]))
    sibling[seed % 32] ^= 1 << (seed % 8)
    deposit.proof[node] = bytes(sibling)
    return block


def deposit_proved_for_the_neighbouring_index(spec, generator, state, seed: int):
    """A sound branch of the tree, for the leaf beside the deposit's own."""
    block = generator.block(state)
    at = 2 + seed % (len(block.body.deposits) - 3)
    index = int(state.deposit_index) + at
    block.body.deposits[at].proof = generator.queue.proof(index + 1)
    return block


def one_deposit_fewer_than_outstanding(spec, generator, state, seed: int):
    block = generator.block(state)
    del block.body.deposits[-1]
    return block


def one_deposit_more_than_the_maximum(spec, generator, state, seed: int):
    block = generator.block(state)
    block.body.deposits += generator.queue.deposits(
        spec, int(state.deposit_index) + len(block.body.deposits), 1)
    return block


SPOILS = (deposit_with_one_proof_node_flipped,
          deposit_proved_for_the_neighbouring_index,
          one_deposit_fewer_than_outstanding,
          one_deposit_more_than_the_maximum)


def keep(spec, state) -> tuple:
    return spoiled_blocks.keep(spec, state), int(state.deposit_index)


def put_back(state, kept, deposit_index) -> None:
    spoiled_blocks.put_back(state, *kept)
    state.deposit_index = deposit_index
