"""Blocks the spec rejects, for the sync mix's last comparison: the valid
block of the state's slot (`block_generator.py`) with one attestation
spoiled, one check of guarantee 5 a spoil. Each must be refused by the
served path and by the plain reference alike. A refused block leaves the
state half written (the spec discards such a state), so `keep` and
`put_back` restore what `process_block` writes before it raises, and every
spoiled block meets the state the valid one would have met."""
from __future__ import annotations


def _spoiled(spec, generator, state, seed: int):
    block = generator.block(state)
    attestations = block.body.attestations
    return block, attestations[seed % len(attestations)]


def bit_past_the_committees_end(spec, generator, state, seed: int):
    """A padding bit of the last byte set; where the committee fills its
    last byte, a byte more with its first bit set."""
    block, att = _spoiled(spec, generator, state, seed)
    size = len(spec.get_crosslink_committee(
        state, att.data.target_epoch, att.data.crosslink.shard))
    bits = bytearray(att.aggregation_bitfield)
    if size % 8:
        bits[-1] |= 0x80
    else:
        bits.append(0x01)
    att.aggregation_bitfield = bytes(bits)
    return block


def wrong_source_epoch(spec, generator, state, seed: int):
    block, att = _spoiled(spec, generator, state, seed)
    att.data.source_epoch += 1
    return block


def wrong_crosslink_parent_root(spec, generator, state, seed: int):
    block, att = _spoiled(spec, generator, state, seed)
    root = bytearray(bytes(att.data.crosslink.parent_root))
    root[seed % 32] ^= 0x01
    att.data.crosslink.parent_root = bytes(root)
    return block


def attestation_older_than_an_epoch(spec, generator, state, seed: int):
    """An attestation of a slot more than SLOTS_PER_EPOCH before the
    block's, sound in everything but its age."""
    block = generator.block(state)
    old = generator.attestations(
        state, int(state.slot) - int(spec.SLOTS_PER_EPOCH) - 1)
    attestations = block.body.attestations
    attestations[seed % len(attestations)] = old[seed % len(old)]
    return block


SPOILS = (bit_past_the_committees_end, wrong_source_epoch,
          wrong_crosslink_parent_root, attestation_older_than_an_epoch)


def keep(spec, state) -> tuple:
    """What `process_block` may write before it raises."""
    at = spec.get_current_epoch(state) % len(state.latest_randao_mixes)
    return (state.latest_block_header.copy(), state.latest_eth1_data.copy(),
            at, bytes(state.latest_randao_mixes[at]),
            len(state.eth1_data_votes), len(state.current_epoch_attestations),
            len(state.previous_epoch_attestations))


def put_back(state, header, eth1, at, mix, votes, current, previous) -> None:
    state.latest_block_header = header
    state.latest_eth1_data = eth1
    state.latest_randao_mixes[at] = mix
    del state.eth1_data_votes[votes:]
    del state.current_epoch_attestations[current:]
    del state.previous_epoch_attestations[previous:]
