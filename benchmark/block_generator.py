"""The sync mix's generator: the block of the state's slot, full to the
preset's attestation limit.

Every committee of the slot MIN_ATTESTATION_INCLUSION_DELAY before the
block's attests in full, and its attestation reaches the proposer as
`aggregates` partial aggregates over disjoint runs of the committee in
committee order (eighths: 16 committees x 8 = 128 attestations at mainnet's
1,000,000 validators). The block carries no other operation, its RANDAO
reveal is seeded bytes (nothing verifies it with BLS off; the mix it leaves
is real), and it votes the state's own `latest_eth1_data`.

What depends on the state (the parent root, the block roots, the justified
checkpoint, the parent crosslinks) is read off it through the program's own
helpers, as the replay mix's generator reads the committee layout; only the
committees' sizes and shards are needed here, never the shuffle. What does
not (the reveals, the bitfields by committee size) is made once. The plain
reference (`plain_block.py`) takes none of this on trust: it holds every
block the generator builds to the spec's checks with its own committees.
"""
from __future__ import annotations

import numpy as np


def eighths(size: int, parts: int) -> list:
    """The aggregation bitfields of `parts` aggregates over disjoint runs
    of a committee of `size` in committee order; their union sets every
    member's bit and no padding bit. A run that would be empty is left out."""
    out = []
    for k in range(parts):
        lo, hi = size * k // parts, size * (k + 1) // parts
        if hi > lo:
            bits = np.zeros(-(-size // 8) * 8, np.uint8)
            bits[lo:hi] = 1
            out.append(np.packbits(bits, bitorder="little").tobytes())
    return out


class BlockGenerator:
    def __init__(self, spec, seed: int, aggregates: int):
        self.spec = spec
        self.aggregates = int(aggregates)
        self._rng = np.random.default_rng(seed)
        self._bitfields: dict = {}      # committee size -> its aggregates' bitfields

    def block(self, state):
        """The block of `state.slot`, on a state that `process_slots` has
        just brought there."""
        spec = self.spec
        block = spec.BeaconBlock(
            slot=int(state.slot),
            parent_root=spec.signing_root(state.latest_block_header))
        body = block.body
        body.randao_reveal = self._rng.bytes(96)
        body.eth1_data = state.latest_eth1_data.copy()
        body.attestations = self.attestations(
            state, int(state.slot) - int(spec.MIN_ATTESTATION_INCLUSION_DELAY))
        return block

    def attestations(self, state, slot: int) -> list:
        """The aggregates of every committee of `slot`, as a block at
        `state.slot` may include them."""
        spec = self.spec
        spe = int(spec.SLOTS_PER_EPOCH)
        epoch = slot // spe
        if epoch == spec.get_current_epoch(state):
            source = (state.current_justified_epoch, state.current_justified_root)
            parents = state.current_crosslinks
        else:
            source = (state.previous_justified_epoch, state.previous_justified_root)
            parents = state.previous_crosslinks
        active = len(spec.get_active_validator_indices(state, epoch))
        count = int(spec.get_epoch_committee_count(state, epoch))
        start_shard = int(spec.get_epoch_start_shard(state, epoch))
        head_root = spec.get_block_root_at_slot(state, slot)
        target_root = spec.get_block_root(state, epoch)
        per_slot = count // spe
        out = []
        for offset in range(slot % spe * per_slot, (slot % spe + 1) * per_slot):
            shard = (start_shard + offset) % int(spec.SHARD_COUNT)
            size = active * (offset + 1) // count - active * offset // count
            parent = parents[shard]
            parent_root = spec.hash_tree_root(parent)
            end_epoch = min(epoch, int(parent.end_epoch)
                            + int(spec.MAX_EPOCHS_PER_CROSSLINK))
            if size not in self._bitfields:
                self._bitfields[size] = eighths(size, self.aggregates)
            for bitfield in self._bitfields[size]:
                out.append(spec.Attestation(
                    aggregation_bitfield=bitfield,
                    data=spec.AttestationData(
                        beacon_block_root=head_root,
                        source_epoch=source[0], source_root=source[1],
                        target_epoch=epoch, target_root=target_root,
                        crosslink=spec.Crosslink(
                            shard=shard, start_epoch=parent.end_epoch,
                            end_epoch=end_epoch, parent_root=parent_root)),
                    custody_bitfield=bytes(len(bitfield))))
        return out
