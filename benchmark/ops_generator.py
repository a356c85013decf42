"""The dirty-slots mix's generator: `block_generator.BlockGenerator`'s block
(every committee of the slot four before, here as ONE full aggregate a
committee) carrying the operations that change the registry, at the rates
the mix's file gives:

- `exits_per_block` voluntary exits (mainnet's MAX_VOLUNTARY_EXITS, 16),
  each of a validator that is active, not exiting and not slashed, drawn in
  order from a seeded permutation of the registry. The generator keeps its
  own pointer into the permutation and the set of validators its own
  slashings have taken; it scans nothing and reads no column.
- one proposer slashing in the first block of every
  `proposer_slashing_every` (8): two headers of the block's slot with
  different body roots, of a validator drawn from the permutation's other
  end.
- one attester slashing in block `attester_slashing_at` (32) of the epoch,
  a double vote: two indexed attestations of the current epoch with
  different data whose `custody_bit_0_indices` are the same
  `attester_slashing_indices` (4) members of one committee of the block's
  own attestations.

No deposit, no transfer. Every signature is seeded bytes (nothing verifies
it with BLS off). A slot whose proposer is itself slashed goes without a
block, as on a chain (`block` returns None and draws nothing): the header
check would refuse it. The plain references (`plain_operations.py`,
`plain_block.py`) take none of this on trust.
"""
from __future__ import annotations

import numpy as np

from benchmark.block_generator import BlockGenerator


class OpsBlockGenerator(BlockGenerator):
    def __init__(self, spec, seed: int, mix: dict, validators: int):
        super().__init__(spec, seed, mix["aggregates_per_committee"])
        self.mix = mix
        # its own stream: the parent's draws the reveals
        rng = np.random.default_rng([seed, 0x0B5])
        self._order = rng.permutation(validators)
        self._next_exit = 0
        self._next_slashing = validators - 1
        self._slashed: set = set()
        self.skipped = 0

    # -- drawing validators ---------------------------------------------------

    def _draw_exit(self):
        """The next validator to exit, or None once the whole registry has
        been drawn (a test's registry after a dozen epochs; at 1,000,000
        validators a window draws a twentieth of it)."""
        while self._next_exit < len(self._order):
            index = int(self._order[self._next_exit])
            self._next_exit += 1
            if index not in self._slashed:      # a slashed validator is exiting
                return index
        return None

    def _draw_slashing(self) -> int:
        while True:
            index = int(self._order[self._next_slashing])
            self._next_slashing -= 1
            if index not in self._slashed:
                return index

    # -- the block -------------------------------------------------------------

    def block(self, state):
        spec = self.spec
        if spec.registry_view(state).slashed(spec.get_beacon_proposer_index(state)):
            self.skipped += 1
            return None
        block = super().block(state)
        body = block.body
        slot = int(state.slot)
        place = slot % int(spec.SLOTS_PER_EPOCH)
        epoch = int(spec.get_current_epoch(state))
        if place % int(self.mix["proposer_slashing_every"]) == 0:
            body.proposer_slashings.append(self.proposer_slashing(slot))
        if place == int(self.mix["attester_slashing_at"]):
            body.attester_slashings.append(self.attester_slashing(state, body))
        for _ in range(int(self.mix["exits_per_block"])):
            index = self._draw_exit()
            if index is None:
                break
            body.voluntary_exits.append(spec.VoluntaryExit(
                epoch=epoch, validator_index=index,
                signature=self._rng.bytes(96)))
        return block

    def proposer_slashing(self, slot: int, index: int | None = None):
        """Two headers of `slot` with different body roots, signed (in
        seeded bytes) by `index`, drawn if not given."""
        spec = self.spec
        if index is None:
            index = self._draw_slashing()
            self._slashed.add(index)
        one, two = (spec.BeaconBlockHeader(
            slot=slot, parent_root=self._rng.bytes(32),
            body_root=self._rng.bytes(32), signature=self._rng.bytes(96))
            for _ in range(2))
        return spec.ProposerSlashing(proposer_index=index, header_1=one,
                                     header_2=two)

    def attester_slashing(self, state, body, indices=None):
        """A double vote of `attester_slashing_indices` members of the
        committee of the block's last attestation: its data, and its data
        again with another head."""
        spec = self.spec
        data = body.attestations[-1].data
        if indices is None:
            committee = spec.get_crosslink_committee_array(
                state, data.target_epoch, data.crosslink.shard)
            indices = sorted(
                int(i) for i in committee[:int(self.mix["attester_slashing_indices"])])
            self._slashed.update(indices)
        other = data.copy()
        other.beacon_block_root = self._rng.bytes(32)
        one, two = (spec.IndexedAttestation(
            custody_bit_0_indices=list(indices), data=d,
            signature=self._rng.bytes(96)) for d in (data, other))
        return spec.AttesterSlashing(attestation_1=one, attestation_2=two)
