"""The deposit-queue mix's generator: `block_generator.BlockGenerator`'s
block (every committee of the slot four before, as ONE full aggregate a
committee) carrying the deposits the chain owes: `min(MAX_DEPOSITS,
deposit_count - deposit_index)` of them, which `check_operations` demands of
every block, in the queue's order (`seeded_deposit_queue.DepositQueue`: of
every 16 the first 12 register a new validator, the last 4 top up one of
the entry registry), each with its 32-deep proof against the state's
`deposit_root`, cut from the finished tree.

No exit, no slashing, no transfer. The generator reads the chain's
`deposit_index` and `deposit_count` off the state and nothing else of the
registry; it scans nothing. The plain reference (`plain_deposits.py`) takes
none of this on trust.
"""
from __future__ import annotations

from benchmark.block_generator import BlockGenerator


class DepositBlockGenerator(BlockGenerator):
    def __init__(self, spec, seed: int, mix: dict, queue):
        super().__init__(spec, seed, mix["aggregates_per_committee"])
        self.queue = queue

    def outstanding(self, state) -> int:
        return int(state.latest_eth1_data.deposit_count) - int(state.deposit_index)

    def block(self, state):
        block = super().block(state)
        count = min(int(self.spec.MAX_DEPOSITS), self.outstanding(state))
        block.body.deposits = self.queue.deposits(
            self.spec, int(state.deposit_index), count)
        return block
