"""The least HBM traffic ONE chip has for `_epoch_transition_traced` under
a serving mesh of the configuration's `chips`: its rows of every column
(in and out) and of every participation fact (in), the validator axis
padded to a multiple of `chips` as the mesh pads it; the two per-shard
balance tables and the slashed-balances vector are replicated, so every
chip reads them whole. The collectives' traffic is not HBM's and is left
out. `chips` absent is one chip: the count is then `epoch_program_bytes`'s."""
from __future__ import annotations

from benchmark.costs.epoch_program_bytes import (
    COLUMN_BYTES, INPUT_BYTES, LATEST_SLASHED_EXIT_LENGTH, SHARD_COUNT)


def count(config: dict) -> int:
    chips = int(config.get("chips", 1))
    rows = -(-int(config["validators"]) // chips)
    return (rows * (2 * COLUMN_BYTES + INPUT_BYTES) + 2 * SHARD_COUNT * 8
            + 2 * LATEST_SLASHED_EXIT_LENGTH * 8)
