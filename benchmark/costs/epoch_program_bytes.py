"""The least HBM traffic of `epoch_soa._epoch_transition_traced`: every
column in and out once, every participation fact in once, the two per-shard
balance vectors and the slashed-balances vector in, the scalars out. Sorts,
scans and the emulated 64-bit arithmetic move far more; that is what the
share shows."""
from __future__ import annotations

SHARD_COUNT = 1024                  # mainnet preset
LATEST_SLASHED_EXIT_LENGTH = 8192   # mainnet preset

# epoch_soa.ValidatorColumns: six uint64 columns and one bool, read once and
# written once (the columns are donated: the output takes the input's place)
COLUMN_BYTES = 6 * 8 + 1
# epoch_soa.EpochInputs per validator: five bool flags, one uint64 inclusion
# delay, two int32 (proposer, shard); read once
INPUT_BYTES = 5 * 1 + 8 + 2 * 4


def count(config: dict) -> int:
    per_validator = 2 * COLUMN_BYTES + INPUT_BYTES
    return (int(config["validators"]) * per_validator + 2 * SHARD_COUNT * 8
            + 2 * LATEST_SLASHED_EXIT_LENGTH * 8)
