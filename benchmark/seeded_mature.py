"""The seeded state of a MATURE chain: `reference.seeded_checkpoint`'s
registry, balances and identity (every validator active from epoch 0,
balances scattered one increment round 32 ETH, random identity bytes) at
the last slot of an epoch past PERSISTENT_COMMITTEE_PERIOD, so that a
voluntary exit of a validator active since epoch 0 is valid (at epoch 1,
where `seeded_checkpoint` stands, the spec rejects every exit).

What a chain of that age would hold is seeded beside it: the justified
checkpoints one and two epochs before the entry epoch and the finalized
one with the older of them (the entry boundary and the first warm-up
epoch run with the finality delay at 2-4 epochs, under
MIN_EPOCHS_TO_INACTIVITY_PENALTY, and full participation brings finality
back within the warm-up), every justification bit set, each shard's
crosslink ending the epoch before the entry, the eth1 votes of the voting
period so far, and the historical roots of its 8,192-slot periods. The
history vectors stay as `seeded_checkpoint` leaves them (zero roots and
mixes: PERF.md section 7 item 1), so the checkpoints' roots are the zero
roots `get_block_root` reads there.

Copies what it needs of `reference.seeded_checkpoint` and edits nothing:
`Deployment.__init__` keeps calling that one.
"""
from __future__ import annotations

import numpy as np


def entry_epoch(spec) -> int:
    """The first epoch in which a validator active since epoch 0 may exit,
    and one more: the entry's own boundary runs before any block."""
    return int(spec.PERSISTENT_COMMITTEE_PERIOD) + 1


def seeded_mature_checkpoint(spec, validators: int, seed: int) -> bytes:
    """A serialized mainnet-preset BeaconState at the last slot of
    `entry_epoch(spec)` with `validators` active validators, assembled from
    numpy columns (no Validator objects)."""
    from consensus_specs_tpu.utils.ssz.bulk import uint64_list_root_from_column
    from consensus_specs_tpu.utils.ssz.columns import state_bytes_from_columns

    rng = np.random.default_rng(seed)
    v = validators
    far = np.uint64(int(spec.FAR_FUTURE_EPOCH))
    max_eb = int(spec.MAX_EFFECTIVE_BALANCE)
    inc = int(spec.EFFECTIVE_BALANCE_INCREMENT)
    balance = (max_eb - inc // 2
               + rng.integers(0, 2 * inc, v)).astype(np.uint64)
    cols = {
        "pubkey": rng.integers(0, 256, (v, 48), dtype=np.uint8),
        "withdrawal_credentials": rng.integers(0, 256, (v, 32),
                                               dtype=np.uint8),
        "activation_eligibility_epoch": np.zeros(v, np.uint64),
        "activation_epoch": np.zeros(v, np.uint64),
        "exit_epoch": np.full(v, far, np.uint64),
        "withdrawable_epoch": np.full(v, far, np.uint64),
        "slashed": np.zeros(v, bool),
        "effective_balance": np.minimum(balance - balance % np.uint64(inc),
                                        np.uint64(max_eb)),
        "balance": balance,
    }
    epoch = entry_epoch(spec)
    spe = int(spec.SLOTS_PER_EPOCH)
    eth1 = spec.Eth1Data(deposit_root=b"\x42" * 32, deposit_count=v,
                         block_hash=spec.ZERO_HASH)
    light = spec.BeaconState(
        genesis_time=0, deposit_index=v, latest_eth1_data=eth1,
        previous_justified_epoch=epoch - 2, current_justified_epoch=epoch - 1,
        finalized_epoch=epoch - 2, justification_bitfield=2 ** 64 - 1)
    light.slot = (epoch + 1) * spe - 1
    index_root = uint64_list_root_from_column(np.arange(v, dtype=np.uint64))
    for i in range(spec.LATEST_ACTIVE_INDEX_ROOTS_LENGTH):
        light.latest_active_index_roots[i] = index_root
    for shard in range(spec.SHARD_COUNT):
        for links in (light.current_crosslinks, light.previous_crosslinks):
            links[shard] = spec.Crosslink(shard=shard, start_epoch=epoch - 2,
                                          end_epoch=epoch - 1)
    # the votes of the voting period's slots so far, all for the data the
    # chain holds; a root for each 8,192-slot period behind the entry
    period = int(spec.SLOTS_PER_ETH1_VOTING_PERIOD)
    light.eth1_data_votes = [eth1.copy() for _ in range((light.slot + 1) % period)]
    light.historical_roots = [
        rng.bytes(32)
        for _ in range((light.slot + 1) // int(spec.SLOTS_PER_HISTORICAL_ROOT))]
    return state_bytes_from_columns(light, cols, spec)
