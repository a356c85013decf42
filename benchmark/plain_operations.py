"""The plain reference for guarantee 6: what the registry-changing
operations of one block leave, in Python integers, numpy and hashlib, from
ethereum/consensus-specs v0.6.x specs/core/0_beacon-chain.md ("Proposer
slashings", "Attester slashings", "Voluntary exits",
"initiate_validator_exit", "slash_validator", "is_slashable_validator",
"is_slashable_attestation_data", "validate_indexed_attestation"). It
imports nothing of the program: a block's header, RANDAO, eth1 vote and
attestations are `plain_block`'s, held to committees over the epoch's own
active set (`plain_epoch_registry.Shuffles`); the operations here run on
the reference's OWN numpy columns, which they write, so that after every
block the device's columns can be compared with them row for row.

The handlers run in the spec's order (proposer slashings, attester
slashings, [attestations], voluntary exits) and make each of its checks,
raising `plain_block.Rejected` where the spec would.
`initiate_validator_exit` scans the whole registry twice an exit, as the
spec writes it (`plain_epoch_registry.initiate_validator_exit`): at a
million validators that is a millisecond a scan, and the reference is not
timed. Signatures are not verified (`bls_verification` is cut); a deposit
or a transfer is `Unsupported` (`deposits` is cut, MAX_TRANSFERS is 0).
"""
from __future__ import annotations

import numpy as np

from benchmark import plain_block, plain_epoch_registry
from benchmark.plain_block import Rejected, Unsupported

WRITTEN = ("exit_epoch", "withdrawable_epoch", "slashed", "balance")


def _validator(cols: dict, index: int) -> int:
    if not 0 <= index < int(cols["balance"].shape[0]):
        raise Rejected(f"an operation names validator {index}, outside the registry")
    return index


def is_slashable(cols: dict, index: int, epoch: int) -> bool:
    return (not bool(cols["slashed"][index])
            and int(cols["activation_epoch"][index]) <= epoch
            < int(cols["withdrawable_epoch"][index]))


def slash_validator(C: dict, cols: dict, table: list, index: int,
                    current: int, proposer: int) -> None:
    """slash_validator with the block's proposer as the whistleblower:
    `table` is latest_slashed_balances, written in place."""
    length = C["LATEST_SLASHED_EXIT_LENGTH"]
    plain_epoch_registry.initiate_validator_exit(C, cols, index, current)
    cols["slashed"][index] = True
    cols["withdrawable_epoch"][index] = current + length
    slashed_balance = int(cols["effective_balance"][index])
    table[current % length] += slashed_balance
    whistleblowing_reward = slashed_balance // C["WHISTLEBLOWING_REWARD_QUOTIENT"]
    proposer_reward = whistleblowing_reward // C["PROPOSER_REWARD_QUOTIENT"]
    balance = cols["balance"]
    balance[proposer] += np.uint64(proposer_reward)
    balance[proposer] += np.uint64(whistleblowing_reward - proposer_reward)
    have = int(balance[index])
    balance[index] = 0 if whistleblowing_reward > have \
        else have - whistleblowing_reward


def _validate_indexed(C: dict, cols: dict, att: dict) -> None:
    zero, one = att["custody_bit_0_indices"], att["custody_bit_1_indices"]
    if one:
        raise Rejected("indexed attestation: a custody bit is set")
    if len(zero) + len(one) > C["MAX_INDICES_PER_ATTESTATION"]:
        raise Rejected("indexed attestation: more indices than the maximum")
    if set(zero) & set(one):
        raise Rejected("indexed attestation: the custody sets intersect")
    if zero != sorted(zero) or one != sorted(one):
        raise Rejected("indexed attestation: indices out of order")
    for index in zero + one:
        _validator(cols, index)


def is_slashable_attestation_data(one: dict, two: dict) -> bool:
    double = one != two and one["target_epoch"] == two["target_epoch"]
    surround = (one["source_epoch"] < two["source_epoch"]
                and two["target_epoch"] < one["target_epoch"])
    return double or surround


def process_operations(C: dict, pre: dict, cols: dict, body: dict,
                       proposer: int) -> dict:
    """The block's proposer slashings, attester slashings and voluntary
    exits on `cols` (written in place) and on a copy of
    `pre["latest_slashed_balances"]`, which is returned with the rows the
    block touched: {"latest_slashed_balances": [...], "rows": [...]}."""
    if body["deposits"] or body["transfers"]:
        raise Unsupported("the block carries a deposit or a transfer")
    spe = C["SLOTS_PER_EPOCH"]
    current = pre["slot"] // spe
    table = list(pre["latest_slashed_balances"])
    rows: set = set()
    for name, limit in (("proposer_slashings", "MAX_PROPOSER_SLASHINGS"),
                        ("attester_slashings", "MAX_ATTESTER_SLASHINGS"),
                        ("voluntary_exits", "MAX_VOLUNTARY_EXITS")):
        if len(body[name]) > C[limit]:
            raise Rejected(f"operations: more {name} than {limit}")

    for slashing in body["proposer_slashings"]:
        index = _validator(cols, slashing["proposer_index"])
        one, two = slashing["header_1"], slashing["header_2"]
        if one["slot"] // spe != two["slot"] // spe:
            raise Rejected("proposer slashing: the headers' epochs differ")
        if one == two:
            raise Rejected("proposer slashing: the headers are equal")
        if not is_slashable(cols, index, current):
            raise Rejected("proposer slashing: the proposer is not slashable")
        slash_validator(C, cols, table, index, current, proposer)
        rows |= {index, proposer}

    for slashing in body["attester_slashings"]:
        one, two = slashing["attestation_1"], slashing["attestation_2"]
        if not is_slashable_attestation_data(one["data"], two["data"]):
            raise Rejected("attester slashing: neither a double vote nor a surround")
        _validate_indexed(C, cols, one)
        _validate_indexed(C, cols, two)
        both = (set(one["custody_bit_0_indices"] + one["custody_bit_1_indices"])
                & set(two["custody_bit_0_indices"] + two["custody_bit_1_indices"]))
        slashed_any = False
        for index in sorted(both):
            if is_slashable(cols, index, current):
                slash_validator(C, cols, table, index, current, proposer)
                rows |= {index, proposer}
                slashed_any = True
        if not slashed_any:
            raise Rejected("attester slashing: nobody to slash")

    far = C["FAR_FUTURE_EPOCH"]
    for exit_ in body["voluntary_exits"]:
        index = _validator(cols, exit_["validator_index"])
        activation = int(cols["activation_epoch"][index])
        exiting = int(cols["exit_epoch"][index])
        if not activation <= current < exiting:
            raise Rejected("exit: the validator is not active")
        if exiting != far:
            raise Rejected("exit: the validator is exiting already")
        if current < exit_["epoch"]:
            raise Rejected("exit: dated in the future")
        if current < activation + C["PERSISTENT_COMMITTEE_PERIOD"]:
            raise Rejected("exit: not active for PERSISTENT_COMMITTEE_PERIOD")
        plain_epoch_registry.initiate_validator_exit(C, cols, index, current)
        rows.add(index)
    return {"latest_slashed_balances": table, "rows": sorted(rows)}


def process_block(C: dict, pre: dict, cols: dict, block: dict, shuffles) -> dict:
    """What `block` leaves: `plain_block.process_block`'s answer for the
    header, RANDAO mix, votes and PendingAttestations (with the body root
    over the whole body), and the operations' writes, made on `cols` in
    place only if every check of the block has passed."""
    body = block["body"]
    stripped = dict(block, body=dict(body, **{
        name: [] for name in plain_block.REGISTRY_OPERATIONS}))
    want = plain_block.process_block(C, pre, cols, stripped, shuffles)
    want["latest_block_header"]["body_root"] = plain_block.root_of(
        body, "BeaconBlockBody")
    spe = C["SLOTS_PER_EPOCH"]
    current = pre["slot"] // spe
    proposer = plain_block.proposer_index(
        C, shuffles.committees(pre, current, current), pre["slot"],
        np.asarray(cols["effective_balance"], np.uint64))
    trial = dict(cols, **{f: cols[f].copy() for f in WRITTEN})
    want.update(process_operations(C, pre, trial, body, proposer))
    for f in WRITTEN:
        cols[f][:] = trial[f]
    return want
