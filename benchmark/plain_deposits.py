"""The plain reference for the deposits' guarantee: what the deposits of one
block leave, in Python integers, numpy and hashlib, from
ethereum/consensus-specs v0.6.x specs/core/0_beacon-chain.md ("Deposits":
`process_deposit`, `verify_merkle_branch`; "Operations": the count a block
must carry). It imports nothing of the program: a block's header, RANDAO,
eth1 vote and attestations are `plain_block`'s, held to committees over the
epoch's own active set (`plain_epoch_registry.Shuffles`); the deposits run
on the reference's OWN registry (`Registry`: the seven numpy columns, the
keys and credentials row by row, and its own key-to-row dict), which they
write, so that after every block the device's columns and the identity rows
fetched from it can be compared with them row for row.

Every deposit proves its branch first (the `DepositData` root by the
schema's hashlib SSZ, then DEPOSIT_CONTRACT_TREE_DEPTH pairs up to the
state's `deposit_root`, at leaf `deposit_index`); `deposit_index` moves; a
key the registry does not hold appends a row (never eligible, active,
exiting or withdrawable, not slashed, the effective balance the amount
rounded down to an increment and capped), a key it holds tops its first
row up. The checks raise `plain_block.Rejected` where the spec would.
Signatures are not verified (`bls_verification` is cut: a proof of
possession is taken as valid); an exit, a slashing or a transfer is
`Unsupported` (the mix carries none: `plain_operations` holds those).
"""
from __future__ import annotations

import hashlib

import numpy as np

from benchmark import plain_block
from benchmark.plain_block import Rejected, Unsupported

COLUMNS = ("activation_eligibility_epoch", "activation_epoch", "exit_epoch",
           "withdrawable_epoch", "slashed", "effective_balance", "balance")
OTHER_OPERATIONS = ("proposer_slashings", "attester_slashings",
                    "voluntary_exits", "transfers")


class Registry:
    """The reference's own registry: `cols` (a dict of the seven columns,
    kept one object so that `plain_epoch_registry.Shuffles` sees every
    row it gains), `pubkeys` and `credentials` (a `bytes` a row) and
    `rows`, its own key -> first row."""

    def __init__(self, cols: dict, pubkeys: np.ndarray, credentials: np.ndarray):
        self.cols = {f: np.array(cols[f]) for f in COLUMNS}
        keys = np.ascontiguousarray(pubkeys).tobytes()
        self.pubkeys = [keys[48 * i:48 * i + 48] for i in range(len(pubkeys))]
        creds = np.ascontiguousarray(credentials).tobytes()
        self.credentials = [creds[32 * i:32 * i + 32]
                            for i in range(len(credentials))]
        self.rows: dict = {}
        for row, key in enumerate(self.pubkeys):
            self.rows.setdefault(key, row)

    def __len__(self) -> int:
        return len(self.pubkeys)


def branch_root(leaf: bytes, proof: list, depth: int, index: int) -> bytes:
    """verify_merkle_branch's walk: the root the branch gives `leaf` at
    `index`."""
    value = leaf
    for i in range(depth):
        if (index >> i) & 1:
            value = hashlib.sha256(proof[i] + value).digest()
        else:
            value = hashlib.sha256(value + proof[i]).digest()
    return value


def process_deposits(C: dict, pre: dict, registry: Registry, body: dict) -> dict:
    """The block's deposits on `registry`, written only if every one of
    them has passed: {"deposit_index": ..., "new_rows": [...],
    "topped_up": [...]}."""
    if any(body[name] for name in OTHER_OPERATIONS):
        raise Unsupported("the block carries an exit, a slashing or a transfer")
    deposits = body["deposits"]
    eth1 = pre["latest_eth1_data"]
    index = pre["deposit_index"]
    if len(deposits) != min(C["MAX_DEPOSITS"], eth1["deposit_count"] - index):
        raise Rejected("operations: not the deposits the chain owes")
    far = C["FAR_FUTURE_EPOCH"]
    inc, cap = C["EFFECTIVE_BALANCE_INCREMENT"], C["MAX_EFFECTIVE_BALANCE"]
    first_new = len(registry)
    appended: list = []         # (pubkey, credentials, amount)
    new_rows: dict = {}         # key -> row, of this block's appends
    top_ups: list = []          # (row, amount), in order
    for deposit in deposits:
        data = deposit["data"]
        leaf = plain_block.root_of(data, "DepositData")
        if len(deposit["proof"]) != C["DEPOSIT_CONTRACT_TREE_DEPTH"] or branch_root(
                leaf, deposit["proof"], C["DEPOSIT_CONTRACT_TREE_DEPTH"],
                index) != eth1["deposit_root"]:
            raise Rejected("deposit: the branch does not prove it at deposit_index")
        index += 1
        key = data["pubkey"]
        row = registry.rows.get(key, new_rows.get(key))
        if row is None:
            new_rows[key] = first_new + len(appended)
            appended.append((key, data["withdrawal_credentials"], data["amount"]))
        else:
            top_ups.append((row, data["amount"]))
    # every check has passed: the writes
    cols = registry.cols
    if appended:
        amounts = np.array([a for _, _, a in appended], np.uint64)
        k = len(appended)
        tails = {
            "activation_eligibility_epoch": np.full(k, far, np.uint64),
            "activation_epoch": np.full(k, far, np.uint64),
            "exit_epoch": np.full(k, far, np.uint64),
            "withdrawable_epoch": np.full(k, far, np.uint64),
            "slashed": np.zeros(k, bool),
            "effective_balance": np.minimum(amounts - amounts % np.uint64(inc),
                                            np.uint64(cap)),
            "balance": amounts}
        for f in COLUMNS:
            cols[f] = np.concatenate([cols[f], tails[f].astype(cols[f].dtype)])
        for key, credentials, _ in appended:
            registry.rows[key] = len(registry.pubkeys)
            registry.pubkeys.append(key)
            registry.credentials.append(credentials)
    for row, amount in top_ups:
        cols["balance"][row] += np.uint64(amount)
    return {"deposit_index": index,
            "new_rows": list(range(first_new, first_new + len(appended))),
            "topped_up": sorted({row for row, _ in top_ups})}


def process_block(C: dict, pre: dict, registry: Registry, block: dict,
                  shuffles) -> dict:
    """What `block` leaves: `plain_block.process_block`'s answer for the
    header, RANDAO mix, votes and PendingAttestations (with the body root
    over the whole body, and the count of deposits held to what the chain
    owes here, not there), and the deposits' writes, made on `registry`
    only if every check of the block has passed."""
    body = block["body"]
    stripped = dict(block, body=dict(body, **{
        name: [] for name in plain_block.REGISTRY_OPERATIONS}))
    settled = dict(pre, deposit_index=pre["latest_eth1_data"]["deposit_count"])
    want = plain_block.process_block(C, settled, registry.cols, stripped, shuffles)
    want["latest_block_header"]["body_root"] = plain_block.root_of(
        body, "BeaconBlockBody")
    want.update(process_deposits(C, pre, registry, body))
    return want
