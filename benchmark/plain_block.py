"""The plain reference for guarantee 5: what one block leaves, in Python
integers, numpy and hashlib, from ethereum/consensus-specs v0.6.x
specs/core/0_beacon-chain.md ("Block processing": header, RANDAO, Eth1
data, attestations; "get_beacon_proposer_index"; "convert_to_indexed",
"validate_indexed_attestation"). It imports nothing of the program and takes
nothing the program has made but the block's inputs: the state's small
fields read before the block (`read_pre`), the `effective_balance` and
`slashed` columns fetched from the device, and the block's own fields
(`read_block`). Committees and the proposer come from `plain_epoch`'s
swap-or-not shuffle (hashlib), the body root from the schema written out
below.

It makes every check the spec makes of such a block for itself and raises
`Rejected` where the spec would: slot, parent root, proposer not slashed;
for every attestation the inclusion window, the target epoch, the FFG
source and target, the crosslink linkage against the parent crosslink's
root, both bitfields' length and padding, no custody bit, and the indexed
attestation's size, bounds, disjointness and order. Signatures are not
verified (`bls_verification` is cut) and a block that carries a
registry-touching operation is `Unsupported` (`registry_operations` is cut).
"""
from __future__ import annotations

import hashlib

import numpy as np

from benchmark import plain_epoch, plain_ssz

ZERO = plain_ssz.ZERO
REGISTRY_OPERATIONS = ("proposer_slashings", "attester_slashings", "deposits",
                       "voluntary_exits", "transfers")

# name -> [(field, type)], in plain_ssz.SCHEMA's notation, for what a block
# holds beyond the state's types
SCHEMA = dict(plain_ssz.SCHEMA, **{
    "IndexedAttestation": [
        ("custody_bit_0_indices", ("list", "uint64")),
        ("custody_bit_1_indices", ("list", "uint64")),
        ("data", "AttestationData"), ("signature", "bytes96")],
    "ProposerSlashing": [
        ("proposer_index", "uint64"), ("header_1", "BeaconBlockHeader"),
        ("header_2", "BeaconBlockHeader")],
    "AttesterSlashing": [("attestation_1", "IndexedAttestation"),
                         ("attestation_2", "IndexedAttestation")],
    "Attestation": [
        ("aggregation_bitfield", "bytes"), ("data", "AttestationData"),
        ("custody_bitfield", "bytes"), ("signature", "bytes96")],
    "DepositData": [
        ("pubkey", "bytes48"), ("withdrawal_credentials", "bytes32"),
        ("amount", "uint64"), ("signature", "bytes96")],
    "Deposit": [("proof", ("vector", "bytes32")), ("data", "DepositData")],
    "VoluntaryExit": [("epoch", "uint64"), ("validator_index", "uint64"),
                      ("signature", "bytes96")],
    "Transfer": [
        ("sender", "uint64"), ("recipient", "uint64"), ("amount", "uint64"),
        ("fee", "uint64"), ("slot", "uint64"), ("pubkey", "bytes48"),
        ("signature", "bytes96")],
    "BeaconBlockBody": [
        ("randao_reveal", "bytes96"), ("eth1_data", "Eth1Data"),
        ("graffiti", "bytes32"),
        ("proposer_slashings", ("list", "ProposerSlashing")),
        ("attester_slashings", ("list", "AttesterSlashing")),
        ("attestations", ("list", "Attestation")),
        ("deposits", ("list", "Deposit")),
        ("voluntary_exits", ("list", "VoluntaryExit")),
        ("transfers", ("list", "Transfer"))],
    "BeaconBlock": [
        ("slot", "uint64"), ("parent_root", "bytes32"),
        ("state_root", "bytes32"), ("body", "BeaconBlockBody"),
        ("signature", "bytes96")],
})

# the state's fields a block reads or writes
PRE_FIELDS = (
    "slot", "latest_randao_mixes", "latest_start_shard",
    "previous_justified_epoch", "current_justified_epoch",
    "previous_justified_root", "current_justified_root",
    "current_crosslinks", "previous_crosslinks", "latest_block_roots",
    "latest_active_index_roots", "latest_block_header", "latest_eth1_data",
    "eth1_data_votes", "deposit_index")


class Rejected(Exception):
    """The spec rejects the block: the check named failed."""


class Unsupported(Exception):
    """The block carries an operation this reference does not cover."""


def _sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


# -- reading the program's objects into plain values ---------------------------

def read_value(value, typ):
    if isinstance(typ, tuple):
        return [read_value(v, typ[1]) for v in value]
    if typ in SCHEMA:
        return {f: read_value(getattr(value, f), t) for f, t in SCHEMA[typ]}
    return plain_ssz.read_value(value, typ)


def read_block(block) -> dict:
    return read_value(block, "BeaconBlock")


def read_pre(state) -> dict:
    """The small fields a block reads or writes, as plain values: a copy."""
    types = dict(SCHEMA["BeaconState"])
    return {f: read_value(getattr(state, f), types[f]) for f in PRE_FIELDS}


def read_pending(attestations) -> list:
    return read_value(attestations, ("list", "PendingAttestation"))


# -- roots over the schema above -------------------------------------------------

def root_of(value, typ) -> bytes:
    if isinstance(typ, tuple) and typ[1] in SCHEMA:
        root = plain_ssz.merkleize([root_of(v, typ[1]) for v in value])
        return (plain_ssz.mix_in_length(root, len(value))
                if typ[0] == "list" else root)
    if typ in SCHEMA:
        return plain_ssz.merkleize([root_of(value[f], t)
                                    for f, t in SCHEMA[typ]])
    return plain_ssz.root_of(value, typ)


def signing_root(value, typ) -> bytes:
    """The root over every field but the last, the signature."""
    return plain_ssz.merkleize([root_of(value[f], t)
                                for f, t in SCHEMA[typ][:-1]])


# -- committees and the proposer ---------------------------------------------------

class Shuffles:
    """`plain_epoch.Committees` by epoch, kept while the epoch's seed and
    start shard stay what they were, whichever epoch they are seen from
    (one hashlib shuffle of the registry an epoch, not one a block)."""

    def __init__(self, C: dict, validators: int):
        self.C, self.validators = C, validators
        self._kept: dict = {}

    def committees(self, pre: dict, epoch: int, current: int):
        C = self.C
        mixes, roots = pre["latest_randao_mixes"], pre["latest_active_index_roots"]
        mix = mixes[(epoch + len(mixes) - C["MIN_SEED_LOOKAHEAD"]) % len(mixes)]
        root = roots[epoch % len(roots)]
        com = self._kept.get(epoch)
        if com is None or (com.mix, com.root) != (mix, root):
            if len(self._kept) > 4:
                self._kept.clear()
            com = plain_epoch.Committees(C, pre, self.validators, epoch, current)
            com.mix, com.root = mix, root
            com.seed = _sha(mix + root + epoch.to_bytes(32, "little"))
            self._kept[epoch] = com
        # get_epoch_start_shard, from where the state stands now: every
        # epoch has the same delta here (every validator active)
        com.start_shard = (pre["latest_start_shard"] + com.shards
                           - com.delta * (current - epoch)) % com.shards
        return com


def proposer_index(C: dict, com, slot: int, eff: np.ndarray) -> int:
    """get_beacon_proposer_index: balance-weighted rejection sampling over
    the first committee of the slot."""
    spe = C["SLOTS_PER_EPOCH"]
    epoch = slot // spe
    first = com.members(com.count // spe * (slot % spe))
    i = 0
    while True:
        candidate = int(first[(epoch + i) % len(first)])
        random_byte = _sha(com.seed + (i // 32).to_bytes(8, "little"))[i % 32]
        if int(eff[candidate]) * 255 >= C["MAX_EFFECTIVE_BALANCE"] * random_byte:
            return candidate
        i += 1


def _bits(bitfield: bytes, size: int, what: str) -> np.ndarray:
    """verify_bitfield, then the first `size` bits."""
    if len(bitfield) != (size + 7) // 8:
        raise Rejected(f"{what}: {len(bitfield)} bytes for a committee of {size}")
    bits = np.unpackbits(np.frombuffer(bitfield, np.uint8), bitorder="little")
    if bits[size:].any():
        raise Rejected(f"{what}: a bit set past the committee's end")
    return bits[:size].astype(bool)


# -- the block -------------------------------------------------------------------

def process_block(C: dict, pre: dict, cols: dict, block: dict,
                  shuffles: Shuffles) -> dict:
    """What `block` leaves on the state whose small fields are `pre` and
    whose registry columns are `cols`: the new `latest_block_header`, the
    RANDAO mix of the current epoch, the vote list with `latest_eth1_data`,
    and the PendingAttestations appended to the current and to the previous
    list, in the block's order."""
    spe = C["SLOTS_PER_EPOCH"]
    slot = pre["slot"]
    current = slot // spe
    previous = max(current - 1, C["GENESIS_EPOCH"])
    body = block["body"]
    eff = np.asarray(cols["effective_balance"], np.uint64)
    validators = int(eff.shape[0])

    # -- header
    if block["slot"] != slot:
        raise Rejected("header: the block's slot is not the state's")
    if block["parent_root"] != signing_root(pre["latest_block_header"],
                                            "BeaconBlockHeader"):
        raise Rejected("header: the parent root is not the latest header's")
    header = {"slot": slot, "parent_root": block["parent_root"],
              "state_root": ZERO, "body_root": root_of(body, "BeaconBlockBody"),
              "signature": b"\x00" * 96}
    com_now = shuffles.committees(pre, current, current)
    proposer = proposer_index(C, com_now, slot, eff)
    if bool(cols["slashed"][proposer]):
        raise Rejected("header: the proposer is slashed")

    # -- RANDAO
    mixes = pre["latest_randao_mixes"]
    mix = bytes(a ^ b for a, b in zip(mixes[current % len(mixes)],
                                      _sha(body["randao_reveal"])))

    # -- Eth1 data
    votes = pre["eth1_data_votes"] + [body["eth1_data"]]
    eth1 = pre["latest_eth1_data"]
    if votes.count(body["eth1_data"]) * 2 > C["SLOTS_PER_ETH1_VOTING_PERIOD"]:
        eth1 = body["eth1_data"]

    # -- operations
    if any(body[name] for name in REGISTRY_OPERATIONS):
        raise Unsupported("the block carries a registry-touching operation")
    if pre["latest_eth1_data"]["deposit_count"] != pre["deposit_index"]:
        raise Rejected("operations: outstanding deposits are not in the block")
    if len(body["attestations"]) > C["MAX_ATTESTATIONS"]:
        raise Rejected("operations: more attestations than MAX_ATTESTATIONS")
    appended = {current: [], previous: []}
    for att in body["attestations"]:
        data = att["data"]
        link = data["crosslink"]
        target = data["target_epoch"]
        # get_attestation_data_slot needs the target's committee layout
        if target > current + 1:
            raise Rejected("attestation: target epoch beyond the next")
        com = shuffles.committees(pre, target, current)
        att_slot = target * spe + com.offset_of(link["shard"]) // (com.count // spe)
        if not (att_slot + C["MIN_ATTESTATION_INCLUSION_DELAY"] <= slot
                <= att_slot + spe):
            raise Rejected("attestation: outside the inclusion window")
        if target not in (previous, current):
            raise Rejected("attestation: target epoch neither previous nor current")
        if target == current:
            ffg = (pre["current_justified_epoch"], pre["current_justified_root"])
            parent = pre["current_crosslinks"][link["shard"]]
        else:
            ffg = (pre["previous_justified_epoch"], pre["previous_justified_root"])
            parent = pre["previous_crosslinks"][link["shard"]]
        if ffg != (data["source_epoch"], data["source_root"]):
            raise Rejected("attestation: wrong FFG source")
        if link["start_epoch"] != parent["end_epoch"]:
            raise Rejected("attestation: crosslink does not start at its parent's end")
        if link["end_epoch"] != min(target, parent["end_epoch"]
                                    + C["MAX_EPOCHS_PER_CROSSLINK"]):
            raise Rejected("attestation: wrong crosslink end epoch")
        if link["parent_root"] != root_of(parent, "Crosslink"):
            raise Rejected("attestation: wrong crosslink parent root")
        if link["data_root"] != ZERO:
            raise Rejected("attestation: crosslink data root not zero")
        # convert_to_indexed and validate_indexed_attestation
        committee = com.members(com.offset_of(link["shard"]))
        attesting = np.sort(committee[_bits(
            att["aggregation_bitfield"], len(committee), "aggregation bitfield")])
        custody_1 = np.sort(committee[_bits(
            att["custody_bitfield"], len(committee), "custody bitfield")])
        custody_0 = attesting[~np.isin(attesting, custody_1)]
        if len(custody_1):
            raise Rejected("attestation: a custody bit is set")
        if len(custody_0) + len(custody_1) > C["MAX_INDICES_PER_ATTESTATION"]:
            raise Rejected("attestation: more indices than MAX_INDICES_PER_ATTESTATION")
        if np.intersect1d(custody_0, custody_1).size:
            raise Rejected("attestation: the custody sets intersect")
        if (np.diff(custody_0) < 0).any() or (np.diff(custody_1) < 0).any():
            raise Rejected("attestation: indices out of order")
        if len(custody_0) and not 0 <= int(custody_0[-1]) < validators:
            raise Rejected("attestation: an index names no validator")
        appended[target].append({
            "aggregation_bitfield": att["aggregation_bitfield"], "data": data,
            "inclusion_delay": slot - att_slot, "proposer_index": proposer})
    return {"latest_block_header": header, "randao_mix": mix,
            "eth1_data_votes": votes, "latest_eth1_data": eth1,
            "current_appended": appended[current],
            "previous_appended": appended[previous] if previous != current else []}
