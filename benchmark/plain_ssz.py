"""The plain reference for guarantee 1: hash_tree_root(BeaconState) with
hashlib alone, from a schema written out here (ethereum/consensus-specs
v0.6.x specs/core/0_beacon-chain.md, "Data structures", and
specs/simple-serialize.md, "Merkleization"). It imports nothing of the
program: the state's small fields are read off the program's state object
by name into plain Python values (`read_state`), and the registry and
balances roots come from the hashlib merkleization of the fetched columns
(`reference.host_registry_balances_roots`).
"""
from __future__ import annotations

import hashlib

ZERO = b"\x00" * 32

# name -> [(field, type)]; a type is "uint64", "bool", "bytesN", "bytes"
# (variable length), a container's name, ("list", type) or ("vector", type)
SCHEMA = {
    "Fork": [("previous_version", "bytes4"), ("current_version", "bytes4"),
             ("epoch", "uint64")],
    "Crosslink": [("shard", "uint64"), ("start_epoch", "uint64"),
                  ("end_epoch", "uint64"), ("parent_root", "bytes32"),
                  ("data_root", "bytes32")],
    "AttestationData": [
        ("beacon_block_root", "bytes32"), ("source_epoch", "uint64"),
        ("source_root", "bytes32"), ("target_epoch", "uint64"),
        ("target_root", "bytes32"), ("crosslink", "Crosslink")],
    "PendingAttestation": [
        ("aggregation_bitfield", "bytes"), ("data", "AttestationData"),
        ("inclusion_delay", "uint64"), ("proposer_index", "uint64")],
    "Eth1Data": [("deposit_root", "bytes32"), ("deposit_count", "uint64"),
                 ("block_hash", "bytes32")],
    "BeaconBlockHeader": [
        ("slot", "uint64"), ("parent_root", "bytes32"),
        ("state_root", "bytes32"), ("body_root", "bytes32"),
        ("signature", "bytes96")],
    "BeaconState": [
        ("slot", "uint64"), ("genesis_time", "uint64"), ("fork", "Fork"),
        ("validator_registry", "registry_root"),    # hashlib, from the columns
        ("balances", "balances_root"),              # hashlib, from the columns
        ("latest_randao_mixes", ("vector", "bytes32")),
        ("latest_start_shard", "uint64"),
        ("previous_epoch_attestations", ("list", "PendingAttestation")),
        ("current_epoch_attestations", ("list", "PendingAttestation")),
        ("previous_justified_epoch", "uint64"),
        ("current_justified_epoch", "uint64"),
        ("previous_justified_root", "bytes32"),
        ("current_justified_root", "bytes32"),
        ("justification_bitfield", "uint64"),
        ("finalized_epoch", "uint64"), ("finalized_root", "bytes32"),
        ("current_crosslinks", ("vector", "Crosslink")),
        ("previous_crosslinks", ("vector", "Crosslink")),
        ("latest_block_roots", ("vector", "bytes32")),
        ("latest_state_roots", ("vector", "bytes32")),
        ("latest_active_index_roots", ("vector", "bytes32")),
        ("latest_slashed_balances", ("vector", "uint64")),
        ("latest_block_header", "BeaconBlockHeader"),
        ("historical_roots", ("list", "bytes32")),
        ("latest_eth1_data", "Eth1Data"),
        ("eth1_data_votes", ("list", "Eth1Data")),
        ("deposit_index", "uint64"),
    ],
}


# -- reading the program's state into plain values ---------------------------

def read_value(value, typ):
    if typ in ("registry_root", "balances_root"):
        return None                 # never read off the state
    if typ in ("uint64", "bool"):
        return int(value)
    if isinstance(typ, str) and typ.startswith("bytes"):
        return bytes(value)
    if isinstance(typ, tuple):
        return [read_value(v, typ[1]) for v in value]
    return {f: read_value(getattr(value, f), t) for f, t in SCHEMA[typ]}


def read_state(state) -> dict:
    """The state's small fields as plain ints, bytes, lists and dicts: a
    copy, so that what the program does next does not reach it."""
    return read_value(state, "BeaconState")


# -- Merkleization ------------------------------------------------------------

def _sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def merkleize(chunks: list) -> bytes:
    """Root over the chunks, zero chunks up to the next power of two."""
    level = list(chunks) or [ZERO]
    zero = ZERO
    while len(level) > 1:
        if len(level) % 2:
            level.append(zero)
        level = [_sha(level[i] + level[i + 1])
                 for i in range(0, len(level), 2)]
        zero = _sha(zero + zero)
    return level[0]


def pack(data: bytes) -> list:
    data += b"\x00" * (-len(data) % 32)
    return [data[i:i + 32] for i in range(0, len(data), 32)]


def mix_in_length(root: bytes, length: int) -> bytes:
    return _sha(root + length.to_bytes(32, "little"))


def root_of(value, typ, big_roots: dict | None = None) -> bytes:
    if typ == "uint64":
        return value.to_bytes(8, "little") + b"\x00" * 24
    if typ == "bool":
        return bytes([value]) + b"\x00" * 31
    if typ in ("registry_root", "balances_root"):
        return big_roots[typ]
    if typ == "bytes":
        return mix_in_length(merkleize(pack(value)), len(value))
    if isinstance(typ, str) and typ.startswith("bytes"):
        assert len(value) == int(typ[5:]), (typ, len(value))
        return merkleize(pack(value))
    if isinstance(typ, tuple):
        kind, elem = typ
        if elem in ("uint64", "bool"):
            size = 8 if elem == "uint64" else 1
            root = merkleize(pack(b"".join(
                v.to_bytes(size, "little") for v in value)))
        else:
            root = merkleize([root_of(v, elem) for v in value])
        return mix_in_length(root, len(value)) if kind == "list" else root
    return merkleize([root_of(value[f], t, big_roots)
                      for f, t in SCHEMA[typ]])


def state_root(plain_state: dict, registry_root: bytes,
               balances_root: bytes) -> bytes:
    return root_of(plain_state, "BeaconState",
                   {"registry_root": registry_root,
                    "balances_root": balances_root})
