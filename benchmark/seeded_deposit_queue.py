"""The seeded state of a mature chain DURING A DEPOSIT RUSH:
`seeded_mature.seeded_mature_checkpoint`'s state (the last slot of an epoch
past PERSISTENT_COMMITTEE_PERIOD, every validator active since epoch 0)
whose eth1 chain is `outstanding` deposits ahead of `deposit_index`, so that
every block MUST carry `min(MAX_DEPOSITS, outstanding)` of them
(`check_operations`).

The state's `latest_eth1_data` (and the voting period's votes so far, which
are for it) names `deposit_count` = V + outstanding and the root of the
deposit contract's depth-32 tree over that many leaves: the first V are
seeded 32-byte chunks (the deposits of the validators the entry registry
holds: nothing reads them again but the proofs' siblings), the rest are the
`DepositData` roots of the deposits to come. Of every `per_block` deposits
the first `new_per_block` register a new validator (seeded pubkey and
withdrawal credentials, `new_gwei`), the others top up a validator of the
entry registry drawn in order from a seeded permutation (`top_up_gwei`,
under that validator's own pubkey). Every signature is seeded bytes (nothing
verifies a proof of possession with BLS off).

Everything is hashed with hashlib: the `DepositData` roots (7 pairs a
deposit), the tree (V + outstanding pairs and a few), and the proofs are cut
from the finished tree's levels. The program's `process_deposit` takes none
of it on trust: it proves every branch against the state's `deposit_root`.

Copies nothing of `seeded_mature` and edits nothing: the checkpoint's bytes
are patched where `Eth1Data` lies (a fixed-size container).
"""
from __future__ import annotations

import hashlib

import numpy as np

from benchmark import seeded_mature

DEPTH = 32      # DEPOSIT_CONTRACT_TREE_DEPTH of both presets


def _pair(a: bytes, b: bytes) -> bytes:
    return hashlib.sha256(a + b).digest()


def _zero_hashes() -> list:
    out = [bytes(32)]
    for _ in range(DEPTH):
        out.append(_pair(out[-1], out[-1]))
    return out


ZERO = _zero_hashes()


def deposit_data_root(pubkey: bytes, credentials: bytes, amount: int,
                      signature: bytes) -> bytes:
    """hash_tree_root(DepositData) by hashlib: four field roots (the 48-byte
    key pads to two chunks, the 96-byte signature to four) under two levels."""
    key = _pair(pubkey[:32], pubkey[32:] + bytes(16))
    sig = _pair(_pair(signature[:32], signature[32:64]),
                _pair(signature[64:], bytes(32)))
    return _pair(_pair(key, credentials),
                 _pair(amount.to_bytes(8, "little") + bytes(24), sig))


class DepositQueue:
    """The deposits to come, in order, and the finished tree they are proved
    against. Deposit `i` of the queue is leaf `first + i` of the tree."""

    def __init__(self, earlier_leaves: bytes, pubkeys: np.ndarray,
                 credentials: np.ndarray, amounts: np.ndarray,
                 signatures: np.ndarray):
        self.first = len(earlier_leaves) // 32
        self.pubkeys, self.credentials = pubkeys, credentials
        self.amounts, self.signatures = amounts, signatures
        # level d: the concatenated 32-byte nodes
        self.levels = _tree_levels(earlier_leaves + b"".join(
            deposit_data_root(pubkeys[i].tobytes(), credentials[i].tobytes(),
                              int(amounts[i]), signatures[i].tobytes())
            for i in range(len(amounts))))

    def __len__(self) -> int:
        return len(self.amounts)

    @property
    def root(self) -> bytes:
        return self.levels[DEPTH]

    def proof(self, index: int) -> list:
        """The DEPTH siblings of leaf `index`, cut from the tree."""
        out = []
        for d in range(DEPTH):
            sibling = ((index >> d) ^ 1) * 32
            level = self.levels[d]
            out.append(level[sibling:sibling + 32] if sibling < len(level)
                       else ZERO[d])
        return out

    def deposits(self, spec, deposit_index: int, count: int) -> list:
        """The `count` deposits from the chain's `deposit_index` on, as a
        block carries them."""
        out = []
        for index in range(deposit_index, deposit_index + count):
            i = index - self.first
            out.append(spec.Deposit(
                proof=self.proof(index),
                data=spec.DepositData(
                    pubkey=self.pubkeys[i].tobytes(),
                    withdrawal_credentials=self.credentials[i].tobytes(),
                    amount=int(self.amounts[i]),
                    signature=self.signatures[i].tobytes())))
        return out


def _tree_levels(leaves: bytes) -> list:
    """Every level of the depth-DEPTH tree over the concatenated `leaves`,
    zero subtrees beyond them: level DEPTH is the root."""
    levels = [leaves]
    for d in range(DEPTH):
        level = levels[-1]
        if (len(level) // 32) % 2:
            level = level + ZERO[d]
        sha = hashlib.sha256
        levels.append(b"".join(sha(level[i:i + 64]).digest()
                               for i in range(0, len(level), 64)))
    return levels


def build_queue(entry_pubkeys: np.ndarray, entry_credentials: np.ndarray,
                seed: int, mix: dict) -> DepositQueue:
    """The queue of `mix["outstanding_deposits"]` deposits behind a registry
    whose identity columns are `entry_*` (`[V, 48]` and `[V, 32]` uint8)."""
    v = entry_pubkeys.shape[0]
    n = int(mix["outstanding_deposits"])
    per_block = int(mix["deposits_per_block"])
    new_per_block = int(mix["new_validators_per_block"])
    rng = np.random.default_rng([seed, 0xDE9051])
    pubkeys = rng.integers(0, 256, (n, 48), dtype=np.uint8)
    credentials = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    signatures = rng.integers(0, 256, (n, 96), dtype=np.uint8)
    amounts = np.full(n, int(mix["new_validator_gwei"]), np.uint64)
    top_up = np.nonzero(np.arange(n) % per_block >= new_per_block)[0]
    # validators of the entry registry, in order from a seeded permutation
    # (round again where a test's registry is smaller than its top-ups)
    drawn = rng.permutation(v)[np.arange(len(top_up)) % v]
    pubkeys[top_up] = entry_pubkeys[drawn]
    credentials[top_up] = entry_credentials[drawn]
    amounts[top_up] = int(mix["top_up_gwei"])
    return DepositQueue(rng.bytes(32 * v), pubkeys, credentials, amounts,
                        signatures)


def seeded_deposit_queue_checkpoint(spec, validators: int, seed: int,
                                    mix: dict) -> tuple:
    """(the serialized entry state, its DepositQueue)."""
    from consensus_specs_tpu.utils.ssz.columns import (container_field_spans,
                                                       state_columns_from_bytes)
    from consensus_specs_tpu.utils.ssz.impl import serialize

    data = bytearray(seeded_mature.seeded_mature_checkpoint(spec, validators, seed))
    cols = state_columns_from_bytes(bytes(data), spec)
    queue = build_queue(np.asarray(cols["pubkey"]),
                        np.asarray(cols["withdrawal_credentials"]), seed, mix)
    eth1 = serialize(spec.Eth1Data(
        deposit_root=queue.root, deposit_count=validators + len(queue),
        block_hash=spec.ZERO_HASH), spec.Eth1Data)
    spans = container_field_spans(bytes(data), spec.BeaconState)
    lo, hi = spans["latest_eth1_data"]
    assert hi - lo == len(eth1)
    data[lo:hi] = eth1
    lo, hi = spans["eth1_data_votes"]       # a list of fixed-size votes
    assert (hi - lo) % len(eth1) == 0
    data[lo:hi] = eth1 * ((hi - lo) // len(eth1))
    return bytes(data), queue
