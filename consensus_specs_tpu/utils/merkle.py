"""Merkle tree utilities: merkleization, full trees, and branch proofs.

Semantics match the reference's merkle_minimal
(/root/reference test_libs/pyspec/eth2spec/utils/merkle_minimal.py:1-54):
`merkleize_chunks` pads the chunk count to the next power of two with zero
chunks and reduces pairwise with SHA-256.

Re-designed for batch execution: each tree level is hashed with one call into
the pluggable pair-hasher (utils.hash.hash_pairs), so the TPU backend hashes a
whole level as a single [N,16]x uint32 kernel launch rather than N host calls.
"""
from __future__ import annotations

from typing import List, Sequence

from .hash import ZERO_BYTES32, hash_pairs, sha256, zerohashes


def next_power_of_two(v: int) -> int:
    if v <= 0:
        return 1
    return 1 << (v - 1).bit_length()


def tree_depth(count: int) -> int:
    """Levels of the power-of-two-padded tree over `count` chunks (SSZ
    merkleize padding, specs/simple-serialize.md:139-147): 0 and 1 chunks
    need no hashing, everything else pads up to next_power_of_two.

    Shared by merkleize_chunks and the incremental forest
    (utils/ssz/incremental.py), which takes a list's root at the level
    this gives for its logical length — a leaf count crossing a power of
    two inside the tree's capacity moves the root one level up."""
    return (next_power_of_two(count) - 1).bit_length()


def merkleize_chunks(chunks: Sequence[bytes]) -> bytes:
    """Root of the power-of-two-padded binary tree over 32-byte chunks."""
    count = len(chunks)
    if count == 0:
        return ZERO_BYTES32
    depth_needed = tree_depth(count)
    level = list(chunks)
    depth = 0
    while len(level) > 1 or depth < depth_needed:
        if len(level) % 2 == 1:
            level.append(zerohashes[depth])
        level = hash_pairs([level[i] + level[i + 1] for i in range(0, len(level), 2)])
        depth += 1
    return level[0]


def calc_merkle_tree_from_leaves(values: Sequence[bytes], layer_count: int = 32) -> List[List[bytes]]:
    """All layers of a fixed-depth tree (layer 0 = leaves), zero-padded."""
    values = list(values)
    tree: List[List[bytes]] = [list(values)]
    for h in range(layer_count):
        if len(values) % 2 == 1:
            values = values + [zerohashes[h]]
        values = hash_pairs([values[i] + values[i + 1] for i in range(0, len(values), 2)])
        tree.append(values)
    return tree

def get_merkle_root(values: Sequence[bytes], pad_to: int = 1) -> bytes:
    """Root of a tree of exactly `pad_to` leaves (zero-padded)."""
    layer_count = max(0, (pad_to - 1).bit_length())
    assert len(values) <= pad_to, f"{len(values)} leaves exceed pad_to={pad_to}"
    if len(values) == 0:
        return zerohashes[layer_count]
    tree = calc_merkle_tree_from_leaves(values, layer_count)
    return tree[-1][0]


def get_merkle_proof(tree: List[List[bytes]], item_index: int) -> List[bytes]:
    """Sibling path (bottom-up) for the leaf at item_index."""
    proof = []
    for i in range(len(tree) - 1):
        subindex = (item_index // (1 << i)) ^ 1
        proof.append(tree[i][subindex] if subindex < len(tree[i]) else zerohashes[i])
    return proof


def verify_merkle_branch(leaf: bytes, proof: Sequence[bytes], depth: int, index: int, root: bytes) -> bool:
    """Check a Merkle branch against a root (spec: verify_merkle_branch,
    /root/reference specs/core/0_beacon-chain.md:843-858)."""
    value = leaf
    for i in range(depth):
        if index // (2 ** i) % 2:
            value = sha256(proof[i] + value)
        else:
            value = sha256(value + proof[i])
    return value == root
