"""Root plans: a container type's hash_tree_root compiled once a type.

impl.hash_tree_root (and bulk.hash_tree_root_bulk's container branch)
walk the TYPE at every node of every value: `get_typed_values`, a chain of
`is_*_type` tests, `pack` / `chunkify`, a list a Merkle level. For a
container whose shape does not depend on its value that walk is the same
every time, so `plan_for` makes it once and writes down what is left: one
Python function a type that reads the fields in order, encodes each leaf,
and hashes the pairs of the fixed Merkle shape in a fixed order, the
positions that pair with a zero subtree filled in beforehand.

A type gets a plan when every field is a uint, a bool, a `BytesN`, `bytes`
(the one field whose shape follows its value: its pack, merkleize and
length mix are `_bytes_root`), or a container that has a plan itself.
A field that is a list or a vector (BeaconState, BeaconBlockBody,
HistoricalBatch, IndexedAttestation) leaves the type without one, and its
callers on bulk.hash_tree_root_bulk's field walk.

Who enters a plan, always through bulk.plan_roots: hash_tree_root_bulk's
container branch (one value: a state's small fields, and since PR 37
every container the spec roots, helpers.hash_tree_root: the parent
crosslink of each attestation of a block), its list branch (a list or
vector of planned elements that are not column-fast, as one batch: the
attestations of a block's body under process_block_header), and
host_tree._leaf_rows (the leaves a persistent tree hashes anew).

A plan is `plan(value) -> (root, pairs)`: the 32-byte root and the SHA-256
pair hashes of the tree that produced it (a `bytes` field's length mix is
not a pair of the tree and is not counted, as bulk.merkleize_few never
counted it). Nothing is kept between calls. The counters are the caller's
(bulk.plan_roots).

Differential gate: tests/test_root_plans.py (every phase-0 container of
both presets against impl.hash_tree_root; the spec's entry on every
phase-0 and phase-1 container and on the bodies a block brings).
"""
from __future__ import annotations

import hashlib
from typing import Any, Callable, List as PyList, Optional, Tuple

from ..hash import zerohashes
from .typing import (
    is_bool_type, is_bytes_type, is_bytesn_type, is_container_type,
    is_uint_type, uint_byte_size)

Plan = Callable[[Any], Tuple[bytes, int]]

_ZEROS = bytes(32)


def _tree_source(leaves: PyList[str]) -> Tuple[str, int]:
    """The expression that merkleizes `leaves` (expressions of 32-byte
    chunks, at least one) as merkle.merkleize_chunks does, an odd level
    closed by the zero subtree of its depth (`Z<depth>`), and the number
    of pairs it hashes."""
    depth = pairs = 0
    while len(leaves) > 1:
        if len(leaves) % 2:
            leaves = leaves + [f"Z{depth}"]
        leaves = [f"sha({a} + {b}).digest()"
                  for a, b in zip(leaves[::2], leaves[1::2])]
        pairs += len(leaves)
        depth += 1
    return leaves[0], pairs


def _bytes_root(data: Any) -> Tuple[bytes, int]:
    """Root of a `bytes` value (a list of `byte`): pack into chunks, the
    last zero-padded (no byte at all is one zero chunk), merkleize, mix in
    the length; with the pairs of the tree."""
    data = bytes(data)
    n = len(data)
    sha, pairs = hashlib.sha256, 0
    if n <= 32:
        root = data + _ZEROS[n:]
    else:
        # the chunks two by two: an odd last one pairs with a zero chunk,
        # which is what the zero subtree of depth 0 is
        data += bytes(-n % 64)
        level = [sha(data[i:i + 64]).digest() for i in range(0, len(data), 64)]
        pairs, depth = len(level), 1
        while len(level) > 1:
            if len(level) % 2:
                level.append(zerohashes[depth])
            level = [sha(level[i] + level[i + 1]).digest()
                     for i in range(0, len(level), 2)]
            pairs += len(level)
            depth += 1
        root = level[0]
    return sha(root + n.to_bytes(32, "little")).digest(), pairs


# What a plan's source may name: the hash, the `bytes` root, both bool
# leaves, `Z<d>` the zero subtree of depth d, `P<n>` n bytes of padding.
_NAMES = {"sha": hashlib.sha256, "bytes_root": _bytes_root,
          "TRUE": b"\x01" + _ZEROS[1:], "FALSE": _ZEROS,
          **{f"Z{d}": zero for d, zero in enumerate(zerohashes)},
          **{f"P{n}": _ZEROS[:n] for n in range(32)}}


def _compile(typ: Any) -> Optional[Plan]:
    fields = typ.get_fields()
    if not fields:
        return None
    env = dict(_NAMES)
    body, leaves, fixed_pairs, counted = [], [], 0, []
    for k, (name, ftyp) in enumerate(fields):
        leaf = f"x{k}"
        if is_bool_type(ftyp):
            body.append(f"{leaf} = TRUE if v.{name} else FALSE")
        elif is_uint_type(ftyp):
            size = uint_byte_size(ftyp)
            body.append(f"{leaf} = int(v.{name}).to_bytes({size}, 'little')"
                        f" + P{32 - size}")
        elif is_bytesn_type(ftyp):
            size = ftyp.length
            body += [f"{leaf} = v.{name}",
                     f"if len({leaf}) != {size}:",
                     f"    raise ValueError('{typ.__name__}.{name} holds %d "
                     f"bytes, not {size}' % len({leaf}))"]
            if size != 32:
                chunks = [f"{leaf}[{i}:{i + 32}]" for i in range(0, size, 32)]
                if size % 32:
                    chunks[-1] += f" + P{-size % 32}"
                expr, pairs = _tree_source(chunks or ["Z0"])
                body.append(f"{leaf} = {expr}")
                fixed_pairs += pairs
        elif is_bytes_type(ftyp):
            body.append(f"{leaf}, n{k} = bytes_root(v.{name})")
            counted.append(f"n{k}")
        elif plan_for(ftyp) is not None:
            env[f"plan{k}"] = plan_for(ftyp)
            body.append(f"{leaf}, n{k} = plan{k}(v.{name})")
            counted.append(f"n{k}")
        else:
            return None
        leaves.append(leaf)
    expr, pairs = _tree_source(leaves)
    total = " + ".join([str(fixed_pairs + pairs)] + counted)
    source = "\n    ".join(["def plan(v):"] + body
                           + [f"return {expr}, {total}"])
    exec(compile(source, f"<root plan of {typ.__name__}>", "exec"), env)
    plan = env["plan"]
    plan.__qualname__ = f"root_plan.{typ.__name__}"
    plan.__doc__ = f"(hash_tree_root, pairs hashed) of a {typ.__name__}."
    return plan


def plan_for(typ: Any) -> Optional[Plan]:
    """The root plan of container type `typ`, compiled the first time it
    is asked for and kept on the class (as `get_fields` keeps its list: a
    subclass that adds fields compiles its own; False stands for a type
    found to get none); None for a container whose fields do not all
    qualify, and for any type that is no container."""
    if not is_container_type(typ):
        return None
    cached = typ.__dict__.get("_root_plan")
    if cached is None:
        cached = typ._root_plan = _compile(typ) or False
    return cached or None
