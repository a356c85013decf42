"""The least HBM traffic of one `resident._masked_leaves_traced`: for every
row of the storage (`registry_capacity`: the program runs over capacity
rows and masks those beyond the registry's length) its 48 pubkey and 32
credential bytes, five uint64 leaf columns and the bool one in, and its
32-byte leaf out."""
from __future__ import annotations


def count(config: dict) -> int:
    rows = int(config.get("registry_capacity") or config["validators"])
    return rows * (48 + 32 + 5 * 8 + 1 + 32)
