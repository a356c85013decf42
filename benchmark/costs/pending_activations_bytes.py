"""The least HBM traffic of one `resident._pending_activations_traced`: the
eligibility and activation columns read at the storage's rows
(`registry_capacity`, where the configuration states one), one int32 out."""
from __future__ import annotations


def count(config: dict) -> int:
    rows = int(config.get("registry_capacity") or config["validators"])
    return rows * 2 * 8 + 4
