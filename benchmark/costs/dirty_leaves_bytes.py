"""The least HBM traffic of one `resident._leaves_at_traced`: for each of
the bucket's validators its 48 pubkey and 32 credential bytes in (the
host's rows), five uint64 epochs and balances and one bool gathered from
the device columns, and its 32-byte root out. The program hashes 9 blocks
a validator on 32 lanes: latency, not traffic, which is what the share
shows."""
from __future__ import annotations

BUCKET = 32         # incremental.bucket_indices' floor: a block's dirty set


def count(config: dict) -> int:
    return BUCKET * (48 + 32 + 5 * 8 + 1 + 32)
