"""The least HBM traffic of one `incremental._update_bucket_traced` on the
registry forest: the bucket's leaf rows in and into level 0, and for every
level of the tree one stored sibling row read and one parent row written
a lane. A row is eight uint32 words. The program is latency-bound (twenty
dependent pair hashes of 32 lanes each), which is what the share shows;
both forests run the module, and the median execution is the registry's
(every block dirties it, the balances forest only a slashing's block)."""
from __future__ import annotations

BUCKET = 32         # incremental.bucket_indices' floor: a block's dirty set
ROW_BYTES = 8 * 4


def count(config: dict) -> int:
    depth = (int(config["validators"]) - 1).bit_length()
    rows = 2 + 2 * depth    # leaves in and written; a sibling and a parent a level
    return BUCKET * ROW_BYTES * rows
