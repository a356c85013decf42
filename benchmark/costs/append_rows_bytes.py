"""The least HBM traffic of one `resident._append_rows_traced`: the seven
validator columns are not donated, so each is read and written whole at the
storage's rows (`registry_capacity`, where the configuration states one: the
program runs over capacity rows, not over the registry's length); the
identity matrices are donated and take only the bucket's rows; the bucket's
host rows come in (48 + 32 identity bytes, an effective balance, a balance
and an index each)."""
from __future__ import annotations

BUCKET = 16         # resident._APPEND_BUCKET: MAX_DEPOSITS
COLUMN_BYTES = 6 * 8 + 1        # six uint64 columns and the bool one, a row


def count(config: dict) -> int:
    rows = int(config.get("registry_capacity") or config["validators"])
    return 2 * rows * COLUMN_BYTES + BUCKET * (2 * (48 + 32) + 2 * 8 + 4
                                               + COLUMN_BYTES)
