"""A reading the harness or the driver took itself, by its key."""


def read(reader: dict, seen) -> float | None:
    value = seen.values.get(reader["key"])
    return None if value is None else float(value)
