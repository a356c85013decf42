"""Sum of the window's deltas of the always-on counters named."""


def read(reader: dict, seen) -> float | None:
    found = [seen.counters[n] for n in reader["counters"] if n in seen.counters]
    return float(sum(found)) if found else None
