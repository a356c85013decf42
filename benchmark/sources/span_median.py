"""Median duration of a program span, in the reader's `scale` (1000 = ms)."""
import statistics


def read(reader: dict, seen) -> float | None:
    durations = [s["dur"] for s in seen.spans if s["name"] == reader["span"]]
    if not durations:
        return None
    return reader.get("scale", 1.0) * statistics.median(durations)
