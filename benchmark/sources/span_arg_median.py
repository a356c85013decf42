"""Median over the window of a value the program noted on a span (a work
count riding on the span's record), by the span's name and the `arg`."""
import statistics


def read(reader: dict, seen) -> float | None:
    noted = [s["args"][reader["arg"]] for s in seen.spans
             if s["name"] == reader["span"]
             and reader["arg"] in (s.get("args") or {})]
    return float(statistics.median(noted)) if noted else None
