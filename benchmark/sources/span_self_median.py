"""Median self time of a program span, in the reader's `scale` (1000 = ms):
its duration minus the durations of the records whose `parent_id` is its
`id` - what the span spends in none of its children."""
import statistics
from collections import defaultdict


def read(reader: dict, seen) -> float | None:
    own = [s for s in seen.spans
           if s["name"] == reader["span"] and s.get("id")]
    if not own:
        return None
    in_children: dict = defaultdict(float)
    for s in seen.spans:
        in_children[s.get("parent_id", 0)] += s["dur"]
    return reader.get("scale", 1.0) * statistics.median(
        s["dur"] - in_children[s["id"]] for s in own)
