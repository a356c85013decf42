"""Least duration of a program span over the window, in the reader's
`scale` (1000 = ms): what the span costs when nothing falls into it. The
median of a span that a full garbage collection or a slow spell of the
host lands in now and then sits in one mode or the other from run to run;
the least does not."""


def read(reader: dict, seen) -> float | None:
    durations = [s["dur"] for s in seen.spans if s["name"] == reader["span"]]
    if not durations:
        return None
    return reader.get("scale", 1.0) * min(durations)
