"""Rise of a value the program noted on a span, per `per` steps of the
span's request key `req` (least squares over the window's records): with
`req` the slot and `per` the slots of an epoch, how much the count grows
from one epoch to the next."""
import statistics


def read(reader: dict, seen) -> float | None:
    points = [(s["req"], s["args"][reader["arg"]]) for s in seen.spans
              if s["name"] == reader["span"] and s.get("req") is not None
              and reader["arg"] in (s.get("args") or {})]
    if len({req for req, _ in points}) < 2:
        return None
    slope = statistics.linear_regression(*zip(*points)).slope
    return float(reader.get("per", 1) * slope)
