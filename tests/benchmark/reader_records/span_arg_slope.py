"""A run that holds what a `span_arg_slope` reader looks for, and the value it must read."""
from synthetic_run import span


def record(reader: dict) -> tuple:
    """130 more every 64 steps of `req`."""
    return dict(spans=[
        span(reader["span"], 1.0, req=64 * k, **{reader["arg"]: 1000 + 130 * k})
        for k in range(4)]), 130.0 * reader.get("per", 1) / 64
