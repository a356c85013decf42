"""A run that holds what a `span_median` reader looks for, and the value it must read."""
from synthetic_run import span


def record(reader: dict) -> tuple:
    return dict(spans=[span(reader["span"], d) for d in (0.020, 0.030, 0.010)]), \
        0.020 * reader.get("scale", 1.0)
