"""A run that holds what a `span_self_median` reader looks for, and the value it must read."""
from synthetic_run import span


def record(reader: dict) -> tuple:
    return dict(spans=[span("child", 0.4, 2, 1), span(reader["span"], 1.0, 1)]), \
        0.6 * reader.get("scale", 1.0)
