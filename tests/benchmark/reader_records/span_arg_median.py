"""A run that holds what a `span_arg_median` reader looks for, and the value it must read."""
from synthetic_run import span


def record(reader: dict) -> tuple:
    return dict(spans=[span(reader["span"], 1.0, **{reader["arg"]: n})
                       for n in (5, 9, 7)]), 7.0
