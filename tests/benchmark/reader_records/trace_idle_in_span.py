"""A run that holds what a `trace_idle_in_span` reader looks for, and the value it must read."""
from synthetic_run import OPS, planes


def record(reader: dict) -> tuple:
    """The span's annotation over [10,50] against idle [0,20] + [30,60]."""
    return dict(planes=planes(OPS, [(reader["span"], 10, 40)])), 30.0
