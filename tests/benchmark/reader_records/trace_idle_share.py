"""A run that holds what a `trace_idle_share` reader looks for, and the value it must read."""
from synthetic_run import OPS, planes


def record(reader: dict) -> tuple:
    return dict(planes=planes(OPS, [])), 80.0
