"""A run that holds what a `roofline_bytes` reader looks for, and the value it must read."""
import importlib

from synthetic_run import OPS, planes

CONFIG = {"validators": 1_000_000}
PEAK = 819e9


def record(reader: dict) -> tuple:
    """Three executions of the module, the median at twice the least time
    its bytes allow; one of another module."""
    count = importlib.import_module(f"benchmark.costs.{reader['bytes']}").count
    least_ms = 1e3 * count(CONFIG) / PEAK
    name = reader["module_prefix"] + "(7)"
    modules = [(name, 10, 2 * least_ms), (name, 40, 3 * least_ms),
               (name, 70, least_ms), ("jit_other(1)", 90, 9 * least_ms)]
    return dict(planes=planes(OPS, [], modules=modules), config=CONFIG,
                peaks={reader["peak"]: PEAK}), 50.0
