"""A program's share of the memory-bandwidth roofline, in percent: the bytes
it must move (benchmark/costs/<bytes>.py, from the shapes) over the
published peak, divided by the median device time of one execution of its
XLA module."""
import importlib
import statistics

from benchmark import reduce


def read(reader: dict, seen) -> float | None:
    if seen.planes is None:
        return None
    runs = reduce.module_seconds(seen.planes, reader["module_prefix"])
    if not runs:
        return None
    count = importlib.import_module(f"benchmark.costs.{reader['bytes']}").count
    least_s = count(seen.config) / seen.peaks[reader["peak"]]
    return 100.0 * least_s / statistics.median(runs)
