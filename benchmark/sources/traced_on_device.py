"""The `inner` reader's value, but only from a run whose trace holds a device
plane: a program span's milliseconds or a work count taken on a host backend
(the harness's own CPU tests) is not reported under a chip metric's name."""
import importlib

from benchmark import reduce


def read(reader: dict, seen) -> float | None:
    if seen.planes is None or not reduce.device_planes(seen.planes):
        return None
    inner = reader["inner"]
    return importlib.import_module(
        f"benchmark.sources.{inner['kind']}").read(inner, seen)
