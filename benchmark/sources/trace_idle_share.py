"""1 - union of device-operation intervals / traced window, in percent."""
from benchmark import reduce


def read(reader: dict, seen) -> float | None:
    return None if seen.planes is None else reduce.idle_share(seen.planes)
