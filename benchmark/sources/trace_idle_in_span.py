"""The device's idle time (first device plane) while the host is inside the
program annotation `span`, in percent of the traced window: how much of the
window the chip waits for that part of the host program. Whatever opens
inside the span is inside it by position, whatever its name (the forests
that a resumed core's first slot root builds are `resident.forests` under
`resident.slot_root.forests`). None where the trace holds no such
annotation."""
from benchmark import reduce


def _overlap(xs: list, ys: list) -> float:
    """Total overlap of two sorted lists of disjoint (start, end) pairs."""
    total, j = 0.0, 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            total += min(b, ys[k][1]) - max(a, ys[k][0])
            k += 1
    return total


def read(reader: dict, seen) -> float | None:
    if seen.planes is None:
        return None
    devices = reduce.device_planes(seen.planes)
    span = reader["span"]
    own = [(e.start_ns, e.end_ns)
           for e in reduce.annotations(seen.planes, prefix=span)
           if e.name == span]
    if not devices or not own:
        return None
    lo, hi = reduce.window(seen.planes)
    gaps, cursor = [], lo
    for a, b in reduce.busy_intervals(devices[0], lo, hi):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = b
    if hi > cursor:
        gaps.append((cursor, hi))
    return 100.0 * _overlap(gaps, reduce.merged(own, lo, hi)) / (hi - lo)
