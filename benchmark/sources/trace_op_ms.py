"""Device time, or the number, of the operations of some kind inside a
program's executions: of the `XLA Ops` events that run while an XLA module
whose name starts with `module_prefix` executes inside the traced window,
those whose own name (`reduce.short_name`) starts with one of
`op_prefixes`. `stat: "ms"` sums their durations per execution, `"count"`
counts them. Several programs may share the prefix (the forest build at two
capacities): the median is taken over the executions of each program, by
its full module name, and the programs' medians are summed. Each device
plane is read for itself and the largest reading stands: the chip the
others wait for. None where the trace holds no such execution."""
import statistics
from collections import defaultdict

from benchmark import reduce


def _events(plane, line: str) -> list:
    return sorted((e for ln in plane.lines if ln.name == line
                   for e in ln.events), key=lambda e: e.start_ns)


def _plane_reading(plane, reader: dict, lo: float, hi: float) -> float | None:
    wanted = tuple(reader["op_prefixes"])
    ops = _events(plane, reduce.OPS_LINE)
    by_program: dict = defaultdict(list)
    i = 0
    for run in _events(plane, reduce.MODULES_LINE):
        if not (run.name.startswith(reader["module_prefix"])
                and lo <= run.start_ns and run.end_ns <= hi):
            continue
        while i < len(ops) and ops[i].start_ns < run.start_ns:
            i += 1
        inside = []
        while i < len(ops) and ops[i].start_ns < run.end_ns:
            if reduce.short_name(ops[i].name).startswith(wanted):
                inside.append(ops[i].duration_ns / 1e6)
            i += 1
        by_program[run.name].append(
            sum(inside) if reader["stat"] == "ms" else float(len(inside)))
    if not by_program:
        return None
    return sum(statistics.median(v) for v in by_program.values())


def read(reader: dict, seen) -> float | None:
    if seen.planes is None:
        return None
    devices = reduce.device_planes(seen.planes)
    if not devices:
        return None
    lo, hi = reduce.window(seen.planes)
    readings = [r for r in (_plane_reading(p, reader, lo, hi)
                            for p in devices) if r is not None]
    return float(max(readings)) if readings else None
