"""A run that holds what a `trace_op_ms` reader looks for, and the value it must read."""
from benchmark import reduce
from benchmark.reduce import Event, Line, Plane
from synthetic_run import MS, planes


def record(reader: dict) -> tuple:
    """Two device planes, two executions of the program on each, inside
    the 100 ms window at [10,30] and [50,70]. On the first plane the wanted
    operations take 1 + 2 ms and then 3 ms (median 3.0, counts 2 and 1,
    median 1.5), on the second 4 + 1 ms and 2 + 1 ms (median 4.0, counts 2
    and 2): the second plane is the slower, and counts more. Left out: the
    other operations inside the executions, a wanted operation that runs
    outside every execution, one inside another program, and the execution
    that runs past the window's end with its wanted operation."""
    wanted, other = reader["op_prefixes"][0], "fusion"
    name = reader["module_prefix"] + "(7)"
    runs = [(name, 10, 20), (name, 50, 20), ("jit_other(1)", 32, 8),
            (name, 95, 10)]
    first = [(f"%{wanted}.1 = u64[] {wanted}(u64[] %p)", 11, 1),
             (f"{other}.5", 13, 6), (f"{wanted}.2", 20, 2),
             (f"{wanted}.9", 34, 5),                # inside another program
             (f"{wanted}.1", 44, 3),                # outside every execution
             (f"{wanted}-start.3", 52, 3), (f"{other}.5", 56, 6),
             (f"{wanted}.1", 96, 2)]                # past the window's end
    second = [(f"{wanted}.1", 12, 4), (f"{wanted}.2", 20, 1),
              (f"{other}.5", 22, 7), (f"{wanted}.1", 51, 2),
              (f"{wanted}.2", 60, 1), (f"{wanted}.1", 97, 1)]
    device0, host = planes(first, [], modules=runs)
    device1 = Plane(reduce.DEVICE_PLANE_PREFIX + "1", [
        Line(reduce.OPS_LINE, [Event(n, a * MS, d * MS) for n, a, d in second]),
        Line(reduce.MODULES_LINE, [Event(n, a * MS, d * MS)
                                   for n, a, d in runs])])
    want = {"ms": 4.0, "count": 2.0}[reader["stat"]]
    return dict(planes=[device0, device1, host]), want
