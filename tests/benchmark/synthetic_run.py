"""Hand-built pieces of what one run observed (`benchmark.sources.Seen`):
program span records, and profiler planes with a 100 ms `bench.window`.
The readers' tests and `reader_records/<kind>.py` build their runs from
these."""
from __future__ import annotations

from benchmark import reduce
from benchmark.reduce import Event, Line, Plane
from benchmark.sources import Seen

MS = 1e6    # the planes' clock is nanoseconds

# two device operations: the device idles in [0,20] [30,60] [70,100] of the window
OPS = [("fusion.1", 20, 10), ("fusion.2", 60, 10)]


def planes(ops, notes, device=True, modules=()):
    """A 100 ms `bench.window`; device operations, executed modules and
    host annotations, each (name, start ms, duration ms)."""
    host = Plane(reduce.HOST_PLANE, [Line("python", [
        Event(reduce.WINDOW_ANNOTATION, 0.0, 100 * MS),
        *(Event(n, a * MS, d * MS) for n, a, d in notes)])])
    if not device:
        return [host]
    return [Plane(reduce.DEVICE_PLANE_PREFIX + "0", [
        Line(reduce.OPS_LINE, [Event(n, a * MS, d * MS) for n, a, d in ops]),
        Line(reduce.MODULES_LINE, [Event(n, a * MS, d * MS)
                                   for n, a, d in modules])]), host]


def on_a_host_backend(seen: Seen) -> Seen:
    """The same run traced where no device plane is written."""
    return seen._replace(planes=[
        p for p in (seen.planes or planes([], []))
        if p.name == reduce.HOST_PLANE])


def seen(**kw) -> Seen:
    base = dict(spans=[], counters={}, values={}, planes=None,
                config={"validators": 1_000_000}, mix={}, peaks={})
    return Seen(**dict(base, **kw))


def span(name, dur, id=0, parent_id=0, req=None, **args):
    return {"name": name, "dur": dur, "id": id, "parent_id": parent_id,
            "req": req, "args": args or None}
