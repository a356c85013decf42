"""From a profiler trace (.xplane.pb) to device intervals, per-module time
and idle gaps by what the host was doing.

`load` turns the file into plain tuples with nothing but JAX; every
reduction below works on those, so that tests hand-build them. Times are
nanoseconds on the profiler's one clock; results are seconds.

    python benchmark/reduce.py <file.xplane.pb>     # look at a trace by hand
"""
from __future__ import annotations

import glob
import os
import sys
from collections import defaultdict
from typing import NamedTuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"            # one event per operation run on the device
MODULES_LINE = "XLA Modules"    # one event per executed program
WINDOW_ANNOTATION = "bench.window"
ANNOTATION_PREFIX = "bench."


class Event(NamedTuple):
    name: str
    start_ns: float
    duration_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.duration_ns


class Line(NamedTuple):
    name: str
    events: list


class Plane(NamedTuple):
    name: str
    lines: list


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    return [Plane(p.name, [
        Line(ln.name, [Event(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in ln.events])
        for ln in p.lines]) for p in data.planes]


# -- pieces -----------------------------------------------------------------

def device_planes(planes: list) -> list:
    return [p for p in planes if p.name.startswith(DEVICE_PLANE_PREFIX)]


def _line(plane: Plane, name: str) -> list:
    return [e for ln in plane.lines if ln.name == name for e in ln.events]


def annotations(planes: list, prefix: str = ANNOTATION_PREFIX) -> list:
    """The harness's own TraceAnnotations, from every host thread."""
    return sorted(
        (e for p in planes if p.name == HOST_PLANE for ln in p.lines
         for e in ln.events if e.name.startswith(prefix)),
        key=lambda e: e.start_ns)


def window(planes: list) -> tuple:
    """(start, end) of the traced window: the `bench.window` annotation."""
    spans = [e for e in annotations(planes) if e.name == WINDOW_ANNOTATION]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_ANNOTATION!r} annotation")
    return spans[0].start_ns, spans[0].end_ns


def merged(intervals, lo: float, hi: float) -> list:
    """Union of (start, end) intervals, clipped to [lo, hi]."""
    out: list = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_intervals(plane: Plane, lo: float, hi: float) -> list:
    return merged(((e.start_ns, e.end_ns) for e in _line(plane, OPS_LINE)),
                  lo, hi)


# -- reductions -------------------------------------------------------------

def device_busy(planes: list) -> dict | None:
    """busy_s: seconds in which an operation ran on the device, averaged
    over the device planes; window_s: the traced window. None where the
    trace has no device plane (a host backend)."""
    devices = device_planes(planes)
    if not devices:
        return None
    lo, hi = window(planes)
    busy = [sum(b - a for a, b in busy_intervals(p, lo, hi)) for p in devices]
    return {"busy_s": sum(busy) / len(busy) / 1e9, "window_s": (hi - lo) / 1e9,
            "chips": len(devices)}


def idle_share(planes: list) -> float | None:
    busy = device_busy(planes)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy["busy_s"] / busy["window_s"])


def module_seconds(planes: list, prefix: str) -> list:
    """Device seconds of each execution, inside the window, of the programs
    whose XLA module name starts with `prefix` (first device plane)."""
    devices = device_planes(planes)
    if not devices:
        return []
    lo, hi = window(planes)
    return [e.duration_ns / 1e9 for e in _line(devices[0], MODULES_LINE)
            if e.name.startswith(prefix) and lo <= e.start_ns and e.end_ns <= hi]


def short_name(name: str) -> str:
    """`%fusion.5 = (u32[...]) fusion(...)` -> `fusion.5`; a module's
    `jit_f(1234)` -> `jit_f`: the trace's own names, without the HLO text
    and the fingerprint."""
    return name.split(" = ", 1)[0].split("(", 1)[0].lstrip("%")


def top_device_ops(planes: list, n: int = 10) -> list:
    """[[name, seconds], ...]: the operations that took most device time
    in the window, summed by `<XLA module>/<op>` as the trace names them
    (the program has no named scopes yet)."""
    devices = device_planes(planes)
    if not devices:
        return []
    lo, hi = window(planes)
    modules = sorted(_line(devices[0], MODULES_LINE), key=lambda e: e.start_ns)
    total: dict = defaultdict(float)
    i = 0
    for e in sorted(_line(devices[0], OPS_LINE), key=lambda e: e.start_ns):
        if not (lo <= e.start_ns and e.end_ns <= hi):
            continue
        while i < len(modules) and modules[i].end_ns <= e.start_ns:
            i += 1
        inside = i < len(modules) and modules[i].start_ns <= e.start_ns
        module = short_name(modules[i].name) if inside else "no_module"
        total[f"{module}/{short_name(e.name)}"] += e.duration_ns / 1e9
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def innermost_segments(notes: list) -> list:
    """Flatten properly nested annotations into disjoint (start, end, name)
    stretches, each owned by the innermost annotation open in it."""
    segs, stack, cursor = [], [], 0.0

    def emit(until: float) -> None:
        nonlocal cursor
        if stack and until > cursor:
            segs.append((cursor, until, stack[-1].name))
        cursor = max(cursor, until)

    for e in sorted(notes, key=lambda e: (e.start_ns, -e.duration_ns)):
        while stack and stack[-1].end_ns <= e.start_ns:
            emit(stack[-1].end_ns)
            stack.pop()
        emit(e.start_ns)
        cursor = e.start_ns
        stack.append(e)
    while stack:
        emit(stack[-1].end_ns)
        stack.pop()
    return segs


def idle_by_annotation(planes: list, n: int = 10) -> list:
    """[[name, seconds], ...]: the device's idle time in the window (first
    device plane), split by the innermost harness annotation the host was
    inside; the rest goes to `unannotated`."""
    devices = device_planes(planes)
    if not devices:
        return []
    lo, hi = window(planes)
    gaps, cursor = [], lo
    for a, b in busy_intervals(devices[0], lo, hi):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = b
    if hi > cursor:
        gaps.append((cursor, hi))
    segs = innermost_segments([e for e in annotations(planes)
                               if e.name != WINDOW_ANNOTATION])
    total: dict = defaultdict(float)
    total["unannotated"] = sum(b - a for a, b in gaps) / 1e9
    i = 0
    for a, b in gaps:                   # both lists are sorted and disjoint
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < b:
            overlap = (min(b, segs[j][1]) - max(a, segs[j][0])) / 1e9
            total[segs[j][2]] += overlap
            total["unannotated"] -= overlap
            j += 1
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n] if v > 1e-12]


def describe(planes: list, out=sys.stdout) -> None:
    """A trace by hand: planes, lines, event counts and the longest names."""
    for p in planes:
        print(f"PLANE {p.name}", file=out)
        for ln in p.lines:
            total: dict = defaultdict(lambda: [0, 0.0])
            for e in ln.events:
                total[e.name][0] += 1
                total[e.name][1] += e.duration_ns
            print(f"  LINE {ln.name!r}: {len(ln.events)} events, "
                  f"{len(total)} names", file=out)
            for k, (c, d) in sorted(total.items(),
                                    key=lambda kv: -kv[1][1])[:12]:
                print(f"      {d / 1e9:12.6f} s  x{c:<7d} {k[:110]}", file=out)


if __name__ == "__main__":
    describe(load(sys.argv[1]))
