"""Spans + metrics registry — the observability core (PAPERS.md [3]).

Dapper-style spans (Sigelman et al., Google TR 2010-1) over the serving
loop's hot path, plus a process-wide metrics registry (counters, gauges,
pow2-bucket histograms) that absorbs the stats previously scattered
across `ops/fq.py` (trace-time REDC lanes), the incremental Merkle
forests (pair lanes per level), and the hand-rolled `perf_counter`
blocks of `epoch_soa.process_epoch_soa` / `resident.py`.

Contract:

  * **zero overhead when off** — `CSTPU_TELEMETRY=0` makes `span()`
    return a shared no-op singleton (no `perf_counter` call, no ring
    write) and turns every counter/gauge/histogram mutation into an
    early return (`tests/test_telemetry.py` asserts the bound). The
    default is ON: spans cost two `perf_counter` reads, one deque
    append and one profiler TraceMe.
  * **fencing at span exit only** — a span never fences between the
    statements it wraps (async dispatch must not be perturbed); outputs
    registered via `Span.fence(tree)` are materialized (one element per
    leaf, the `bench._sync` idiom) at `__exit__`, *inside* the measured window, so the
    recorded wall time covers the device work the region dispatched.
    `CSTPU_TELEMETRY_FENCE=0` disables the exit fences (dispatch-only
    timing).
  * **nesting and identity** — spans thread a per-thread parent/child
    stack; every span has a process-unique `id`, its parent's
    `parent_id` (0 at a root) and a request key `req` that a root sets
    (`span("resident.slot", req=slot)`) and every descendant inherits,
    so the spans of one slot are one tree and self time can be
    computed. The ring buffer (`CSTPU_TELEMETRY_RING` entries, default
    4096) keeps the most recent finished spans for Chrome-trace export
    (export.py), and a per-name aggregate (count / total / last)
    survives ring eviction for `snapshot()` / Prometheus.
  * **one clock with the device trace** — once something else has
    imported jax, a span also opens a `jax.profiler.TraceAnnotation` of
    its own name for its extent, so an open profiler session holds the
    program's spans in its `/host:CPU` plane beside the device's
    operations. With no session open that is one TraceMe construction.

This module is stdlib-only (numpy imported lazily inside the fence, the
profiler looked up in `sys.modules`, never imported): it must stay
importable from `ops/fq.py` and the analyzer fixtures without dragging
jax in.
"""
from __future__ import annotations

import collections
import itertools
import math as _math
import os
import sys
import threading
import time
from typing import Dict, Iterator, List, Optional

# ---------------------------------------------------------------------------
# On/off state (env-driven, test-overridable — the set_fq_redc_backend idiom)
# ---------------------------------------------------------------------------

_enabled_override: Optional[bool] = None
_fence_override: Optional[bool] = None


def _env_flag(name: str, default: bool) -> bool:
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    return raw.strip().lower() not in ("0", "off", "false", "no")


def enabled() -> bool:
    """Telemetry master switch: CSTPU_TELEMETRY (default on)."""
    if _enabled_override is not None:
        return _enabled_override
    return _env_flag("CSTPU_TELEMETRY", True)


def set_enabled(value: Optional[bool]) -> None:
    """Pin telemetry on/off for a scope; None returns control to the
    CSTPU_TELEMETRY environment variable."""
    global _enabled_override
    assert value is None or isinstance(value, bool), value
    _enabled_override = value


def fencing() -> bool:
    """Span-exit fencing switch: CSTPU_TELEMETRY_FENCE (default on)."""
    if _fence_override is not None:
        return _fence_override
    return _env_flag("CSTPU_TELEMETRY_FENCE", True)


def set_fencing(value: Optional[bool]) -> None:
    global _fence_override
    assert value is None or isinstance(value, bool), value
    _fence_override = value


# ---------------------------------------------------------------------------
# Span API
# ---------------------------------------------------------------------------

_RING_MAX = max(1, int(os.environ.get("CSTPU_TELEMETRY_RING", "4096") or 4096))
_EPOCH = time.perf_counter()     # session time zero for trace timestamps

_ring: collections.deque = collections.deque(maxlen=_RING_MAX)
# name -> [count, total_seconds, last_seconds]
_span_agg: Dict[str, List] = {}
_tls = threading.local()
_lock = threading.Lock()
_span_ids = itertools.count(1)   # next() is atomic under the GIL
_annotation = None               # jax.profiler.TraceAnnotation, once bound


def _annotation_factory():
    """`jax.profiler.TraceAnnotation` once some other module has imported
    jax, else None: a process that never loaded jax has no profiler
    session for a span to appear in, and this module must not load it."""
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def _leaves(tree) -> Iterator:
    """Pytree-ish leaf iteration without jax: tuples (namedtuples
    included), lists, and dict values recurse; everything else is a
    leaf."""
    if isinstance(tree, (tuple, list)):
        for item in tree:
            yield from _leaves(item)
    elif isinstance(tree, dict):
        for item in tree.values():
            yield from _leaves(item)
    else:
        yield tree


def _materialize(trees) -> None:
    """The fence: fetch one element of every one-device leaf (the
    repo-wide `_sync` idiom — materialized output bytes cannot arrive
    before the program that makes them), and wait on every shard of a
    leaf that lies over several devices."""
    import numpy as np
    for tree in trees:
        for leaf in _leaves(tree):
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None and len(sharding.device_set) > 1:
                # a leaf laid out over a mesh: wait for every shard. One
                # fetched element there is five programs launched on all
                # the devices (ravel, the index's converts, a gather with
                # its all-reduce, a broadcast): 4 ms a leaf on four v5e
                # chips, which the span would book as its own
                leaf.block_until_ready()
                continue
            ravel = getattr(leaf, "ravel", None)
            if ravel is not None:
                np.asarray(ravel()[0:1])


class Span:
    """One timed region. Use via the `span(...)` factory:

        with telemetry.span("epoch.device") as sp:
            out = jitted_program(args)
            sp.fence(out)           # materialized at exit, never inside
        sp.duration                 # seconds

    `req` is the request key of a root span (the slot, the cycle); a
    span opened without one inherits its parent's.
    """

    __slots__ = ("name", "args", "t0", "dur", "id", "parent_id", "req",
                 "_depth", "_parent", "_fenced", "_note")

    def __init__(self, name: str, args: Optional[dict] = None, req=None):
        self.name = name
        self.args = args or {}
        self.t0 = 0.0
        self.dur = 0.0
        self.id = next(_span_ids)
        self.parent_id = 0
        self.req = req
        self._depth = 0
        self._parent = ""
        self._fenced: list = []
        self._note = None

    # -- annotations --------------------------------------------------------

    def note(self, **kv) -> "Span":
        self.args.update(kv)
        return self

    def fence(self, *trees) -> "Span":
        """Register device outputs to materialize at span exit (one
        element per leaf). Exit-only by design: fencing inside the span
        would serialize the async dispatch being measured."""
        self._fenced.extend(trees)
        return self

    @property
    def duration(self) -> float:
        return self.dur

    # -- context manager ----------------------------------------------------

    def __enter__(self) -> "Span":
        stack = _stack()
        if stack:
            parent = stack[-1]
            self._parent = parent.name
            self.parent_id = parent.id
            if self.req is None:
                self.req = parent.req
        self._depth = len(stack)
        stack.append(self)
        factory = _annotation_factory()
        if factory is not None:
            self._note = factory(self.name)
            self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        # no fencing on the exception path: materializing a
        # partially-dispatched output could raise a secondary device
        # error and mask the original
        if exc_type is None and self._fenced and fencing():
            _materialize(self._fenced)
        self.dur = time.perf_counter() - self.t0
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
            self._note = None
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:        # unbalanced exit (generator teardown)
            stack.remove(self)
        # span close is boundary/stage-scale, never per-lane: the lock is
        # cheap here and lets snapshot()/ring() (a concurrent /metrics
        # scrape) iterate without racing dict/deque mutation
        with _lock:
            agg = _span_agg.setdefault(self.name, [0, 0.0, 0.0])
            agg[0] += 1
            agg[1] += self.dur
            agg[2] = self.dur
            _ring.append({
                "name": self.name,
                "ts": self.t0 - _EPOCH,
                "dur": self.dur,
                "depth": self._depth,
                "parent": self._parent,
                "tid": threading.get_ident(),
                "args": dict(self.args) if self.args else None,
                "id": self.id,
                "parent_id": self.parent_id,
                "req": self.req,
            })
        return False


class _NullSpan:
    """Shared no-op span: what `span()` hands out when telemetry is off.
    Every method returns immediately; `duration` is 0.0."""

    __slots__ = ()
    name = ""
    args: dict = {}
    duration = 0.0
    dur = 0.0

    def note(self, **kv):
        return self

    def fence(self, *trees):
        return self

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


def span(name: str, *, req=None, **args):
    """A context-managed span named `name` (dot-separated scheme:
    `subsystem.stage`, e.g. "epoch.device", "resident.slot_root").
    `req` is a field of the record (the request key a root sets and its
    descendants inherit), never one of the free-form `args` noted on the
    span. Returns the shared no-op singleton when telemetry is off."""
    if not enabled():
        return _NULL_SPAN
    return Span(name, args or None, req)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

class Counter:
    """Monotonic counter. `always=True` records even when telemetry is
    off — the trace-time accounting (`fq.redc.*`) whose values tests
    assert regardless of the observability switch."""

    __slots__ = ("name", "always", "value")

    def __init__(self, name: str, always: bool = False):
        self.name = name
        self.always = always
        self.value = 0

    def inc(self, n: int = 1) -> None:
        if self.always or enabled():
            self.value += n

    def reset(self) -> None:
        self.value = 0


class Gauge:
    __slots__ = ("name", "always", "value")

    def __init__(self, name: str, always: bool = False):
        self.name = name
        self.always = always
        self.value = 0.0

    def set(self, v) -> None:
        if self.always or enabled():
            self.value = v

    def reset(self) -> None:
        self.value = 0.0


_NONPOS_BUCKET = -(10 ** 9)   # sentinel exponent for the `<= 0` bucket


class Histogram:
    """Power-of-two buckets: an observation v lands in the bucket whose
    upper bound is the smallest 2**k >= v (negative exponents included —
    sub-second wall times bucket at 0.5, 0.25, ...; non-positive values
    land in the `0` bucket). Tracks count and sum like Prometheus."""

    __slots__ = ("name", "always", "counts", "total", "count")

    def __init__(self, name: str, always: bool = False):
        self.name = name
        self.always = always
        self.counts: Dict[int, int] = {}   # exponent k -> observations
        self.total = 0.0
        self.count = 0

    @staticmethod
    def bucket_exp(v) -> Optional[int]:
        if v <= 0:
            return None
        # frexp gives v = m * 2**e with 0.5 <= m < 1, so the smallest k
        # with v <= 2**k is e — except exactly at powers of two (m == 0.5),
        # where it is e - 1
        m, e = _math.frexp(v)
        return e - 1 if m == 0.5 else e

    def observe(self, v) -> None:
        if not (self.always or enabled()):
            return
        self.count += 1
        self.total += v
        k = self.bucket_exp(v)
        key = _NONPOS_BUCKET if k is None else k  # `<= 0` bucket sorts first
        self.counts[key] = self.counts.get(key, 0) + 1

    def reset(self) -> None:
        self.counts = {}
        self.total = 0.0
        self.count = 0


_counters: Dict[str, Counter] = {}
_gauges: Dict[str, Gauge] = {}
_histograms: Dict[str, Histogram] = {}


def _get(registry: dict, cls, name: str, always: bool):
    metric = registry.get(name)
    if metric is None:
        with _lock:
            metric = registry.setdefault(name, cls(name, always))
    if always and not metric.always:
        metric.always = True
    return metric


def counter(name: str, always: bool = False) -> Counter:
    return _get(_counters, Counter, name, always)


def gauge(name: str, always: bool = False) -> Gauge:
    return _get(_gauges, Gauge, name, always)


def histogram(name: str, always: bool = False) -> Histogram:
    return _get(_histograms, Histogram, name, always)


# ---------------------------------------------------------------------------
# Snapshot / reset
# ---------------------------------------------------------------------------

def snapshot() -> dict:
    """One JSON-ready view of everything: counters, gauges, histograms,
    and per-span-name aggregates (tools/telemetry_smoke.py writes it, the
    JSONL exporter appends it to every row). The span names keep the keys
    the old bespoke `timings` dicts used ("epoch.distill" carries the old
    "distill" bucket, etc.). Taken under the module lock
    so a concurrent scrape (BeaconNodeAPI.get_metrics) never races
    first-use metric creation or a span close on the serving thread."""
    with _lock:
        return _snapshot_locked()


def _snapshot_locked() -> dict:
    return {
        "enabled": enabled(),
        "counters": {n: c.value for n, c in sorted(_counters.items())},
        "gauges": {n: g.value for n, g in sorted(_gauges.items())},
        "histograms": {
            n: {
                "count": h.count,
                "sum": h.total,
                "buckets": {
                    ("0" if k == _NONPOS_BUCKET else
                     str(2.0 ** k) if k < 0 else str(2 ** k)): v
                    for k, v in sorted(h.counts.items())
                },
            }
            for n, h in sorted(_histograms.items())
        },
        "spans": {
            n: {"count": a[0], "total_ms": round(a[1] * 1e3, 3),
                "last_ms": round(a[2] * 1e3, 3)}
            for n, a in sorted(_span_agg.items())
        },
    }


def reset() -> None:
    """Zero every metric and drop span history. Registered metric OBJECTS
    survive (module-level handles like fq.py's REDC counters keep their
    identity); watchdog state is separate (watchdog.reset())."""
    with _lock:
        for registry in (_counters, _gauges, _histograms):
            for metric in registry.values():
                metric.reset()
        _span_agg.clear()
        _ring.clear()


def ring() -> list:
    """The finished-span ring buffer (most recent _RING_MAX spans)."""
    with _lock:
        return list(_ring)
