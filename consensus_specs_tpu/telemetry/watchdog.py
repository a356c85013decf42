"""Runtime watchdogs for the two silent performance killers of the
GSPMD/pjit staging contract (PAPERS.md [1], SNIPPETS.md [1][2]).

The analyzer catches both classes statically (CSA5xx jit-cache hygiene,
CSA605 producer/consumer sharding mismatch); these are their RUNTIME
counterparts, watching the programs actually dispatched:

  * **retrace watchdog** — `dispatch(key, jitted_fn, *args)` wraps a
    jitted-program call site and reads the program's compile-cache size
    (`fn._cache_size()`) around the call. Keys embed the static context
    the caller believes pins the program (shape, backend mode, mesh
    size), so after a key's first compile every further cache miss IS a
    retrace of the same logical program — weak-typed scalars, dtype
    drift, a traced value that became shape-like. Each one increments
    `watchdog.retrace_events` and warns (`TelemetryWarning`).
  * **re-layout watchdog** — `layout_check(key, tree)` fingerprints the
    `.sharding` of every leaf (sharding class, partition spec, device
    set) and compares against the key's previous fingerprint: a chained
    slot/epoch step whose inputs or outputs changed placement between
    steps pays a cross-device re-layout transfer the serving loop is
    designed never to pay. Each change increments
    `watchdog.relayout_events` and warns.

Both are no-ops when telemetry is off (`CSTPU_TELEMETRY=0`): `dispatch`
degrades to a plain call, `layout_check` to `None`.

The acceptance contract (ISSUE 8, checked by tests/test_telemetry.py and,
as `guard_events`, by every benchmark run): four chained resident slot steps plus one
epoch boundary on the 8-device mesh report ZERO events of either kind.
"""
from __future__ import annotations

import threading
import warnings
from typing import Dict, Optional

from . import core


class TelemetryWarning(UserWarning):
    """Watchdog warnings (retrace / re-layout in a steady-state loop)."""


_lock = threading.Lock()
# key -> {"calls", "compiles", "events", "seen": {id(fn): compiles}}
_retrace: Dict[object, dict] = {}
# key -> last layout fingerprint
_layouts: Dict[object, tuple] = {}


def _cache_size(fn) -> Optional[int]:
    size = getattr(fn, "_cache_size", None)
    if size is None:
        return None
    try:
        return int(size())
    except Exception:       # AOT-compiled / jax-version drift: no counting
        return None


def dispatch(key, fn, *args):
    """Call `fn(*args)` counting compile-cache misses under `key`.

    The key should name the logical program INCLUDING its static context
    (e.g. `("mesh.epoch", size, Vp)`): the first compile per
    (key, fn, input layout) is warm-up; any later miss at the SAME input
    layout is a retrace event — jax re-keying on dtype/weak-type drift or
    a value that became shape-like. A compile triggered by inputs
    arriving under a *different placement* is deliberately not counted
    here (that is the re-layout watchdog's domain: `layout_check` on the
    chained values). Degenerates to a plain call when telemetry is off or
    the callable exposes no cache."""
    if not core.enabled():
        return fn(*args)
    before = _cache_size(fn)
    out = fn(*args)
    if before is None:
        return out
    after = _cache_size(fn)
    grew = (after or 0) - before
    retraced = False
    # accounting under the lock so stats()/a concurrent scrape never
    # iterates _retrace mid-insertion (the package's concurrency
    # contract); the warning itself stays outside it
    with _lock:
        state = _retrace.setdefault(
            key, {"calls": 0, "compiles": 0, "events": 0, "seen": {}})
        state["calls"] += 1
        if grew > 0:
            # fingerprint only on the (rare) compile path — cache hits
            # stay two integer reads + the counter bump
            fid = (id(fn), layout_fingerprint(args))
            prev = state["seen"].get(fid, 0)
            state["seen"][fid] = prev + grew
            state["compiles"] += grew
            if prev > 0:
                state["events"] += grew
                retraced = True
    if retraced:
        core.counter("watchdog.retrace_events").inc(grew)
        warnings.warn(
            f"telemetry: jitted program {key!r} recompiled after "
            f"warm-up — a steady-state loop is retracing (weak-typed "
            f"scalar? dtype drift? shape leaking out of the key?)",
            TelemetryWarning, stacklevel=2)
    return out


def layout_fingerprint(tree) -> tuple:
    """Per-leaf `.sharding` identity: (sharding class, partition spec,
    sorted device ids); host arrays fingerprint as "host"."""
    fps = []
    for leaf in core._leaves(tree):
        sharding = getattr(leaf, "sharding", None)
        if sharding is None:
            fps.append("host")
            continue
        try:
            devices = tuple(sorted(d.id for d in sharding.device_set))
        except Exception:
            devices = ()
        fps.append((type(sharding).__name__,
                    str(getattr(sharding, "spec", "")), devices))
    return tuple(fps)


def layout_check(key, tree) -> Optional[tuple]:
    """Record `tree`'s layout fingerprint under `key`; a change versus
    the previous fingerprint for the same key is a re-layout event. Use
    ONE key for a chained value (e.g. the resident columns checked on
    both the epoch program's input and its output), so any in->out or
    out->next-in placement change trips it — the runtime counterpart of
    CSA605's producer/consumer sharding match."""
    if not core.enabled():
        return None
    fp = layout_fingerprint(tree)
    with _lock:
        prev = _layouts.get(key)
        _layouts[key] = fp
    if prev is not None and prev != fp:
        core.counter("watchdog.relayout_events").inc()
        warnings.warn(
            f"telemetry: {key!r} changed device layout between steps — "
            f"a chained program is re-laying-out (out_shardings != the "
            f"next call's in_shardings; the pjit staging contract)",
            TelemetryWarning, stacklevel=2)
    return fp


def stats(key=None) -> dict:
    """Retrace bookkeeping: per-key {calls, compiles, events} (the whole
    table when `key` is None)."""
    def row(st):
        return {"calls": st["calls"], "compiles": st["compiles"],
                "events": st["events"]}
    with _lock:
        if key is not None:
            st = _retrace.get(key)
            return row(st) if st else {"calls": 0, "compiles": 0,
                                       "events": 0}
        return {k: row(st) for k, st in _retrace.items()}


def reset() -> None:
    """Forget warm-up state and layout fingerprints (the event COUNTERS
    live in the metrics registry — core.reset() zeroes those)."""
    with _lock:
        _retrace.clear()
        _layouts.clear()


def forget(key) -> None:
    """Drop ONE key's warm-up/fingerprint state. For deliberate,
    reported re-placements — the resilience ladder's sharded→
    single-device rung re-places the chained columns on purpose, and the
    next observation under the key must count as warm-up, not as a
    steady-state re-layout event."""
    with _lock:
        _retrace.pop(key, None)
        _layouts.pop(key, None)


# ---------------------------------------------------------------------------
# Global compile counter (optional, jax.monitoring-based)
# ---------------------------------------------------------------------------

_compile_listener_installed = False


def install_compile_listener() -> bool:
    """Count every backend compile in this process into the
    `jax.backend_compiles` counter via jax's monitoring hooks —
    the watchdog's cross-check (dispatch() only sees wrapped call
    sites). Idempotent; returns False when the hooks are unavailable.
    Listeners cannot be unregistered, so the callback itself checks the
    telemetry switch per event."""
    global _compile_listener_installed
    if _compile_listener_installed:
        return True
    try:
        from jax._src import monitoring
    except Exception:
        return False

    def _on_duration(event: str, duration: float, **kw) -> None:
        if event.endswith("backend_compile_duration") and core.enabled():
            core.counter("jax.backend_compiles").inc()
            core.histogram("jax.backend_compile_seconds").observe(duration)

    monitoring.register_event_duration_secs_listener(_on_duration)
    _compile_listener_installed = True
    return True
