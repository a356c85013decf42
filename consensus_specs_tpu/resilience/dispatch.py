"""Deadline-budgeted guarded dispatch with a degradation ladder
(ISSUE 13 tentpole (b)).

`guarded_dispatch(key, fn, *args, deadline_ms=...)` wraps every
ResidentCore / ServingMesh program launch:

  * **fast path** — with no fault schedule armed, no deadline budget,
    and no integrity check, it is `telemetry.watchdog.dispatch` (a
    cache-size read around the call) inside one try-frame: NO
    `block_until_ready`, so async dispatch is undisturbed, and the cost
    is held by the <20 µs no-op bound of tests/test_resilience.py. The
    error classification + retry still apply when the dispatch itself
    raises — real weather does not wait for a schedule.
  * **deadline** — when a budget is armed (`deadline_ms` argument or
    `CSTPU_DEADLINE_MS`), the guard measures wall clock around the
    dispatch plus `jax.block_until_ready(out)` — the fork-choice
    deadline of ROADMAP item 1: a result that arrives late is a miss
    even when it is correct. A cold compile can legitimately blow the
    budget once; the miss is RETRIED before anything degrades, and the
    warm retry passes, so compile time never walks the ladder. On
    zero-retry (donated) sites a valid-but-late output is SALVAGED
    instead of raised — the consumed buffers make re-dispatch
    impossible, so discarding correct work would only convert lateness
    into unavailability; the miss (and a `deadline_salvaged` counter)
    stays on /healthz.
  * **classification + retry** — failures classify into the typed errors of
    resilience/errors.py. Transients (RESOURCE_EXHAUSTED / UNAVAILABLE /
    INTERNAL / ABORTED — a flaky device link, preemption, injected faults) and
    deadline misses retry with exponential backoff; corrupt outputs
    (integrity tripwires) re-dispatch; everything else is fatal
    immediately. The clock and sleeper are injectable, so the retry
    tests run on a fake clock with zero real sleeps.
  * **degradation ladder** — `run_with_recovery` walks the global
    `DegradationLadder` when retries exhaust: each rung re-uses a
    COMMITTED differential-oracle knob, so every rung is bit-identical
    by the tests that gated those PRs in:

        rung  knob                               effect
        0     (full speed)                        —
        1     CSTPU_MERKLE_BACKEND pallas→xla    pair-hash oracle kernel
        2     CSTPU_FQ_REDC        coeff→leaf    per-leaf REDC oracle
        3     CSTPU_SCALAR_MUL     window→double_add   scalar-mul oracle
        4     sharded→single-device epoch        ResidentCore re-places

    Every transition is counted (`resilience.degradations`), gauged
    (`resilience.rung`), and spanned (`resilience.degrade`) through the
    telemetry registry; /healthz reports the current rung.

Donation caveat: retrying re-dispatches with the SAME argument buffers.
On XLA:CPU (tests, the chaos drill, every committed capture) the epoch
program is deliberately undonated, so this is always safe. On
accelerator backends the donated sites opt out of retry
(`ServingMesh.epoch_transition` passes `retries=0` when donating — a
post-dispatch failure must not re-call fn on deleted arrays), and
`ResidentCore._epoch_dispatch` escalates post-consume failures straight
to `FatalDispatchError` pointing at `CheckpointStore.restore`: once the
resident buffers are consumed, the checkpoint store IS the recovery
grain. Pre-dispatch transients keep their buffers and recover in
memory everywhere.
"""
from __future__ import annotations

import os
import time
from typing import Callable, Optional

from ..telemetry import watchdog as _watchdog
from . import faults
from .errors import (CorruptOutput, DeadlineExceeded, DispatchError,
                     FatalDispatchError, TransientDispatchError)

RETRIES_DEFAULT = 2
BACKOFF_MS_DEFAULT = 25.0

# message classes a real XLA runtime raises for infrastructure weather;
# the injected-fault text (faults.raise_injected) deliberately reuses them
_TRANSIENT_MARKERS = ("RESOURCE_EXHAUSTED", "UNAVAILABLE", "INTERNAL",
                      "ABORTED", "DEADLINE_EXCEEDED", "CANCELLED")


def _counter(name: str):
    from .. import telemetry
    return telemetry.counter(name, always=True)


def deadline_ms_default() -> float:
    """The armed wall-clock budget: CSTPU_DEADLINE_MS, 0/unset = off."""
    raw = os.environ.get("CSTPU_DEADLINE_MS", "").strip()
    if not raw:
        return 0.0
    return float(raw)


def classify(exc: Exception) -> str:
    """-> "transient" | "fatal" by exception message class (the status
    text is the only stable surface across jaxlib versions)."""
    msg = str(exc)
    if any(marker in msg for marker in _TRANSIENT_MARKERS):
        return "transient"
    return "fatal"


def guarded_dispatch(key, fn: Callable, *args,
                     deadline_ms: Optional[float] = None,
                     check: Optional[Callable] = None,
                     retries: int = RETRIES_DEFAULT,
                     backoff_ms: float = BACKOFF_MS_DEFAULT,
                     clock: Callable[[], float] = time.perf_counter,
                     sleep: Callable[[float], None] = time.sleep):
    """Call `fn(*args)` through the retrace watchdog under `key`, with
    the guard rails above. Raises the typed DispatchError classification after
    `retries` extra attempts; returns the (verified) output otherwise.

    `check(out) -> bool` is the integrity tripwire (resilience/
    integrity.py); `clock`/`sleep` are injectable for fake-clock tests.
    """
    if deadline_ms is None:
        deadline_ms = deadline_ms_default()
    faulty = faults.active()
    # only a DEADLINE needs the full-tree fence (its wall clock must
    # include the device work); a tripwire alone syncs exactly the
    # leaves it reads through its own jitted reduction, and unarmed
    # dispatch never fences at all — async dispatch stays async and the
    # guard is one try-frame + two env reads. The classification/retry still
    # applies if the dispatch itself raises.
    armed = bool(deadline_ms)
    last_error: Optional[DispatchError] = None
    attempt = 0
    while True:
        if attempt:
            from .. import telemetry
            _counter("resilience.retries").inc()
            delay = backoff_ms * (2.0 ** (attempt - 1)) / 1e3
            with telemetry.span("resilience.backoff", key=str(key),
                                attempt=attempt):
                sleep(delay)
        fault = faults.on_dispatch(key) if faulty else None
        t0 = clock() if armed else 0.0
        dispatched = False      # has fn possibly consumed (donated) inputs?
        try:
            if fault is not None and fault.action in ("raise", "fatal"):
                faults.raise_injected(key, fault)
            dispatched = True
            out = _watchdog.dispatch(key, fn, *args)
            if fault is not None and fault.action == "hang":
                # the injected wedge: burn wall clock inside the
                # measured window, exactly like a stuck collective
                sleep(float(fault.param or 100.0) / 1e3)
            if armed:
                import jax
                jax.block_until_ready(out)
        except DispatchError:
            raise
        except Exception as exc:        # noqa: BLE001 - classified below
            if classify(exc) == "transient":
                _counter("resilience.transient_errors").inc()
                last_error = TransientDispatchError(
                    str(exc), key=key, attempts=attempt + 1,
                    consumed_inputs=dispatched)
                last_error.__cause__ = exc
                # a failure that provably preceded the dispatch leaves
                # the argument buffers intact even for a DONATED
                # program: honor the standard retry budget although the
                # caller pinned retries=0 for post-consume safety — a
                # one-off pre-dispatch transient must not walk the
                # ladder on a donating backend. The allowance is
                # PER-FAILURE, never sticky: once any attempt has
                # entered fn, every later decision reverts to the
                # caller's pin (a retained escalation would re-call fn
                # on consumed buffers from the deadline/corrupt branches)
                allowance = retries if dispatched \
                    else max(retries, RETRIES_DEFAULT)
                if attempt >= allowance:
                    break
                attempt += 1
                continue
            _counter("resilience.fatal_errors").inc()
            raise FatalDispatchError(
                f"non-retryable dispatch failure at {key!r}: {exc}",
                key=key, attempts=attempt + 1) from exc
        # the measured window closes HERE: the deadline covers dispatch +
        # block_until_ready, never the tripwire's own reduction below
        elapsed_ms = (clock() - t0) * 1e3 if armed else 0.0
        if fault is not None and fault.action == "poison":
            out = faults.poison_tree(out, fault.param)
        # the tripwire's own jitted reduction can hit the same transient
        # weather as the dispatch — run it ONCE per attempt under the
        # same classification, so a preempted check retries typed
        # instead of escaping as a raw XLA error
        check_ok = True
        if check is not None:
            try:
                check_ok = bool(check(out))
            except Exception as exc:    # noqa: BLE001 - classified below
                if classify(exc) != "transient":
                    _counter("resilience.fatal_errors").inc()
                    raise FatalDispatchError(
                        f"integrity check failed at {key!r}: {exc}",
                        key=key, attempts=attempt + 1) from exc
                _counter("resilience.transient_errors").inc()
                last_error = TransientDispatchError(
                    f"integrity check transiently failed at {key!r}: "
                    f"{exc}", key=key, attempts=attempt + 1)
                last_error.__cause__ = exc
                if attempt >= retries:
                    break
                attempt += 1
                continue
        if deadline_ms:
            if elapsed_ms > deadline_ms:
                _counter("resilience.deadline_misses").inc()
                if retries == 0 and check_ok:
                    # zero-retry (donated) site: the output is VALID,
                    # merely late, and the input buffers are consumed —
                    # raising would convert lateness into unavailability
                    # and (on the resident path) a restore loop whose
                    # cold compile misses again. Salvage the late
                    # output; the miss stays visible on /healthz. A
                    # caller with a retry budget keeps the strict
                    # behavior: retry warm, then raise for the ladder.
                    _counter("resilience.deadline_salvaged").inc()
                    return out
                last_error = DeadlineExceeded(
                    f"dispatch {key!r} took {elapsed_ms:.1f} ms against "
                    f"a {deadline_ms:.0f} ms budget",
                    key=key, attempts=attempt + 1,
                    elapsed_ms=elapsed_ms, deadline_ms=deadline_ms)
                if attempt >= retries:
                    break
                attempt += 1
                continue
        if not check_ok:
            _counter("resilience.corrupt_outputs").inc()
            last_error = CorruptOutput(
                f"integrity tripwire rejected the output of {key!r} "
                f"(out-of-hull or NaN — the buffer never reaches the "
                f"chain)", key=key, attempts=attempt + 1)
            if attempt >= retries:
                break
            attempt += 1
            continue
        return out
    assert last_error is not None
    raise last_error


# ---------------------------------------------------------------------------
# Degradation ladder
# ---------------------------------------------------------------------------

class DegradationLadder:
    """Global serving-loop conservatism level. Rung k applies oracle
    knobs 1..k; `reset()` returns every knob to env control. The rungs
    re-use the committed differential-oracle backends, so degradation
    NEVER changes results — only speed (bit-identity is each backend
    pair's committed test gate)."""

    RUNGS = ("full", "merkle_xla", "redc_leaf", "scalar_double_add",
             "single_device")

    def __init__(self):
        self._rung = 0
        self._single_device_cbs = []

    # -- state ----------------------------------------------------------

    @property
    def rung(self) -> int:
        return self._rung

    @property
    def rung_name(self) -> str:
        return self.RUNGS[self._rung]

    @property
    def exhausted(self) -> bool:
        return self._rung >= len(self.RUNGS) - 1

    def register_single_device(self, cb: Callable[[], None]) -> None:
        """Hook the bottom rung: ResidentCore registers its
        `degrade_to_single_device` here so the ladder can re-place the
        serving loop without importing it."""
        if cb not in self._single_device_cbs:
            self._single_device_cbs.append(cb)

    def unregister_single_device(self, cb: Callable[[], None]) -> None:
        if cb in self._single_device_cbs:
            self._single_device_cbs.remove(cb)

    # -- transitions ----------------------------------------------------

    def _apply(self, name: str) -> None:
        if name == "merkle_xla":
            from ..ops.sha256 import set_merkle_pair_backend
            set_merkle_pair_backend("xla")
        elif name == "redc_leaf":
            from ..ops.fq import set_fq_redc_backend
            set_fq_redc_backend("leaf")
        elif name == "scalar_double_add":
            from ..ops.scalar_mul import set_scalar_mul_backend
            set_scalar_mul_backend("double_add")
        elif name == "single_device":
            for cb in list(self._single_device_cbs):
                cb()

    def degrade(self, reason: str = "") -> Optional[str]:
        """Step one rung down; returns the new rung name, or None when
        already at the bottom (the caller escalates to fatal). Counted,
        gauged, and spanned through the telemetry registry."""
        if self.exhausted:
            return None
        from .. import telemetry
        self._rung += 1
        name = self.rung_name
        with telemetry.span("resilience.degrade", rung=name,
                            reason=reason or None):
            self._apply(name)
        _counter("resilience.degradations").inc()
        _counter(f"resilience.degradations.{name}").inc()
        telemetry.gauge("resilience.rung", always=True).set(self._rung)
        return name

    def reset(self) -> None:
        """Back to full speed: every oracle KNOB returns to env control
        (the operator's recovery action after the weather passes).

        The bottom rung is deliberately NOT undone here: a core that
        fail-overed to single-device has re-placed its buffers, and the
        only way back to a sharded mesh is a restore
        (`CheckpointStore.restore` / a fresh ResidentCore under a mesh).
        That history stays visible on /healthz as the cumulative
        `degradations.single_device` counter even after the rung gauge
        returns to 0 — reset() must not let the health surface hide a
        still-unsharded core."""
        from ..ops.fq import set_fq_redc_backend
        from ..ops.scalar_mul import set_scalar_mul_backend
        from ..ops.sha256 import set_merkle_pair_backend
        from .. import telemetry
        set_merkle_pair_backend(None)
        set_fq_redc_backend(None)
        set_scalar_mul_backend(None)
        self._rung = 0
        telemetry.gauge("resilience.rung", always=True).set(0)


_LADDER = DegradationLadder()


def ladder() -> DegradationLadder:
    """The process-global ladder (what /healthz and bench report)."""
    return _LADDER


def run_with_recovery(key, make: Callable[[], tuple], *,
                      deadline_ms: Optional[float] = None,
                      check: Optional[Callable] = None,
                      ladder: Optional[DegradationLadder] = None,
                      retries: int = RETRIES_DEFAULT,
                      backoff_ms: float = BACKOFF_MS_DEFAULT,
                      clock: Callable[[], float] = time.perf_counter,
                      sleep: Callable[[float], None] = time.sleep):
    """guarded_dispatch + the ladder: `make()` returns a fresh
    `(fn, args)` pair per attempt (re-read AFTER each degradation, so a
    rung that swaps a backend or re-places the loop is picked up), and
    every typed failure that survives its retries walks one rung before
    the next attempt. Raises FatalDispatchError only when the ladder is
    exhausted."""
    lad = ladder if ladder is not None else _LADDER
    while True:
        fn, args = make()
        try:
            return guarded_dispatch(key, fn, *args,
                                    deadline_ms=deadline_ms, check=check,
                                    retries=retries, backoff_ms=backoff_ms,
                                    clock=clock, sleep=sleep)
        except FatalDispatchError:
            raise
        except DispatchError as exc:
            rung = lad.degrade(reason=type(exc).__name__)
            if rung is None:
                raise FatalDispatchError(
                    f"dispatch {key!r} failed at the bottom of the "
                    f"degradation ladder: {exc}",
                    key=key, attempts=exc.attempts) from exc


# ---------------------------------------------------------------------------
# Trace-tier contract (tools/analysis/trace/, `make contracts`)
# ---------------------------------------------------------------------------
# guarded_dispatch is a HOST-side wrapper, so its own behavior cannot
# appear in any jaxpr — what CAN be pinned statically is the PROGRAM the
# guard launches on the steady-state chained slot path: the exact
# sharded epoch program ServingMesh builds, same chained out==in
# shardings across the (cols, scal) prefix, same collective inventory,
# zero device_put/callbacks. This contract re-pins that program under
# the resilience name (through the same builder, deliberately — the two
# baseline entries must move together), so a resilience-layer change
# that swaps or forks the dispatched program fails `make contracts`.
# Guard-side regressions (an input re-placement, an extra transfer
# before dispatch) are HOST behavior and are gated at runtime instead:
# zero retrace/re-layout watchdog events across guarded chained slot
# steps, asserted in tests/test_resilience.py, bench's watchdog drive,
# and the whole chaos drill.

_CONTRACT_MESH_DEVICES = 8


def _guarded_epoch_chain_build():
    from ..parallel.sharding import _mesh_epoch_chain_build
    return _mesh_epoch_chain_build()


TRACE_CONTRACTS = [
    dict(
        name="resilience.dispatch.guarded_epoch_chain",
        build=_guarded_epoch_chain_build,
        requires_devices=_CONTRACT_MESH_DEVICES,
        # ValidatorColumns (7) + EpochScalars (7) — the chained prefix;
        # tests/test_resilience.py cross-checks the literal against the
        # namedtuples so a field addition cannot silently shrink the pin
        chained_prefix=14,
        collectives=("all-gather", "all-reduce"),
        budgets={"collective_ops": 20},
        forbid=("callback", "device_put"),
    ),
]
