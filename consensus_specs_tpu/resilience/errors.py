"""Error classification of the resilience subsystem (ISSUE 13).

Every failure the serving loop can survive gets a TYPED class, so the
recovery policy (resilience/dispatch.py's retry/degradation machinery,
resilience/checkpoint.py's generation fallback) branches on type, never
on string matching — and so callers that want to die loudly still can:
everything here derives from `ResilienceError`.

The dispatch classification mirrors the gRPC-ish status classes real XLA
runtimes raise (RESOURCE_EXHAUSTED / UNAVAILABLE / INTERNAL are
transient infrastructure weather; INVALID_ARGUMENT is a bug):

  * `TransientDispatchError` — worth retrying with backoff (a flaky
    device link, a preempted device, an injected `raise` fault);
  * `DeadlineExceeded`      — the dispatch + `block_until_ready` wall
    clock blew the armed budget (the fork-choice deadline: the result
    may be correct but arrived too late to matter);
  * `CorruptOutput`         — an integrity tripwire rejected the output
    (NaN, out-of-hull limbs — resilience/integrity.py); the buffer must
    never reach the chain;
  * `FatalDispatchError`    — not retryable (shape/type bugs, exhausted
    ladder); wraps and chains the original exception.

This module is stdlib-only and imports nothing from the package, so any
layer (models/phase0/resident.py included) can import the types without
creating a cycle.
"""
from __future__ import annotations


class ResilienceError(Exception):
    """Base class of every typed failure the subsystem raises."""


class DispatchError(ResilienceError):
    """Base class of the guarded-dispatch classification. `key` names the
    logical program (the watchdog/telemetry dispatch key); `attempts`
    counts how many tries the guard spent before giving up;
    `consumed_inputs` records whether the failing attempt ever entered
    the dispatched program — the fact recovery code MUST branch on for
    donated buffers (True = the arguments may be deleted arrays, so
    in-memory re-dispatch is unsafe on a donating backend)."""

    def __init__(self, message: str = "", *, key=None, attempts: int = 1,
                 consumed_inputs: bool = True):
        super().__init__(message)
        self.key = key
        self.attempts = attempts
        self.consumed_inputs = consumed_inputs


class TransientDispatchError(DispatchError):
    """Retryable infrastructure failure (flaky device link, preemption)."""


class DeadlineExceeded(DispatchError):
    """The dispatch missed its wall-clock budget. `elapsed_ms` /
    `deadline_ms` carry the measurement for telemetry and /healthz."""

    def __init__(self, message: str = "", *, key=None, attempts: int = 1,
                 elapsed_ms: float = 0.0, deadline_ms: float = 0.0):
        super().__init__(message, key=key, attempts=attempts)
        self.elapsed_ms = elapsed_ms
        self.deadline_ms = deadline_ms


class CorruptOutput(DispatchError):
    """An integrity tripwire rejected the dispatch output — the poisoned
    buffer is dropped, never written into the resident state."""


class FatalDispatchError(DispatchError):
    """Not retryable: a real bug, or retries + the whole degradation
    ladder exhausted. The original exception (when one exists) rides as
    `__cause__`."""


class CheckpointCorrupt(ResilienceError):
    """A checkpoint payload failed validation: bad magic/version, length
    mismatch, CRC failure (resilience/checkpoint.py framing), or state
    bytes that do not parse as a serialized BeaconState
    (`ResidentCore.from_checkpoint`'s up-front validation). Carries the
    `generation` when the store knows it (None for raw byte entries)."""

    def __init__(self, message: str = "", *, generation=None):
        super().__init__(message)
        self.generation = generation


class SimulatedCrash(ResilienceError):
    """Raised by the fault harness to model a process killed mid-write
    (`ckpt.write=crash`). Deliberately NOT a subclass of
    CheckpointCorrupt: recovery code must treat it like a real crash
    (nothing to catch in-process except at a drill boundary)."""


class InjectedFault(RuntimeError):
    """The exception body of a `dispatch=raise` fault. Styled after a
    real XlaRuntimeError so the guarded-dispatch classifier exercises
    the same message-class path production errors take; RuntimeError
    (not ResilienceError) on purpose — injected faults must be
    indistinguishable from the weather they simulate."""
