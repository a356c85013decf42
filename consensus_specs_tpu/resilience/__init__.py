"""Resilience subsystem: fault injection, deadline-budgeted dispatch
with a degradation ladder, and crash-safe checkpointing (ISSUE 13).

The serving loop's failure-mode contract, in one sentence per module:

  * faults.py     — `CSTPU_FAULTS=<schedule>` injects seeded faults at
                    the dispatch / checkpoint-I/O / mesh seams;
                    zero-overhead no-op when unset.
  * dispatch.py   — `guarded_dispatch` wraps every ResidentCore /
                    ServingMesh launch: wall-clock deadline, typed error
                    classification, bounded retry + backoff, and the
                    degradation ladder over the committed oracle knobs.
  * integrity.py  — output tripwires against the hulls the value-range
                    tier proved (`RANGE_CONTRACTS`): poisoned buffers
                    re-dispatch instead of corrupting the chain.
  * checkpoint.py — CRC-framed, atomic-rename, generational checkpoints
                    with fallback to the previous good generation and
                    restore across a changed serving-mesh shape.
  * errors.py     — the typed classification everything above raises.

`tools/chaos_drill.py` (`make chaos`, CI) drives the whole stack under a
seeded fault schedule and asserts bit-identical recovery;
`BeaconNodeAPI.get_healthz()` serves `health_snapshot()` below.

All resilience counters are registered `always=True`: the accounting
must survive `CSTPU_TELEMETRY=0`, because an operator reads /healthz
most urgently exactly when the node is degraded.
"""
from __future__ import annotations

from typing import Optional

from . import checkpoint, dispatch, faults, integrity  # noqa: F401
from .checkpoint import CheckpointStore, last_good_generation
from .dispatch import (DegradationLadder, guarded_dispatch, ladder,
                       run_with_recovery)
from .errors import (CheckpointCorrupt, CorruptOutput, DeadlineExceeded,
                     DispatchError, FatalDispatchError, ResilienceError,
                     SimulatedCrash, TransientDispatchError)

__all__ = [
    "CheckpointStore", "CheckpointCorrupt", "CorruptOutput",
    "DeadlineExceeded", "DegradationLadder", "DispatchError",
    "FatalDispatchError", "ResilienceError", "SimulatedCrash",
    "TransientDispatchError", "checkpoint", "dispatch", "faults",
    "guarded_dispatch", "health_snapshot", "integrity", "ladder",
    "last_good_generation", "run_with_recovery",
]

_HEALTH_COUNTERS = (
    "resilience.retries", "resilience.deadline_misses",
    "resilience.transient_errors", "resilience.fatal_errors",
    "resilience.corrupt_outputs", "resilience.degradations",
    # single_device is called out separately: that rung is IRREVERSIBLE
    # in memory (only a checkpoint restore re-shards), so its cumulative
    # count must stay visible even after ladder().reset() returns the
    # rung gauge to 0 — an operator reading status "ok" with
    # degradations.single_device > 0 knows a core may still be serving
    # unsharded until the next restore
    "resilience.degradations.single_device",
    # salvaged = deadline-missed-but-landed outputs: the firehose flush
    # (streaming/pipeline.py) and zero-retry donated sites both surface
    # lateness here rather than as unavailability
    "resilience.deadline_salvaged",
    "resilience.faults_injected", "watchdog.retrace_events",
    "watchdog.relayout_events", "firehose.deadline_miss",
)


def health_snapshot() -> dict:
    """The /healthz body: current degradation rung, recovery counters,
    and checkpoint provenance — a plain JSON-ready dict, available (and
    meaningful) even while syncing or degraded."""
    from .. import telemetry

    lad = ladder()
    counters = {name.split("resilience.", 1)[-1]:
                int(telemetry.counter(name, always=True).value)
                for name in _HEALTH_COUNTERS}
    return {
        "status": "ok" if lad.rung == 0 else "degraded",
        "rung": {
            "index": lad.rung,
            "name": lad.rung_name,
            "of": list(DegradationLadder.RUNGS),
        },
        "counters": counters,
        "checkpoint": {
            "last_good_generation": last_good_generation(),
            "saves": int(telemetry.counter(
                "resilience.checkpoint.saves", always=True).value),
            "corrupt_generations": int(telemetry.counter(
                "resilience.checkpoint.corrupt_generations",
                always=True).value),
        },
        "faults_active": faults.active(),
        "deadline_ms": dispatch.deadline_ms_default() or None,
    }


def reset() -> None:
    """Test/drill hygiene: ladder back to full speed and the occurrence
    state of a pinned schedule dropped (metric VALUES live in the
    telemetry registry — telemetry.reset() zeroes those)."""
    ladder().reset()
    faults.set_schedule(None)
