"""Telemetry: spans, metrics registry, runtime watchdogs, export.

The one coherent observability layer for the serving loop (ISSUE 8):

    from consensus_specs_tpu import telemetry

    with telemetry.span("resident.slot", req=slot):     # a root: sets `req`
        with telemetry.span("epoch.device") as sp:      # inherits it
            out = program(args)
            sp.fence(out)                   # materialized at exit only
            sp.note(lanes=n)                # a work count on the record
    telemetry.counter("fq.redc.lanes").inc(n)
    telemetry.snapshot()                    # one JSON-ready dict of it all
    telemetry.prometheus_text()             # BeaconNodeAPI.get_metrics()
    telemetry.watchdog.dispatch(key, fn, *args)   # retrace watchdog
    telemetry.watchdog.layout_check(key, tree)    # re-layout watchdog

Env knobs: CSTPU_TELEMETRY (default on; 0 = every span/metric a no-op),
CSTPU_TELEMETRY_FENCE (default on; 0 = spans never fence at exit),
CSTPU_TELEMETRY_RING (span ring-buffer size, default 4096).

Every ring record has `name, ts, dur, depth, parent, tid, args` and the
span's identity: a process-unique `id`, its parent's `parent_id` (0 at a
root) and the inherited request key `req`. Once jax is loaded, a span is
also a `jax.profiler.TraceAnnotation` of the same name, so a profiler
session shows the program's spans in its `/host:CPU` plane on the device
trace's clock (core.py binds it lazily; the package never imports jax).

Naming scheme (dot-separated `subsystem.stage`): spans `epoch.*`
(process_epoch_soa stages), `distill.*` (the host distillation's own
parts, opened by epoch_soa's builders under whichever stage calls them,
`epoch.distill` or `resident.stage.distill`: `distill.context` over
`.layouts .participants .crosslink_roots`, `distill.crosslinks`,
`distill.inputs` over `.flags .inclusion`, and `distill.winners` /
`distill.committee_balances` once a pass, three a boundary),
`resident.*` (the resident serving loop: the
roots `resident.slot` / `resident.boundary_slot` (`req` = the slot) over
`resident.slot_root` with its groups `.forests .attestations .history
.small .merkleize`, and at a boundary `resident.stage` (`.distill`, which
ends in `resident.stage.distill.place`, and `.upload`),
`resident.device`, `resident.refresh` (`.forests_dispatch .download
.final_updates`) and `resident.forests` (at a boundary the wait for the
forests the refresh dispatched first); `resident.block` (a root of
its own, `req` = the block's slot: the slot's root span has closed when
`process_slots` returned) over `.header .randao .eth1 .slashings
.attestations .deposits .exits` (`.attestations` notes `plan_elements`,
`committees` and `sequential`: the parent crosslinks rooted, the
committees resolved, and 1 for a family the per-attestation loop took;
`.deposits` notes `new_validators`, `top_ups` and `proof_pairs_hashed`)
and, for a block that wrote the
registry, `resident.registry_write` (notes `rows`, `appended_rows`) and
`resident.forests.update` (notes `registry_leaves`, `appended_leaves`,
`balance_chunks`, `pair_lanes`); `resident.registry.pubkey_index` (the one
build of a core's pubkey -> row index, at its first deposit);
`resident.stage.distill` notes `registry_rows` and `pending_activations`
beside `active_validators`; `resident.checkpoint_write`
(`.download .assemble`) and `resident.restore` (`.decode .upload`)),
`firehose.*` (streaming-verifier pipeline stages: stage/dispatch/flush,
exit-only fences), `bench.*` / `followup.*` (harnesses); counters
`fq.redc.*` (trace-time REDC accounting), `merkle.forest.*` (pair-hash
lanes/launches/builds), `merkle.host.*` (pairs hashed / taken from the
zero-hash table by the host Merkleizer), `scalar_mul.*`, `bls.grouped.*` (grouped-pairing
launch occupancy), `shuffle.permutations_computed` (misses of the spec's
permutation cache: shuffles really run), `firehose.*` (queue depth / batch occupancy /
deadline misses — always-on: /healthz reads them), `watchdog.*`
(retrace/re-layout events), `resident.block.fallbacks` (blocks that left
the served path for the object model; always-on),
`resident.block.attestations.sequential` (attestation families that left
the one pass for the per-attestation loop; always-on),
`resident.registry.capacity_grown` (re-layouts of a resident core at a
larger registry capacity; always-on), `jax.backend_compiles` (global compile
listener).
"""
from .core import (Counter, Gauge, Histogram, Span, counter, enabled,
                   fencing, gauge, histogram, reset, ring, set_enabled,
                   set_fencing, snapshot, span)
from .export import (chrome_trace, dump_chrome_trace, dump_prometheus,
                     prometheus_text, write_jsonl)
from . import watchdog
from .watchdog import TelemetryWarning

__all__ = [
    "Counter", "Gauge", "Histogram", "Span", "TelemetryWarning",
    "chrome_trace", "counter", "dump_chrome_trace", "dump_prometheus",
    "enabled", "fencing", "gauge", "histogram", "prometheus_text", "reset",
    "ring", "set_enabled", "set_fencing", "snapshot", "span", "watchdog",
    "write_jsonl",
]
