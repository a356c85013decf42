"""`make chaos`: drive the resident serving loop through a seeded fault
schedule and prove it recovers BIT-IDENTICALLY (ISSUE 13 acceptance).

Phases (all on the virtual 8-device CPU mesh, minimal preset):

    baseline   fault-free reference: warm-up epoch, then 3 epochs of
               chained sharded slot steps (24 slot steps + 3 boundaries
               >= the required 8 steps + boundary) -> reference
               checkpoint bytes + state root.
    dispatch   >=3 fault kinds, ONE per boundary so each recovery is
               retry-shaped — a transient raise, a poisoned output
               (tripwired against the committed RANGE_CONTRACTS hulls),
               a hang past the armed deadline — recovered WITHOUT any
               ladder degradation (asserted: degradations == 0) and
               bit-identical to the reference.
    ladder     the wedged-mesh scenario: EVERY sharded epoch dispatch
               raises, so recovery walks the whole degradation ladder
               (merkle pallas->xla, REDC coeff->leaf, scalar-mul
               window->double_add, sharded->single-device) and finishes
               the drive single-device — still bit-identical, because
               every rung is a committed differential oracle.
    checkpoint crash-safe failover: good generation at the warm-up
               point, a TRUNCATED generation mid-drive (written
               "successfully" — silent media corruption), a kill
               mid-write (partial temp file, no rename), then a
               simulated restart: restore falls back to the previous
               good generation, replays, and lands on the reference
               bytes. The restore also runs under a CHANGED serving-mesh
               shape (8 -> 2 devices; the payload is logical bytes).

Across the WHOLE drill the retrace/re-layout watchdogs must record ZERO
events (recoveries use fresh keys; the deliberate single-device
re-placement forgets its keys) — the "zero residual watchdog events"
acceptance bar. Artifact: out/chaos.json. Exit 0 = every phase held.

Usage: python tools/chaos_drill.py  (from the repo root)
"""
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SLOTS = {}          # phase -> slots driven (reported in the artifact)

# one fault per boundary (each recovery consumes 2 occurrences: the
# faulted attempt + the clean retry): boundary 1 -> transient raise,
# boundary 2 -> poisoned balance column (leaf 6), boundary 3 -> hang
# past the armed deadline. Every recovery is pure retry/re-dispatch —
# the phase asserts ZERO ladder degradations.
DISPATCH_SCHEDULE = ("seed=7;"
                     "dispatch:*mesh.epoch*@1=raise;"
                     "dispatch:*mesh.epoch*@3=poison:6;"
                     "dispatch:*mesh.epoch*@5=hang:4000")
LADDER_SCHEDULE = "seed=7;dispatch:*mesh.epoch*@1-99=raise"
DEADLINE_MS = "3000"


def main() -> int:
    if os.environ.get("CSTPU_TEST_TPU") != "1":
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if os.environ.get("CSTPU_TEST_TPU") != "1":
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)
    from consensus_specs_tpu.utils import compile_cache
    compile_cache.configure()

    from consensus_specs_tpu import resilience, telemetry
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    from consensus_specs_tpu.resilience import CheckpointStore, faults
    from consensus_specs_tpu.resilience.errors import SimulatedCrash
    from consensus_specs_tpu.testing import factories
    from consensus_specs_tpu.utils.ssz.impl import serialize

    telemetry.set_enabled(True)
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "..", "out")
    os.makedirs(out_dir, exist_ok=True)

    n_dev = 1
    while n_dev * 2 <= min(8, len(jax.devices())):
        n_dev *= 2
    if n_dev < 2:
        print("chaos drill needs a multi-device mesh (have "
              f"{len(jax.devices())} device)", flush=True)
        return 1

    bls.bls_active = False
    spec = phase0.get_spec("minimal")
    spec.clear_caches()
    state = factories.seed_genesis_state(spec, 4 * spec.SLOTS_PER_EPOCH)
    factories.advance_slots(spec, state, 2)
    data = serialize(state, spec.BeaconState)
    spe = int(spec.SLOTS_PER_EPOCH)
    start = int(state.slot)
    warm = (start // spe + 1) * spe + 1        # one boundary in
    target = warm + 3 * spe                    # + 24 slot steps, 3 boundaries
    SLOTS["warmup"] = warm - start
    SLOTS["drive"] = target - warm

    report = {"devices": n_dev, "preset": "minimal",
              "validators": len(state.validator_registry),
              "slots": dict(SLOTS), "deadline_ms": float(DEADLINE_MS),
              "schedules": {"dispatch": DISPATCH_SCHEDULE,
                            "ladder": LADDER_SCHEDULE},
              "phases": {}}
    failures = []
    retrace0 = telemetry.counter("watchdog.retrace_events").value
    relayout0 = telemetry.counter("watchdog.relayout_events").value

    def fresh_core(mesh="default"):
        faults.set_schedule(None)
        os.environ.pop("CSTPU_DEADLINE_MS", None)
        core = ResidentCore.from_checkpoint(
            spec, data,
            mesh=ServingMesh.create(n_dev) if mesh == "default" else mesh)
        core.process_slots(core.state, warm)      # warm boundary, no faults
        return core

    def finish(core):
        final = core.checkpoint_bytes()
        root = core._state_root(core.state)
        core._uninstall()
        faults.set_schedule(None)
        os.environ.pop("CSTPU_DEADLINE_MS", None)
        return final, root

    def phase(name, fn):
        t0 = time.perf_counter()
        counters0 = {k: telemetry.counter(k, always=True).value
                     for k in ("resilience.retries",
                               "resilience.deadline_misses",
                               "resilience.corrupt_outputs",
                               "resilience.transient_errors",
                               "resilience.degradations",
                               "resilience.faults_injected")}
        try:
            row = fn()
        except Exception as exc:        # noqa: BLE001 - a failed phase
            # must still land in out/chaos.json (the CI artifact exists
            # precisely to diagnose failures) and must not keep later
            # phases from running
            import traceback
            traceback.print_exc()
            faults.set_schedule(None)
            os.environ.pop("CSTPU_DEADLINE_MS", None)
            row = {"ok": False,
                   "error": f"{type(exc).__name__}: {exc}"}
        row["seconds"] = round(time.perf_counter() - t0, 2)
        row["counters"] = {
            k.split("resilience.", 1)[-1]:
                int(telemetry.counter(k, always=True).value - v)
            for k, v in counters0.items()}
        ok = row.get("ok", True)
        report["phases"][name] = row
        status = "ok" if ok else "FAIL"
        print(f"[{name}] {status} in {row['seconds']}s: "
              f"{row['counters']}", flush=True)
        if not ok:
            failures.append(name)

    # -- baseline ---------------------------------------------------------
    ref = {}

    def run_baseline():
        core = fresh_core()
        core.process_slots(core.state, target)
        ref["bytes"], ref["root"] = finish(core)
        return {"root": ref["root"].hex(), "ok": True}

    phase("baseline", run_baseline)

    # -- dispatch faults --------------------------------------------------
    def run_dispatch():
        deg0 = telemetry.counter("resilience.degradations", always=True).value
        core = fresh_core()
        os.environ["CSTPU_DEADLINE_MS"] = DEADLINE_MS
        faults.set_schedule(DISPATCH_SCHEDULE)
        core.process_slots(core.state, target)
        final, root = finish(core)
        degraded = telemetry.counter("resilience.degradations",
                                     always=True).value - deg0
        return {"root": root.hex(),
                "bit_identical": final == ref["bytes"],
                "retry_only": degraded == 0,
                "ok": (final == ref["bytes"] and root == ref["root"]
                       and degraded == 0)}

    phase("dispatch", run_dispatch)

    # -- ladder walk ------------------------------------------------------
    def run_ladder():
        resilience.ladder().reset()
        core = fresh_core()
        faults.set_schedule(LADDER_SCHEDULE)
        core.process_slots(core.state, target)
        rung = resilience.ladder().rung_name
        single = core._mesh is None
        final, root = finish(core)
        resilience.ladder().reset()
        return {"root": root.hex(), "final_rung": rung,
                "single_device": single,
                "bit_identical": final == ref["bytes"],
                "ok": (final == ref["bytes"] and rung == "single_device"
                       and single)}

    phase("ladder", run_ladder)

    # -- checkpoint failover ---------------------------------------------
    def run_checkpoint():
        ckpt_root = os.path.join(out_dir, "chaos_ckpt")
        shutil.rmtree(ckpt_root, ignore_errors=True)
        store = CheckpointStore(ckpt_root, keep=4)
        core = fresh_core()
        gen1 = store.save(core.checkpoint_bytes())          # good, at `warm`
        core.process_slots(core.state, warm + spe)
        faults.set_schedule("ckpt.write@1=truncate:33")     # silent corruption
        gen2 = store.save(core.checkpoint_bytes())
        faults.set_schedule("ckpt.write@1=crash:0.5")       # kill mid-write
        crashed = False
        try:
            store.save(core.checkpoint_bytes())
        except SimulatedCrash:
            crashed = True
        core._uninstall()                                   # "the process died"
        faults.set_schedule(None)

        # restart: newest intact generation wins (gen2 is corrupt, the
        # crashed write never committed), under a CHANGED mesh shape
        gen, core2 = store.restore(spec, mesh=ServingMesh.create(2))
        fell_back = (gen == gen1) and (gen2 == gen1 + 1)
        core2.process_slots(core2.state, target)            # replay
        final, root = finish(core2)
        return {"root": root.hex(), "generations": store.generations(),
                "restored_generation": gen, "fell_back": fell_back,
                "kill_mid_write_survived": crashed,
                "restore_mesh_devices": 2,
                "bit_identical": final == ref["bytes"],
                "ok": (final == ref["bytes"] and fell_back and crashed)}

    phase("checkpoint", run_checkpoint)

    # -- residual watchdog gate ------------------------------------------
    retrace = telemetry.counter("watchdog.retrace_events").value - retrace0
    relayout = telemetry.counter("watchdog.relayout_events").value - relayout0
    report["watchdog"] = {"retrace_events": int(retrace),
                          "relayout_events": int(relayout)}
    if retrace or relayout:
        failures.append("watchdog")
    report["health"] = resilience.health_snapshot()
    report["ok"] = not failures

    path = os.path.join(out_dir, "chaos.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"artifact: out/chaos.json; watchdogs across the whole drill: "
          f"{retrace} retrace, {relayout} re-layout events", flush=True)
    if failures:
        print(f"CHAOS DRILL FAIL: {failures}", flush=True)
        return 1
    print("CHAOS DRILL OK — recovered bit-identically from "
          "raise/poison/hang, a full ladder walk, a corrupt checkpoint "
          "generation, and a kill mid-write", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
