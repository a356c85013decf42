"""`make telemetry`: drive the resident serving loop with telemetry on
and dump the observability artifacts:

    out/trace.json        Chrome-trace/Perfetto span timeline
    out/metrics.prom      Prometheus text exposition (the /metrics body)
    out/telemetry.jsonl   one snapshot line per epoch driven

Runs on the virtual 8-device CPU mesh (the test topology; a real
accelerator brings its own devices), asserts the retrace and re-layout
watchdogs stay at ZERO events across the steady-state drive — the
runtime pjit layout-stability contract — and exits non-zero otherwise.

Usage: python tools/telemetry_smoke.py  (from the repo root)
"""
import os
import sys
import time

# `python tools/telemetry_smoke.py` puts tools/ (not the repo root) on
# sys.path; the package lives at the root.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    # CPU pin + virtual mesh BEFORE backend init (the conftest recipe)
    if os.environ.get("CSTPU_TEST_TPU") != "1":
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if os.environ.get("CSTPU_TEST_TPU") != "1":
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", 8)
    from consensus_specs_tpu.utils import compile_cache
    compile_cache.configure()

    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    from consensus_specs_tpu.testing import factories

    telemetry.set_enabled(True)
    telemetry.watchdog.install_compile_listener()
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "..", "out")
    os.makedirs(out_dir, exist_ok=True)
    jsonl_path = os.path.join(out_dir, "telemetry.jsonl")
    if os.path.exists(jsonl_path):
        os.remove(jsonl_path)

    n_dev = 1
    while n_dev * 2 <= min(8, len(jax.devices())):
        n_dev *= 2
    mesh = ServingMesh.create(n_dev) if n_dev >= 2 else None
    print(f"devices: {len(jax.devices())} ({jax.devices()[0].platform}); "
          f"serving mesh: {n_dev if mesh else 'single-device'}", flush=True)

    bls.bls_active = False
    spec = phase0.get_spec("minimal")
    spec.clear_caches()
    state = factories.seed_genesis_state(spec, 4 * spec.SLOTS_PER_EPOCH)
    factories.advance_slots(spec, state, 2)
    core = ResidentCore(spec, state, mesh=mesh)
    spe = spec.SLOTS_PER_EPOCH
    epochs = int(os.environ.get("CSTPU_TELEMETRY_EPOCHS", "3"))
    try:
        target = (state.slot // spe + 1) * spe + 1
        t0 = time.perf_counter()
        core.process_slots(state, target)             # warm-up epoch
        print(f"warm-up epoch: {time.perf_counter() - t0:.2f}s", flush=True)
        retrace0 = telemetry.counter("watchdog.retrace_events").value
        relayout0 = telemetry.counter("watchdog.relayout_events").value
        for i in range(epochs):
            t0 = time.perf_counter()
            core.process_slots(state, target + (i + 1) * spe)
            last = {name: agg["last_ms"] for name, agg
                    in telemetry.snapshot()["spans"].items()}
            print(f"epoch {i}: {time.perf_counter() - t0:.2f}s "
                  f"(stage {last['resident.stage']:.0f} ms, device "
                  f"{last['resident.device']:.0f} ms, refresh "
                  f"{last['resident.refresh']:.0f} ms)", flush=True)
            telemetry.write_jsonl(jsonl_path, extra={"epoch": i})
        retrace = telemetry.counter("watchdog.retrace_events").value - retrace0
        relayout = (telemetry.counter("watchdog.relayout_events").value
                    - relayout0)
    finally:
        core.exit()

    telemetry.dump_chrome_trace(os.path.join(out_dir, "trace.json"))
    telemetry.dump_prometheus(os.path.join(out_dir, "metrics.prom"))
    telemetry.set_enabled(None)
    snap = telemetry.snapshot()
    print(f"artifacts: out/trace.json ({len(telemetry.ring())} spans), "
          f"out/metrics.prom ({len(snap['counters'])} counters, "
          f"{len(snap['spans'])} span names), out/telemetry.jsonl "
          f"({epochs} lines)", flush=True)
    print(f"watchdogs over {epochs} steady epochs "
          f"({epochs * spe} slot steps, {epochs} boundaries): "
          f"{retrace} retrace, {relayout} re-layout events", flush=True)
    if retrace or relayout:
        print("FAIL: the steady-state resident loop tripped a watchdog",
              flush=True)
        return 1
    print("TELEMETRY SMOKE OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
