"""`make firehose`: drive the streaming verifier under sustained
synthetic gossip load on the 8-device virtual mesh and dump the
acceptance artifact:

    out/firehose.json     load shape, throughput, occupancy, verdict
                          diff, watchdog + deadline counters

Each wave mixes VALID aggregates with a deterministic-FALSE one
(group 0's G1 points against group 1's G2 points), so the verdict
diff against the synchronous `_grouped_pairing_dispatch` exercises
both polarities every round. Exits non-zero on ANY of: a streamed
verdict differing from the synchronous path, a retrace or re-layout
watchdog event, or a deadline miss at the nominal load point.

Usage: python tools/firehose_smoke.py  (from the repo root)
Env:   CSTPU_FIREHOSE_GROUPS (target batch occupancy, default 8 — the
       smoke shape; the committed contract shape is 128),
       CSTPU_FIREHOSE_ROUNDS (waves, default 4),
       CSTPU_FIREHOSE_DEADLINE_MS (flush budget, default 600000).
"""
import json
import os
import sys
import time

# `python tools/firehose_smoke.py` puts tools/ (not the repo root) on
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

    from consensus_specs_tpu import streaming, telemetry
    from consensus_specs_tpu.ops import bls_jax as BJ

    telemetry.set_enabled(True)
    telemetry.watchdog.install_compile_listener()
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "..", "out")
    os.makedirs(out_dir, exist_ok=True)

    target = int(os.environ.get("CSTPU_FIREHOSE_GROUPS", 8))
    rounds = int(os.environ.get("CSTPU_FIREHOSE_ROUNDS", 4))
    deadline_ms = float(os.environ.get("CSTPU_FIREHOSE_DEADLINE_MS",
                                       600_000.0))
    print(f"devices: {len(jax.devices())} ({jax.devices()[0].platform}); "
          f"firehose target {target} groups x {rounds} waves, "
          f"deadline {deadline_ms:.0f} ms", flush=True)

    g1, g2 = BJ.stage_example_groups(min(8, max(2, target)))
    n_distinct, P = g1.shape[0], g1.shape[1]

    def pairs_for(k):
        if k % target == target - 1:
            # the wave's deterministic-FALSE group: mismatched points
            return [(g1[0, p], g2[1, p]) for p in range(P)]
        i = k % n_distinct
        return [(g1[i, p], g2[i, p]) for p in range(P)]

    v = streaming.StreamingVerifier(target_groups=target,
                                    deadline_ms=deadline_ms)
    t0 = time.perf_counter()
    for k in range(target):                 # one full wave: compiles the
        v.submit_staged(("warm", k), pairs_for(k))   # steady batch shape
    v.pump()
    v.flush()
    print(f"warm-up flush: {time.perf_counter() - t0:.2f}s", flush=True)

    retrace0 = telemetry.counter("watchdog.retrace_events").value
    relayout0 = telemetry.counter("watchdog.relayout_events").value
    miss0 = telemetry.counter("firehose.deadline_miss", always=True).value
    keys = []
    t0 = time.perf_counter()
    for w in range(rounds):
        for k in range(target):
            key = (w, k)
            keys.append(key)
            v.submit_staged(key, pairs_for(k))
        v.pump()
    streamed = {}
    streamed.update(v.flush())
    wall = time.perf_counter() - t0

    sync = BJ._grouped_pairing_dispatch(
        [(key, pairs_for(key[1])) for key in keys])
    mismatches = [key for key in keys if streamed[key] != sync[key]]
    retrace = telemetry.counter("watchdog.retrace_events").value - retrace0
    relayout = (telemetry.counter("watchdog.relayout_events").value
                - relayout0)
    misses = (telemetry.counter("firehose.deadline_miss",
                                always=True).value - miss0)
    n_false = sum(1 for key in keys if not streamed[key])

    row = {
        "target_groups": target,
        "rounds": rounds,
        "groups": len(keys),
        "false_verdicts": n_false,
        "wall_s": round(wall, 3),
        "aggverify_per_s": round(len(keys) / wall, 2),
        "pairings_per_s": round(len(keys) * P / wall, 2),
        "verdict_mismatches": len(mismatches),
        "deadline_misses": int(misses),
        "watchdog": {"retrace_events": int(retrace),
                     "relayout_events": int(relayout)},
        "health": streaming.firehose_health(),
    }
    streaming.activate(None)
    path = os.path.join(out_dir, "firehose.json")
    with open(path, "w") as fh:
        json.dump(row, fh, indent=2)
    print(f"artifact: out/firehose.json — {row['aggverify_per_s']} "
          f"aggverify/s ({row['pairings_per_s']} pairings/s), "
          f"{n_false}/{len(keys)} false verdicts (expected {rounds}), "
          f"{misses} deadline misses, watchdogs {retrace} retrace / "
          f"{relayout} re-layout", flush=True)
    if mismatches:
        print(f"FAIL: {len(mismatches)} streamed verdict(s) differ from "
              f"the synchronous path: {mismatches[:5]}", flush=True)
        return 1
    if n_false != rounds:
        print(f"FAIL: expected exactly {rounds} false verdicts (one per "
              f"wave), saw {n_false}", flush=True)
        return 1
    if retrace or relayout:
        print("FAIL: the steady-state firehose tripped a watchdog",
              flush=True)
        return 1
    if misses:
        print("FAIL: deadline miss at the nominal load point", flush=True)
        return 1
    print("FIREHOSE SMOKE OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
