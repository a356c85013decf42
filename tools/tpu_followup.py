"""One-shot TPU validation + profiling pass (run on a machine with the chip).

Drives, on the real chip, everything added since the last on-TPU check:
batched G1/G2 decompression, the fused decompress+aggregate paths, the
batched hash_to_g2 cofactor multiply — each against the bignum oracle —
then profiles the epoch-transition sub-stages with honest fences so the
next optimization targets the real bottleneck.

Usage: python tools/tpu_followup.py  (from the repo root)
"""
import os
import sys
import time

import numpy as np

# `python tools/tpu_followup.py` puts tools/ (not the repo root) on
# sys.path; the package and bench live at the root.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def sync(x):
    import jax
    leaf = jax.tree_util.tree_leaves(x)[0]
    return np.asarray(leaf.ravel()[0:1])


def _modeled_traffic_gb(label, fn, *args):
    """(lo, hi) GB of HBM traffic for `fn(*args)` from the memory
    tier's cost model (tools/analysis/memory/liveness.py) over the real
    jaxpr — the roofline's byte denominators, deduped onto the same
    accounting `make memory` budgets — cross-checked against the bytes
    the compiled HLO actually allocates. A >2x peak divergence between
    model and compiled aborts the run: a roofline over an untrusted
    byte model is noise, not a denominator."""
    import jax
    from tools.analysis.memory import liveness as ML
    closed = jax.make_jaxpr(fn)(*args)
    lo, hi = ML.traffic_bounds(closed)
    model = ML.analyze(closed)
    stats = jax.jit(fn).lower(*args).compile().memory_analysis()
    if stats is not None:
        compiled_peak = (int(stats.argument_size_in_bytes)
                         + int(stats.output_size_in_bytes)
                         - int(getattr(stats, "alias_size_in_bytes", 0))
                         + int(stats.temp_size_in_bytes))
        ratio = (max(model.peak_bytes, compiled_peak)
                 / max(1, min(model.peak_bytes, compiled_peak)))
        print(f"[roofline] {label}: modeled peak "
              f"{model.peak_bytes/1e6:.1f} MB vs compiled HLO "
              f"{compiled_peak/1e6:.1f} MB (x{ratio:.2f})", flush=True)
        assert ratio <= 2.0, (
            f"{label}: liveness model and compiled memory_analysis "
            f"diverge x{ratio:.2f} (> 2x) — fix the model before "
            f"trusting this roofline")
    return lo / 1e9, hi / 1e9


class _Stages:
    """Linear stage marker: `stages.next("followup.x")` closes the
    previous stage's telemetry span (printing its wall time + the
    watchdog counters so far) and opens the next — the per-stage
    snapshot embedding without restructuring the linear script."""

    def __init__(self, telemetry):
        self._t = telemetry
        self._cur = None

    def next(self, name=None):
        if self._cur is not None:
            self._cur.__exit__(None, None, None)
            agg = self._t.snapshot()["spans"].get(self._cur.name)
            if agg is not None:
                print(f"[telemetry] {self._cur.name}: "
                      f"{agg['last_ms']:.0f} ms | watchdog retrace="
                      f"{self._t.counter('watchdog.retrace_events').value} "
                      f"relayout="
                      f"{self._t.counter('watchdog.relayout_events').value}",
                      flush=True)
            self._cur = None
        if name is not None:
            self._cur = self._t.span(name)
            self._cur.__enter__()

    def finish(self):
        import json
        self.next(None)
        print("[telemetry] snapshot: "
              + json.dumps(self._t.snapshot()), flush=True)


def main():
    import os

    import jax
    # CPU smoke mode for the harness itself
    if os.environ.get("CSTPU_FOLLOWUP_CPU") == "1":
        jax.config.update("jax_platforms", "cpu")
    # share bench.py's persistent compile cache: the pairing/Merkle programs
    # take minutes to compile fresh on the chip
    from consensus_specs_tpu.utils import compile_cache
    compile_cache.configure()
    print("devices:", jax.devices(), flush=True)

    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.crypto import bls12_381 as gt
    from consensus_specs_tpu.ops import decompress as D
    from consensus_specs_tpu.ops.bls_jax import JaxBackend, hash_to_g2_batch

    telemetry.watchdog.install_compile_listener()
    stages = _Stages(telemetry)
    stages.next("followup.decompress_aggregate")

    # 1) batched G1 decompress: 256 pubkeys, oracle spot-check
    enc = [gt.privtopub(k) for k in range(1, 17)] * 16
    data = np.stack([np.frombuffer(e, np.uint8) for e in enc])
    t0 = time.time()
    x, y, valid, inf = D.g1_decompress_batch(data)
    print(f"g1 decompress 256 first: {time.time()-t0:.1f}s "
          f"valid={bool(valid.all())}", flush=True)
    t0 = time.time()
    D.g1_decompress_batch(data)
    print(f"g1 decompress 256 steady: {time.time()-t0:.2f}s", flush=True)
    from consensus_specs_tpu.ops import fq as F
    ox, oy = gt.decompress_g1(enc[3])
    assert (F.from_mont(np.asarray(x)[3]), F.from_mont(np.asarray(y)[3])) \
        == (ox, oy), "G1 decompress oracle mismatch on TPU"

    # 2) fused aggregate (decompress + addition tree) parity
    jx, py = JaxBackend(), gt.PythonBackend()
    t0 = time.time()
    agg = jx.aggregate_pubkeys(enc)
    print(f"fused aggregate 256 first: {time.time()-t0:.1f}s", flush=True)
    assert agg == py.aggregate_pubkeys(enc), "aggregate parity fail on TPU"
    t0 = time.time()
    jx.aggregate_pubkeys(enc)
    print(f"fused aggregate 256 steady: {time.time()-t0:.2f}s", flush=True)

    # 3) batched hash_to_g2 parity on chip
    reqs = [(bytes([m]) * 32, 1) for m in range(8)]
    t0 = time.time()
    got = hash_to_g2_batch(reqs)
    print(f"hash_to_g2 batch8 first: {time.time()-t0:.1f}s", flush=True)
    assert got == [gt.hash_to_g2(mh, d) for mh, d in reqs], \
        "hash_to_g2 batch parity fail on TPU"
    t0 = time.time()
    hash_to_g2_batch([(bytes([m]) * 32, 2) for m in range(8)])
    print(f"hash_to_g2 batch8 steady: {time.time()-t0:.2f}s", flush=True)

    stages.next("followup.sha_pallas_ab")
    # Sections 4/4b need the real Mosaic pipeline: the unrolled SHA form
    # trips XLA:CPU's algebraic-simplifier rewrite loop (ops/sha256.py) and
    # the compiled Pallas lowering exists only for TPU. Gating them on the
    # device platform lets the REST of this pass smoke-test on CPU, so a
    # Python-level bug here can't waste chip time.
    on_tpu = jax.devices()[0].platform == "tpu"
    import jax.numpy as jnp
    from consensus_specs_tpu.ops.sha256 import sha256_pairs
    rng = np.random.default_rng(5)
    words = jnp.asarray(rng.integers(0, 2 ** 32, (8192, 16), dtype=np.uint32))
    if on_tpu:
        # 4) unrolled == fori sha256 on chip
        a = np.asarray(sha256_pairs(words, unroll=True))
        b = np.asarray(sha256_pairs(words, unroll=False))
        assert (a == b).all(), "unrolled != fori on TPU"
        print("sha256 unrolled == fori on chip", flush=True)

        # 4b) Pallas (Mosaic) pair-hash vs XLA kernel on chip + A/B timing
        from consensus_specs_tpu.ops.sha256_pallas import sha256_pairs_pallas
        t0 = time.time()
        p = np.asarray(sha256_pairs_pallas(words, interpret=False))
        print(f"pallas pair-hash first: {time.time()-t0:.1f}s", flush=True)
        assert (p == a).all(), "pallas != XLA pair-hash on TPU"
        for label, fn in (("pallas", lambda: sha256_pairs_pallas(words, interpret=False)),
                          ("xla", lambda: sha256_pairs(words, unroll=True))):
            t0 = time.time()
            for _ in range(3):
                np.asarray(fn())
            print(f"sha256 pair-hash {label} steady: {(time.time()-t0)/3*1e3:.1f} ms",
                  flush=True)
    else:
        print("[skip] unrolled-SHA + Pallas A/B (TPU-only lowering; "
              "CPU smoke mode)", flush=True)

    stages.next("followup.roofline")
    # 4c) roofline accounting: per kernel, the modeled
    #     bytes/ops, the measured wall-clock, and the implied fraction of
    #     chip peak — so "is this actually fast?" has a denominator.
    #     Peaks assumed (TPU v5e, documented upper bounds): HBM 819 GB/s;
    #     VPU int32 ~4 Tops/s (4 ALUs x 8x128 lanes x ~0.94 GHz x 4-wide).
    #     The fence floor (one tiny-transfer host round trip) is measured
    #     and subtracted: it can dominate ms-scale kernels.
    import jax.numpy as jnp
    HBM_PEAK = 819e9
    VPU_PEAK = 4e12

    tiny = jnp.zeros(8, jnp.uint32)
    jax.block_until_ready(tiny)
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(tiny[0:1])
        rtts.append(time.perf_counter() - t0)
    rtt = min(rtts)
    print(f"[roofline] fence floor (tiny-transfer round trip): {rtt*1e3:.1f} ms",
          flush=True)

    from consensus_specs_tpu.ops.shuffle import shuffle_permutation_on_device
    Vr = 1_000_000
    R = 90
    perm = shuffle_permutation_on_device(bytes(range(32)), Vr, R)
    np.asarray(perm.ravel()[0:1])
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        p2 = shuffle_permutation_on_device(bytes(range(32)), Vr, R)
        np.asarray(p2.ravel()[0:1])
        ts.append(time.perf_counter() - t0)
    t_shuf = max(min(ts) - rtt, 1e-9)
    # traffic bounds from the memory tier's cost model over the REAL
    # round kernel's jaxpr (tools/analysis/memory/liveness.py — the
    # same per-eqn byte accounting the MEM_CONTRACTS budgets use),
    # replacing the hand-maintained B/elem/round table this block used
    # to carry: `hi` streams every eqn's operands/results (no fusion),
    # `lo` is the perfectly-fused floor. The model is cross-checked
    # against what the compiled HLO actually allocates and FAILS on
    # >2x divergence instead of silently trusting itself.
    from consensus_specs_tpu.ops.sha256 import bytes_to_words as _b2w
    from consensus_specs_tpu.ops.shuffle import (_shuffle_rounds_stacked,
                                                 host_pivots)
    _sd = bytes(range(32))
    _sw = jnp.asarray(_b2w(np.frombuffer(_sd, dtype=np.uint8)))
    _pv = jnp.asarray(host_pivots(_sd, Vr, R))
    lo_gb, hi_gb = _modeled_traffic_gb(
        "shuffle rounds", lambda s, p: _shuffle_rounds_stacked(s, p, Vr, R),
        _sw, _pv)
    hbm_gbs = HBM_PEAK / 1e9   # peak in GB/s (traffic model is in GB)
    print(f"[roofline] shuffle 1M x {R} rounds: {t_shuf*1e3:.1f} ms "
          f"(fence-corrected) | traffic model {lo_gb:.1f}-{hi_gb:.1f} GB -> "
          f"{lo_gb/t_shuf:.0f}-{hi_gb/t_shuf:.0f} GB/s = "
          f"{100*lo_gb/t_shuf/hbm_gbs:.1f}-{100*hi_gb/t_shuf/hbm_gbs:.1f}% "
          f"of HBM peak; bandwidth-bound floor {hi_gb/hbm_gbs*1e3:.1f} ms",
          flush=True)

    # A/B: the stacked-movement variant (one [2, n] reverse+roll per round
    # instead of two; bit-equality pinned in tests/test_shuffle_kernel.py)
    sw, pv = _sw, _pv
    ps = _shuffle_rounds_stacked(sw, pv, Vr, R)
    assert np.array_equal(np.asarray(ps), np.asarray(perm)), \
        "stacked shuffle != reference kernel on TPU"
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(_shuffle_rounds_stacked(sw, pv, Vr, R).ravel()[0:1])
        ts.append(time.perf_counter() - t0)
    t_stk = max(min(ts) - rtt, 1e-9)
    print(f"[roofline] shuffle stacked variant: {t_stk*1e3:.1f} ms "
          f"({t_shuf/t_stk:.2f}x vs reference kernel) — adopt via "
          f"install_device_shuffler if it wins", flush=True)

    from consensus_specs_tpu.utils.ssz import bulk as _bulk
    rng_r = np.random.default_rng(3)
    cols_r = [
        jnp.asarray(rng_r.integers(0, 256, (Vr, 48), dtype=np.uint8)),
        jnp.asarray(rng_r.integers(0, 256, (Vr, 32), dtype=np.uint8)),
        jnp.asarray(np.zeros(Vr, np.uint64)), jnp.asarray(np.zeros(Vr, np.uint64)),
        jnp.asarray(np.zeros(Vr, np.uint64)), jnp.asarray(np.zeros(Vr, np.uint64)),
        jnp.asarray(np.zeros(Vr, bool)),
        jnp.asarray(np.full(Vr, 32_000_000_000, np.uint64)),
        jnp.asarray(rng_r.integers(31e9, 33e9, Vr).astype(np.uint64)),
    ]
    jax.block_until_ready(cols_r)
    _bulk.registry_and_balances_roots_device(*cols_r)
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        _bulk.registry_and_balances_roots_device(*cols_r)  # host-materializing
        ts.append(time.perf_counter() - t0)
    t_root = max(min(ts) - rtt, 1e-9)
    # compressions: 8 subtree hashes/validator + ~V top-tree + V/4 balances
    n_comp = 8 * Vr + Vr + Vr // 4
    # one SHA-256 compression ~= 64 rounds x ~25 int ops + schedule ~48 x 15
    ops = n_comp * (64 * 25 + 48 * 15)
    print(f"[roofline] registry+balances root 1M: {t_root*1e3:.1f} ms "
          f"(fence-corrected) | ~{n_comp/1e6:.1f}M compressions, "
          f"~{ops/1e9:.0f} Gop -> {ops/t_root/1e12:.2f} Tops/s = "
          f"{100*ops/t_root/VPU_PEAK:.0f}% of VPU int peak; "
          f"compute-bound floor {ops/VPU_PEAK*1e3:.0f} ms", flush=True)

    # grouped pairing throughput model (if the cache is warm this is fast)
    from consensus_specs_tpu.ops.bls_jax import (grouped_pairing_check,
                                                 stage_example_groups)
    g1s, g2s = stage_example_groups(8)
    dg1s, dg2s = jnp.asarray(g1s), jnp.asarray(g2s)
    ok8 = np.asarray(grouped_pairing_check(dg1s, dg2s))
    assert bool(ok8.all())
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(grouped_pairing_check(dg1s, dg2s))
        ts.append(time.perf_counter() - t0)
    t_pair = max(min(ts) - rtt, 1e-9)
    print(f"[roofline] grouped pairing G=8 (24 Miller loops): "
          f"{t_pair*1e3:.0f} ms fence-corrected = {8/t_pair:.1f} aggverify/s "
          f"(per-group cost amortizes further at G=128)", flush=True)

    stages.next("followup.epoch_profile")
    # 5) epoch sub-stage profile (which term dominates the ~400 ms?)
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        EpochConfig, epoch_transition_device, synthetic_epoch_state)
    spec = phase0.get_spec("mainnet")
    cfg = EpochConfig.from_spec(spec)
    V = 1_000_000
    cols, scal, inp = synthetic_epoch_state(cfg, V, np.random.default_rng(42),
                                            slashed_p=0.001, incl_delay_max=32,
                                            random_slashed_balances=True)
    # epoch_transition_device donates the columns on TPU: hold the host copy
    # needed below, then chain each call's output columns into the next
    elig_host = np.asarray(cols.activation_eligibility_epoch, dtype=np.uint64)
    out = epoch_transition_device(cfg, cols, scal, inp)
    sync(out)
    cols = out[0]
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = epoch_transition_device(cfg, cols, scal, inp)
        sync(out)
        cols = out[0]
        ts.append(time.perf_counter() - t0)
    print(f"epoch full: {min(ts)*1e3:.0f} ms", flush=True)

    import jax
    # isolate the activation-queue sort (suspected dominant term)
    elig = elig_host
    if elig is not None:
        key = jnp.asarray(elig)
        f_sort = jax.jit(lambda k: jnp.argsort(k, stable=True))
        sync(f_sort(key))
        t0 = time.perf_counter()
        sync(f_sort(key))
        print(f"stable argsort alone: {(time.perf_counter()-t0)*1e3:.0f} ms",
              flush=True)

    stages.next("followup.config3_block")
    # 6) the config-3 batched block pipeline on chip: a minimal-preset block
    #    of real attestations through process_attestations_batched ->
    #    verify_indexed_batch (grouped G1 agg, batched G2 decompress,
    #    hash_to_G2, grouped pairing), plus a tampered-signature rejection
    import bench
    from copy import deepcopy
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.utils.ssz.impl import hash_tree_root
    spec_min = phase0.get_spec("minimal")
    old_active = bls.bls_active
    bls.bls_active = True
    bls.set_backend("python")
    try:
        state, block = bench.build_config3_state_and_block(
            spec_min, 8 * spec_min.SLOTS_PER_EPOCH, 4, n_keys=8)
        bls.set_backend("jax")
        good = deepcopy(state)
        t0 = time.time()
        spec_min.state_transition(good, block)
        print(f"config-3 batched block (4 atts) first: {time.time()-t0:.1f}s",
              flush=True)
        good2 = deepcopy(state)
        t0 = time.time()
        spec_min.state_transition(good2, block)
        print(f"config-3 batched block steady: {time.time()-t0:.2f}s", flush=True)
        assert hash_tree_root(good) == hash_tree_root(good2)
        bad = deepcopy(block)
        sig = bytearray(bad.body.attestations[1].signature)
        sig[-1] ^= 1
        bad.body.attestations[1].signature = bytes(sig)
        try:
            spec_min.state_transition(deepcopy(state), bad)
            raise SystemExit("tampered attestation accepted on TPU!")
        except AssertionError:
            pass
        print("config-3 batched block verified + tampered sig rejected on chip",
              flush=True)
    finally:
        bls.bls_active = old_active
        bls.set_backend("python")

    stages.finish()
    print("ALL TPU FOLLOW-UP CHECKS PASSED", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
