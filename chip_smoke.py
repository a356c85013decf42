#!/usr/bin/env python3
"""chip_smoke.py — what the benchmark does not drive, on the chip, in ONE
process.

    python chip_smoke.py              # one TPU chip: phases 1-2
    python chip_smoke.py --bls        # phase 1 + the BLS block phase only

The served path (the small oracle, the 1M resident replay, the four-chip
mesh) is the benchmark's: `python3 benchmark/run.py --workload <cell>`
runs it at full size, held to plain references and to controls
(benchmark/reference.py). What no cell runs is here.

Phases (one JSON object per phase on stdout, then the verdict line):

  1 device            jax.devices(); anything but a TPU is refused
  2 pair_hash_pallas  the Mosaic pair-hash kernel (interpret=False)
                      bit-identical to the XLA kernel and hashlib, and a
                      forest build + dirty update under the pallas backend
                      equal to the xla backend
  3 bls_block         (--bls) one mainnet-preset block with real aggregate
                      attestations through the jax BLS backend, verdicts
                      equal to the python backend, a tampered one rejected

Any failed check or exception exits non-zero with no verdict line; nothing
here catches a phase's failure. `--rehearse` relaxes ONLY the phase-1
platform check (so the script can be walked on the CPU), is echoed in
every phase line, and never prints the verdict line. All timings printed
here are smoke readings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time
from copy import deepcopy

import numpy as np


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------

class Run:
    """Per-process bookkeeping: the rehearsal flag echoed in every phase
    line, and a jax.monitoring listener that counts every executable this
    process builds or loads (a warm persistent cache still counts one
    per program)."""

    def __init__(self, rehearsal: bool, seed: int):
        self.rehearsal = rehearsal
        self.seed = seed
        self.compiles = 0
        self.compile_seconds = 0.0
        self.cache_hits = 0
        self.largest: list = []        # seconds of the five slowest
        from jax._src import monitoring

        def on_duration(event: str, duration: float, **kw) -> None:
            if event.endswith("backend_compile_duration"):
                self.compiles += 1
                self.compile_seconds += duration
                self.largest = sorted(
                    self.largest + [round(duration, 2)], reverse=True)[:5]

        def on_event(event: str, **kw) -> None:
            if event.endswith("compilation_cache/cache_hits"):
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def mark(self):
        return (self.compiles, self.compile_seconds, self.cache_hits,
                time.perf_counter())

    def emit(self, phase: str, since, **row) -> dict:
        c0, s0, h0, t0 = since
        line = {"phase": phase, "rehearsal": self.rehearsal,
                "seconds": round(time.perf_counter() - t0, 3),
                "compile_seconds": round(self.compile_seconds - s0, 3),
                "compiles": self.compiles - c0,
                "cache_hits": self.cache_hits - h0, **row}
        print(json.dumps(line), flush=True)
        return line


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


# ---------------------------------------------------------------------------
# Phase 1: device
# ---------------------------------------------------------------------------

def phase_device(run: Run) -> dict:
    since = run.mark()
    import jax
    import jaxlib
    from consensus_specs_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    devices = jax.devices()
    d0 = devices[0]
    if not run.rehearsal and d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: the first device is {d0.platform!r}, not a TPU — "
            f"this script measures nothing on a host backend")
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    run.emit("device", since, device=device, jax=jax.__version__,
             jaxlib=jaxlib.__version__, libtpu=libtpu,
             compile_cache_dir=cache_dir, seed=run.seed)
    return device


# ---------------------------------------------------------------------------
# Phase 2: pair_hash_pallas
# ---------------------------------------------------------------------------

def phase_pair_hash_pallas(run: Run, lanes: int = 1 << 16,
                           leaves: int = 1 << 12) -> dict:
    """The Mosaic body on the chip (the rehearsal runs the interpreter —
    Mosaic lowers for TPUs only — and says so in its line)."""
    since = run.mark()
    import jax.numpy as jnp
    from consensus_specs_tpu.ops import sha256 as S
    from consensus_specs_tpu.ops.sha256_pallas import sha256_pairs_pallas
    from consensus_specs_tpu.utils.ssz.incremental import IncrementalMerkleTree

    interpret = run.rehearsal
    rng = np.random.default_rng(run.seed)
    words = rng.integers(0, 1 << 32, (lanes, 16), dtype=np.uint32)
    t0 = time.perf_counter()
    got = np.asarray(sha256_pairs_pallas(jnp.asarray(words),
                                         interpret=interpret))
    t_first = time.perf_counter() - t0
    want = np.asarray(S.sha256_pairs(jnp.asarray(words)))
    check(np.array_equal(got, want),
          "pallas pair hash != the XLA kernel (ops/sha256.sha256_pairs)")
    raw = S.words_to_bytes(words)
    for i in rng.integers(0, lanes, 64):
        check(S.words_to_bytes(got[i:i + 1]).tobytes()
              == hashlib.sha256(raw[i].tobytes()).digest(),
              f"pallas pair hash lane {i} != hashlib")

    # one forest build + dirty update + root per backend at a reduced
    # leaf count: xla takes the one-program build the cells run, pallas the
    # per-level launches behind CSTPU_MERKLE_BACKEND=pallas
    leaf_words = rng.integers(0, 1 << 32, (leaves, 8), dtype=np.uint32)
    dirty = np.sort(rng.choice(leaves, 16, replace=False)).astype(np.int32)
    rows = rng.integers(0, 1 << 32, (dirty.shape[0], 8), dtype=np.uint32)
    roots = {}
    for name in ("xla", "pallas"):
        # the rehearsal cannot select the backend by name: its default
        # kernel is Mosaic, which lowers for TPUs only
        pair_fn = None
        if name == "pallas" and interpret:
            pair_fn = lambda w: sha256_pairs_pallas(w, interpret=True)  # noqa: E731
        S.set_merkle_pair_backend(name)
        try:
            tree = IncrementalMerkleTree(jnp.asarray(leaf_words),
                                         pair_fn=pair_fn)
            built = tree.root()
            tree.update(dirty, rows)
            roots[name] = (built, tree.root())
        finally:
            S.set_merkle_pair_backend(None)
    check(roots["xla"] == roots["pallas"],
          "forest build/update roots differ between the xla and pallas "
          "pair-hash backends")
    check(roots["xla"][0] != roots["xla"][1], "the dirty update was a no-op")
    return run.emit(
        "pair_hash_pallas", since, lanes=lanes, forest_leaves=leaves,
        dirty_leaves=int(dirty.shape[0]),
        kernel="interpreter (rehearsal)" if interpret else "mosaic",
        first_call_seconds=round(t_first, 3), identical=True,
        forest_root=roots["pallas"][1].hex())


# ---------------------------------------------------------------------------
# Phase 3 (--bls): bls_block
# ---------------------------------------------------------------------------

def phase_bls_block(run: Run, validators: int = 256, attestations: int = 1,
                    keys: int = 8) -> dict:
    """One mainnet-preset process_block whose every signature — proposer,
    randao, and the aggregate attestation through verify_indexed_batch —
    is checked by the jax backend. One attestation keeps every pairing
    launch at ONE group shape (G=1, P=2): each further shape is another
    Miller + final-exponentiation compile, minutes apiece on the v5e."""
    since = run.mark()
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.testing.states import (
        build_config3_state_and_block)

    spec = phase0.get_spec("mainnet")
    spec.clear_caches()
    bls.bls_active = True
    try:
        bls.set_backend("python")       # stage with the bignum backend
        state, block = build_config3_state_and_block(
            spec, validators, attestations, n_keys=keys)
        # tampered: a VALID signature of the wrong message (the block's
        # randao reveal) in the attestation's place, the block re-signed
        # so that the attestation check is the only one that can fail
        tampered = deepcopy(block)
        tampered.body.attestations[0].signature = block.body.randao_reveal
        proposer_key = (spec.get_beacon_proposer_index(state) % keys) + 1
        tampered.signature = bls.get_backend().sign(
            spec.signing_root(tampered), proposer_key,
            spec.get_domain(state, spec.DOMAIN_BEACON_PROPOSER))

        verdicts = {}
        for backend in ("python", "jax"):
            bls.set_backend(backend)
            rows = []
            for name, blk in (("valid", block), ("tampered", tampered)):
                s = deepcopy(state)
                c0 = run.compile_seconds
                t0 = time.perf_counter()
                try:
                    spec.process_block(s, blk)
                    failed_in = None
                except AssertionError:
                    import traceback
                    failed_in = "/".join(f.name for f in traceback.extract_tb(
                        sys.exc_info()[2])[1:][-3:])
                rows.append({"block": name, "accepted": failed_in is None,
                             "rejected_in": failed_in,
                             "seconds": round(time.perf_counter() - t0, 3),
                             "compile_seconds": round(
                                 run.compile_seconds - c0, 3)})
            verdicts[backend] = rows
    finally:
        bls.set_backend("python")
        bls.bls_active = False
        spec.clear_caches()
    accepted = {b: [r["accepted"] for r in rows]
                for b, rows in verdicts.items()}
    check(accepted["python"] == [True, False],
          f"python backend verdicts {accepted['python']}")
    check(accepted["jax"] == accepted["python"],
          f"jax backend verdicts {accepted['jax']} != python backend")
    for rows in verdicts.values():
        check("attestation" in rows[1]["rejected_in"],
              f"the tampered block was rejected in "
              f"{rows[1]['rejected_in']!r}, not by its attestation signature")
    return run.emit("bls_block", since, preset="mainnet",
                    validators=validators, attestations=attestations,
                    distinct_keys=keys, group_shape={"G": 1, "P": 2},
                    verdicts=verdicts,
                    slowest_compiles_seconds=run.largest)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--rehearse", action="store_true",
                    help="accept a non-TPU first device; never prints the "
                         "verdict line")
    ap.add_argument("--bls", action="store_true",
                    help="run phase 1 and the BLS block phase only")
    args = ap.parse_args(argv)

    import jax  # noqa: F401 - fails here where jax itself cannot start
    run = Run(rehearsal=args.rehearse, seed=args.seed)
    device = phase_device(run)
    if args.bls:
        phase_bls_block(run)
    else:
        phase_pair_hash_pallas(run)
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "phases_passed": True,
                          "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
