#!/usr/bin/env python3
"""chip_smoke.py — the resident serving path on the chip, in ONE process.

    python chip_smoke.py              # one TPU chip: phases 1-4
    python chip_smoke.py --bls        # phase 1 + the BLS block phase only
    python chip_smoke.py --chips 4    # phase 1 + the 4-chip serving mesh only

Phases (one JSON object per phase on stdout, then the verdict line):

  1 device            jax.devices(); anything but a TPU is refused
  2 oracle_small      minimal preset, a few hundred validators:
                      ResidentCore(spec, state) driven with blocks across
                      >= 2 epoch boundaries and one registry-mutating
                      block (fallback + incremental re-entry), post-state
                      byte-identical to the pure-Python object model
  3 resident_1m       mainnet preset, V = 1,000,000 from --seed: numpy
                      columns -> SSZ bytes -> ResidentCore.from_checkpoint
                      -> 65 slots with per-slot full-state roots and
                      full-participation attestations -> 2 epoch
                      boundaries -> checkpoint round trip; roots checked
                      against an independent hashlib merkleization, the
                      epoch program against its un-donated twin on the
                      host backend, and the watchdog/resilience counters
                      against zero
  4 pair_hash_pallas  the Mosaic pair-hash kernel (interpret=False)
                      bit-identical to the XLA kernel and hashlib, and a
                      forest build + dirty update under the pallas backend
                      equal to the xla backend
  5 bls_block         (--bls) one mainnet-preset block with real aggregate
                      attestations through the jax BLS backend, verdicts
                      equal to the python backend, a tampered one rejected

Any failed check or exception exits non-zero with no verdict line; nothing
here catches a phase's failure. `--rehearse` relaxes ONLY the phase-1
platform check (so the script can be walked on the CPU at a tiny
`--validators`), is echoed in every phase line, and never prints the
verdict line. All timings printed here are smoke readings, not benchmark
numbers.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time
from copy import deepcopy

import numpy as np

_RESILIENCE_COUNTERS = ("resilience.degradations", "resilience.retries",
                        "resilience.transient_errors")
_WATCHDOG_COUNTERS = ("watchdog.retrace_events", "watchdog.relayout_events")


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------

class Run:
    """Per-process bookkeeping: the rehearsal flag echoed in every phase
    line, and a jax.monitoring listener that counts every executable this
    process builds or loads (a warm persistent cache still counts one
    per program — a steady state must count ZERO)."""

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


def _counters(names) -> dict:
    from consensus_specs_tpu import telemetry
    return {n: int(telemetry.counter(n, always=True).value) for n in names}


def _byte_fetch(out) -> None:
    """The one-element byte-fetch fence (bench.py `_sync`)."""
    import jax
    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(leaf.ravel()[0:1])


# ---------------------------------------------------------------------------
# Independent host merkleization (hashlib only — shares nothing with the
# package's SSZ code, which is the code under test)
# ---------------------------------------------------------------------------

def _sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def _zero_hashes(depth: int) -> list:
    z = [b"\x00" * 32]
    for _ in range(depth):
        z.append(_sha(z[-1] + z[-1]))
    return z


def _merkle_root(chunks: list, mix_len: int) -> bytes:
    """SSZ list root: next-pow2 virtual zero padding, length mixed in."""
    depth = max(len(chunks) - 1, 0).bit_length()
    zeros = _zero_hashes(depth)
    level = chunks or [zeros[0]]
    for d in range(depth):
        if len(level) % 2:
            level = level + [zeros[d]]
        level = [_sha(level[i] + level[i + 1])
                 for i in range(0, len(level), 2)]
    return _sha(level[0] + mix_len.to_bytes(32, "little"))


def host_registry_balances_roots(cols: dict, pk: np.ndarray,
                                 wc: np.ndarray) -> tuple:
    """(registry_root, balances_root) of `List[Validator]` / `List[uint64]`
    from host columns with nothing but hashlib: each Validator is eight
    32-byte leaves (pubkey root, withdrawal credentials, four epochs,
    slashed, effective balance) under a depth-3 tree."""
    n = int(cols["balance"].shape[0])

    def u64_chunks(col) -> bytes:
        out = np.zeros((n, 32), np.uint8)
        out[:, :8] = np.asarray(col, np.uint64).astype("<u8") \
            .view(np.uint8).reshape(n, 8)
        return out.tobytes()

    pk_pad = np.zeros((n, 64), np.uint8)
    pk_pad[:, :48] = pk
    pkb = pk_pad.tobytes()
    wcb = np.ascontiguousarray(wc).tobytes()
    fields = [u64_chunks(cols[f]) for f in (
        "activation_eligibility_epoch", "activation_epoch", "exit_epoch",
        "withdrawable_epoch")]
    fields.append(u64_chunks(np.asarray(cols["slashed"], np.uint8)))
    fields.append(u64_chunks(cols["effective_balance"]))
    leaves = []
    for i in range(n):
        lo, hi = 32 * i, 32 * i + 32
        c = [_sha(pkb[64 * i:64 * i + 64]), wcb[lo:hi]] \
            + [f[lo:hi] for f in fields]
        leaves.append(_sha(_sha(_sha(c[0] + c[1]) + _sha(c[2] + c[3]))
                           + _sha(_sha(c[4] + c[5]) + _sha(c[6] + c[7]))))
    bal = np.zeros(-(-n // 4) * 4, "<u8")
    bal[:n] = cols["balance"]
    balb = bal.tobytes()
    chunks = [balb[i:i + 32] for i in range(0, len(balb), 32)]
    return _merkle_root(leaves, n), _merkle_root(chunks, n)


# ---------------------------------------------------------------------------
# Phase 1: device
# ---------------------------------------------------------------------------

def phase_device(run: Run, want_chips: int) -> dict:
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
    check(len(devices) >= want_chips,
          f"need {want_chips} device(s), jax reports {len(devices)}")
    try:
        jax.devices("cpu")
    except RuntimeError as exc:
        raise SystemExit(
            "chip_smoke: no CPU backend beside the accelerator (does "
            "JAX_PLATFORMS name the tpu alone?) — the host-backend twin "
            f"comparison of the epoch program cannot run: {exc}")
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
# Phase 2: oracle_small
# ---------------------------------------------------------------------------

def phase_oracle_small(run: Run, validators: int = 256) -> dict:
    """ResidentCore vs the pure-Python object model at a size where the
    oracle is affordable. The reference path runs under
    `core.suspended()` (the unpatched spec) on its own deep copy."""
    since = run.mark()
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    from consensus_specs_tpu.testing import factories
    from consensus_specs_tpu.utils.ssz.impl import hash_tree_root, serialize

    resil0 = _counters(_RESILIENCE_COUNTERS)
    bls.bls_active = False
    spec = phase0.get_spec("minimal")
    spec.clear_caches()
    spe = int(spec.SLOTS_PER_EPOCH)
    state = factories.seed_genesis_state(spec, validators)
    factories.advance_slots(spec, state, 2)
    ref, res = deepcopy(state), deepcopy(state)
    core = ResidentCore(spec, res, mesh=None)
    n_blocks = spe + 2
    slashing_at = spe // 2 + 1     # mid-drive, epoch > 0
    boundaries = fallbacks = 0
    try:
        for i in range(n_blocks):
            with core.suspended():
                att = factories.new_attestation(spec, ref)
                block = factories.empty_block_next(spec, ref)
                block.slot = ref.slot + spec.MIN_ATTESTATION_INCLUSION_DELAY
                block.body.attestations.append(att)
                if i == slashing_at:
                    block.body.proposer_slashings.append(
                        factories.double_proposal(spec, ref))
                    fallbacks += 1
                epoch0 = spec.get_current_epoch(ref)
                spec.state_transition(ref, block)
                boundaries += spec.get_current_epoch(ref) - epoch0
            core.state_transition(res, block)
            check(hash_tree_root(ref) == core._state_root(res),
                  f"oracle_small: state root diverged from the object "
                  f"model after block {i} (slot {block.slot})")
    finally:
        core.exit()
    check(boundaries >= 2, f"oracle_small crossed {boundaries} boundaries")
    check(any(v.slashed for v in ref.validator_registry),
          "oracle_small: the slashing block did not slash")
    post = serialize(ref, spec.BeaconState)
    check(post == serialize(res, spec.BeaconState),
          "oracle_small: serialized post-state differs from the object model")
    check(_counters(_RESILIENCE_COUNTERS) == resil0,
          "oracle_small: a resilience counter moved")
    spec.clear_caches()
    return run.emit(
        "oracle_small", since, preset="minimal", validators=validators,
        blocks=n_blocks, boundaries=int(boundaries),
        fallback_blocks=fallbacks, reference="object model (pure Python)",
        post_state_root=bytes(hash_tree_root(ref)).hex(),
        post_state_bytes=len(post), identical=True)


# ---------------------------------------------------------------------------
# Phase 3: resident_1m
# ---------------------------------------------------------------------------

def seeded_checkpoint(spec, validators: int, seed: int) -> bytes:
    """A serialized mainnet-preset BeaconState at the last slot of epoch
    1 with `validators` active validators, assembled from numpy columns
    (no Validator objects): balances scatter around 32 ETH so the
    effective-balance hysteresis and the reward/penalty arithmetic see
    both sides, identity columns are random bytes."""
    from consensus_specs_tpu.utils.ssz.bulk import uint64_list_root_from_column
    from consensus_specs_tpu.utils.ssz.columns import state_bytes_from_columns

    rng = np.random.default_rng(seed)
    v = validators
    far = np.uint64(int(spec.FAR_FUTURE_EPOCH))
    max_eb = int(spec.MAX_EFFECTIVE_BALANCE)
    inc = int(spec.EFFECTIVE_BALANCE_INCREMENT)
    balance = (max_eb - inc // 2
               + rng.integers(0, 2 * inc, v)).astype(np.uint64)
    cols = {
        "pubkey": rng.integers(0, 256, (v, 48), dtype=np.uint8),
        "withdrawal_credentials": rng.integers(0, 256, (v, 32),
                                               dtype=np.uint8),
        "activation_eligibility_epoch": np.zeros(v, np.uint64),
        "activation_epoch": np.zeros(v, np.uint64),
        "exit_epoch": np.full(v, far, np.uint64),
        "withdrawable_epoch": np.full(v, far, np.uint64),
        "slashed": np.zeros(v, bool),
        "effective_balance": np.minimum(balance - balance % np.uint64(inc),
                                        np.uint64(max_eb)),
        "balance": balance,
    }
    light = spec.BeaconState(
        genesis_time=0, deposit_index=v,
        latest_eth1_data=spec.Eth1Data(deposit_root=b"\x42" * 32,
                                       deposit_count=v,
                                       block_hash=spec.ZERO_HASH))
    index_root = uint64_list_root_from_column(np.arange(v, dtype=np.uint64))
    for i in range(spec.LATEST_ACTIVE_INDEX_ROOTS_LENGTH):
        light.latest_active_index_roots[i] = index_root
    light.slot = 2 * spec.SLOTS_PER_EPOCH - 1
    return state_bytes_from_columns(light, cols, spec)


def append_slot_attestations(spec, state, lay, slot, target_epoch, source,
                             store) -> int:
    """Full-participation PendingAttestations for every committee of
    `slot`, from the committee layout — what the slot's blocks would
    have appended (bench.py's resident stage stages the same way)."""
    cps = lay.count // spec.SLOTS_PER_EPOCH
    start = spec.get_epoch_start_slot(target_epoch)
    for off in range((slot - start) * cps, (slot - start + 1) * cps):
        shard = (lay.start_shard + off) % spec.SHARD_COUNT
        size = int(lay.bounds[off + 1] - lay.bounds[off])
        parent = state.current_crosslinks[shard]
        bitfield = bytearray(b"\xff" * (size // 8))
        if size % 8:
            bitfield.append((1 << (size % 8)) - 1)
        store.append(spec.PendingAttestation(
            aggregation_bitfield=bytes(bitfield),
            data=spec.AttestationData(
                beacon_block_root=spec.get_block_root_at_slot(state, slot),
                source_epoch=source[0], source_root=source[1],
                target_epoch=target_epoch,
                target_root=spec.get_block_root(state, target_epoch),
                crosslink=spec.Crosslink(
                    shard=shard,
                    parent_root=spec.hash_tree_root(parent),
                    start_epoch=parent.end_epoch,
                    end_epoch=min(target_epoch, parent.end_epoch
                                  + spec.MAX_EPOCHS_PER_CROSSLINK))),
            inclusion_delay=spec.MIN_ATTESTATION_INCLUSION_DELAY,
            proposer_index=int(lay.shuffled[lay.bounds[off]])))
    return cps


def _median(xs) -> float:
    return float(np.median(np.asarray(xs))) if len(xs) else 0.0


def phase_resident_1m(run: Run, validators: int = 1_000_000) -> dict:
    since = run.mark()
    import jax
    import jax.numpy as jnp
    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        ValidatorColumns, _epoch_layout, _epoch_transition_jit,
        _epoch_transition_pd)
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    from consensus_specs_tpu.ops.shuffle import install_device_shuffler
    from consensus_specs_tpu.resilience import dispatch as rdispatch

    telemetry.set_enabled(True)
    resil0 = _counters(_RESILIENCE_COUNTERS)
    bls.bls_active = False
    install_device_shuffler()
    spec = phase0.get_spec("mainnet")
    spec.clear_caches()
    spe = int(spec.SLOTS_PER_EPOCH)

    t0 = time.perf_counter()
    data = seeded_checkpoint(spec, validators, run.seed)
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    core = ResidentCore.from_checkpoint(spec, data, mesh=None)
    _byte_fetch(core.cols)
    t_enter = time.perf_counter() - t0
    state = core.state

    # the epoch dispatch's inputs, kept for the host-backend twin: cols is
    # DONATED on the chip, so it is taken to the host before the boundary
    # slot (outside every timing); scal/inp are not donated and are only
    # referenced here
    captured = {}
    dispatch = core._epoch_dispatch

    def recording_dispatch(scal, inp):
        captured["scal"], captured["inp"] = scal, inp
        return dispatch(scal, inp)
    core._epoch_dispatch = recording_dispatch

    slot_rows, boundary_rows = [], []
    n_atts = 0
    try:
        lay = None
        watch1 = compiles1 = None
        for _ in range(spe + 1):
            slot = int(state.slot)
            boundary = (slot + 1) % spe == 0
            if boundary and boundary_rows:
                captured["cols"] = core._materialize_np_cols()
            c0, s0 = run.compiles, run.compile_seconds
            t0 = time.perf_counter()
            core.process_slots(state, slot + 1)
            dt = time.perf_counter() - t0
            row = {"slot": slot, "seconds": round(dt, 6),
                   "compiles": run.compiles - c0,
                   "compile_seconds": round(run.compile_seconds - s0, 3)}
            if boundary:
                row.update({k: round(v, 6) for k, v in core.timings.items()})
                boundary_rows.append(row)
                lay = None      # rotation: the next epoch's layout is fresh
                if watch1 is None:
                    # everything is warm from here on
                    watch1 = _counters(_WATCHDOG_COUNTERS)
                    compiles1 = run.compiles
            else:
                slot_rows.append(row)
            if int(state.slot) % spe == 0 and len(boundary_rows) == 2:
                break
            # staging (untimed): the attestations the slot's blocks carry
            if lay is None:
                lay = _epoch_layout(spec, state, core.mirrors,
                                    spec.get_current_epoch(state))
            if int(state.slot) > lay.epoch * spe:
                n_atts += append_slot_attestations(
                    spec, state, lay, int(state.slot) - 1,
                    spec.get_current_epoch(state),
                    (state.current_justified_epoch,
                     state.current_justified_root),
                    state.current_epoch_attestations)
        check(len(boundary_rows) == 2 and len(slot_rows) == spe - 1,
              f"drive shape: {len(boundary_rows)} boundaries, "
              f"{len(slot_rows)} plain slots")
        warm_compiles = run.compiles - compiles1
        check(warm_compiles == 0,
              f"{warm_compiles} executable(s) built after the first "
              f"boundary — the steady state is compiling")
        check(_counters(_WATCHDOG_COUNTERS) == watch1,
              "a retrace/re-layout watchdog fired after the first boundary")

        # -- roots vs an independent host merkleization --------------------
        reg_root, bal_root = core._registry_balances_roots()
        state_root = core._state_root(state)
        host_cols = core._materialize_np_cols()
        t0 = time.perf_counter()
        want_reg, want_bal = host_registry_balances_roots(
            host_cols, core._pk_np, core._wc_np)
        t_host_merkle = time.perf_counter() - t0
        check(bytes(reg_root) == want_reg,
              "registry root != independent hashlib merkleization")
        check(bytes(bal_root) == want_bal,
              "balances root != independent hashlib merkleization")

        # -- the epoch program vs its un-donated twin on the host backend --
        cpu = jax.devices("cpu")[0]
        twin_in = jax.device_put(
            (ValidatorColumns(**captured["cols"]),
             jax.device_get(captured["scal"]),
             jax.device_get(captured["inp"])), cpu)
        t0 = time.perf_counter()
        twin_cols, twin_scal, twin_report = jax.device_get(
            _epoch_transition_pd.undonated(core.cfg, *twin_in))
        t_twin = time.perf_counter() - t0
        for f in ValidatorColumns._fields:
            check(np.array_equal(np.asarray(getattr(twin_cols, f)),
                                 host_cols[f]),
                  f"epoch program column {f!r}: chip != host-backend twin")
        check(int(state.latest_start_shard)
              == int(twin_scal.latest_start_shard)
              and [int(x) for x in state.latest_slashed_balances]
              == [int(x) for x in np.asarray(
                  twin_scal.latest_slashed_balances)],
              "epoch program scalars: chip != host-backend twin")
        justified = int(state.current_justified_epoch)
        check(justified == spec.get_current_epoch(state) - 1,
              f"full participation did not justify the epoch just ended "
              f"(current_justified_epoch = {justified})")

        # -- block_until_ready vs the byte-fetch fence, one dispatch each --
        program = _epoch_transition_jit()
        fence = {}
        for name in ("block_until_ready", "byte_fetch"):
            cols = ValidatorColumns(
                **{f: jnp.asarray(host_cols[f])
                   for f in ValidatorColumns._fields})
            _byte_fetch(cols)
            c0 = run.compiles
            t0 = time.perf_counter()
            out = program(core.cfg, cols, captured["scal"], captured["inp"])
            t_dispatch = time.perf_counter() - t0
            if name == "block_until_ready":
                jax.block_until_ready(out)
                t_ready = time.perf_counter() - t0
                _byte_fetch(out)
                fence[name] = {
                    "dispatch_returned": round(t_dispatch, 6),
                    "ready": round(t_ready, 6),
                    "byte_fetch_after_ready": round(
                        time.perf_counter() - t0 - t_ready, 6)}
            else:
                _byte_fetch(out)
                fence[name] = {"dispatch_returned": round(t_dispatch, 6),
                               "fetched": round(
                                   time.perf_counter() - t0, 6)}
            check(run.compiles == c0, "the fence reading recompiled")
            del out, cols

        # -- checkpoint round trip ------------------------------------------
        t0 = time.perf_counter()
        ckpt = core.checkpoint_bytes()
        t_write = time.perf_counter() - t0
        t0 = time.perf_counter()
        core2 = ResidentCore.from_checkpoint(spec, ckpt, mesh=None)
        try:
            roots2 = core2._registry_balances_roots()
            root2 = core2._state_root(core2.state)
        finally:
            core2._uninstall()
        t_resume = time.perf_counter() - t0
        check(tuple(map(bytes, roots2)) == (want_reg, want_bal)
              and bytes(root2) == bytes(state_root),
              "checkpoint round trip: the resumed core's entry roots "
              "differ from the first core's current roots")
    finally:
        core._uninstall()

    check(_counters(_RESILIENCE_COUNTERS) == resil0,
          f"a resilience counter moved: {_counters(_RESILIENCE_COUNTERS)}")
    check(rdispatch.ladder().rung_name == "full",
          f"degradation ladder at rung {rdispatch.ladder().rung_name!r}")
    spec.clear_caches()
    warm = boundary_rows[1]
    return run.emit(
        "resident_1m", since, preset="mainnet", validators=validators,
        slots=len(slot_rows) + len(boundary_rows),
        boundaries=len(boundary_rows), pending_attestations=n_atts,
        checkpoint_bytes=len(ckpt),
        state_root=bytes(state_root).hex(),
        registry_root=want_reg.hex(), balances_root=want_bal.hex(),
        checks={
            "roots_vs_host_hashlib": True,
            "epoch_columns_vs_twin": "same program, host backend",
            "checkpoint_round_trip": True,
            "compiles_after_first_boundary": warm_compiles,
            "watchdog": _counters(_WATCHDOG_COUNTERS),
            "resilience": _counters(_RESILIENCE_COUNTERS),
            "ladder_rung": rdispatch.ladder().rung_name},
        smoke_readings_seconds={
            "note": "smoke readings, not benchmark numbers",
            "state_build": round(t_build, 3),
            "enter_residency": round(t_enter, 3),
            "slot_root_first": slot_rows[0]["seconds"],
            "slot_root_median_warm": _median(
                [r["seconds"] for r in slot_rows[1:]]),
            "slot_root_max_warm": max(r["seconds"] for r in slot_rows[1:]),
            "boundary_cold": boundary_rows[0],
            "boundary_warm": warm,
            "fence": fence,
            "checkpoint_write": round(t_write, 3),
            "checkpoint_resume_to_roots": round(t_resume, 3),
            "host_hashlib_merkleization": round(t_host_merkle, 3),
            "host_backend_twin": round(t_twin, 3)},
        slowest_compiles_seconds=run.largest)


# ---------------------------------------------------------------------------
# Phase 4: pair_hash_pallas
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
              == _sha(raw[i].tobytes()),
              f"pallas pair hash lane {i} != hashlib")

    # one forest build + dirty update + root per backend at a reduced
    # leaf count: xla takes phase 3's one-program build, pallas the
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
# Phase 5 (--bls): bls_block
# ---------------------------------------------------------------------------

def phase_bls_block(run: Run, validators: int = 256, attestations: int = 1,
                    keys: int = 8) -> dict:
    """One mainnet-preset process_block whose every signature — proposer,
    randao, and the aggregate attestation through verify_indexed_batch —
    is checked by the jax backend. One attestation keeps every pairing
    launch at ONE group shape (G=1, P=2): each further shape is another
    Miller + final-exponentiation compile, minutes apiece on the v5e."""
    since = run.mark()
    import bench
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0

    spec = phase0.get_spec("mainnet")
    spec.clear_caches()
    bls.bls_active = True
    try:
        bls.set_backend("python")       # stage with the bignum backend
        state, block = bench.build_config3_state_and_block(
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
# --chips 4: the serving mesh vs one device
# ---------------------------------------------------------------------------

def phase_mesh(run: Run, chips: int = 4,
               validators: int = 1_000_000) -> dict:
    """`from_checkpoint(..., mesh=ServingMesh.create(chips))` against a
    single-device core on jax.devices()[0], same process, same bytes:
    one epoch boundary and 4 per-slot incremental forest updates."""
    since = run.mark()
    import jax
    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.resident import (
        ResidentCore, _balance_chunk_words_np)
    from consensus_specs_tpu.ops.shuffle import install_device_shuffler
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    from consensus_specs_tpu.resilience import dispatch as rdispatch

    telemetry.set_enabled(True)
    resil0 = _counters(_RESILIENCE_COUNTERS)
    bls.bls_active = False
    install_device_shuffler()
    spec = phase0.get_spec("mainnet")
    spec.clear_caches()
    data = seeded_checkpoint(spec, validators, run.seed)
    mesh = ServingMesh.create(chips)

    def shard_devices(arr) -> list:
        return sorted({s.device.id for s in arr.addressable_shards
                       if s.data.size})

    def drive(serving):
        """One boundary + 4 dirty updates on a core placed by `serving`
        (None = single device); the sharded core also reports where its
        shards live. Both drives dirty the same seeded rows."""
        rng = np.random.default_rng(run.seed + 1)
        core = ResidentCore.from_checkpoint(spec, data, mesh=serving)
        state = core.state
        placement = {}
        try:
            t0 = time.perf_counter()
            core.process_slots(state, int(state.slot) + 1)   # the boundary
            t_boundary = time.perf_counter() - t0
            timings = dict(core.timings)
            relayout0 = _counters(_WATCHDOG_COUNTERS)
            roots = [core._registry_balances_roots()]
            t_updates = []
            for _ in range(4):
                # a slot's worth of dirty balances: rewrite 64 seeded
                # rows on device, re-hash only their root paths
                idx = np.unique(rng.integers(0, validators, 64))
                bal = np.asarray(jax.device_get(core.cols.balance))
                bal = bal[:validators].copy()
                bal[idx] += np.uint64(1)
                chunks = np.unique(idx // 4)
                t0 = time.perf_counter()
                core.cols = core.cols._replace(
                    balance=core.cols.balance.at[idx].set(bal[idx]))
                core._bal_forest.update(
                    chunks.astype(np.int32),
                    _balance_chunk_words_np(bal, chunks))
                core._big_roots = None
                roots.append(core._registry_balances_roots())
                core.process_slots(state, int(state.slot) + 1)
                t_updates.append(time.perf_counter() - t0)
            if serving is not None:
                placement = {
                    "columns": shard_devices(core.cols.balance),
                    "pubkeys": shard_devices(core.pk_dev),
                    "registry_forest_l0": shard_devices(
                        core._reg_forest.levels[0]),
                    "balances_forest_l0": shard_devices(
                        core._bal_forest.levels[0])}
                check(core.cols.balance.sharding.is_equivalent_to(
                    serving.shard_v, 1),
                    "the resident columns lost the mesh sharding")
            check(_counters(_WATCHDOG_COUNTERS) == relayout0,
                  "a retrace/re-layout watchdog fired on the chained steps")
            return {
                "columns": core._materialize_np_cols(),
                "roots": [tuple(map(bytes, r)) for r in roots],
                "state_root": bytes(core._state_root(state)),
                "readings": {
                    "boundary": round(t_boundary, 6),
                    **{k: round(v, 6) for k, v in timings.items()},
                    "slot_update_median": _median(t_updates)},
                "placement": placement}
        finally:
            core._uninstall()

    single = drive(None)
    sharded = drive(mesh)
    for f, col in single["columns"].items():
        check(np.array_equal(col, sharded["columns"][f]),
              f"column {f!r}: sharded != single-device")
    check(single["roots"] == sharded["roots"],
          "registry/balances roots: sharded != single-device")
    check(single["state_root"] == sharded["state_root"],
          "state root: sharded != single-device")
    placement = sharded["placement"]
    for what, ids in placement.items():
        check(len(ids) == chips,
              f"{what}: shards live on devices {ids}, expected {chips} "
              f"distinct devices")
    check(_counters(_RESILIENCE_COUNTERS) == resil0,
          "a resilience counter moved")
    check(rdispatch.ladder().rung_name == "full",
          f"degradation ladder at rung {rdispatch.ladder().rung_name!r}")
    spec.clear_caches()
    return run.emit(
        "serving_mesh", since, preset="mainnet", validators=validators,
        chips=chips, identical=True, placement=placement,
        state_root=sharded["state_root"].hex(),
        smoke_readings_seconds={
            "note": "smoke readings, not benchmark numbers",
            "single_device": single["readings"],
            "sharded": sharded["readings"]},
        slowest_compiles_seconds=run.largest)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--validators", type=int, default=1_000_000,
                    help="registry size of the mainnet-preset phases")
    ap.add_argument("--rehearse", action="store_true",
                    help="accept a non-TPU first device; never prints the "
                         "verdict line")
    ap.add_argument("--bls", action="store_true",
                    help="run phase 1 and the BLS block phase only")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run phase 1 and the serving-mesh phase only")
    args = ap.parse_args(argv)

    import jax  # noqa: F401 - fails here where jax itself cannot start
    if args.rehearse and args.chips > 1:
        # the CPU backend has one device unless asked before it starts
        from consensus_specs_tpu.utils import cpu_devices
        cpu_devices.request(args.chips)
    run = Run(rehearsal=args.rehearse, seed=args.seed)
    device = phase_device(run, args.chips)
    if args.chips == 4:
        phase_mesh(run, 4, args.validators)
    elif args.bls:
        phase_bls_block(run)
    else:
        phase_oracle_small(run)
        phase_resident_1m(run, args.validators)
        phase_pair_hash_pallas(run)
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "phases_passed": True,
                          "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
