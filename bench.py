#!/usr/bin/env python
"""Headline benchmark: the BASELINE config-5 slot-boundary workload at 1M
validators on one chip — epoch transition + full-registry shuffle + bulk
state-root Merkleization + a block's worth of batched BLS aggregate
verification (config-3 shape: 128 attestations, product-of-pairings each).

Three device measurements (all steady-state, all on whatever jax.devices()
provides; CSTPU_BENCH_CPU=1 pins the host CPU for a harness smoke run):
  1. epoch+shuffle ms   (SoA epoch transition + 90-round swap-or-not, 1M)
  2. state-root ms      (validator-registry + balances hash_tree_root via
                         the bulk device Merkleizer, 1M)
  3. BLS batch ms       (128 aggregate-verifies in ONE grouped pairing
                         program: 384 Miller loops + batched final exp)

Baseline = the same semantics in reference-shaped Python (object-model
process_epoch, recursive hash_tree_root, bignum verify_multiple), measured
at a reduced validator count and scaled per-validator / per-verify — the
reference publishes no numbers (BASELINE.md) so the comparison is
measured-vs-measured on identical semantics; device paths are bit-exactness
-tested against these oracles in tests/.

Prints exactly one JSON line. Every row carries a `probe` provenance tag
(the live platform). A stage that fails ends the run non-zero: there is no
fallback to another backend and no partial record.
"""
import json
import os
import time
from copy import deepcopy

import numpy as np

# env knobs exist for smoke-testing the harness; the defaults are the chip
# shape. CSTPU_BENCH_CPU=1 pins jax to the host CPU.
if os.environ.get("CSTPU_BENCH_CPU") == "1":
    import jax as _jax
    _jax.config.update("jax_platforms", "cpu")
V_DEVICE = int(os.environ.get("CSTPU_BENCH_V", 1_000_000))
V_STATE = int(os.environ.get("CSTPU_BENCH_STATE_V", V_DEVICE))
V_BASELINE = 512   # python object-model path is O(V*A); scaled per-validator
N_ATTESTATIONS = int(os.environ.get("CSTPU_BENCH_ATT", 128))
EPOCH_ITERS = 3   # steady-state timed iterations per device workload


def _sync(out):
    """Force completion by fetching one element of a result: the fence
    every timing here uses — it waits for the producing program and makes
    the download itself negligible. (chip_smoke.py prints this fence next
    to jax.block_until_ready for one epoch dispatch.)"""
    import jax
    import numpy as np
    leaf = jax.tree_util.tree_leaves(out)[0]
    return np.asarray(leaf.ravel()[0:1])


def bench_epoch_device() -> float:
    """Seconds per (epoch transition + full-registry shuffle) at V_DEVICE."""
    import jax
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        EpochConfig, epoch_transition_device)
    from consensus_specs_tpu.ops.shuffle import shuffle_permutation_on_device

    from consensus_specs_tpu.models.phase0.epoch_soa import synthetic_epoch_state
    spec = phase0.get_spec("mainnet")
    cfg = EpochConfig.from_spec(spec)
    cols, scal, inp = synthetic_epoch_state(
        cfg, V_DEVICE, np.random.default_rng(42),
        slashed_p=0.001, incl_delay_max=32, random_slashed_balances=True)
    seed = bytes(range(32))

    # epoch_transition_device DONATES the columns; chain each iteration's
    # output columns into the next call (the production shape: epoch N's
    # registry feeds epoch N+1) instead of reusing a deleted buffer
    out = epoch_transition_device(cfg, cols, scal, inp)
    _sync(out)
    cols = out[0]
    _sync(shuffle_permutation_on_device(seed, V_DEVICE, spec.SHUFFLE_ROUND_COUNT))

    iters = EPOCH_ITERS
    t0 = time.perf_counter()
    for _ in range(iters):
        perm = shuffle_permutation_on_device(seed, V_DEVICE, spec.SHUFFLE_ROUND_COUNT)
        out = epoch_transition_device(cfg, cols, scal, inp)
        cols = out[0]
        _sync(perm)
        _sync(out)
    return (time.perf_counter() - t0) / iters


def bench_state_root_device() -> float:
    """Seconds for the 1M-validator registry + balances hash_tree_root:
    ONE device program (leaf construction + every Merkle level traced
    together), columns device-resident as in the production SoA pipeline —
    the only steady-state transfer is 64 bytes of roots coming back."""
    import jax
    from consensus_specs_tpu.ops import intmath  # noqa: F401 (x64 BEFORE uint64 uploads)
    import jax.numpy as jnp
    from consensus_specs_tpu.utils.ssz import bulk

    rng = np.random.default_rng(7)
    V = V_DEVICE
    cols = [
        rng.integers(0, 256, (V, 48), dtype=np.uint8),            # pubkeys
        rng.integers(0, 256, (V, 32), dtype=np.uint8),            # wc
        np.zeros(V, np.uint64), np.zeros(V, np.uint64),           # epochs
        np.zeros(V, np.uint64), np.zeros(V, np.uint64),
        np.zeros(V, bool),                                        # slashed
        np.full(V, 32_000_000_000, np.uint64),                    # eff bal
        rng.integers(31_000_000_000, 33_000_000_000, V).astype(np.uint64),
    ]
    dev = [jnp.asarray(c) for c in cols]
    jax.block_until_ready(dev)

    bulk.registry_and_balances_roots_device(*dev)  # warm the jit
    t0 = time.perf_counter()
    iters = 3
    for _ in range(iters):
        # the callee materializes the 32-byte roots on the host
        # (np.asarray + tobytes), which IS the completion fence here
        bulk.registry_and_balances_roots_device(*dev)
    return (time.perf_counter() - t0) / iters


def bench_incremental_root_device():
    """Incremental state-root: ≤1k dirty leaves of a V_DEVICE-leaf resident
    Merkle forest (utils/ssz/incremental.py) vs the full forest rebuild —
    the cost a registry-mutating block pays between epoch boundaries now
    (O(dirty·log V)) vs what the old all-or-nothing cache forced (O(V)).
    Leaves stay device-resident throughout; the only download per root is
    its 32 bytes. Returns a dict for the JSON artifact."""
    import jax.numpy as jnp
    from consensus_specs_tpu.utils.ssz.incremental import IncrementalMerkleTree

    rng = np.random.default_rng(3)
    V = V_DEVICE
    n_dirty = min(1024, max(1, V // 64))
    leaves_dev = jnp.asarray(rng.integers(0, 2 ** 32, (V, 8), dtype=np.uint32))
    _sync(leaves_dev)

    def rebuild():
        # the tree takes ownership (level scatters donate): hand it a fresh
        # DEVICE copy so the source leaves stay reusable and no host
        # transfer pollutes the measurement
        t = IncrementalMerkleTree(jnp.array(leaves_dev, copy=True))
        t.root()                      # 32-byte download = the fence
        return t

    tree = rebuild()                  # warm the per-level compile cache
    iters = 2
    t0 = time.perf_counter()
    for _ in range(iters):
        tree = rebuild()
    t_rebuild = (time.perf_counter() - t0) / iters
    pairs_rebuild = sum(tree.last_pairs_per_level)

    dirty = np.sort(rng.choice(V, n_dirty, replace=False)).astype(np.int32)
    rows = rng.integers(0, 2 ** 32, (n_dirty, 8), dtype=np.uint32)
    tree.update(dirty, rows)          # warm the update-shape compiles
    tree.root()
    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        tree.update(dirty, rows)
        tree.root()
    t_update = (time.perf_counter() - t0) / iters
    # the acceptance bound, asserted at the real shape: an update re-hashes
    # O(dirty·log V) pair lanes (pow2 index padding at worst doubles them)
    assert sum(tree.last_pairs_per_level) <= 2 * n_dirty * tree.depth, \
        tree.last_pairs_per_level
    return {
        "leaves": V,
        "dirty": int(n_dirty),
        "incremental_ms": round(t_update * 1e3, 2),
        "full_rebuild_ms": round(t_rebuild * 1e3, 2),
        "speedup": round(t_rebuild / t_update, 1),
        "pair_lanes_incremental": int(sum(tree.last_pairs_per_level)),
        "pair_lanes_full": int(pairs_rebuild),
    }


def bench_merkle_backend_ab():
    """A/B the two pair-hash kernels (CSTPU_MERKLE_BACKEND=xla|pallas) on
    one Merkle-level-shaped batch — the selection ops/sha256_pallas.py's
    docstring always promised. Mosaic lowers for TPUs only, so any other
    backend gets a "skipped" row (there is no kernel to time there)."""
    import jax
    import jax.numpy as jnp
    from consensus_specs_tpu.ops import sha256 as S

    if jax.devices()[0].platform != "tpu":
        return {"skipped": "the Pallas pair hash lowers for TPUs only"}
    lanes = 1 << 20
    rng = np.random.default_rng(9)
    words = jnp.asarray(rng.integers(0, 2 ** 32, (lanes, 16), dtype=np.uint32))
    _sync(words)
    out = {"lanes": lanes}
    for name in ("xla", "pallas"):
        S.set_merkle_pair_backend(name)
        try:
            _sync(S.pair_hash_words(words))     # warm compile
            iters = 3
            t0 = time.perf_counter()
            for _ in range(iters):
                _sync(S.pair_hash_words(words))
            out[f"{name}_ms"] = round((time.perf_counter() - t0) / iters * 1e3, 2)
        finally:
            S.set_merkle_pair_backend(None)
    return out


def bench_scalar_mul_ab():
    """A/B the scalar-mul backends (CSTPU_SCALAR_MUL=window|double_add) on
    the two hot shapes: the fixed ~509-bit G2 cofactor clearing (the
    hash_to_g2 tail — ~95% of hash-to-curve time) and a traced 256-bit
    scalar. Per backend and shape: steady-state ms plus the dependent
    jac_add chain length (ops/scalar_mul.sequential_adds — the latency
    currency the windowed backend exists to cut). Results are checked
    value-equal across backends against the host bignum before anything
    is timed."""
    import jax.numpy as jnp
    from consensus_specs_tpu.crypto import bls12_381 as gt
    from consensus_specs_tpu.ops import bls_jax as BJ
    from consensus_specs_tpu.ops import fq_tower as T
    from consensus_specs_tpu.ops import scalar_mul as SM

    batch = 8
    pts = [gt.ec_mul(gt.G2_GEN, 7 * i + 3) for i in range(batch)]
    arr = np.stack([BJ.g2_to_limbs(p) for p in pts])
    x, y = jnp.asarray(arr[:, 0]), jnp.asarray(arr[:, 1])
    _sync((x, y))
    w = SM.scalar_mul_window()
    k256 = int.from_bytes(bytes(range(11, 43)), "big")   # fixed 256-bit
    shapes = (("cofactor", gt.G2_COFACTOR, gt.G2_COFACTOR.bit_length()),
              ("k256", k256, 256))
    out = {"batch": batch, "window_w": w}
    values = {}
    for name in ("double_add", "window"):
        SM.set_scalar_mul_backend(name)
        try:
            for label, k, nbits in shapes:
                gx, gy, ginf = BJ.g2_scalar_mul(x, y, k, nbits=nbits)
                got = [None if bool(i) else
                       (T.fq2_from_limbs(px), T.fq2_from_limbs(py))
                       for px, py, i in zip(np.asarray(gx), np.asarray(gy),
                                            np.asarray(ginf))]
                values[(label, name)] = got
                iters = 3
                t0 = time.perf_counter()
                for _ in range(iters):
                    _sync(BJ.g2_scalar_mul(x, y, k, nbits=nbits))
                out[f"{label}_{name}_ms"] = round(
                    (time.perf_counter() - t0) / iters * 1e3, 2)
                out[f"{label}_{name}_seq_adds"] = SM.sequential_adds(
                    name, nbits, w)
        finally:
            SM.set_scalar_mul_backend(None)
    for label, k, nbits in shapes:
        want = [gt.ec_mul(p, k) for p in pts]
        assert values[(label, "window")] == want, f"{label}: window != bignum"
        assert values[(label, "double_add")] == want, \
            f"{label}: double_add != bignum"
        ratio = (out[f"{label}_double_add_seq_adds"]
                 / out[f"{label}_window_seq_adds"])
        out[f"{label}_seq_add_ratio"] = round(ratio, 2)
        assert ratio >= 2.5, f"{label}: sequential-add cut only {ratio:.2f}x"
    return out


def bench_pairing_redc_ab():
    """A/B the tower reduction placement (CSTPU_FQ_REDC=leaf|coeff) on ONE
    grouped_pairing_check at the spec shape (N_ATTESTATIONS groups x 3
    pairs). Per backend: steady-state ms plus the REDC lane count of the
    traced grouped-Miller + final-exp programs (ops/fq.py's trace-time
    counters over FRESH traces — bls_jax's jitted pairing programs are
    mode-keyed, so each backend really runs its own executable). Group
    verdicts are asserted bit-identical across backends, and the >=2.5x
    lane cut — the reason the coeff backend exists — is asserted, not
    just recorded."""
    import jax
    import jax.numpy as jnp
    from consensus_specs_tpu.ops import bls_jax as BJ
    from consensus_specs_tpu.ops import fq as F

    g1, g2 = _stage_attestation_pairs(N_ATTESTATIONS)
    dg1, dg2 = jnp.asarray(g1), jnp.asarray(g2)
    _sync((dg1, dg2))
    f12 = jnp.zeros((N_ATTESTATIONS, 2, 3, 2, F.L), jnp.int64)
    out = {"groups": int(N_ATTESTATIONS), "pairs_per_group": int(g1.shape[1])}
    verdicts = {}
    for name in ("leaf", "coeff"):
        with F.pinned_fq_redc_backend(name):
            # lane counts off fresh abstract traces (fresh lambdas: jax's
            # trace cache keys on function identity and would otherwise
            # serve the other mode's jaxpr)
            F.reset_redc_trace_stats()
            jax.make_jaxpr(lambda a, b: BJ.miller_loop_grouped(a, b))(dg1, dg2)
            jax.make_jaxpr(lambda f: BJ.final_exponentiation_3x(f))(f12)
            out[f"{name}_redc_lanes"] = F.redc_trace_stats()["lanes"]
            verdicts[name] = np.asarray(
                BJ.grouped_pairing_check(dg1, dg2))     # warm compile
            iters = 3
            t0 = time.perf_counter()
            for _ in range(iters):
                # np.asarray materializes the [G] verdicts (honest fence)
                np.asarray(BJ.grouped_pairing_check(dg1, dg2))
            out[f"{name}_ms"] = round(
                (time.perf_counter() - t0) / iters * 1e3, 2)
    assert bool(verdicts["coeff"].all()), "staged signatures must verify"
    assert np.array_equal(verdicts["leaf"], verdicts["coeff"]), \
        "grouped-pairing verdicts differ between REDC backends"
    ratio = out["leaf_redc_lanes"] / out["coeff_redc_lanes"]
    out["redc_lane_ratio"] = round(ratio, 2)
    assert ratio >= 2.5, f"REDC lane cut only {ratio:.2f}x"
    return out


def _stage_attestation_pairs(n_groups, n_distinct=8):
    """See ops/bls_jax.stage_example_groups (shared with the mesh tests and
    dryrun_multichip so all three present identical program shapes)."""
    from consensus_specs_tpu.ops.bls_jax import stage_example_groups
    return stage_example_groups(n_groups, n_distinct)


def bench_bls_device():
    """(seconds per 128-aggregate-verify batch, python seconds per single
    verify_multiple) — the config-3 block shape."""
    import jax
    import jax.numpy as jnp
    from consensus_specs_tpu.crypto import bls12_381 as gt
    from consensus_specs_tpu.ops.bls_jax import grouped_pairing_check

    g1, g2 = _stage_attestation_pairs(N_ATTESTATIONS)
    dg1, dg2 = jnp.asarray(g1), jnp.asarray(g2)
    ok = np.asarray(grouped_pairing_check(dg1, dg2))
    assert bool(ok.all()), "staged signatures must verify"

    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        # np.asarray materializes the [G] verdicts: the honest fence (_sync)
        np.asarray(grouped_pairing_check(dg1, dg2))
    t_batch = (time.perf_counter() - t0) / iters

    # python oracle: one verify_multiple of the same shape
    py = gt.PythonBackend()
    msg = b"\x05" * 32
    agg = py.aggregate_signatures([py.sign(msg, 3, 1), py.sign(msg, 4, 1)])
    pubs = [gt.privtopub(3), gt.privtopub(4)]
    t0 = time.perf_counter()
    assert py.verify_multiple(pubs, [msg, msg], agg, 1)
    t_py_single = time.perf_counter() - t0
    return t_batch, t_py_single


def build_baseline_state(spec, V):
    """Pre-epoch-boundary object-model state with a full epoch of
    attestations (genesis-zero block roots keep everything consistent)."""
    state = spec.BeaconState(genesis_time=0, deposit_index=V)
    state.balances = [spec.MAX_EFFECTIVE_BALANCE] * V
    state.validator_registry = [
        spec.Validator(
            pubkey=i.to_bytes(48, "little"),
            withdrawal_credentials=b"\x00" * 32,
            activation_eligibility_epoch=spec.GENESIS_EPOCH,
            activation_epoch=spec.GENESIS_EPOCH,
            exit_epoch=spec.FAR_FUTURE_EPOCH,
            withdrawable_epoch=spec.FAR_FUTURE_EPOCH,
            effective_balance=spec.MAX_EFFECTIVE_BALANCE,
        )
        for i in range(V)
    ]
    from consensus_specs_tpu.utils.ssz.impl import hash_tree_root as _htr
    from consensus_specs_tpu.utils.ssz.typing import List as SSZList, uint64 as _u64
    root = _htr(list(range(V)), SSZList[_u64])
    for i in range(spec.LATEST_ACTIVE_INDEX_ROOTS_LENGTH):
        state.latest_active_index_roots[i] = root
    state.slot = 3 * spec.SLOTS_PER_EPOCH - 1
    # Committee layout via the vectorized distillation machinery — the
    # naive per-committee get_crosslink_committee rebuilds the O(V) active
    # list per call, which is hours of scaffolding at V=1M.
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        _epoch_layout, columns_np_from_state)
    np_cols = columns_np_from_state(state)
    prev_epoch = spec.get_previous_epoch(state)
    for epoch, store in (
        (prev_epoch, state.previous_epoch_attestations),
        (spec.get_current_epoch(state), state.current_epoch_attestations),
    ):
        lay = _epoch_layout(spec, state, np_cols, epoch)
        committee_count, start_shard = lay.count, lay.start_shard
        for offset in range(committee_count):
            shard = (start_shard + offset) % spec.SHARD_COUNT
            committee = lay.shuffled[lay.bounds[offset]:lay.bounds[offset + 1]]
            slot = spec.get_epoch_start_slot(epoch) + offset // (committee_count // spec.SLOTS_PER_EPOCH)
            if slot >= state.slot:
                continue
            data = spec.AttestationData(
                beacon_block_root=spec.get_block_root_at_slot(state, slot),
                source_epoch=state.current_justified_epoch,
                source_root=state.current_justified_root,
                target_epoch=epoch,
                target_root=spec.get_block_root(state, epoch),
                crosslink=spec.Crosslink(
                    shard=shard,
                    parent_root=spec.hash_tree_root(state.current_crosslinks[shard]),
                    end_epoch=min(epoch, spec.MAX_EPOCHS_PER_CROSSLINK),
                ),
            )
            # full participation, excess bits zero (verify_bitfield :355-361)
            size = len(committee)
            bitfield = bytearray(b"\xff" * (size // 8))
            if size % 8:
                bitfield.append((1 << (size % 8)) - 1)
            store.append(spec.PendingAttestation(
                aggregation_bitfield=bytes(bitfield),
                data=data,
                inclusion_delay=spec.MIN_ATTESTATION_INCLUSION_DELAY,
                proposer_index=int(committee[0]),
            ))
    return state


def build_config3_state_and_block(spec, V, n_attestations, n_keys=64):
    """A state at an epoch boundary + a valid block carrying
    `n_attestations` previous-epoch attestations with REAL aggregate
    signatures over FULL committees (BASELINE config 3).

    Staging trick (verifier work unchanged): validator i's keypair is
    privkey (i % n_keys) + 1, so a committee's aggregate signature over the
    shared message is ONE sign with the sum of member privkeys mod r. The
    verifier still decompresses + aggregates every member pubkey and runs
    the full grouped pairing — only the attester-side signing (not the
    node's measured work) is shortcut."""
    from consensus_specs_tpu.crypto import bls12_381 as gt
    from consensus_specs_tpu.crypto.bls import get_backend
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        _epoch_layout, columns_np_from_state)

    backend = get_backend()
    keypub = [gt.privtopub(k + 1) for k in range(n_keys)]
    state = spec.BeaconState(
        genesis_time=0, deposit_index=V,
        latest_eth1_data=spec.Eth1Data(deposit_count=V))
    state.balances = [spec.MAX_EFFECTIVE_BALANCE] * V
    state.validator_registry = [
        spec.Validator(
            pubkey=keypub[i % n_keys],
            withdrawal_credentials=b"\x00" * 32,
            activation_eligibility_epoch=spec.GENESIS_EPOCH,
            activation_epoch=spec.GENESIS_EPOCH,
            exit_epoch=spec.FAR_FUTURE_EPOCH,
            withdrawable_epoch=spec.FAR_FUTURE_EPOCH,
            effective_balance=spec.MAX_EFFECTIVE_BALANCE,
        )
        for i in range(V)
    ]
    # First slot of epoch 2: every prev-epoch attestation slot s satisfies
    # s + MIN_ATTESTATION_INCLUSION_DELAY <= slot <= s + SLOTS_PER_EPOCH
    state.slot = 2 * spec.SLOTS_PER_EPOCH
    prev = spec.get_previous_epoch(state)
    lay = _epoch_layout(spec, state, columns_np_from_state(state), prev)
    assert n_attestations <= lay.count, \
        f"only {lay.count} committees at V={V}; raise V for {n_attestations}"
    domain = spec.get_domain(state, spec.DOMAIN_ATTESTATION, prev)

    attestations = []
    for offset in range(n_attestations):
        shard = (lay.start_shard + offset) % spec.SHARD_COUNT
        committee = lay.shuffled[lay.bounds[offset]:lay.bounds[offset + 1]]
        att_slot = (spec.get_epoch_start_slot(prev)
                    + offset // (lay.count // spec.SLOTS_PER_EPOCH))
        parent = state.previous_crosslinks[shard]
        data = spec.AttestationData(
            beacon_block_root=spec.get_block_root_at_slot(state, att_slot),
            source_epoch=state.previous_justified_epoch,
            source_root=state.previous_justified_root,
            target_epoch=prev,
            target_root=spec.get_block_root(state, prev),
            crosslink=spec.Crosslink(
                shard=shard,
                parent_root=spec.hash_tree_root(parent),
                end_epoch=min(prev, parent.end_epoch + spec.MAX_EPOCHS_PER_CROSSLINK),
            ),
        )
        size = len(committee)
        bitfield = bytearray(b"\xff" * (size // 8))
        if size % 8:
            bitfield.append((1 << (size % 8)) - 1)
        msg = spec.hash_tree_root(
            spec.AttestationDataAndCustodyBit(data=data, custody_bit=False))
        k_agg = sum((int(i) % n_keys) + 1 for i in committee) % gt.r
        attestations.append(spec.Attestation(
            aggregation_bitfield=bytes(bitfield),
            data=data,
            custody_bitfield=bytes(len(bitfield)),
            signature=backend.sign(msg, k_agg, domain),
        ))

    block = spec.BeaconBlock()
    block.slot = state.slot
    block.parent_root = spec.signing_root(state.latest_block_header)
    block.body.eth1_data.deposit_count = state.deposit_index
    block.body.attestations = attestations
    proposer_key = (spec.get_beacon_proposer_index(state) % n_keys) + 1
    epoch = spec.get_current_epoch(state)
    block.body.randao_reveal = backend.sign(
        spec.hash_tree_root(epoch), proposer_key,
        spec.get_domain(state, spec.DOMAIN_RANDAO, epoch))
    block.signature = backend.sign(
        spec.signing_root(block), proposer_key,
        spec.get_domain(state, spec.DOMAIN_BEACON_PROPOSER))
    return state, block


def bench_block_device() -> float:
    """Config-3: seconds for ONE process_block carrying N_ATTESTATIONS real
    attestations, every signature verified on device through the batched
    pipeline (block.process_attestations_batched -> verify_indexed_batch).
    Timed per state_transition semantics from a pre-built valid block;
    compile warm-up runs the same shapes first on copies."""
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0

    old_active = bls.bls_active
    bls.bls_active = True
    bls.set_backend("jax")
    try:
        spec = phase0.get_spec("mainnet")
        # smallest V whose prev epoch has >= N_ATTESTATIONS committees
        # (count = SLOTS_PER_EPOCH * (V // SLOTS_PER_EPOCH // TARGET))
        V = int(os.environ.get(
            "CSTPU_BENCH_BLOCK_V",
            spec.SLOTS_PER_EPOCH * spec.TARGET_COMMITTEE_SIZE
            * max(1, -(-N_ATTESTATIONS // spec.SLOTS_PER_EPOCH))))
        state, block = build_config3_state_and_block(spec, V, N_ATTESTATIONS)
        warm_state = deepcopy(state)
        spec.state_transition(warm_state, block)     # compile warm-up
        fresh = deepcopy(state)
        spec.clear_caches()
        t0 = time.perf_counter()
        spec.state_transition(fresh, block)
        return time.perf_counter() - t0
    finally:
        bls.bls_active = old_active
        bls.set_backend("python")


def bench_state_to_state(prebuilt_state=None):
    """Config-5 as a TRUE state-to-state measurement: an
    actual V_STATE-validator mainnet BeaconState with a full epoch of
    attestations in; updated state + device state root out.

    Returns (timings, post_state): the transitioned state is handed to
    bench_resident so the ~30 s host-side 1M-state construction is paid
    once per bench run, not once per stage.

    Returned dict: distill (vectorized input distillation incl. 2 device
    shuffles + upload), device (the one-program epoch transition, output-
    fetch fenced), root (registry+balances roots from the still-device-
    resident post-transition columns), writeback (device->object copy; the
    production pipeline keeps columns resident and skips this). Compiles
    are warmed at identical shapes first; permutation/hash caches are
    cleared so the timed run pays all per-state work. Bit-equality of this
    exact path vs the object model is asserted in tests/test_epoch_soa.py
    and tests/test_state_to_state.py at reduced V."""
    import jax
    import jax.numpy as jnp
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        EpochConfig, epoch_transition_device, process_epoch_soa,
        synthetic_epoch_state)
    from consensus_specs_tpu.ops.shuffle import (
        install_device_shuffler, shuffle_permutation_on_device)
    from consensus_specs_tpu.utils.ssz import bulk

    bls.bls_active = False
    install_device_shuffler()
    spec = phase0.get_spec("mainnet")
    V = V_STATE
    state = (prebuilt_state if prebuilt_state is not None
             else build_baseline_state(spec, V))

    # Registry identity columns (pubkeys/withdrawal_credentials) are static
    # across the epoch; production keeps them device-resident.
    pk = np.zeros((V, 48), np.uint8)
    pk[:, :8] = np.arange(V, dtype=np.uint64).astype("<u8").view(np.uint8).reshape(V, 8)
    wc = np.zeros((V, 32), np.uint8)
    pk_dev, wc_dev = jnp.asarray(pk), jnp.asarray(wc)
    _sync((pk_dev, wc_dev))

    # Warm every compile at the exact shapes of the timed run
    cfg = EpochConfig.from_spec(spec)
    c0, s0, i0 = synthetic_epoch_state(cfg, V, np.random.default_rng(0))
    warm_cols, _, _ = epoch_transition_device(cfg, c0, s0, i0)
    _sync(warm_cols)
    shuffle_permutation_on_device(b"\x01" * 32, V, spec.SHUFFLE_ROUND_COUNT)
    bulk.registry_and_balances_roots_device(
        pk_dev, wc_dev, warm_cols.activation_eligibility_epoch,
        warm_cols.activation_epoch, warm_cols.exit_epoch,
        warm_cols.withdrawable_epoch, warm_cols.slashed,
        warm_cols.effective_balance, warm_cols.balance)

    spec.clear_caches()  # the state build filled the permutation cache
    tm = {}
    dev_cols, _ = process_epoch_soa(spec, state, timings=tm)
    t0 = time.perf_counter()
    # registry_and_balances_roots_device materializes the two 32-byte roots
    # on the host — that download IS the fence
    bulk.registry_and_balances_roots_device(
        pk_dev, wc_dev, dev_cols.activation_eligibility_epoch,
        dev_cols.activation_epoch, dev_cols.exit_epoch,
        dev_cols.withdrawable_epoch, dev_cols.slashed,
        dev_cols.effective_balance, dev_cols.balance)
    tm["root"] = time.perf_counter() - t0
    return tm, state


def bench_resident(n_epochs: int = 3, resumed_state=None):
    """Config-5 the way production runs it: enter residency
    ONCE, then drive `n_epochs` consecutive epochs with the registry and
    balances never leaving the device. Per-epoch boundary cost =
      stage    host distillation straight off the mirrors (no object walk;
               committee permutations reused from the epoch's cache)
      device   the one-program epoch transition on the resident columns
      refresh  3-column mirror download + cached device registry/balances
               root recompute + byte-rooted final updates
    plus "slots": the epoch's 64 per-slot full-state roots (device big-field
    roots cached; host-memoized small fields). Attestations are synthesized
    per slot against the live state (real committee layout, full
    participation) as staging, exactly what arriving blocks would append —
    block-path costs are measured by bench_block_device, not here.

    Bit-equality of this pipeline vs the object model is gated at reduced V
    in tests/test_resident.py; this stage measures the 1M steady state.

    Returns a list of per-epoch timing dicts (epoch 0 warms compiles and is
    reported separately by the caller)."""
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.epoch_soa import _epoch_layout
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    from consensus_specs_tpu.ops.shuffle import install_device_shuffler

    bls.bls_active = False
    install_device_shuffler()
    spec = phase0.get_spec("mainnet")
    if resumed_state is not None:
        # bench_state_to_state's post-state: its epoch transition ran via
        # process_epoch_soa (slot NOT yet incremented past the boundary
        # slot — the bench calls it directly, outside process_slots).
        # Completing the increment resumes a consistent mid-chain state;
        # the drive's first measured boundary is then a full epoch away.
        state = resumed_state
        state.slot += 1
    else:
        state = build_baseline_state(spec, V_STATE)
    spec.clear_caches()
    core = ResidentCore(spec, state)

    def synth_slot_attestations(lay, slot, target_epoch, source, store):
        """Full-participation PendingAttestations for every committee of
        `slot` (committee layout from the resident mirrors). `target_epoch`
        / `source` (justified pair) / `store` distinguish in-epoch arrivals
        from the boundary slot's, which land after rotation in the
        previous-epoch list with previous-justified source."""
        cps = lay.count // spec.SLOTS_PER_EPOCH
        start_slot = spec.get_epoch_start_slot(target_epoch)
        for off in range((slot - start_slot) * cps, (slot - start_slot + 1) * cps):
            shard = (lay.start_shard + off) % spec.SHARD_COUNT
            committee = lay.shuffled[lay.bounds[off]:lay.bounds[off + 1]]
            data = spec.AttestationData(
                beacon_block_root=spec.get_block_root_at_slot(state, slot),
                source_epoch=source[0],
                source_root=source[1],
                target_epoch=target_epoch,
                target_root=spec.get_block_root(state, target_epoch),
                crosslink=spec.Crosslink(
                    shard=shard,
                    parent_root=spec.hash_tree_root(state.current_crosslinks[shard]),
                    # canonical chains extend the parent: the vote's span
                    # starts where the current crosslink ended
                    start_epoch=state.current_crosslinks[shard].end_epoch,
                    end_epoch=min(target_epoch, state.current_crosslinks[shard].end_epoch
                                  + spec.MAX_EPOCHS_PER_CROSSLINK),
                ),
            )
            size = len(committee)
            bitfield = bytearray(b"\xff" * (size // 8))
            if size % 8:
                bitfield.append((1 << (size % 8)) - 1)
            store.append(spec.PendingAttestation(
                aggregation_bitfield=bytes(bitfield),
                data=data,
                inclusion_delay=spec.MIN_ATTESTATION_INCLUSION_DELAY,
                proposer_index=int(committee[0]),
            ))

    results = []
    lay = None
    try:
        for _ in range(n_epochs):
            t_slots = 0.0
            while True:
                t0 = time.perf_counter()
                core._process_slot(state)
                t_slots += time.perf_counter() - t0
                # same ordering as ResidentCore.process_slots (the path
                # bit-equality-tested in tests/test_resident.py): the epoch
                # transition runs BEFORE the slot increments
                if (state.slot + 1) % spec.SLOTS_PER_EPOCH == 0:
                    ended_epoch = spec.get_current_epoch(state)
                    t0 = time.perf_counter()
                    core.process_epoch_resident(state)
                    total = time.perf_counter() - t0
                    results.append(dict(core.timings, slots=t_slots, total=total))
                    state.slot += 1
                    # the boundary slot's attestations arrive on the real
                    # chain AFTER rotation, into the previous-epoch list
                    # with the previous-justified source — keep the next
                    # boundary at genuine full participation (64/64 slots)
                    if lay is not None:
                        synth_slot_attestations(
                            lay, state.slot - 1, ended_epoch,
                            (state.previous_justified_epoch,
                             state.previous_justified_root),
                            state.previous_epoch_attestations)
                    lay = None   # rotation: next epoch's layout is fresh
                    break
                state.slot += 1
                # staging (unmeasured): the attestations blocks would have
                # carried for the slot that just completed
                if lay is None:
                    ep = spec.get_current_epoch(state)
                    lay = _epoch_layout(spec, state, core.mirrors, ep)
                synth_slot_attestations(
                    lay, state.slot - 1, spec.get_current_epoch(state),
                    (state.current_justified_epoch,
                     state.current_justified_root),
                    state.current_epoch_attestations)
        # checkpoint cycle at full scale: WRITE the resident state to SSZ
        # bytes (vectorized from columns, no object materialization), then
        # RESUME a fresh light residency from those bytes — the production
        # entry path, vs the object-walk entry the s2s stage measures.
        t0 = time.perf_counter()
        ckpt = core.checkpoint_bytes()
        t_write = time.perf_counter() - t0
        from consensus_specs_tpu.models.phase0.resident import ResidentCore as _RC
        core2 = None
        t0 = time.perf_counter()
        try:
            core2 = _RC.from_checkpoint(spec, ckpt)
            core2._registry_balances_roots()   # fence: entry root on device
            t_resume = time.perf_counter() - t0
        finally:
            if core2 is not None:
                core2._uninstall()
        results.append({"checkpoint_write": t_write,
                        "checkpoint_resume": t_resume,
                        "checkpoint_bytes": len(ckpt)})
    finally:
        # the spec is a cached singleton: residency overrides MUST come off
        # even when the drive aborts, or every later bench stage (incl. the
        # host-only python baseline) runs monkey-patched
        core.exit()
    return results


def bench_python_baseline():
    """(epoch seconds, registry+balances hash_tree_root seconds) for the
    object-model path at V_BASELINE."""
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.utils.ssz.impl import hash_tree_root
    from consensus_specs_tpu.utils.ssz.typing import List as SSZList, uint64

    bls.bls_active = False
    spec = phase0.get_spec("mainnet")
    state = build_baseline_state(spec, V_BASELINE)
    s = deepcopy(state)
    t0 = time.perf_counter()
    spec.process_epoch(s)
    t_epoch = time.perf_counter() - t0
    t0 = time.perf_counter()
    hash_tree_root(state.validator_registry, SSZList[spec.Validator])
    hash_tree_root(state.balances, SSZList[uint64])
    t_root = time.perf_counter() - t0
    return t_epoch, t_root


def _progress(msg):
    import sys
    print(f"[bench +{time.perf_counter() - _T_START:.0f}s] {msg}",
          file=sys.stderr, flush=True)


_T_START = time.perf_counter()


def _probe_tag() -> str:
    """The per-row provenance stamp: the live backend platform, on every
    JSON row (not just a top-level note)."""
    import jax
    return jax.devices()[0].platform


def bench_sharded_vs_single():
    """The serving loop's sharded==single gate at bench scale (ROADMAP
    item 1 acceptance): the SAME epoch program and the SAME incremental
    forests once on one device and once under the validator-axis
    ServingMesh, asserting (not just recording) bit-identical epoch
    outputs, registry/balances forest roots, and per-slot incremental
    update roots — plus the layout-stability contract: output columns come
    back sharded and chain into the next call with zero re-layout.
    Returns a dict for the JSON row, or a "skipped" row on single-device
    backends."""
    import jax
    import jax.numpy as jnp
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        EpochConfig, epoch_transition_device, synthetic_epoch_state)
    from consensus_specs_tpu.parallel.sharding import (
        ServingMesh, trees_bitwise_equal)
    from consensus_specs_tpu.utils.ssz import bulk
    from consensus_specs_tpu.utils.ssz.incremental import (
        IncrementalMerkleTree, ShardedIncrementalMerkleTree)

    n_dev = 1
    while n_dev * 2 <= min(8, len(jax.devices())):
        n_dev *= 2
    if n_dev < 2:
        return {"skipped": f"single-device backend "
                           f"({len(jax.devices())} device)"}
    V = V_DEVICE - V_DEVICE % (4 * n_dev)   # divisible: padding not the point here
    mesh = ServingMesh.create(n_dev)
    spec = phase0.get_spec("mainnet")
    cfg = EpochConfig.from_spec(spec)
    cols, scal, inp = synthetic_epoch_state(
        cfg, V, np.random.default_rng(42),
        slashed_p=0.001, incl_delay_max=32, random_slashed_balances=True)
    rng = np.random.default_rng(7)
    pk = rng.integers(0, 256, (V, 48), dtype=np.uint8)
    wc = rng.integers(0, 256, (V, 32), dtype=np.uint8)

    # shard (device_put copies) BEFORE the single run: the single-device
    # call donates `cols` on accelerator backends
    cols_sh, scal_sh, inp_sh = mesh.epoch_shardings()
    cols_s = jax.device_put(cols, cols_sh)
    scal_s = jax.device_put(scal, scal_sh)
    inp_s = jax.device_put(inp, inp_sh)
    pk_s = jax.device_put(jnp.asarray(pk), mesh.shard_v)
    wc_s = jax.device_put(jnp.asarray(wc), mesh.shard_v)
    _sync((cols_s, pk_s, wc_s))

    out = {"devices": n_dev, "validators": V}
    single = epoch_transition_device(cfg, cols, scal, inp)
    _sync(single)
    iters = EPOCH_ITERS
    t0 = time.perf_counter()
    for _ in range(iters):
        single = epoch_transition_device(cfg, single[0], scal, inp)
        _sync(single)
    out["epoch_single_ms"] = round((time.perf_counter() - t0) / iters * 1e3, 2)

    sharded = mesh.epoch_transition(cfg, cols_s, scal_s, inp_s)
    _sync(sharded)
    assert sharded[0].balance.sharding.is_equivalent_to(mesh.shard_v, 1), \
        "epoch output columns lost the validator-axis sharding"
    t0 = time.perf_counter()
    for _ in range(iters):
        # chained steps: this call's out_shardings ARE the next call's
        # in_shardings — the output arrays pass through without re-layout
        sharded = mesh.epoch_transition(cfg, sharded[0], scal_s, inp_s)
        _sync(sharded)
    out["epoch_sharded_ms"] = round((time.perf_counter() - t0) / iters * 1e3, 2)
    # iteration parity: both chained the same number of boundaries, so the
    # equality below really compares the same program state
    assert trees_bitwise_equal(single, sharded), \
        "sharded epoch output != single-device (bitwise)"

    # forests from the post-epoch columns: build + root (first build warms
    # the per-capacity compiles, the timed rebuild is the steady state),
    # then per-slot incremental updates (what the loop pays between blocks)
    c1 = single[0]

    def build_single():
        reg = IncrementalMerkleTree(bulk.registry_leaf_words_device(
            jnp.asarray(pk), jnp.asarray(wc), c1.activation_eligibility_epoch,
            c1.activation_epoch, c1.exit_epoch, c1.withdrawable_epoch,
            c1.slashed, c1.effective_balance))
        bal = IncrementalMerkleTree(
            bulk.balances_chunk_words_device(c1.balance))
        return reg, bal, (reg.root(), bal.root())

    c8 = sharded[0]

    def build_sharded():
        reg = ShardedIncrementalMerkleTree(
            mesh.registry_forest_leaves(
                pk_s, wc_s, c8.activation_eligibility_epoch,
                c8.activation_epoch, c8.exit_epoch, c8.withdrawable_epoch,
                c8.slashed, c8.effective_balance, v_count=V),
            mesh, logical_n=V)
        bal = ShardedIncrementalMerkleTree(
            mesh.balances_forest_chunks(c8.balance, V), mesh,
            logical_n=max(1, -(-V // 4)))
        return reg, bal, (reg.root(), bal.root())

    build_single()                      # warm compiles
    t0 = time.perf_counter()
    reg_1, bal_1, roots_1 = build_single()
    out["root_single_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
    build_sharded()                     # warm compiles
    t0 = time.perf_counter()
    reg_8, bal_8, roots_8 = build_sharded()
    out["root_sharded_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
    assert roots_1 == roots_8, "forest roots != under sharding"
    assert reg_8.levels[0].sharding.is_equivalent_to(mesh.shard_v, 2), \
        "registry forest level 0 lost the validator-axis sharding"

    # per-slot roots: a block's worth of dirty validators, identical on
    # both layouts, roots asserted equal each step (the first update warms
    # the scatter/gather shapes and is timed separately by neither side)
    n_dirty = min(1024, max(1, V // 64))
    slot_iters = 4
    roots_single, roots_sharded = [], []
    dirties = []
    for i in range(slot_iters + 1):
        dirty = np.sort(rng.choice(V, n_dirty, replace=False)).astype(np.int32)
        rows = rng.integers(0, 2 ** 32, (n_dirty, 8), dtype=np.uint32)
        dirties.append((dirty, rows))
    reg_1.update(*map(np.copy, dirties[0]))   # warm
    roots_single.append(reg_1.root())
    t0 = time.perf_counter()
    for dirty, rows in dirties[1:]:
        reg_1.update(dirty, rows.copy())
        roots_single.append(reg_1.root())
    out["slot_update_single_ms"] = round(
        (time.perf_counter() - t0) / slot_iters * 1e3, 2)
    reg_8.update(*dirties[0])                 # warm
    roots_sharded.append(reg_8.root())
    t0 = time.perf_counter()
    for dirty, rows in dirties[1:]:
        reg_8.update(dirty, rows)
        roots_sharded.append(reg_8.root())
    out["slot_update_sharded_ms"] = round(
        (time.perf_counter() - t0) / slot_iters * 1e3, 2)
    assert roots_single == roots_sharded, "per-slot roots != under sharding"
    assert reg_8.levels[0].sharding.is_equivalent_to(mesh.shard_v, 2)
    out["dirty_per_slot"] = int(n_dirty)
    out["bitwise_equal"] = True
    out["layout_stable"] = True
    return out


def bench_telemetry():
    """The telemetry acceptance row (ISSUE 8): (a) zero-overhead bound —
    the epoch program timed with telemetry fully exercised (span + exit
    fence + layout watchdog + counter) vs CSTPU_TELEMETRY=0, interleaved
    min-of-5 per arm, <3%% asserted; (b) the watchdog gate — >= 4 chained
    resident slot steps plus one epoch boundary under the validator-axis
    serving mesh must report ZERO retrace and ZERO re-layout events (the
    pjit layout-stability contract, checked at runtime). JSON keys:
    epoch_{on,off}_ms, overhead_pct, watchdog.{devices, slot_steps,
    boundaries, retrace_events, relayout_events, drive_ms}."""
    import jax
    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.telemetry import watchdog as wd
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        EpochConfig, epoch_transition_device, synthetic_epoch_state)

    spec = phase0.get_spec("mainnet")
    cfg = EpochConfig.from_spec(spec)
    cols, scal, inp = synthetic_epoch_state(
        cfg, V_DEVICE, np.random.default_rng(11))
    out = epoch_transition_device(cfg, cols, scal, inp)   # warm compile
    _sync(out)
    cols = out[0]

    def run_once(cols):
        t0 = time.perf_counter()
        with telemetry.span("bench.telemetry_probe") as sp:
            out = epoch_transition_device(cfg, cols, scal, inp)
            wd.layout_check("bench.telemetry_probe.cols", out[0])
            telemetry.counter("bench.telemetry_probe.iters").inc()
            sp.fence(out[0].balance)
        _sync(out)      # both arms end fully fenced (off-arm span no-ops)
        return time.perf_counter() - t0, out[0]

    # main() pins telemetry on for the harness; restore that pin (not env
    # control) after each arm-toggling section
    prev_enabled = telemetry.core._enabled_override
    times = {True: [], False: []}
    try:
        for _ in range(5):
            for flag in (False, True):    # interleaved: drift lands evenly
                telemetry.set_enabled(flag)
                dt, cols = run_once(cols)
                times[flag].append(dt)
    finally:
        telemetry.set_enabled(prev_enabled)
    on_s, off_s = min(times[True]), min(times[False])
    overhead_pct = max(0.0, (on_s - off_s) / off_s * 100.0)
    row = {
        "epoch_on_ms": round(on_s * 1e3, 2),
        "epoch_off_ms": round(off_s * 1e3, 2),
        "overhead_pct": round(overhead_pct, 2),
        "validators": V_DEVICE,
    }
    if V_DEVICE >= 16384:
        # the bound is meaningful once the epoch program amortizes the
        # fixed ~0.5 ms fence round trip; at toy smoke shapes (an epoch of
        # a few ms) the on-arm's one extra tiny fetch IS a few percent, so
        # record without asserting there (committed captures run >= 65536)
        assert overhead_pct < 3.0, \
            f"telemetry overhead {overhead_pct:.2f}% >= 3% bound"
    else:
        row["overhead_asserted"] = False

    n_dev = 1
    while n_dev * 2 <= min(8, len(jax.devices())):
        n_dev *= 2
    if n_dev < 2:
        row["watchdog"] = {"skipped": f"single-device backend "
                                      f"({len(jax.devices())} device)"}
        return row
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    from consensus_specs_tpu.testing import factories
    bls.bls_active = False
    spec_min = phase0.get_spec("minimal")
    spec_min.clear_caches()
    state = factories.seed_genesis_state(
        spec_min, 4 * spec_min.SLOTS_PER_EPOCH)
    factories.advance_slots(spec_min, state, 2)
    # pin telemetry ON for the drive: with CSTPU_TELEMETRY=0 in the env
    # the watchdogs would no-op and a 0/0 row would be vacuous, not a
    # verified acceptance result
    telemetry.set_enabled(True)
    core = ResidentCore(spec_min, state, mesh=ServingMesh.create(n_dev))
    try:
        spe = spec_min.SLOTS_PER_EPOCH
        target = (state.slot // spe + 1) * spe + 1
        core.process_slots(state, target)          # warm-up epoch
        retrace0 = telemetry.counter("watchdog.retrace_events").value
        relayout0 = telemetry.counter("watchdog.relayout_events").value
        t0 = time.perf_counter()
        core.process_slots(state, target + spe)    # >= 4 slots + 1 boundary
        drive_s = time.perf_counter() - t0
        retrace = telemetry.counter("watchdog.retrace_events").value - retrace0
        relayout = (telemetry.counter("watchdog.relayout_events").value
                    - relayout0)
        assert retrace == 0 and relayout == 0, \
            f"watchdog events on the steady resident loop: " \
            f"retrace={retrace} relayout={relayout}"
        row["watchdog"] = {
            "devices": n_dev, "slot_steps": int(spe), "boundaries": 1,
            "retrace_events": int(retrace), "relayout_events": int(relayout),
            "drive_ms": round(drive_s * 1e3, 2),
        }
    finally:
        core.exit()
        telemetry.set_enabled(prev_enabled)
    return row


def bench_resilience():
    """The resilience acceptance row (ISSUE 13): (a) guarded-dispatch
    overhead — the epoch program dispatched through
    resilience.guarded_dispatch WITH the integrity tripwire armed
    (hull check of every output column) vs the raw watchdog dispatch,
    interleaved min-of-8 per arm, <3%% asserted (the telemetry bound's
    sibling); (b) a recovery micro-drill — an injected transient raise
    plus a poisoned output on the same guarded key must recover via
    retry/re-dispatch to a BIT-IDENTICAL output. JSON keys:
    epoch_guarded_ms, epoch_raw_ms, overhead_pct, recovery.*."""
    import jax
    from consensus_specs_tpu import resilience
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        EpochConfig, _epoch_transition_jit, synthetic_epoch_state)
    from consensus_specs_tpu.parallel.sharding import trees_bitwise_equal
    from consensus_specs_tpu.resilience import dispatch as rdispatch
    from consensus_specs_tpu.resilience import faults
    from consensus_specs_tpu.resilience.integrity import epoch_output_check
    from consensus_specs_tpu.telemetry import watchdog as wd

    spec = phase0.get_spec("mainnet")
    cfg = EpochConfig.from_spec(spec)
    cols, scal, inp = synthetic_epoch_state(
        cfg, V_DEVICE, np.random.default_rng(13))
    fn = _epoch_transition_jit()
    out = fn(cfg, cols, scal, inp)          # warm compile (epoch + check)
    _sync(out)
    assert epoch_output_check(out), "synthetic state outside declared hulls"
    cols = out[0]

    def run_raw(cols):
        t0 = time.perf_counter()
        out = wd.dispatch(("bench.resilience.raw", V_DEVICE),
                          fn, cfg, cols, scal, inp)
        _sync(out)
        return time.perf_counter() - t0, out[0]

    # the donated-site rule every production call site follows
    # (sharding.ServingMesh.epoch_transition, ResidentCore._epoch_dispatch):
    # _epoch_transition_jit() donates off-CPU, so no in-memory retry there
    guard_retries = 0 if jax.default_backend() != "cpu" \
        else rdispatch.RETRIES_DEFAULT

    def run_guarded(cols):
        t0 = time.perf_counter()
        out = rdispatch.guarded_dispatch(
            ("bench.resilience.guarded", V_DEVICE),
            fn, cfg, cols, scal, inp, check=epoch_output_check,
            retries=guard_retries)
        _sync(out)
        return time.perf_counter() - t0, out[0]

    # interleaved min-of-8: the true guard cost is one try-frame + a
    # ~0.3 ms fused hull reduction on a ~70 ms program, well inside
    # run-to-run variance — the mins need enough reps to converge
    times = {"raw": [], "guarded": []}
    for _ in range(8):
        for arm, runner in (("guarded", run_guarded), ("raw", run_raw)):
            dt, cols = runner(cols)
            times[arm].append(dt)
    raw_s, guarded_s = min(times["raw"]), min(times["guarded"])
    overhead_pct = max(0.0, (guarded_s - raw_s) / raw_s * 100.0)
    row = {
        "epoch_guarded_ms": round(guarded_s * 1e3, 2),
        "epoch_raw_ms": round(raw_s * 1e3, 2),
        "overhead_pct": round(overhead_pct, 2),
        "validators": V_DEVICE,
        "tripwire_armed": True,
    }
    if V_DEVICE >= 16384:
        # same amortization note as the telemetry bound: the guard adds
        # one block_until_ready + one fused hull reduction, which is only
        # meaningfully <3% once the epoch program dominates
        assert overhead_pct < 3.0, \
            f"guarded-dispatch overhead {overhead_pct:.2f}% >= 3% bound"
    else:
        row["overhead_asserted"] = False

    # recovery micro-drill: transient raise then a poisoned balance
    # column on one guarded key — retry + tripwire re-dispatch must land
    # on the bit-identical output (the chaos drill's acceptance, at
    # bench scale and embedded in the capture). The drill re-dispatches
    # the SAME cols (retry) and then reuses them for the clean arm, so
    # it must run the UNDONATED program on every backend — the donated
    # form would hand the retry deleted arrays (the repo rule donating
    # call sites follow with retries=0)
    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        _epoch_transition_undonated)
    before = {k: telemetry.counter(k, always=True).value
              for k in ("resilience.retries", "resilience.faults_injected",
                        "resilience.corrupt_outputs")}
    faults.set_schedule("seed=13;dispatch:*bench.recovery*@1=raise;"
                        "dispatch:*bench.recovery*@2=poison:6")
    try:
        out_faulted = rdispatch.guarded_dispatch(
            ("bench.recovery", V_DEVICE), _epoch_transition_undonated,
            cfg, cols, scal, inp, check=epoch_output_check)
        out_clean = _epoch_transition_undonated(cfg, cols, scal, inp)
        _sync((out_faulted, out_clean))
        identical = trees_bitwise_equal(out_faulted, out_clean)
    finally:
        faults.set_schedule(None)
    assert identical, "guarded recovery must be bit-identical"
    row["recovery"] = dict(
        bit_identical=bool(identical),
        **{k.split("resilience.", 1)[-1]:
           int(telemetry.counter(k, always=True).value - v)
           for k, v in before.items()})
    row["health"] = resilience.health_snapshot()
    return row


def bench_firehose():
    """The streaming-verifier acceptance row (ISSUE 15): sustained
    synthetic gossip load through the firehose — waves of `target`
    aggregates per slot tick, staged/dispatched while the previous batch
    pairs on device, ONE guarded materialization per flush. Asserts:
    streamed verdicts bit-identical to the synchronous
    _grouped_pairing_dispatch, batch occupancy >= target (128 nominal)
    in steady state, 0 deadline misses at the nominal load point, and 0
    retrace / 0 re-layout watchdog events across the run. The headline
    is the north-star: aggregate-verifies (and pairings) per second per
    chip under firehose load, not per-block latency."""
    from consensus_specs_tpu import streaming, telemetry
    from consensus_specs_tpu.ops import bls_jax as BJ

    target = int(os.environ.get("CSTPU_BENCH_FIREHOSE_GROUPS", 128))
    rounds = int(os.environ.get("CSTPU_BENCH_FIREHOSE_ROUNDS", 3))
    # the nominal-load deadline: generous on the CPU harness (the 128-
    # group pairing is seconds there); a real accelerator run tightens it
    deadline_ms = float(os.environ.get("CSTPU_BENCH_FIREHOSE_DEADLINE_MS",
                                       600_000.0))
    g1, g2 = _stage_attestation_pairs(8)   # device work value-independent
    n_distinct, P = g1.shape[0], g1.shape[1]

    def pairs_for(k):
        i = k % n_distinct
        return [(g1[i, p], g2[i, p]) for p in range(P)]

    v = streaming.StreamingVerifier(target_groups=target,
                                    deadline_ms=deadline_ms)

    def wave(tag):
        for k in range(target):
            v.submit_staged((tag, k), pairs_for(k))

    # warm-up flush compiles the grouped programs at the firehose shape;
    # its verdicts double as the differential gate vs the sync dispatch
    wave("warm")
    v.pump()
    warm = v.flush()
    assert len(warm) == target and all(warm.values())
    sync = BJ._grouped_pairing_dispatch(
        [(("warm", k), pairs_for(k)) for k in range(target)])
    assert sync == warm, "streamed verdicts != synchronous dispatch"

    retrace0 = telemetry.counter("watchdog.retrace_events").value
    relayout0 = telemetry.counter("watchdog.relayout_events").value
    miss0 = telemetry.counter("firehose.deadline_miss", always=True).value
    n_occ0 = len(v.pipeline.occupancies)
    t0 = time.perf_counter()
    for w in range(rounds):
        wave(w)      # host staging of wave w overlaps wave w-1's pairing
        v.pump()
    res = v.flush()
    dt = time.perf_counter() - t0
    groups = rounds * target
    assert len(res) == groups and all(res.values())
    occupancies = list(v.pipeline.occupancies)[n_occ0:]
    misses = (telemetry.counter("firehose.deadline_miss",
                                always=True).value - miss0)
    retrace = telemetry.counter("watchdog.retrace_events").value - retrace0
    relayout = (telemetry.counter("watchdog.relayout_events").value
                - relayout0)
    assert min(occupancies) >= target, \
        f"steady-state occupancy {min(occupancies)} < target {target}"
    assert misses == 0, f"{misses} deadline miss(es) at the nominal load"
    assert retrace == 0 and relayout == 0, \
        f"firehose steady state tripped watchdogs: {retrace}/{relayout}"
    health = streaming.firehose_health()
    streaming.activate(None)
    return {
        "target_groups": target,
        "rounds": rounds,
        "groups_verified": groups,
        "batches": len(occupancies),
        "occupancy_min": int(min(occupancies)),
        "wall_s": round(dt, 3),
        "aggverify_per_s": round(groups / dt, 2),
        "pairings_per_s": round(groups * P / dt, 2),
        "deadline_ms": deadline_ms,
        "deadline_misses": int(misses),
        "watchdog": {"retrace_events": int(retrace),
                     "relayout_events": int(relayout)},
        "health": health,
    }


def main():
    # virtual 8-device mesh for the sharded_vs_single stage on CPU runs
    # (real accelerators bring their own device count). Must precede
    # backend init.
    if os.environ.get("CSTPU_BENCH_CPU") == "1":
        import jax as _j
        _j.config.update("jax_num_cpu_devices", 8)
    # persistent compile cache: the traced Merkle/pairing programs take
    # ~1 min each to compile; cache hits make repeat bench runs fast
    from consensus_specs_tpu.utils import compile_cache
    compile_cache.configure()

    # every stage runs under a telemetry span (the snapshot embedded in
    # the JSON row carries per-stage wall times), and the global compile
    # listener cross-checks the per-key retrace watchdog. Telemetry is
    # PINNED ON for the whole harness: the staged timings (s2s, resident)
    # are span-derived now, and an ambient CSTPU_TELEMETRY=0 would
    # silently zero them into a bogus-but-plausible capture.
    from consensus_specs_tpu import telemetry
    telemetry.set_enabled(True)
    telemetry.watchdog.install_compile_listener()

    def _device(label, fn):
        # a stage that fails raises: the run exits non-zero with no JSON
        with telemetry.span("bench." + label.replace(" ", "_")):
            return fn()

    _progress(f"state-to-state epoch ({V_STATE} validators, real BeaconState)")
    tm, s2s_state = _device("state-to-state", bench_state_to_state)
    s2s_ms = (tm["distill"] + tm.get("perm", 0.0) + tm["device"]
              + tm["root"]) * 1e3
    s2s_txt = ("s2s entry-path %.0f ms = distill(host) %.0f + perm(dev) %.0f "
               "+ epoch %.0f + root %.0f, writeback %.0f ms excl." % (
                   s2s_ms, tm["distill"] * 1e3, tm.get("perm", 0.0) * 1e3,
                   tm["device"] * 1e3, tm["root"] * 1e3,
                   tm["writeback"] * 1e3))
    _progress(f"{s2s_txt}; resident multi-epoch drive ({V_STATE} validators)")
    res_epochs = _device(
        "resident", lambda: bench_resident(resumed_state=s2s_state))
    resident_ms = None
    res_txt = None
    epochs = [r for r in (res_epochs or []) if "stage" in r]
    ckpt = next((r for r in (res_epochs or [])
                 if "checkpoint_write" in r), None)
    if len(epochs) >= 2:
        # compiles are warm (shared with the s2s stage); the last epoch is
        # the steady state
        steady = epochs[-1]
        resident_ms = (steady["stage"] + steady["device"]
                       + steady["refresh"]) * 1e3
        res_txt = ("resident per-epoch %.0f ms = stage %.0f + epoch %.0f + "
                   "refresh(root) %.0f over %d epochs; 64 slot-roots %.0f ms" % (
                       resident_ms, steady["stage"] * 1e3,
                       steady["device"] * 1e3, steady["refresh"] * 1e3,
                       len(epochs), steady["slots"] * 1e3))
        if ckpt is not None:
            res_txt += ("; checkpoint write %.0f ms / resume %.0f ms "
                        "(%.0f MB, no object materialization)" % (
                            ckpt["checkpoint_write"] * 1e3,
                            ckpt["checkpoint_resume"] * 1e3,
                            ckpt["checkpoint_bytes"] / 1e6))
        _progress(res_txt)
    _progress(f"kernel epoch+shuffle ({V_DEVICE} validators)")
    t_epoch = _device("epoch kernel", bench_epoch_device)
    if t_epoch is not None:
        _progress(f"epoch {t_epoch * 1e3:.1f} ms; state root ({V_DEVICE} validators)")
    t_root = _device("state-root kernel", bench_state_root_device)
    if t_root is not None:
        _progress(f"state root {t_root * 1e3:.1f} ms; incremental root "
                  f"({V_DEVICE} leaves)")
    inc = _device("incremental root", bench_incremental_root_device)
    if inc is not None:
        _progress("incremental root %(incremental_ms).1f ms (%(dirty)d dirty) "
                  "vs full rebuild %(full_rebuild_ms).0f ms = %(speedup).1fx; "
                  "pair-hash backend A/B" % inc)
    ab = _device("merkle backend A/B", bench_merkle_backend_ab)
    if "skipped" not in ab:
        _progress("pair-hash A/B: xla %(xla_ms).1f ms, pallas %(pallas_ms).1f "
                  "ms @ %(lanes)d lanes" % ab)
    smab = _device("scalar-mul A/B", bench_scalar_mul_ab)
    if smab is not None:
        _progress("scalar-mul A/B (w=%(window_w)d): cofactor "
                  "%(cofactor_window_ms).1f ms / %(cofactor_window_seq_adds)d "
                  "adds vs %(cofactor_double_add_ms).1f ms / "
                  "%(cofactor_double_add_seq_adds)d adds; k256 "
                  "%(k256_window_ms).1f vs %(k256_double_add_ms).1f ms" % smab)
    prab = _device("pairing REDC A/B", bench_pairing_redc_ab)
    if prab is not None:
        _progress("pairing REDC A/B: coeff %(coeff_ms).1f ms / "
                  "%(coeff_redc_lanes)d lanes vs leaf %(leaf_ms).1f ms / "
                  "%(leaf_redc_lanes)d lanes (%(redc_lane_ratio).1fx) @ "
                  "%(groups)d groups" % prab)
    svs = _device("sharded vs single", bench_sharded_vs_single)
    if svs is not None and "skipped" not in svs:
        _progress("sharded serving loop vs single (%(devices)d-device mesh, "
                  "%(validators)d validators): epoch %(epoch_sharded_ms).1f "
                  "vs %(epoch_single_ms).1f ms, forest build+root "
                  "%(root_sharded_ms).1f vs %(root_single_ms).1f ms, slot "
                  "update %(slot_update_sharded_ms).1f vs "
                  "%(slot_update_single_ms).1f ms — bit-identical" % svs)
    elif svs is not None:
        _progress("sharded vs single skipped: %(skipped)s" % svs)
    rrow = _device("resilience", bench_resilience)
    if rrow is not None:
        _progress("guarded-dispatch overhead %(overhead_pct).2f%% (epoch "
                  "guarded+tripwire %(epoch_guarded_ms).1f / raw "
                  "%(epoch_raw_ms).1f ms); recovery drill bit-identical "
                  "after %(r)d injected faults" % dict(
                      rrow, r=rrow["recovery"]["faults_injected"]))
    trow = _device("telemetry", bench_telemetry)
    if trow is not None:
        msg = ("telemetry overhead %(overhead_pct).2f%% (epoch on "
               "%(epoch_on_ms).1f / off %(epoch_off_ms).1f ms)" % trow)
        watch = trow.get("watchdog", {})
        if "retrace_events" in watch:
            msg += ("; watchdogs: %(retrace_events)d retrace / "
                    "%(relayout_events)d re-layout events over "
                    "%(slot_steps)d slots + %(boundaries)d boundary on the "
                    "%(devices)d-device mesh" % watch)
        _progress(msg)
    t_bls, t_py_verify = _device("BLS batch", bench_bls_device)
    if t_bls is not None:
        _progress(f"BLS batch {t_bls * 1e3:.1f} ms; firehose streaming "
                  f"verifier (sustained synthetic gossip load)")
    fh = _device("firehose", bench_firehose)
    if fh is not None:
        _progress("firehose: %(aggverify_per_s).1f aggverify/s/chip "
                  "(%(pairings_per_s).0f pairings/s) at occupancy >= "
                  "%(occupancy_min)d over %(batches)d batches, "
                  "%(deadline_misses)d deadline misses, watchdogs 0/0; "
                  "config-3 block next" % fh)
    t_block = _device("config-3 block", bench_block_device)
    if t_block is not None:
        _progress(f"config-3 block {t_block * 1e3:.0f} ms; python baseline")
    py_epoch, py_root = bench_python_baseline()
    _progress("done")

    # python equivalents, scaled per validator / per verify (the python
    # object path at 1M is hours; scaling is linear in V and N)
    scale = V_STATE / V_BASELINE
    base = ("config5_1M_validator_slot_boundary_ms" if V_STATE == 1_000_000
            else f"config5_{V_STATE}_validator_slot_boundary_ms")
    # headline epoch term: the resident steady-state boundary (production
    # shape — columns never leave the device); the one-shot entry path
    # stays reported in the unit string
    headline_epoch_ms = resident_ms if resident_ms is not None else s2s_ms
    parts = [res_txt] if res_txt is not None else []
    parts.append(s2s_txt)
    if t_epoch is not None:
        parts.append("kernel epoch %.1f ms" % (t_epoch * 1e3))
    if t_root is not None:
        parts.append("kernel root %.1f ms" % (t_root * 1e3))
    if inc is not None:
        parts.append(
            "incremental state-root %.1f ms (%d dirty of %d leaves; full "
            "forest rebuild %.0f ms, %.1fx)" % (
                inc["incremental_ms"], inc["dirty"], inc["leaves"],
                inc["full_rebuild_ms"], inc["speedup"]))
    if "skipped" not in ab:
        parts.append("pair-hash A/B xla %.1f / pallas %.1f ms @ %d lanes" % (
            ab["xla_ms"], ab["pallas_ms"], ab["lanes"]))
    if smab is not None:
        parts.append(
            "scalar-mul A/B w=%d: cofactor %d->%d seq adds (%.1f/%.1f ms), "
            "256-bit %d->%d (%.1f/%.1f ms)" % (
                smab["window_w"], smab["cofactor_double_add_seq_adds"],
                smab["cofactor_window_seq_adds"],
                smab["cofactor_double_add_ms"], smab["cofactor_window_ms"],
                smab["k256_double_add_seq_adds"], smab["k256_window_seq_adds"],
                smab["k256_double_add_ms"], smab["k256_window_ms"]))
    if prab is not None:
        parts.append(
            "pairing REDC A/B: %d->%d lanes (%.1fx), coeff %.1f / leaf "
            "%.1f ms @ %d groups" % (
                prab["leaf_redc_lanes"], prab["coeff_redc_lanes"],
                prab["redc_lane_ratio"], prab["coeff_ms"], prab["leaf_ms"],
                prab["groups"]))
    if svs is not None and "skipped" not in svs:
        parts.append(
            "sharded serving loop bit-identical on the %d-device mesh: "
            "epoch %.1f/%.1f ms, forest %.1f/%.1f ms, slot update "
            "%.1f/%.1f ms (sharded/single)" % (
                svs["devices"], svs["epoch_sharded_ms"],
                svs["epoch_single_ms"], svs["root_sharded_ms"],
                svs["root_single_ms"], svs["slot_update_sharded_ms"],
                svs["slot_update_single_ms"]))
    if trow is not None:
        txt = "telemetry overhead %.2f%% (<3%% asserted)" % \
            trow["overhead_pct"]
        if "retrace_events" in trow.get("watchdog", {}):
            txt += (", watchdogs 0 retrace / 0 re-layout events over the "
                    "%d-device resident drive" % trow["watchdog"]["devices"])
        parts.append(txt)
    if t_bls is not None:
        parts.append("%d-agg-verify %.1f ms = %.0f aggverify/s/chip" % (
            N_ATTESTATIONS, t_bls * 1e3, N_ATTESTATIONS / t_bls))
    if fh is not None:
        parts.append(
            "firehose %.1f aggverify/s/chip sustained (occupancy >= %d, "
            "%d deadline misses, 0 retrace / 0 re-layout)" % (
                fh["aggverify_per_s"], fh["occupancy_min"],
                fh["deadline_misses"]))
    if t_block is not None:
        parts.append("config-3 block e2e %.0f ms" % (t_block * 1e3))
    total_ms = headline_epoch_ms + t_bls * 1e3
    py_total_ms = (py_epoch * scale + py_root * scale
                   + t_py_verify * N_ATTESTATIONS) * 1e3
    parts.append("python baseline %.0f ms scaled over the measured stages"
                 % py_total_ms)
    record = {
        "metric": base,
        "value": round(total_ms, 1),
        "unit": "ms (%s)" % "; ".join(parts),
        "vs_baseline": round(py_total_ms / total_ms, 1),
    }
    if inc is not None:
        record["incremental_root"] = inc
    if ab is not None:
        record["merkle_backend_ab"] = ab
    if smab is not None:
        record["scalar_mul_ab"] = smab
    if prab is not None:
        record["pairing_redc_ab"] = prab
    if svs is not None:
        record["sharded_vs_single"] = svs
    if trow is not None:
        record["telemetry_overhead"] = trow
    if rrow is not None:
        record["resilience_overhead"] = rrow
    if fh is not None:
        record["firehose"] = fh
    # provenance stamp on EVERY row (not just a top-level note): a CPU
    # smoke run must be distinguishable from a chip capture without
    # reading logs
    tag = _probe_tag()
    record["probe"] = tag
    for row in (inc, ab, smab, prab, svs, trow, rrow, fh):
        if isinstance(row, dict):
            row["probe"] = tag
    # the full registry snapshot rides the artifact: per-stage span wall
    # times, REDC/forest/scalar-mul counters, watchdog event totals
    record["telemetry"] = telemetry.snapshot()
    # ... and the fault/degradation snapshot (current ladder rung, retry/
    # deadline-miss/fault counters, checkpoint provenance) on the capture
    # — end-of-run state, like the telemetry registry dump above: a
    # capture that FINISHED degraded says so in the artifact itself (the
    # cumulative counters also expose any mid-run recoveries)
    record["resilience"] = _resilience_snapshot()
    # ... and the static contract-budget snapshot next to it (declared
    # kernel budgets + the committed trace-baseline values), so a bench
    # capture and the op budgets it ran under are cross-checkable in ONE
    # artifact — e.g. pairing_redc_ab's measured lane counts against the
    # miller/verdict contracts' pins. Pure declaration reads: nothing is
    # traced here (`make contracts` does the measuring).
    record["contracts"] = _contract_snapshot()
    # ... and the range-contract snapshot (declared output bounds + the
    # committed proven-interval baseline) next to the trace-tier one, so
    # a capture also records the value budgets its kernels were proven
    # under. Pure declaration reads again: `make ranges` does the proving.
    record["ranges"] = _ranges_snapshot()
    # ... and the buffer-lifetime snapshot (the donation/aliasing
    # prover's finding count over the committed tree + a hash of the
    # accepted-findings baseline), so a capture records that the code
    # it measured proved clean of use-after-donate hazards. The prover
    # is pure AST interpretation (no lowering here: `make lifetime`
    # does the cross-check).
    record["lifetime"] = _lifetime_snapshot()
    # ... and the memory-contract snapshot (declared peak-HBM budgets +
    # the committed liveness baseline and its hash), so a capture records
    # the memory envelopes its kernels were proven inside. Declaration
    # reads only — nothing is traced here (`make memory` does the
    # liveness walk and the compiled cross-check).
    record["memory"] = _memory_snapshot()
    print(json.dumps(record))


def _contract_snapshot():
    try:
        from tools.analysis.trace import engine as _trace_engine
        contracts = _trace_engine.discover()
        return {"budgets": _trace_engine.budget_snapshot(contracts),
                "baseline": _trace_engine.load_trace_baseline()}
    except Exception as exc:   # a broken registry must not sink a capture
        return {"error": f"{type(exc).__name__}: {exc}"}


def _ranges_snapshot():
    try:
        from tools.analysis.ranges import engine as _ranges_engine
        contracts = _ranges_engine.discover()
        return {"declared": _ranges_engine.declared_snapshot(contracts),
                "baseline": _ranges_engine.load_ranges_baseline()}
    except Exception as exc:   # a broken registry must not sink a capture
        return {"error": f"{type(exc).__name__}: {exc}"}


def _lifetime_snapshot():
    try:
        import hashlib
        from tools.analysis.lifetime import engine as _lt_engine
        report = _lt_engine.run_lifetime(lower=False)
        base = _lt_engine.DEFAULT_BASELINE
        digest = hashlib.sha256(base.read_bytes()).hexdigest() \
            if base.exists() else None
        return {"findings": len(report.findings),
                "suppressed": len(report.suppressed),
                "baselined": len(report.baselined),
                "donors": report.donors,
                "files_checked": report.files_checked,
                "baseline_sha256": digest}
    except Exception as exc:   # a broken prover must not sink a capture
        return {"error": f"{type(exc).__name__}: {exc}"}


def _memory_snapshot():
    try:
        import hashlib
        from tools.analysis.memory import engine as _mem_engine
        base = _mem_engine.DEFAULT_BASELINE
        digest = hashlib.sha256(base.read_bytes()).hexdigest() \
            if base.exists() else None
        return {"declared": _mem_engine.declared_snapshot(),
                "baseline": _mem_engine.load_memory_baseline(),
                "baseline_sha256": digest}
    except Exception as exc:   # a broken registry must not sink a capture
        return {"error": f"{type(exc).__name__}: {exc}"}


def _resilience_snapshot():
    try:
        from consensus_specs_tpu import resilience
        return resilience.snapshot()
    except Exception as exc:   # a broken registry must not sink a capture
        return {"error": f"{type(exc).__name__}: {exc}"}


if __name__ == "__main__":
    main()
