"""The yardstick's copies: references, generator and compile listener.

Copied from chip_smoke.py (PR 22) so that a later PR may change that script
and not the benchmark (the plain references written for the benchmark itself
are plain_ssz.py and plain_epoch.py): the hashlib merkleization (shares no code with the
package), the small object-model oracle, the seeded state, the per-slot
attestations and the jax.monitoring compile listener. The host-backend twin,
the fence comparison, the Pallas phase, --bls and --chips 4 were not copied.
Nothing here reads the clock for a metric; run.py and the drivers do.
"""
from __future__ import annotations

import hashlib
from copy import deepcopy
from typing import NamedTuple

import numpy as np

RESILIENCE_COUNTERS = ("resilience.degradations", "resilience.retries",
                       "resilience.transient_errors")
WATCHDOG_COUNTERS = ("watchdog.retrace_events", "watchdog.relayout_events")


class Compared(NamedTuple):
    """One number of the comparison that decides `correct`, beside its
    limit. Everything is exact integers or bytes, so every limit is 0
    mismatches: `got` counts them (or differing bytes)."""
    name: str
    got: int
    limit: int

    @property
    def ok(self) -> bool:
        return self.got <= self.limit


class CompileListener:
    """Counts every executable this process builds (`compiles`, with the
    seconds spent) and every persistent-cache hit. A warm cache still
    counts one build per program it does not hold; a steady window must
    count zero."""

    def __init__(self):
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


def counters(names) -> dict:
    from consensus_specs_tpu import telemetry
    return {n: int(telemetry.counter(n, always=True).value) for n in names}


# ---------------------------------------------------------------------------
# Independent host merkleization (hashlib only - shares nothing with the
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
    return _merkle_root(leaves, n), host_balances_root(cols["balance"])


def host_balances_root(balance: np.ndarray) -> bytes:
    """`List[uint64]` root of the balances column, hashlib alone."""
    n = int(balance.shape[0])
    bal = np.zeros(-(-n // 4) * 4, "<u8")
    bal[:n] = balance
    balb = bal.tobytes()
    chunks = [balb[i:i + 32] for i in range(0, len(balb), 32)]
    return _merkle_root(chunks, n)


# ---------------------------------------------------------------------------
# The epoch arithmetic against the plain reference: the object model
# ---------------------------------------------------------------------------

def oracle_small(validators: int = 256) -> list:
    """ResidentCore on this backend vs the pure-Python object model
    (minimal preset, the unpatched spec under `core.suspended()` on its
    own deep copy): blocks across two boundaries and one registry-mutating
    block. Returns the numbers compared; the only independent check of the
    chip's emulated 64-bit epoch arithmetic and of the shuffle."""
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    from consensus_specs_tpu.testing import factories
    from consensus_specs_tpu.utils.ssz.impl import hash_tree_root, serialize

    resil0 = counters(RESILIENCE_COUNTERS)
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
    boundaries = diverged = 0
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
                epoch0 = spec.get_current_epoch(ref)
                spec.state_transition(ref, block)
                boundaries += spec.get_current_epoch(ref) - epoch0
            core.state_transition(res, block)
            diverged += hash_tree_root(ref) != core._state_root(res)
    finally:
        core.exit()
    want = serialize(ref, spec.BeaconState)
    got = serialize(res, spec.BeaconState)
    differing = abs(len(want) - len(got)) + sum(
        a != b for a, b in zip(want, got))
    moved = sum(abs(v - resil0[k])
                for k, v in counters(RESILIENCE_COUNTERS).items())
    spec.clear_caches()
    return [
        Compared("oracle.blocks_with_diverged_state_root", int(diverged), 0),
        Compared("oracle.post_state_bytes_differing", int(differing), 0),
        Compared("oracle.boundaries_short_of_2", max(0, 2 - int(boundaries)), 0),
        Compared("oracle.slashing_block_did_not_slash",
                 int(not any(v.slashed for v in ref.validator_registry)), 0),
        Compared("oracle.resilience_counter_moves", int(moved), 0),
    ]


# ---------------------------------------------------------------------------
# The seeded deployment and its traffic
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
