"""Blocks full of deposits on the resident core: `process_deposit` through
the registry view, appended rows inside a fixed capacity, the activation
queue at the boundary; on a checkpoint-resumed (light) core and on an
object-entered one, one device and four virtual ones, against the unpatched
object model.

The deposit-queue mix's generator (`benchmark/deposit_generator.py`) builds
the blocks on `benchmark/seeded_deposit_queue.py`'s entry (the mature seed
with the eth1 chain ahead of `deposit_index`, every deposit proved against
the state's own `deposit_root`). After EVERY block the whole serialized
state and the state root must equal the object model's; the minimal preset
runs epochs enough for new validators to become eligible, queue and
activate at the churn limit. A block the spec rejects leaves the registry's
length, device columns, host mirrors and identity copies, the pubkey index
and both forests as they stood.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import (seeded_deposit_queue, spoiled_blocks,  # noqa: E402
                       spoiled_deposits)
from benchmark.deposit_generator import DepositBlockGenerator  # noqa: E402
from benchmark.ops_generator import OpsBlockGenerator  # noqa: E402
from consensus_specs_tpu import telemetry  # noqa: E402
from consensus_specs_tpu.crypto import bls  # noqa: E402
from consensus_specs_tpu.models import phase0  # noqa: E402
from consensus_specs_tpu.models.phase0.epoch_soa import inert_column_tail  # noqa: E402
from consensus_specs_tpu.models.phase0.resident import ResidentCore  # noqa: E402
from consensus_specs_tpu.utils.ssz.impl import (deserialize,  # noqa: E402
                                                hash_tree_root, serialize)

SEED = 2**31 + 40
MIX = json.loads((REPO / "benchmark/traffic/deposit-queue.json").read_text())
OPS_MIX = json.loads((REPO / "benchmark/traffic/dirty-slots.json").read_text())
V = 128
FALLBACKS = telemetry.counter("resident.block.fallbacks", always=True)
GROWN = telemetry.counter("resident.registry.capacity_grown", always=True)


def _mesh(devices: int):
    if devices == 1:
        return None
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    return ServingMesh.create(devices)


def _entry(validators: int = V, epochs: int = 10, preset: str = "minimal"):
    """(spec, the serialized entry state, its deposit queue): `epochs`
    epochs of full blocks outstanding."""
    bls.bls_active = False
    spec = phase0.get_spec(preset)
    spec.clear_caches()
    mix = dict(MIX, outstanding_deposits=int(
        spec.MAX_DEPOSITS * spec.SLOTS_PER_EPOCH * epochs))
    data, queue = seeded_deposit_queue.seeded_deposit_queue_checkpoint(
        spec, validators, SEED, mix)
    return spec, data, queue


def _core(spec, data, entered: str, devices: int, capacity):
    if entered == "light":
        return ResidentCore.from_checkpoint(spec, data, mesh=_mesh(devices),
                                            capacity=capacity)
    return ResidentCore(spec, deserialize(data, spec.BeaconState),
                        mesh=_mesh(devices), capacity=capacity)


def _assert_equals_object_model(spec, core, ref, slot) -> None:
    """The served core against the object state `ref` after a block."""
    res = core.state
    v = len(ref.validator_registry)
    assert len(spec.registry_view(res)) == core._v == v, slot
    assert core.checkpoint_bytes() == serialize(ref, spec.BeaconState), \
        f"the states differ after the block of slot {slot}"
    assert core._state_root(res) == hash_tree_root(ref), slot
    far = int(spec.FAR_FUTURE_EPOCH)
    for field, mirror in core.mirrors.items():
        assert len(mirror) == core._capacity
        assert (mirror[:v] == [getattr(x, field) for x in ref.validator_registry]).all()
        assert (mirror[v:] == inert_column_tail(field, len(mirror) - v, far)).all()
    assert core._pk_np[:v].tobytes() == b"".join(
        bytes(x.pubkey) for x in ref.validator_registry)
    assert not core._pk_np[v:].any() and not core._wc_np[v:].any()


def _drive(spec, core, ref, generator, slots: int, each=None) -> int:
    """`slots` slots with their blocks on the core and on the object model,
    compared after every block; returns the blocks taken."""
    res = core.state
    for _ in range(slots):
        slot = int(res.slot) + 1
        core.process_slots(res, slot)
        with core.suspended():
            spec.process_slots(ref, slot)
        block = generator.block(res)
        if block is None:           # the slot's proposer is slashed
            continue
        core.process_block(res, block)
        with core.suspended():
            spec.process_block(ref, block)
        _assert_equals_object_model(spec, core, ref, slot)
        if each is not None:
            each(slot)
    return slots


# -- the chain ----------------------------------------------------------------------

@pytest.mark.parametrize("entered,devices", [("light", 1), ("light", 4),
                                             ("object", 1), ("object", 4)])
def test_a_core_follows_the_object_model_through_epochs_of_deposits(entered, devices):
    """Nine epochs of full deposit blocks inside one capacity: 12 new
    validators and 4 top-ups a block; the rows become eligible at their
    epoch's boundary, queue, and activate four an epoch (the churn limit's
    floor); no block falls back, nothing is re-laid out."""
    spec, data, queue = _entry()
    spe = int(spec.SLOTS_PER_EPOCH)
    ref = deserialize(data, spec.BeaconState)
    core = _core(spec, data, entered, devices, capacity=1024)
    generator = DepositBlockGenerator(spec, SEED, MIX, queue)
    fallbacks0, grown0 = FALLBACKS.value, GROWN.value
    active, notes = [], []

    def each(slot):
        if slot % spe == 0:
            active.append(len(spec.get_active_validator_indices(
                core.state, slot // spe)))
            notes.append([s["args"] for s in telemetry.ring()
                          if s["name"] == "resident.stage.distill"][-1])
    try:
        blocks = _drive(spec, core, ref, generator, 9 * spe + 1, each)
        assert FALLBACKS.value == fallbacks0 and GROWN.value == grown0
        assert core._v == V + 12 * blocks == len(ref.validator_registry)
        assert int(ref.deposit_index) == V + 16 * blocks
        # an epoch's rows are eligible from its boundary on; the first
        # four (the churn limit's floor) are given an activation epoch at
        # the next one and are active ACTIVATION_EXIT_DELAY + 1 later; a
        # row that has its activation epoch keeps its place in the queue
        # until finality passes it (the spec's queue), so the set grows by
        # four every other epoch
        assert active[:5] == [V] * 5 and active[-1] > V + 8
        assert {b - a for a, b in zip(active[4:], active[5:])} == {0, 4}
        assert [n["registry_rows"] for n in notes] \
            == [V + 12 * spe * i for i in range(len(notes))]
        pending = [n["pending_activations"] for n in notes]
        assert pending[:2] == [0, 0]
        assert {b - a for a, b in zip(pending[1:], pending[2:])} \
            == {12 * spe - 4, 12 * spe}
        if entered == "object":
            # the objects come back with the rows the deposits appended
            state = core.exit()
            assert serialize(state, spec.BeaconState) \
                == serialize(ref, spec.BeaconState)
    finally:
        core._uninstall()
        spec.clear_caches()


class _MixedGenerator(OpsBlockGenerator):
    """The dirty-slots mix's block with the deposits the chain owes."""

    def __init__(self, spec, seed, mix, validators, queue):
        super().__init__(spec, seed, mix, validators)
        self.queue = queue

    def block(self, state):
        block = super().block(state)
        if block is None:
            return None
        owed = int(state.latest_eth1_data.deposit_count) - int(state.deposit_index)
        block.body.deposits = self.queue.deposits(
            self.spec, int(state.deposit_index),
            min(int(self.spec.MAX_DEPOSITS), owed))
        return block


@pytest.mark.parametrize("devices", [1, 4])
def test_blocks_that_mix_deposits_exits_and_slashings(devices):
    spec, data, queue = _entry(epochs=3)
    ref = deserialize(data, spec.BeaconState)
    core = _core(spec, data, "light", devices, capacity=512)
    mix = dict(OPS_MIX, exits_per_block=2, proposer_slashing_every=2,
               attester_slashing_at=3, attester_slashing_indices=2)
    generator = _MixedGenerator(spec, SEED, mix, V, queue)
    try:
        _drive(spec, core, ref, generator, 2 * int(spec.SLOTS_PER_EPOCH) + 2)
        assert sum(bool(v.slashed) for v in ref.validator_registry) > 2
        assert sum(v.exit_epoch != spec.FAR_FUTURE_EPOCH
                   for v in ref.validator_registry) > 16
        assert len(ref.validator_registry) > V + 100
    finally:
        core._uninstall()
        spec.clear_caches()


def test_a_deposit_of_a_key_appended_earlier_in_the_same_block_tops_it_up():
    """The second and fourth deposits of the first block carry the first
    one's new key: top-ups of a row one operation old, found through the
    pubkey index the append kept in step."""
    spec, data, queue = _entry(epochs=1)
    for i in (1, 3):
        queue.pubkeys[i] = queue.pubkeys[0]
    queue = seeded_deposit_queue.DepositQueue(
        queue.levels[0][:32 * V], queue.pubkeys, queue.credentials,
        queue.amounts, queue.signatures)
    ref = deserialize(data, spec.BeaconState)
    ref.latest_eth1_data.deposit_root = queue.root
    for vote in ref.eth1_data_votes:
        vote.deposit_root = queue.root
    data = serialize(ref, spec.BeaconState)
    core = _core(spec, data, "light", 1, capacity=256)
    try:
        _drive(spec, core, ref, DepositBlockGenerator(spec, SEED, MIX, queue), 2)
        assert len(ref.validator_registry) == V + 10 + 12
        assert int(ref.balances[V]) == 3 * int(MIX["new_validator_gwei"])
    finally:
        core._uninstall()
        spec.clear_caches()


# -- capacity ---------------------------------------------------------------------

@pytest.mark.parametrize("entered,devices,validators,capacity,relayouts,ends_at", [
    # no room: the first append re-lays out at the next power of two, which
    # 128 -> 129 crosses (both lists' trees a level deeper), and again at 257
    ("light", 1, 128, None, 2, 512),
    ("object", 4, 128, None, 2, 512),
    # a capacity that is no power of two crossed by the second block; a
    # power of two crossed inside the epoch, by its third
    ("light", 4, 120, 140, 1, 256),
    ("light", 1, 100, 128, 1, 256),
])
def test_an_append_past_the_capacity_re_lays_the_core_out(
        entered, devices, validators, capacity, relayouts, ends_at):
    spec, data, queue = _entry(validators=validators, epochs=2)
    ref = deserialize(data, spec.BeaconState)
    core = _core(spec, data, entered, devices, capacity)
    assert core._capacity == (capacity or validators)
    grown0, fallbacks0 = GROWN.value, FALLBACKS.value
    try:
        _drive(spec, core, ref, DepositBlockGenerator(spec, SEED, MIX, queue),
               int(spec.SLOTS_PER_EPOCH) + 3)
        assert GROWN.value - grown0 == relayouts
        assert FALLBACKS.value == fallbacks0
        assert core._capacity == ends_at <= core._reg_forest.capacity
        assert core._reg_forest.n == core._v == validators + 12 * 11
    finally:
        core._uninstall()
        spec.clear_caches()


def test_consecutive_deposit_blocks_inside_one_capacity_compile_nothing():
    """The number that says the capacity works: once every program of the
    serving path has met its shape (two epochs: blocks, slot roots, a
    boundary with a queue), two more epochs of a registry that grows by 96
    rows an epoch build no executable and ask the cache for none."""
    from jax._src import monitoring
    spec, data, queue = _entry(epochs=5)
    ref = deserialize(data, spec.BeaconState)
    core = _core(spec, data, "light", 1, capacity=1024)
    generator = DepositBlockGenerator(spec, SEED, MIX, queue)
    spe = int(spec.SLOTS_PER_EPOCH)
    built = []

    def on_duration(event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            built.append(event)

    def on_event(event, **kw):
        if event.endswith("compilation_cache/cache_hits"):
            built.append(event)
    try:
        _drive(spec, core, ref, generator, 2 * spe + 1)
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        res = core.state
        for _ in range(2 * spe):
            core.process_slots(res, int(res.slot) + 1)
            core.process_block(res, generator.block(res))
        assert built == []
        assert core._v == V + 12 * (4 * spe + 1)
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)
        core._uninstall()
        spec.clear_caches()


def test_a_checkpoint_after_deposits_resumes_to_the_live_cores_roots():
    spec, data, queue = _entry(epochs=3)
    ref = deserialize(data, spec.BeaconState)
    core = _core(spec, data, "light", 1, capacity=512)
    generator = DepositBlockGenerator(spec, SEED, MIX, queue)
    try:
        _drive(spec, core, ref, generator, int(spec.SLOTS_PER_EPOCH) + 2)
        saved = core.checkpoint_bytes()
        assert saved == serialize(ref, spec.BeaconState)
        roots = core._registry_balances_roots()
        levels = [np.asarray(level) for forest in (core._reg_forest, core._bal_forest)
                  for level in forest.levels]
        core._uninstall()
        core = ResidentCore.from_checkpoint(spec, saved, mesh=None, capacity=512)
        assert core._v == len(ref.validator_registry) and core._capacity == 512
        assert core._registry_balances_roots() == roots
        resumed = [np.asarray(level) for forest in (core._reg_forest, core._bal_forest)
                   for level in forest.levels]
        assert all((a == b).all() for a, b in zip(levels, resumed))
        _drive(spec, core, ref, generator, 2)
    finally:
        core._uninstall()
        spec.clear_caches()


def test_the_pubkey_index_is_built_at_the_first_deposit_not_at_entry():
    spec, data, queue = _entry(epochs=1)
    core = _core(spec, data, "light", 1, capacity=256)
    generator = DepositBlockGenerator(spec, SEED, MIX, queue)
    built = lambda: sum(s["name"] == "resident.registry.pubkey_index"  # noqa: E731
                        for s in telemetry.ring())
    try:
        before = built()
        res = core.state
        core.process_slots(res, int(res.slot) + 1)
        assert core._pubkey_index is None and built() == before
        core.process_block(res, generator.block(res))
        assert len(core._pubkey_index) == V + 12 and built() == before + 1
        core.process_slots(res, int(res.slot) + 1)
        core.process_block(res, generator.block(res))
        assert len(core._pubkey_index) == V + 24 and built() == before + 1
        spans = {s["name"]: s["args"] for s in telemetry.ring()}
        assert spans["resident.block.deposits"] == {
            "new_validators": 12, "top_ups": 4, "proof_pairs_hashed": 16 * 32}
        assert spans["resident.registry_write"]["appended_rows"] == 12
        assert spans["resident.forests.update"]["appended_leaves"] == 12
    finally:
        core._uninstall()
        spec.clear_caches()


# -- rejections ---------------------------------------------------------------------

def _served_state(core) -> tuple:
    cols = core._materialize_np_cols()
    return (core._v, core._capacity,
            {f: np.array(a) for f, a in cols.items()},
            {f: np.array(a) for f, a in core.mirrors.items()},
            core._pk_np.copy(), core._wc_np.copy(),
            np.asarray(core.pk_dev).copy(), np.asarray(core.wc_dev).copy(),
            dict(core._pubkey_index),
            tuple(bytes(r) for r in core._registry_balances_roots()),
            tuple(np.asarray(level).tobytes()
                  for forest in (core._reg_forest, core._bal_forest)
                  for level in forest.levels),
            (core._reg_forest.n, core._bal_forest.n))


def _same(was: tuple, now: tuple) -> bool:
    def same(a, b):
        if isinstance(a, dict) and a and isinstance(next(iter(a.values())), np.ndarray):
            return all((a[f] == b[f]).all() for f in a)
        if isinstance(a, np.ndarray):
            return a.shape == b.shape and (a == b).all()
        return a == b
    return all(same(a, b) for a, b in zip(was, now))


def _too_many_new_rows_for_the_room_left(spec, generator, state, seed):
    """A sound block whose last deposit's branch is spoiled: refused after
    fifteen deposits have run, eleven of them appends."""
    block = generator.block(state)
    block.body.deposits[-1].proof = generator.queue.proof(0)
    return block


REJECTED = list(spoiled_deposits.SPOILS) + list(spoiled_blocks.SPOILS) \
    + [_too_many_new_rows_for_the_room_left]


@pytest.fixture(scope="module", params=[1, 4], ids=["one-device", "four-devices"])
def served(request):
    """A light core one deposit block into its first epoch, its forests and
    its pubkey index built."""
    spec, data, queue = _entry(epochs=2)
    core = _core(spec, data, "light", request.param, capacity=256)
    generator = DepositBlockGenerator(spec, SEED, MIX, queue)
    core.process_slots(core.state, int(core.state.slot) + 1)
    core.process_block(core.state, generator.block(core.state))
    core.process_slots(core.state, int(core.state.slot) + 1)
    yield spec, core, generator
    core._uninstall()
    spec.clear_caches()


@pytest.mark.parametrize("spoil", REJECTED, ids=lambda f: f.__name__.lstrip("_"))
def test_a_rejected_block_leaves_length_columns_mirrors_index_and_forests_untouched(
        served, spoil):
    spec, core, generator = served
    state = core.state
    was = _served_state(core)
    block = spoil(spec, generator, state, SEED)
    kept = spoiled_deposits.keep(spec, state)
    with pytest.raises((AssertionError, IndexError)):
        core.process_block(state, block)
    spoiled_deposits.put_back(state, *kept)
    assert core._writes is None
    assert _same(was, _served_state(core))


def test_after_the_rejections_the_sound_block_is_taken(served):
    spec, core, generator = served
    state = core.state
    block = generator.block(state)
    with core.suspended():
        ref = deserialize(core.checkpoint_bytes(), spec.BeaconState)
        spec.process_block(ref, block)
    core.process_block(state, block)
    _assert_equals_object_model(spec, core, ref, int(state.slot))
