"""Blocks that change the registry on the resident core: voluntary exits and
proposer and attester slashings through the registry view, on a
checkpoint-resumed (light) core, against the unpatched object model.

The dirty-slots mix's generator (`benchmark/ops_generator.py`) builds the
blocks on the mature seed (`benchmark/seeded_mature.py`: the last slot of an
epoch past PERSISTENT_COMMITTEE_PERIOD, where an exit is valid). After
EVERY block the whole serialized state (the seven columns, the balances,
every small field) and the state root must equal the object model's; the
minimal preset runs epochs enough for the active set to shrink by the churn
limit, on one device and on four virtual ones, the mainnet preset (the
object model costs a second and a half a slot there) across one boundary.
A block the spec rejects leaves device columns, host mirrors, the kept exit
queue and both forests as they stood.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import seeded_mature, spoiled_operations  # noqa: E402
from benchmark.ops_generator import OpsBlockGenerator  # noqa: E402
from consensus_specs_tpu import telemetry  # noqa: E402
from consensus_specs_tpu.crypto import bls  # noqa: E402
from consensus_specs_tpu.models import phase0  # noqa: E402
from consensus_specs_tpu.models.phase0.resident import ResidentCore  # noqa: E402
from consensus_specs_tpu.utils.ssz.impl import (deserialize,  # noqa: E402
                                                hash_tree_root, serialize)

SEED = 2**31 + 36
MIX = json.loads((REPO / "benchmark/traffic/dirty-slots.json").read_text())
# the minimal preset has 8 slots an epoch and this test 128 validators: an
# exit a block, two proposer slashings and a double vote of two an epoch
SMALL_MIX = dict(MIX, exits_per_block=1, proposer_slashing_every=4,
                 attester_slashing_at=5, attester_slashing_indices=2)
# (preset, validators, the mix, epochs driven, devices)
CHAINS = [("minimal", 128, SMALL_MIX, 9, 1), ("minimal", 128, SMALL_MIX, 9, 4),
          ("mainnet", 2048, MIX, 1, 1)]


def _mesh(devices: int):
    if devices == 1:
        return None
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    return ServingMesh.create(devices)


def _mature(preset: str, validators: int):
    bls.bls_active = False
    spec = phase0.get_spec(preset)
    spec.clear_caches()
    return spec, seeded_mature.seeded_mature_checkpoint(spec, validators, SEED)


@pytest.fixture(params=CHAINS, ids=lambda c: f"{c[0]}-{c[4]}")
def chain(request):
    preset, validators, mix, epochs, devices = request.param
    spec, data = _mature(preset, validators)
    yield spec, data, mix, validators, epochs, devices
    spec.clear_caches()


def _active(spec, ref) -> int:
    return len(spec.get_active_validator_indices(ref, spec.get_current_epoch(ref)))


def test_light_core_follows_the_object_model_through_exits_and_slashings(chain):
    spec, data, mix, validators, epochs, devices = chain
    spe = int(spec.SLOTS_PER_EPOCH)
    ref = deserialize(data, spec.BeaconState)
    core = ResidentCore.from_checkpoint(spec, data, mesh=_mesh(devices))
    res = core.state
    assert len(res.validator_registry) == 0     # a light state: no objects
    generator = OpsBlockGenerator(spec, SEED, mix, validators)
    fallbacks = telemetry.counter("resident.block.fallbacks", always=True)
    fallbacks0 = fallbacks.value
    blocks, active = 0, []
    try:
        for _ in range(epochs * spe + 2):
            slot = int(res.slot) + 1
            core.process_slots(res, slot)
            with core.suspended():
                # the reference runs the UNPATCHED spec on a full object state
                spec.process_slots(ref, slot)
            if slot % spe == 0:
                active.append(_active(spec, ref))
            block = generator.block(res)
            if block is None:       # the slot's proposer is slashed
                with core.suspended():
                    assert ref.validator_registry[
                        spec.get_beacon_proposer_index(ref)].slashed
                continue
            core.process_block(res, block)
            with core.suspended():
                spec.process_block(ref, block)
            blocks += 1
            assert core.checkpoint_bytes() == serialize(ref, spec.BeaconState), \
                f"the states differ after the block of slot {slot}"
            assert core._state_root(res) == hash_tree_root(ref), slot
            for field, mirror in core.mirrors.items():
                assert (mirror == [getattr(v, field) for v in ref.validator_registry]).all()
        exited = sum(v.exit_epoch != spec.FAR_FUTURE_EPOCH for v in ref.validator_registry)
        slashed = sum(bool(v.slashed) for v in ref.validator_registry)
        assert fallbacks.value == fallbacks0 and slashed > 0
        assert exited >= blocks * int(mix["exits_per_block"]) > 0
        if epochs > 6:
            # the first exits leave ACTIVATION_EXIT_DELAY + 1 epochs after
            # their block; from then on the churn limit's floor an epoch
            assert active[0] == active[4] == validators
            assert [a - b for a, b in zip(active[5:], active[6:])] \
                == [4] * (len(active) - 6)
    finally:
        core._uninstall()


# -- rejections --------------------------------------------------------------------

def _served_state(core) -> tuple:
    cols = core._materialize_np_cols()
    return ({f: np.array(a) for f, a in cols.items()},
            {f: np.array(a) for f, a in core.mirrors.items()},
            None if core._exit_queue is None else list(core._exit_queue),
            tuple(bytes(r) for r in core._registry_balances_roots()),
            tuple(np.asarray(level).tobytes()
                  for forest in (core._reg_forest, core._bal_forest)
                  for level in forest.levels))


def _same(was: tuple, now: tuple) -> bool:
    return (all((was[0][f] == now[0][f]).all() for f in was[0])
            and all((was[1][f] == now[1][f]).all() for f in was[1])
            and was[2:] == now[2:])


def _exit_of_a_young_validator(spec, generator, state, seed):
    """An exit of a validator that has not been active for
    PERSISTENT_COMMITTEE_PERIOD: the mirror is told it joined late (the
    test's hand on the core, put back by the caller's comparison)."""
    block = generator.block(state)
    block.body.voluntary_exits[-1].validator_index = YOUNG
    return block


def _exit_of_a_validator_outside_the_registry(spec, generator, state, seed):
    block = generator.block(state)
    block.body.voluntary_exits[0].validator_index = 10 ** 9
    return block


def _proposer_slashing_across_two_epochs(spec, generator, state, seed):
    block = generator.block(state)
    slashing = generator.proposer_slashing(
        int(state.slot), index=int(block.body.voluntary_exits[0].validator_index))
    slashing.header_2.slot = int(state.slot) - int(spec.SLOTS_PER_EPOCH)
    block.body.proposer_slashings.append(slashing)
    return block


def _proposer_slashing_of_a_slashed_validator(spec, generator, state, seed):
    """One sound proposer slashing, twice: the second finds the
    validator slashed, after the first has written mirrors and balances."""
    block = generator.block(state)
    slashing = generator.proposer_slashing(int(state.slot))
    block.body.proposer_slashings += [slashing, slashing.copy()]
    return block


def _attester_slashing_with_nobody_to_slash(spec, generator, state, seed):
    block = generator.block(state)
    block.body.attester_slashings.append(
        generator.attester_slashing(state, block.body, indices=[]))
    return block


def _too_many_exits(spec, generator, state, seed):
    block = generator.block(state)
    while len(block.body.voluntary_exits) <= int(spec.MAX_VOLUNTARY_EXITS):
        block.body.voluntary_exits.append(block.body.voluntary_exits[0].copy())
    return block


YOUNG = 77
REJECTED = list(spoiled_operations.SPOILS) + [
    _exit_of_a_young_validator, _exit_of_a_validator_outside_the_registry,
    _proposer_slashing_across_two_epochs,
    _proposer_slashing_of_a_slashed_validator,
    _attester_slashing_with_nobody_to_slash, _too_many_exits]


@pytest.fixture(scope="module", params=[1, 4], ids=["one-device", "four-devices"])
def served(request):
    """A light core on the mature seed (mainnet preset, 2,048 validators,
    one device or four virtual ones) one block into its first epoch, its
    forests built and its exit queue known; validator YOUNG activated late."""
    spec, data = _mature("mainnet", 2048)
    ref = deserialize(data, spec.BeaconState)
    ref.validator_registry[YOUNG].activation_epoch = 100
    data = serialize(ref, spec.BeaconState)
    core = ResidentCore.from_checkpoint(spec, data, mesh=_mesh(request.param))
    generator = OpsBlockGenerator(spec, SEED, MIX, 2048)
    core.process_slots(core.state, int(core.state.slot) + 1)
    core.process_block(core.state, generator.block(core.state))
    core.process_slots(core.state, int(core.state.slot) + 1)
    yield spec, core, generator
    core._uninstall()
    spec.clear_caches()


@pytest.mark.parametrize("spoil", REJECTED, ids=lambda f: f.__name__.lstrip("_"))
def test_a_rejected_block_leaves_columns_mirrors_queue_and_forests_untouched(
        served, spoil):
    spec, core, generator = served
    state = core.state
    was = _served_state(core)
    assert was[2] is not None       # the first block's exits asked for the queue
    block = spoil(spec, generator, state, SEED)
    kept = spoiled_operations.keep(spec, state)
    with pytest.raises((AssertionError, IndexError)):
        core.process_block(state, block)
    spoiled_operations.put_back(state, *kept)
    assert core._writes is None
    assert _same(was, _served_state(core))


def test_a_deposit_the_chain_does_not_owe_is_rejected_by_the_spec(served):
    """What was refused by name until deposits were served: the block now
    reaches the spec's own check (the chain of the mature seed owes no
    deposit, so a block that carries one is invalid), which rejects it
    with everything as it stood."""
    spec, core, generator = served
    was = _served_state(core)
    checkpoint = core.checkpoint_bytes()
    block = generator.block(core.state)
    block.body.deposits.append(spec.Deposit())
    kept = spoiled_operations.keep(spec, core.state)
    with pytest.raises(AssertionError):
        core.process_block(core.state, block)
    spoiled_operations.put_back(core.state, *kept)
    assert core.checkpoint_bytes() == checkpoint and _same(was, _served_state(core))


def test_a_registry_write_outside_a_block_is_an_error(served):
    """The view's writes are a block's: `process_block` commits them to the
    device columns and the forests or rolls them back; one made outside it
    would reach neither, so it is refused."""
    spec, core, _ = served
    was = _served_state(core)
    for write in (lambda: spec.increase_balance(core.state, 3, 1),
                  lambda: spec.initiate_validator_exit(core.state, 5),
                  lambda: spec.slash_validator(core.state, 9)):
        with pytest.raises(RuntimeError, match="outside process_block"):
            write()
    core._active_idx_memo.clear()
    assert _same(was, _served_state(core))


def test_after_the_rejections_the_sound_block_is_taken(served):
    """Last on the module's core: nothing a rejected block left behind
    stands in the way of the slot's sound block, which leaves what the
    object model leaves."""
    spec, core, generator = served
    state = core.state
    block = generator.block(state)
    with core.suspended():
        ref = deserialize(core.checkpoint_bytes(), spec.BeaconState)
        spec.process_block(ref, block)
    core.process_block(state, block)
    assert core.checkpoint_bytes() == serialize(ref, spec.BeaconState)
    assert core._state_root(state) == hash_tree_root(ref)
