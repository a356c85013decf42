"""The differential chain of the resident core's block path: a
checkpoint-resumed (light) core, which holds no Validator objects, against
the unpatched object model.

The same blocks (the sync mix's generator: every committee of the slot four
before, as partial aggregates, 8 a committee) go through `spec.state_transition`
on a full object state and through `ResidentCore.from_checkpoint(...)`; every
slot's recorded root, the small fields and the columns after two boundaries
must come out byte for byte: the minimal preset on one device and on four
virtual ones, the mainnet preset (one committee of 32 a slot, so an aggregate
holds 4 members; the object model costs 1.6 s a slot there) on one.
"""
import sys
from copy import deepcopy
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark.block_generator import BlockGenerator  # noqa: E402
from consensus_specs_tpu.crypto import bls  # noqa: E402
from consensus_specs_tpu.models import phase0  # noqa: E402
from consensus_specs_tpu.models.phase0.resident import ResidentCore  # noqa: E402
from consensus_specs_tpu.testing import factories  # noqa: E402
from consensus_specs_tpu.utils.ssz.impl import hash_tree_root, serialize  # noqa: E402

SEED = 2**31 + 99
# (preset, validators, slots the genesis state is advanced before entry,
# devices): entry late in mainnet's first epoch keeps the chain to 70 blocks
CHAINS = [("minimal", 64, 2, 1), ("minimal", 64, 2, 4), ("mainnet", 2048, 60, 1)]


@pytest.fixture(params=CHAINS, ids=lambda c: f"{c[0]}-{c[3]}")
def chain(request):
    """(spec, the object state at entry, its bytes, devices)."""
    preset, validators, advance, devices = request.param
    bls.bls_active = False
    spec = phase0.get_spec(preset)
    spec.clear_caches()
    state = factories.seed_genesis_state(spec, validators)
    factories.advance_slots(spec, state, advance)
    yield spec, state, serialize(state, spec.BeaconState), devices
    spec.clear_caches()


def _mesh(devices: int):
    if devices == 1:
        return None
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    return ServingMesh.create(devices)


def test_light_core_follows_the_object_model_through_full_blocks(chain):
    """Two boundaries of blocks: every slot's recorded state root as the
    chain goes, at the end the post-state root and the whole serialized
    state (pending lists, header, mixes, votes, registry and balances
    columns), byte for byte."""
    spec, state, data, devices = chain
    spe = int(spec.SLOTS_PER_EPOCH)
    ref = deepcopy(state)
    core = ResidentCore.from_checkpoint(spec, data, mesh=_mesh(devices))
    res = core.state
    assert len(res.validator_registry) == 0     # a light state: no objects
    generator = BlockGenerator(spec, SEED, aggregates=8)
    first_epoch = int(spec.get_current_epoch(ref))
    attestations = 0
    try:
        while int(spec.get_current_epoch(ref)) < first_epoch + 2 \
                or int(ref.slot) % spe < 2:
            core.process_slots(res, int(res.slot) + 1)
            block = generator.block(res)
            core.process_block(res, block)
            attestations += len(block.body.attestations)
            with core.suspended():
                # the reference runs the UNPATCHED spec on a full object state
                spec.state_transition(ref, block)
            # the root `process_slots` has just recorded is the root of the
            # state as the block before left it: every slot's root is held
            recorded = (int(ref.slot) - 1) % len(ref.latest_state_roots)
            assert bytes(res.latest_state_roots[recorded]) \
                == bytes(ref.latest_state_roots[recorded]), \
                f"state root diverged at slot {int(ref.slot) - 1}"
        with core.suspended():
            assert hash_tree_root(ref) == core._state_root(res)
        assert core.checkpoint_bytes() == serialize(ref, spec.BeaconState)
    finally:
        core._uninstall()
    committees = spec.get_epoch_committee_count(ref, first_epoch) // spe
    assert attestations >= spe * committees * 4     # partial aggregates
    assert len(ref.previous_epoch_attestations) > spe * committees
    assert bytes(ref.latest_randao_mixes[first_epoch + 1]) != bytes(32)
