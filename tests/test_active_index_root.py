"""The boundary's active-index root as the resident core computes it: a
device tree build by the balances forest's programs over the zero-filled
index column (ResidentCore._active_index_root), on one device and on a
serving mesh of four.

  1. parity: bit-equal to bulk.uint64_list_root_from_column (the host path
     every other owner of a registry keeps) and to the recursive
     hash_tree_root(list, List[uint64]), for every shape of active set a
     registry of capacity V can have, through the spec method the final
     updates call;
  2. the serving loop: three boundaries over which the active set moves,
     against the object model, with what the spans note and no compile;
  3. a state the core does not hold goes to the saved host function.
"""
from copy import deepcopy

import numpy as np
import pytest

from consensus_specs_tpu import telemetry
from consensus_specs_tpu.crypto import bls
from consensus_specs_tpu.models import phase0
from consensus_specs_tpu.models.phase0.resident import ResidentCore
from consensus_specs_tpu.telemetry import watchdog
from consensus_specs_tpu.testing import factories
from consensus_specs_tpu.testing.states import _active_registry
from consensus_specs_tpu.utils.merkle import next_power_of_two
from consensus_specs_tpu.utils.ssz import bulk
from consensus_specs_tpu.utils.ssz.impl import hash_tree_root
from consensus_specs_tpu.utils.ssz.typing import List as SSZList, uint64

LAYOUTS = (1, 4)        # devices: no mesh, ServingMesh.create(4)
CAPACITIES = (5, 256, 8194)


@pytest.fixture
def spec():
    s = phase0.get_spec("minimal")
    bls.bls_active = False
    s.clear_caches()
    yield s
    s.clear_caches()


@pytest.fixture
def spans():
    """Telemetry pinned on and emptied; returns a reader of the ring."""
    telemetry.set_enabled(True)
    telemetry.reset()
    yield telemetry.ring
    telemetry.set_enabled(None)


def _mesh(devices):
    if devices == 1:
        return None
    import jax
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    if len(jax.devices()) < devices:
        pytest.skip(f"needs {devices} devices, have {len(jax.devices())}")
    return ServingMesh.create(devices)


def _lanes(leaves: int, devices: int) -> int:
    """Pair-hash lanes of one forest build: the one-device tree pads each
    odd level by one node, the mesh's materialises its power of two."""
    if devices > 1:
        return next_power_of_two(leaves) - 1
    total = 0
    while leaves > 1:
        leaves = (leaves + 1) // 2
        total += leaves
    return total


# -- 1. parity ---------------------------------------------------------------

def _active_counts(V):
    """0, 1, 3, 4, 5, V - 1, V, and round every power of two: 2^k -> 2^k + 1
    is where the chunk count ceil(n / 4) leaves a power of two behind, so
    the list's root moves one level up the tree."""
    counts = {0, 1, 3, 4, 5, V - 1, V}
    k = 2
    while (1 << k) - 1 <= V:
        counts.update(((1 << k) - 1, 1 << k, (1 << k) + 1))
        k += 1
    return sorted(n for n in counts if 0 <= n <= V)


def _active_set(V, n, holes):
    """n ascending indices of a registry of V: the first n, or (`holes`)
    what is left when V - n validators spread over the registry have exited
    (the first and the last stay wherever n allows)."""
    if not holes:
        return np.arange(n)
    gone = np.unique(np.linspace(1, V - 2, V - n).round().astype(np.int64))
    rest = np.setdiff1d(np.arange(V), gone)
    return rest[:n]     # linspace may round two exits onto one index


PARITY_CASES = [
    pytest.param(V, n, holes, devices,
                 id=f"V{V}-n{n}-{'holes' if holes else 'first'}-dev{devices}")
    for V in CAPACITIES for devices in LAYOUTS for n in _active_counts(V)
    for holes in (False, True) if not holes or 0 < n < V]

@pytest.fixture(scope="module")
def parity_core():
    """One resident core a capacity and a layout, kept for the module: a
    case only rewrites the two mirror columns the active set is read from."""
    spec = phase0.get_spec("minimal")
    cores = {}

    def get(V, devices):
        if (V, devices) not in cores:
            state = spec.BeaconState(genesis_time=0, deposit_index=V)
            state.balances = [spec.MAX_EFFECTIVE_BALANCE] * V
            state.validator_registry = _active_registry(
                spec, V, lambda i: i.to_bytes(48, "little"))
            core = ResidentCore(spec, state, mesh=_mesh(devices))
            core._uninstall()       # installed a case at a time, below
            cores[V, devices] = core
        return cores[V, devices]
    return get


@pytest.mark.parametrize("V,n,holes,devices", PARITY_CASES)
def test_device_root_equals_the_host_paths(spec, spans, parity_core, V, n,
                                           holes, devices):
    core = parity_core(V, devices)
    active = _active_set(V, n, holes)
    assert len(active) == n and (np.diff(active) > 0).all()
    far = np.uint64(int(spec.FAR_FUTURE_EPOCH))
    core.mirrors["activation_epoch"][:] = far
    core.mirrors["activation_epoch"][active] = 0
    core.mirrors["exit_epoch"][:] = far
    core._active_idx_memo.clear()
    lanes = telemetry.counter("merkle.forest.pair_lanes")
    hashed = telemetry.counter("merkle.host.pairs_hashed")
    core._install()
    try:
        lanes0, hashed0 = lanes.value, hashed.value
        got = spec.compute_active_index_root(core.state, 3)
        built, on_host = lanes.value - lanes0, hashed.value - hashed0
    finally:
        core._uninstall()
    assert got == bulk.uint64_list_root_from_column(active.astype(np.uint64))
    assert got == hash_tree_root([int(i) for i in active], SSZList[uint64])
    # the device built the tree, whatever n: the host hashed no pair of it
    assert on_host == 0
    assert built == _lanes(-(-V // 4), devices)


# -- 2. the serving loop -------------------------------------------------------

@pytest.mark.parametrize("devices", LAYOUTS)
def test_three_boundaries_over_a_moving_active_set(spec, spans, devices):
    """33 validators, the last not yet activated, one in the middle whose
    balance has fallen to the ejection balance while its effective balance
    has not yet followed. The first boundary activates the one (the root it
    writes holds all 33: the top of the tree), the second ejects the other
    (32 with a gap: 8 of 9 chunks, one level down), the third changes
    nothing. Each root equals the object model's."""
    spe = spec.SLOTS_PER_EPOCH
    V, late, poor = 33, 32, 7
    state = factories.seed_genesis_state(spec, V)
    state.validator_registry[late].activation_eligibility_epoch = \
        state.validator_registry[late].activation_epoch = \
        spec.FAR_FUTURE_EPOCH
    state.balances[poor] = spec.EJECTION_BALANCE
    ref, res = deepcopy(state), deepcopy(state)
    assert watchdog.install_compile_listener()
    compiles = telemetry.counter("jax.backend_compiles")
    core = ResidentCore(spec, res, mesh=_mesh(devices))
    written, compiled = [], []
    try:
        for boundary in (1, 2, 3):
            with core.suspended():
                spec.process_slots(ref, boundary * spe)
            before = compiles.value
            core.process_slots(res, boundary * spe)
            compiled.append(compiles.value - before)
            assert list(res.latest_active_index_roots) \
                == list(ref.latest_active_index_roots)
            position = (boundary + spec.ACTIVATION_EXIT_DELAY) \
                % spec.LATEST_ACTIVE_INDEX_ROOTS_LENGTH
            written.append(bytes(res.latest_active_index_roots[position]))
            assert hash_tree_root(ref) == core._state_root(res)
        records = spans()
    finally:
        core.exit()
    everyone = list(range(V))
    assert written == [
        hash_tree_root(active, SSZList[uint64]) for active in (
            everyone, [i for i in everyone if i != poor],
            [i for i in everyone if i != poor])]
    assert len(set(written)) == 2
    assert ref.validator_registry[late].activation_epoch == 1 + 4
    assert ref.validator_registry[poor].exit_epoch == 2 + 4
    # nothing compiles once the first boundary has run: the active count
    # is no shape of any program, the level fetched is a transfer
    assert compiled[1:] == [0, 0]

    final = [r for r in records
             if r["name"] == "resident.refresh.final_updates"]
    index_lanes = _lanes(-(-V // 4), devices)
    assert [r["args"] for r in final] == [
        {"index_root_lanes": index_lanes, "host_pairs_hashed": 0}] * 3
    # the third build stays outside `resident.forests`, whose lanes are the
    # two forests' and nothing else, as before: the forests are dispatched
    # before the final updates open (their lanes are counted there and
    # carried to the wait), the index tree is built inside them
    forests = [r for r in records if r["name"] == "resident.forests"
               and r["parent"] == "resident.refresh"]
    assert [r["args"]["pair_lanes"] for r in forests] \
        == [_lanes(V, devices) + index_lanes] * 3
    assert all(f["ts"] >= r["ts"] + r["dur"] for f, r in zip(forests, final))
    dispatched = [r for r in records
                  if r["name"] == "resident.refresh.forests_dispatch"]
    assert len(dispatched) == 3
    assert all(d["ts"] + d["dur"] <= r["ts"]
               for d, r in zip(dispatched, final))
    assert all(f["args"]["ahead_ms"] >= r["dur"] * 1e3
               for f, r in zip(forests, final))


# -- 3. a state the core does not hold -----------------------------------------

@pytest.mark.parametrize("devices", LAYOUTS)
def test_another_state_goes_through_the_saved_host_function(spec, spans,
                                                            devices):
    state = factories.seed_genesis_state(spec, 4 * spec.SLOTS_PER_EPOCH)
    res, other = deepcopy(state), deepcopy(state)
    epoch = spec.slot_to_epoch(other.slot)
    for i in (3, 4, 20):
        other.validator_registry[i].exit_epoch = epoch
    lanes = telemetry.counter("merkle.forest.pair_lanes")
    hashed = telemetry.counter("merkle.host.pairs_hashed")
    core = ResidentCore(spec, res, mesh=_mesh(devices))
    try:
        saved = core._saved_methods["compute_active_index_root"]
        lanes0, hashed0 = lanes.value, hashed.value
        got = spec.compute_active_index_root(other, epoch)
        assert lanes.value == lanes0 and hashed.value > hashed0
        assert got == saved(other, epoch)
        assert got == hash_tree_root(
            [i for i in range(len(other.validator_registry))
             if i not in (3, 4, 20)], SSZList[uint64])
        # the resident state is answered by the device, from the mirrors
        assert spec.compute_active_index_root(res, epoch) == hash_tree_root(
            list(range(len(res.validator_registry))), SSZList[uint64])
        assert lanes.value > lanes0
    finally:
        core.exit()
