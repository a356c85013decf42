"""The block's attestation family as one pass against the loop it replaced.

`block.process_attestations_batched` checks a block's attestations in one
pass (a committee's slot, members, FFG triple and crosslink lineage once a
committee, the bitfields as one array) and writes only when every check has
passed; a family with a failing check is run again by the loop,
`process_attestation` an attestation. Held here, on an object state and on
a checkpoint-resumed ResidentCore, at the minimal preset and at
mainnet-shaped committees (both with a registry whose size does not divide,
so one block holds committees of two sizes):

- a sound family leaves the PendingAttestations the forced loop leaves,
  root for root and in order, and with BLS on the same sink tuples;
- an unsound one raises what the loop raises, where the loop raises it,
  over the lists half written as the loop leaves them, whichever
  attestation of the family is the unsound one, and
  `resident.block.attestations.sequential` counts it.

The family is run on the state as it stands, with no slot processed: the
lists it appended to are cut back after each run, so a case costs its
family and nothing else.
"""
import sys
import traceback
from copy import deepcopy
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import spoiled_blocks  # noqa: E402
from benchmark.block_generator import BlockGenerator  # noqa: E402
from benchmark.seeded_mature import seeded_mature_checkpoint  # noqa: E402
from consensus_specs_tpu import telemetry  # noqa: E402
from consensus_specs_tpu.crypto import bls  # noqa: E402
from consensus_specs_tpu.models import phase0  # noqa: E402
from consensus_specs_tpu.models.phase0 import block as block_mod  # noqa: E402
from consensus_specs_tpu.models.phase0.resident import ResidentCore  # noqa: E402
from consensus_specs_tpu.testing import factories  # noqa: E402
from consensus_specs_tpu.testing.cases.attestation import CASES  # noqa: E402
from consensus_specs_tpu.utils.ssz.impl import deserialize, hash_tree_root, serialize  # noqa: E402

SEED = 2**31 + 41
SEQUENTIAL = telemetry.counter("resident.block.attestations.sequential", always=True)


def _run(spec, state, attestations, batching: bool, sink=None):
    """The family on `state`, through the pass (`batching`) or the forced
    loop: what it handed back, or (exception type, function, line) of the
    frame that raised; the roots of what it appended to the current and the
    previous list; the loops counted. The lists are cut back to where they
    stood."""
    lists = (state.current_epoch_attestations, state.previous_epoch_attestations)
    stood = [len(lst) for lst in lists]
    loops = SEQUENTIAL.value
    block_mod.set_attestation_batching(batching)
    spec._att_verify_sink = sink
    try:
        outcome = block_mod.process_attestations_batched(spec, state, attestations)
    except (AssertionError, IndexError) as exc:
        frame = traceback.extract_tb(exc.__traceback__)[-1]
        outcome = (type(exc).__name__, frame.name, frame.lineno)
    finally:
        block_mod.set_attestation_batching(True)
        spec._att_verify_sink = None
    left = [[hash_tree_root(p) for p in lst[n:]] for lst, n in zip(lists, stood)]
    for lst, n in zip(lists, stood):
        del lst[n:]
    assert getattr(state, "_proposer_memo", None) is None
    return outcome, left, SEQUENTIAL.value - loops


# -- the states -------------------------------------------------------------------

def _minimal_state(spec):
    """100 validators: committees of 12 and 13, two slots into the third
    epoch, so that slots of both epochs are includable."""
    state = factories.seed_genesis_state(spec, 100)
    factories.advance_slots(spec, state, 2 * int(spec.SLOTS_PER_EPOCH) + 2)
    return serialize(state, spec.BeaconState)


@pytest.fixture(scope="module", params=[
    ("minimal", "object"), ("minimal", "light"),
    ("mainnet", "object"), ("mainnet", "light")], ids="-".join)
def entered(request):
    """(spec, state, includable slots of the current and the previous
    epoch): an object state or a checkpoint-resumed core's, at the minimal
    preset or at mainnet's with 16,448 validators (two committees a slot,
    of 128 and 129)."""
    preset, entry = request.param
    old = bls.bls_active
    bls.bls_active = False
    spec = phase0.get_spec(preset)
    spec.clear_caches()
    data = (_minimal_state(spec) if preset == "minimal"
            else seeded_mature_checkpoint(spec, 16_448, SEED))
    core = None
    if entry == "light":
        core = ResidentCore.from_checkpoint(spec, data, mesh=None)
        state = core.state
    else:
        state = deserialize(data, spec.BeaconState)
    slot, spe = int(state.slot), int(spec.SLOTS_PER_EPOCH)
    current = slot - int(spec.MIN_ATTESTATION_INCLUSION_DELAY)
    previous = slot // spe * spe - 1
    assert current // spe == slot // spe and slot - spe <= previous
    yield spec, state, current, previous
    if core is not None:
        core._uninstall()
    spec.clear_caches()
    bls.bls_active = old


# -- sound families: the pass leaves what the loop leaves -------------------------

def _empty(generator, state, current, previous):
    return []


def _one_attestation(generator, state, current, previous):
    return generator.attestations(state, current)[:1]


def _eight_aggregates_of_each_committee(generator, state, current, previous):
    return generator.attestations(state, current)


def _targets_of_both_epochs(generator, state, current, previous):
    one, other = (generator.attestations(state, s) for s in (current, previous))
    assert one[0].data.target_epoch == other[0].data.target_epoch + 1
    # interleaved: each list keeps its own order
    return [a for pair in zip(one, other) for a in pair]


def _two_equal_aggregates(generator, state, current, previous):
    family = generator.attestations(state, current)
    return family[:3] + [deepcopy(family[1])] + family[3:]


def _overlapping_aggregates(generator, state, current, previous):
    family = generator.attestations(state, current)
    a, b = family[0], family[1]
    b.aggregation_bitfield = bytes(
        x | y for x, y in zip(a.aggregation_bitfield, b.aggregation_bitfield))
    return family


def _committees_of_two_sizes(generator, state, current, previous):
    return [a for s in (current, previous) for a in generator.attestations(state, s)]


def _the_whole_committee_in_one(generator, state, current, previous):
    return BlockGenerator(generator.spec, SEED, 1).attestations(state, previous)


FAMILIES = [_empty, _one_attestation, _eight_aggregates_of_each_committee,
            _targets_of_both_epochs, _two_equal_aggregates,
            _overlapping_aggregates, _committees_of_two_sizes,
            _the_whole_committee_in_one]


@pytest.mark.parametrize("build", FAMILIES, ids=lambda f: f.__name__.lstrip("_"))
def test_a_sound_family_leaves_what_the_loop_leaves(entered, build):
    spec, state, current, previous = entered
    family = build(BlockGenerator(spec, SEED, 8), state, current, previous)
    distinct = len({(a.data.target_epoch, a.data.crosslink.shard) for a in family})
    if build is _committees_of_two_sizes:
        assert len({len(spec.get_crosslink_committee_array(
            state, a.data.target_epoch, a.data.crosslink.shard)) for a in family}) == 2
    by_loop, left_by_loop, loops = _run(spec, state, family, batching=False)
    assert by_loop == {"committees": len(family), "sequential": 1} and loops == 1
    assert sum(map(len, left_by_loop)) == len(family)
    by_pass, left_by_pass, loops = _run(spec, state, family, batching=True)
    assert by_pass == {"committees": distinct, "sequential": 0} and loops == 0
    assert left_by_pass == left_by_loop
    if build is _targets_of_both_epochs:
        assert all(left_by_pass)


def _plain(sink) -> list:
    return [([[bytes(k) for k in keys] for keys in sets], [bytes(m) for m in messages],
             bytes(signature), domain)
            for sets, messages, signature, domain in sink]


@pytest.mark.parametrize("build", [_eight_aggregates_of_each_committee,
                                   _targets_of_both_epochs, _overlapping_aggregates],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_with_bls_on_the_sink_receives_the_tuples_the_loop_hands_it(entered, build):
    """An installed sink defers every verify, so nothing here is verified:
    what is compared is what a verify would read, an attestation."""
    spec, state, current, previous = entered
    family = build(BlockGenerator(spec, SEED, 8), state, current, previous)
    for i, attestation in enumerate(family):
        attestation.signature = bytes([i + 1]) * 96
    bls.bls_active = True
    try:
        by_loop, by_pass = [], []
        assert _run(spec, state, family, False, sink=by_loop)[0]["sequential"] == 1
        outcome, _, loops = _run(spec, state, family, True, sink=by_pass)
        assert outcome["sequential"] == 0 and loops == 0
    finally:
        bls.bls_active = False
    assert len(by_pass) == len(family) and _plain(by_pass) == _plain(by_loop)
    # an attestation's first set holds its attesting members' keys, ascending
    registry = spec.registry_view(state)
    for (sets, _, signature, _), attestation in zip(by_pass, family):
        indices = spec.get_attesting_indices(
            state, attestation.data, attestation.aggregation_bitfield)
        assert indices == sorted(indices) and sets[1] == []
        assert [bytes(k) for k in sets[0]] == [bytes(k) for k in registry.pubkeys(indices)]
        assert signature == bytes(attestation.signature)


# -- unsound families: the loop's exception, the loop's half-written lists --------

POSITIONS = ("first", "middle", "last")


def _placed(sound: list, unsound, position: str) -> list:
    at = {"first": 0, "middle": len(sound) // 2, "last": len(sound)}[position]
    return sound[:at] + [unsound] + sound[at:]


def _rejected_alike(spec, state, family, raises_in=None):
    by_loop, left_by_loop, _ = _run(spec, state, family, batching=False)
    assert isinstance(by_loop, tuple), "the loop takes the unsound family"
    by_pass, left_by_pass, loops = _run(spec, state, family, batching=True)
    assert by_pass == by_loop and left_by_pass == left_by_loop
    assert loops == 1
    if raises_in is not None:
        assert by_pass[:2] == raises_in
    return by_pass


REJECTED = [case for case in CASES if not case.valid and not case.bls]


@pytest.mark.parametrize("position", POSITIONS)
@pytest.mark.parametrize("case", REJECTED, ids=lambda case: case.name)
def test_a_rejected_scenario_is_rejected_at_the_specs_place(case, position):
    """The scenario table's rejections (testing/cases/attestation.py), each
    among the eight sound aggregates of an includable slot of its state
    (none is includable yet where the scenario stands at the genesis slot:
    the unsound one is then the whole family)."""
    bls.bls_active = False
    spec = phase0.get_spec("minimal")
    state = factories.seed_genesis_state(spec, spec.SLOTS_PER_EPOCH * 8)
    unsound = case.build(spec, state)
    slot = int(state.slot) - int(spec.MIN_ATTESTATION_INCLUSION_DELAY)
    sound = BlockGenerator(spec, SEED, 8).attestations(state, slot) if slot >= 0 else []
    assert _run(spec, state, sound, batching=False)[0]["sequential"] == 1
    assert _run(spec, state, sound, batching=True)[0]["sequential"] == 0
    kind, _, _ = _rejected_alike(spec, state, _placed(sound, unsound, position))
    assert kind == "AssertionError"


@pytest.mark.parametrize("position", POSITIONS)
@pytest.mark.parametrize("spoil", spoiled_blocks.SPOILS, ids=lambda f: f.__name__)
def test_a_spoiled_block_of_the_sync_mix_is_rejected_at_the_specs_place(
        entered, spoil, position):
    """The four spoils of the sync cell's last comparison
    (benchmark/spoiled_blocks.py), the spoiled attestation first, in the
    middle or last among the slot's aggregates."""
    spec, state, current, _ = entered
    generator = BlockGenerator(spec, SEED, 8)
    n = len(generator.attestations(state, current))
    at = {"first": 0, "middle": n // 2, "last": n - 1}[position]
    # the block of the state's slot, of which only the family is run
    family = spoil(spec, generator, state, at).body.attestations
    assert len(family) == n >= 8
    kind, where, _ = _rejected_alike(spec, state, family)
    assert kind == "AssertionError"
    assert where == ("_attesting_members" if spoil is spoiled_blocks.bit_past_the_committees_end
                     else "process_attestation")


def _too_long(attestation):
    attestation.aggregation_bitfield += b"\x00"


def _wrong_source(attestation):
    attestation.data.source_epoch += 1


@pytest.mark.parametrize("first,second,raises_in", [
    (_wrong_source, _too_long, "process_attestation"),
    (_too_long, _wrong_source, "_attesting_members")],
    ids=["source-then-bitfield", "bitfield-then-source"])
def test_of_two_unsound_attestations_the_first_in_list_order_raises(
        entered, first, second, raises_in):
    spec, state, current, _ = entered
    family = BlockGenerator(spec, SEED, 8).attestations(state, current)
    first(family[2])
    second(family[5])
    _rejected_alike(spec, state, family, ("AssertionError", raises_in))


def test_an_index_outside_the_registry_raises_index_error(entered):
    """A committee that names no validator of the registry (no state of
    the spec has one: the committee array is bent for the test) is
    `validate_indexed_attestation`'s IndexError through the pass as through
    the loop."""
    spec, state, current, _ = entered
    family = BlockGenerator(spec, SEED, 8).attestations(state, current)
    real = spec.get_crosslink_committee_array
    outside = len(spec.registry_view(state))

    def bent(state, epoch, shard):
        members = real(state, epoch, shard).copy()
        members[-1] = outside
        return members
    spec.get_crosslink_committee_array = bent
    try:
        _rejected_alike(spec, state, family,
                        ("IndexError", "validate_indexed_attestation"))
    finally:
        spec.get_crosslink_committee_array = real
