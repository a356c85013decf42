"""Root plans == the recursive oracle, for every phase-0 container.

utils/ssz/root_plan.py compiles a container type's hash_tree_root once a
type; bulk.hash_tree_root_bulk's container and list branches and
host_tree._leaf_rows enter it. Here every container type of the phase-0
spec, under both presets, either has a plan (exactly when its fields
qualify, by a test written out here from the type predicates) whose root
and batch rows equal impl.hash_tree_root on seeded random values and on
the edges, or has none and keeps the path and the root it had.

Since PR 37 `spec.hash_tree_root` and `spec.signing_root` send every
container value but a BeaconState through bulk (helpers.hash_tree_root):
the last section holds that entry to the oracle for every container of
phase 0 and phase 1, for the block bodies the serving path meets, and
holds the list rule to the element's type (planned elements as one batch,
column-fast ones on the numpy columns, whatever the length).
"""
from random import Random

import pytest

from consensus_specs_tpu import telemetry
from consensus_specs_tpu.debug.random_value import (
    RandomizationMode, get_random_ssz_object)
from consensus_specs_tpu.models import phase0, phase1
from consensus_specs_tpu.utils.ssz import bulk, host_tree, impl
from consensus_specs_tpu.utils.ssz.root_plan import plan_for
from consensus_specs_tpu.utils.ssz.typing import (
    Bytes32, Bytes96, Container, List, Vector, is_bool_type, is_bytes_type,
    is_bytesn_type, is_container_type, is_uint_type, uint8, uint16, uint32,
    uint64, uint128, uint256)

PRESETS = ("minimal", "mainnet")
TYPE_NAMES = sorted(phase0.get_spec("minimal").container_types)
# What the issue says of the types the serving path meets.
PLANNED = {"PendingAttestation", "Attestation", "AttestationData", "Crosslink",
           "Eth1Data", "Fork", "BeaconBlockHeader", "Validator"}
UNPLANNED = {"BeaconState", "BeaconBlockBody", "HistoricalBatch",
             "IndexedAttestation"}
BYTES_EDGES = (0, 31, 32, 33, 64, 122)


@pytest.fixture(autouse=True)
def counting():
    """The host counters count only while telemetry is on."""
    telemetry.set_enabled(True)
    yield
    telemetry.set_enabled(None)


def _type(preset, name):
    return phase0.get_spec(preset).container_types[name]


def _qualifies(typ):
    """The issue's rule, from the type alone: every field a uint, a bool,
    a BytesN, `bytes`, or a container that qualifies."""
    return bool(typ.get_fields()) and all(
        is_uint_type(t) or is_bool_type(t) or is_bytesn_type(t)
        or is_bytes_type(t) or (is_container_type(t) and _qualifies(t))
        for t in typ.get_field_types())


def _planned(preset):
    return [n for n in TYPE_NAMES if _qualifies(_type(preset, n))]


def _counts():
    return bulk.HOST_PAIRS_HASHED.value, bulk.PLAN_ELEMENTS.value


def _assert_plan_equals_oracle(typ, values):
    """One by one through bulk's dispatcher and all at once through the
    batch form: the oracle's roots, and the elements counted."""
    want = [impl.hash_tree_root(v, typ) for v in values]
    assert len(values) < bulk._MEMO_MIN_CHUNKS      # under the column path
    _, elements0 = _counts()
    assert [bulk.hash_tree_root_bulk(v, typ) for v in values] == want
    assert bulk.plan_roots(plan_for(typ), values) == b"".join(want)
    assert host_tree._leaf_rows(values, typ) == b"".join(want)
    assert _counts()[1] - elements0 == 3 * len(values)


# -- which types get a plan ----------------------------------------------------

@pytest.mark.parametrize("name", TYPE_NAMES)
@pytest.mark.parametrize("preset", PRESETS)
def test_a_plan_exists_exactly_where_the_fields_qualify(preset, name):
    typ = _type(preset, name)
    assert (plan_for(typ) is not None) == _qualifies(typ)
    assert plan_for(typ) is plan_for(typ)       # compiled once a type
    if name in PLANNED | UNPLANNED:
        assert (plan_for(typ) is not None) == (name in PLANNED)


@pytest.mark.parametrize("typ", [uint64, bool, bytes, Bytes32, List[uint64],
                                 List[phase0.get_spec("minimal").Crosslink]],
                         ids=lambda t: t.__name__)
def test_what_is_no_container_has_no_plan(typ):
    assert plan_for(typ) is None


def test_a_subclass_that_adds_a_field_compiles_its_own_plan():
    """phase 1 extends containers by subclassing: the base's plan does not
    answer for the subclass, and an added list field leaves it with none."""
    base = _type("minimal", "Crosslink")
    wider = type("WiderCrosslink", (base,), {"__annotations__": {"extra": uint64}})
    listed = type("ListedCrosslink", (base,),
                  {"__annotations__": {"extra": List[uint64]}})
    assert plan_for(base) is not None and plan_for(listed) is None
    assert plan_for(wider) not in (None, plan_for(base))
    value = wider(shard=3, extra=9)
    assert bulk.hash_tree_root_bulk(value, wider) == impl.hash_tree_root(value)


# -- a plan's root is the oracle's ---------------------------------------------

@pytest.mark.parametrize("mode", ["RANDOM", "ZERO", "MAX", "ONE", "LENGTHY"])
@pytest.mark.parametrize("name", _planned("minimal"))
@pytest.mark.parametrize("preset", PRESETS)
def test_planned_roots_equal_the_oracle(preset, name, mode):
    """Seeded random values, the nested container of zeros (ZERO), every
    uint at 2^n - 1 (MAX) and bitfields of 50-100 bytes (LENGTHY)."""
    typ = _type(preset, name)
    rng = Random(f"{preset}.{name}.{mode}")
    values = [get_random_ssz_object(rng, typ, RandomizationMode[mode])
              for _ in range(5)]
    _assert_plan_equals_oracle(typ, values)


@pytest.mark.parametrize("length", BYTES_EDGES + (2048, 4097))
@pytest.mark.parametrize("name", ["PendingAttestation", "Attestation"])
@pytest.mark.parametrize("preset", PRESETS)
def test_bytes_fields_at_the_chunk_edges(preset, name, length):
    """`bytes` of no byte, one short of a chunk, a chunk, one over, two
    chunks, a mainnet committee's 122, and past the width at which bulk's
    own `bytes` root changes to the numpy level pass."""
    typ = _type(preset, name)
    rng = Random(length)
    values = []
    for fill in (None, 0, 255):
        value = get_random_ssz_object(rng, typ, RandomizationMode.RANDOM)
        for field, ftyp in typ.get_fields():
            if is_bytes_type(ftyp):
                setattr(value, field, bytes(
                    rng.randrange(256) if fill is None else fill
                    for _ in range(length)))
        values.append(value)
    _assert_plan_equals_oracle(typ, values)


@pytest.mark.parametrize("number", [0, 1, 2 ** 32, 2 ** 64 - 1])
@pytest.mark.parametrize("name", _planned("minimal"))
def test_uint64_fields_at_their_ends(name, number):
    typ = _type("mainnet", name)
    value = typ()
    for field, ftyp in typ.get_fields():
        if is_uint_type(ftyp):
            setattr(value, field, number)
    _assert_plan_equals_oracle(typ, [value, typ()])


class Mixed(Container):
    """Every leaf kind a plan encodes, at widths the spec does not use."""
    a: uint8
    b: uint16
    c: uint32
    d: uint128
    e: uint256
    flag: bool
    tag: bytes
    sig: Bytes96
    root: Bytes32
    link: phase0.get_spec("minimal").Crosslink
    n: int                  # a bare int is a uint64


@pytest.mark.parametrize("seed", range(6))
def test_every_leaf_kind_at_every_width(seed):
    rng = Random(seed)
    values = [get_random_ssz_object(rng, Mixed, RandomizationMode.RANDOM,
                                    max_list_length=200) for _ in range(4)]
    _assert_plan_equals_oracle(Mixed, values)


def test_a_plan_refuses_a_bytesn_field_of_another_length():
    """The shape is the type's: a Bytes32 field that holds 31 bytes is a
    malformed value, named, not a root of some other tree."""
    typ = _type("minimal", "Eth1Data")
    value = typ()
    value.block_hash = b"\x01" * 31
    with pytest.raises(ValueError, match="Eth1Data.block_hash holds 31"):
        bulk.hash_tree_root_bulk(value, typ)


def test_the_pairs_counted_are_the_pairs_of_the_recursive_walk():
    """A plan hashes what bulk.merkleize_few hashed field by field: the
    same pairs, counted in one bump (the walk written out here as the
    parent had it, so that the count is held to it and not to itself)."""
    def walk(obj, typ):
        if is_container_type(typ):
            return bulk.merkleize_few(
                [walk(v, t) for v, t in obj.get_typed_values()])
        if is_bytes_type(typ) or is_bytesn_type(typ):
            root = bulk.merkleize_few(impl.chunkify(bytes(obj)))
            return impl.mix_in_length(root, len(obj)) \
                if is_bytes_type(typ) else root
        return impl.hash_tree_root(obj, typ)

    rng = Random(35)
    for name in _planned("mainnet"):
        typ = _type("mainnet", name)
        value = get_random_ssz_object(rng, typ, RandomizationMode.RANDOM,
                                      max_list_length=130)
        before = _counts()[0]
        want = walk(value, typ)
        walked = _counts()[0] - before
        assert bulk.hash_tree_root_bulk(value, typ) == want
        assert _counts()[0] - before - walked == walked, name


# -- a type without a plan keeps its path --------------------------------------

@pytest.mark.parametrize("name", sorted(set(TYPE_NAMES) - set(_planned("minimal"))))
@pytest.mark.parametrize("preset", PRESETS)
def test_a_type_without_a_plan_takes_the_old_path(preset, name):
    """Walked field by field by bulk's dispatcher, as before: the oracle's
    root, and the only plan elements are its planned fields and elements."""
    typ = _type(preset, name)
    rng = Random(f"{preset}.{name}")
    value = get_random_ssz_object(rng, typ, RandomizationMode.RANDOM,
                                  max_list_length=3)
    assert plan_for(typ) is None
    assert bulk.hash_tree_root_bulk(value, typ) == impl.hash_tree_root(value, typ)


# -- the spec's entry: every container value through bulk (PR 37) ----------------

PHASES = {"phase0": phase0, "phase1": phase1}
PHASE_TYPES = [(phase, name) for phase, module in PHASES.items()
               for name in sorted(module.get_spec("minimal").container_types)]


@pytest.mark.parametrize("mode", ["RANDOM", "ZERO", "LENGTHY"])
@pytest.mark.parametrize("phase,name", PHASE_TYPES,
                         ids=[f"{p}.{n}" for p, n in PHASE_TYPES])
@pytest.mark.parametrize("preset", PRESETS)
def test_the_specs_entry_equals_the_oracle(preset, phase, name, mode):
    """`spec.hash_tree_root(x)` and `spec.signing_root(x)` of every phase-0
    and phase-1 container, a BeaconState (which keeps its path) among
    them: the oracle's, whether the type has a plan, a list field (empty
    under ZERO, of bitfields past a chunk under LENGTHY) or neither."""
    spec = PHASES[phase].get_spec(preset)
    typ = spec.container_types[name]
    rng = Random(f"{preset}.{phase}.{name}.{mode}")
    for _ in range(1 if name == "BeaconState" else 2):     # the widest value
        value = get_random_ssz_object(rng, typ, RandomizationMode[mode],
                                      max_list_length=4)
        assert spec.hash_tree_root(value) == impl.hash_tree_root(value, typ)
        assert spec.signing_root(value) == impl.signing_root(value, typ)


@pytest.mark.parametrize("typed", ["uint64", "List[uint64]", "Container"])
def test_an_explicit_type_and_a_basic_value_keep_the_oracles_path(typed):
    """No plan element is counted for what the entry hands to impl as it
    did: a basic value, and anything asked with its type spelled out."""
    spec = phase0.get_spec("minimal")
    value, typ = {"uint64": (7, None), "List[uint64]": ([1, 2, 3], List[uint64]),
                  "Container": (spec.Crosslink(shard=3), spec.Crosslink)}[typed]
    elements0 = _counts()[1]
    assert spec.hash_tree_root(value, typ) == impl.hash_tree_root(value, typ)
    assert _counts()[1] == elements0


def _attestations(spec, rng, lengths):
    out = []
    for n in lengths:
        value = get_random_ssz_object(rng, spec.Attestation,
                                      RandomizationMode.RANDOM)
        value.aggregation_bitfield = bytes(rng.randrange(256) for _ in range(n))
        value.custody_bitfield = bytes(n)
        out.append(value)
    return out


def _random(spec, rng, name, count):
    return [get_random_ssz_object(rng, spec.container_types[name],
                                  RandomizationMode.RANDOM, max_list_length=4)
            for _ in range(count)]


def _body(spec, case):
    rng = Random(f"body.{case}")
    body = spec.BeaconBlockBody()
    if case == "empty":
        return body
    body.randao_reveal = bytes(rng.randrange(256) for _ in range(96))
    # bitfields of no byte, either side of a chunk and of two, a mainnet
    # committee's 122 bytes and its eighth's 16
    body.attestations = _attestations(
        spec, rng, [(0, 1, 16, 31, 32, 33, 63, 64, 65, 122)[k % 10]
                    for k in range(int(spec.MAX_ATTESTATIONS))])
    if case == "every_list_full":
        for field, name, limit in (
                ("proposer_slashings", "ProposerSlashing", spec.MAX_PROPOSER_SLASHINGS),
                ("attester_slashings", "AttesterSlashing", spec.MAX_ATTESTER_SLASHINGS),
                ("deposits", "Deposit", spec.MAX_DEPOSITS),
                ("voluntary_exits", "VoluntaryExit", spec.MAX_VOLUNTARY_EXITS),
                ("transfers", "Transfer", spec.MAX_TRANSFERS)):
            setattr(body, field, _random(spec, rng, name, int(limit)))
    return body


@pytest.mark.parametrize("case", ["empty", "full_of_unequal_attestations",
                                  "every_list_full"])
@pytest.mark.parametrize("preset", PRESETS)
def test_a_block_bodys_root_equals_the_oracle(preset, case):
    """The bodies `process_block_header` meets: none of its lists filled,
    MAX_ATTESTATIONS attestations of unequal bitfield lengths, every
    operation list at the preset's maximum. The attestations (and the
    proposer slashings) are counted as plan elements, one batch a list."""
    spec = phase0.get_spec(preset)
    body = _body(spec, case)
    pairs0, elements0 = _counts()
    assert spec.hash_tree_root(body) == impl.hash_tree_root(body)
    planned = 1 + len(body.attestations) + len(body.proposer_slashings)
    assert _counts()[1] - elements0 >= planned          # eth1_data is the 1
    # an attestation's 18 pairs or more, and its list's tree above them
    assert _counts()[0] - pairs0 >= 19 * len(body.attestations) - 1
    block = spec.BeaconBlock(slot=3, body=body)
    assert spec.signing_root(block) == impl.signing_root(block)
    assert spec.hash_tree_root(block) == impl.hash_tree_root(block)


@pytest.mark.parametrize("roots", ["default", "both_roots_set"])
@pytest.mark.parametrize("preset", PRESETS)
def test_a_crosslinks_root_is_one_plan_element(preset, roots):
    """What `process_attestation` asks once an attestation."""
    spec = phase0.get_spec(preset)
    value = spec.Crosslink() if roots == "default" else spec.Crosslink(
        shard=1023, start_epoch=2 ** 40, end_epoch=2 ** 64 - 1,
        parent_root=b"\xa5" * 32, data_root=b"\x5a" * 32)
    pairs0, elements0 = _counts()
    assert spec.hash_tree_root(value) == impl.hash_tree_root(value)
    assert (_counts()[0] - pairs0, _counts()[1] - elements0) == (6, 1)


BATCHED = ["Attestation", "PendingAttestation", "ProposerSlashing"]
COLUMN_FAST = ["Validator", "VoluntaryExit", "Crosslink", "Transfer"]


@pytest.mark.parametrize("length", [1, 2, 3, 63, 64, 65, 130])
@pytest.mark.parametrize("kind", [List, Vector], ids=["list", "vector"])
@pytest.mark.parametrize("name", BATCHED + COLUMN_FAST)
def test_a_lists_path_follows_its_element_type_at_every_length(name, kind, length):
    """Planned elements that are not column-fast go through their plan as
    one batch, column-fast ones through the numpy columns and never one
    Python call an element (the registry is such a list): by the type, on
    both sides of the width at which bulk's memo and level pass begin."""
    spec = phase0.get_spec("mainnet")
    elem = spec.container_types[name]
    values = _random(spec, Random(f"{name}.{length}"), name, length)
    typ = List[elem] if kind is List else Vector[elem, length]
    obj = values if kind is List else Vector[elem, length](*values)
    assert bulk.container_list_is_fast(elem) == (name in COLUMN_FAST)
    elements0 = _counts()[1]
    assert bulk.hash_tree_root_bulk(obj, typ) == impl.hash_tree_root(obj, typ)
    assert _counts()[1] - elements0 == (length if name in BATCHED else 0)


def test_the_same_list_twice_hashes_the_same_pairs():
    """Nothing is kept between two roots of a list of planned elements:
    the second call hashes every pair the first did."""
    spec = phase0.get_spec("mainnet")
    values = _attestations(spec, Random(37), [16] * 128)
    typ = List[spec.Attestation]
    hashed = []
    for _ in range(2):
        pairs0 = _counts()[0]
        bulk.hash_tree_root_bulk(values, typ)
        hashed.append(_counts()[0] - pairs0)
    assert hashed[0] == hashed[1] >= 128 * 18 + 127
