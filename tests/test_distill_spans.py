"""Distillation from inside: the spans and work counts beneath
`resident.stage.distill` (and, the builders being the same code, beneath
`process_epoch_soa`'s `epoch.distill`).

The builders of `epoch_soa` open their own spans (`distill.context` over
`.layouts`, `.participants`, `.crosslink_roots`, `.winner_groups`;
`distill.crosslinks`;
`distill.inputs` over `.flags`, `.inclusion`; `distill.winners` and
`distill.committee_balances` once a pass), the core opens
`resident.stage.distill.place`, and `resident.stage.distill` notes
`pending_rows` and `crosslink_roots_hashed_singly`: sixteen records a
boundary, none in a slot or a block, none at all with telemetry off.
"""
import sys
from collections import Counter
from copy import deepcopy
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import reference  # noqa: E402
from benchmark.block_generator import BlockGenerator  # noqa: E402
from consensus_specs_tpu import telemetry  # noqa: E402
from consensus_specs_tpu.models.phase0 import epoch_soa  # noqa: E402
from consensus_specs_tpu.models.phase0.resident import ResidentCore  # noqa: E402
from consensus_specs_tpu.testing import factories  # noqa: E402
from consensus_specs_tpu.utils.ssz.impl import serialize  # noqa: E402
from test_resident import (  # noqa: E402,F401  (fixtures)
    _children, _descendants, spans, spec)

SEED = 2**31 + 38
DISTILL = "resident.stage.distill"
PLACE = "resident.stage.distill.place"
CONTEXT_PARTS = ["distill.layouts", "distill.participants",
                 "distill.crosslink_roots", "distill.winner_groups"]
# what a boundary closes that it did not close before, with the count of each
NEW_RECORDS = {
    "distill.context": 1, **dict.fromkeys(CONTEXT_PARTS, 1),
    "distill.crosslinks": 1, "distill.inputs": 1,
    "distill.inputs.flags": 1, "distill.inputs.inclusion": 1,
    "distill.winners": 3, "distill.committee_balances": 3, PLACE: 1}


def _named(records, name):
    return [r for r in records if r["name"] == name]


def _replay_slots(spec, core, state, until, generate=True):
    """The replay mix at a test's size: before each slot the generator
    appends what the slot's block would have left (the epoch's layout built
    at the epoch's first slot, as `benchmark/deployment.py` builds it), then
    the core takes the slot. Without `generate` the core takes bare slots."""
    spe = int(spec.SLOTS_PER_EPOCH)
    lay = None
    while int(state.slot) < until:
        epoch = spec.get_current_epoch(state)
        if generate:
            if lay is None or lay.epoch != epoch:
                lay = epoch_soa._epoch_layout(spec, state, core.mirrors, epoch)
            if int(state.slot) > epoch * spe:
                reference.append_slot_attestations(
                    spec, state, lay, int(state.slot) - 1, epoch,
                    (state.current_justified_epoch,
                     state.current_justified_root),
                    state.current_epoch_attestations)
        core.process_slots(state, int(state.slot) + 1)


def _replay_core(spec):
    state = factories.seed_genesis_state(spec, 8 * spec.SLOTS_PER_EPOCH)
    return ResidentCore(spec, state, mesh=None), state


def _sync_slots(spec, core, generator, until):
    state = core.state
    while int(state.slot) < until:
        core.process_slots(state, int(state.slot) + 1)
        core.process_block(state, generator.block(state))


def _sync_core(spec):
    state = factories.seed_genesis_state(spec, 64)
    factories.advance_slots(spec, state, 2)
    return ResidentCore.from_checkpoint(
        spec, serialize(state, spec.BeaconState), mesh=None)


# -- the tree ------------------------------------------------------------------

def test_distill_is_cut_into_its_parts_in_order(spec, spans):
    """`resident.stage.distill` has four children in order and
    `distill.context` four; every part lies inside its parent by `ts` and
    `dur`, siblings do not overlap and sum to no more than the parent; the
    winner and committee-balance passes close three times under the parents
    the table names; every record carries the boundary slot's `req`."""
    spe = int(spec.SLOTS_PER_EPOCH)
    core, state = _replay_core(spec)
    try:
        _replay_slots(spec, core, state, 2 * spe)
        records = spans()
    finally:
        core.exit()
    distills = _named(records, DISTILL)
    assert len(distills) == 2
    for distill in distills:
        kids = _children(records, distill)
        assert [k["name"] for k in kids] == [
            "distill.context", "distill.crosslinks", "distill.inputs", PLACE]
        context, crosslinks, inputs, _ = kids
        assert [k["name"] for k in _children(records, context)] \
            == CONTEXT_PARTS
        assert [k["name"] for k in _children(records, crosslinks)] == [
            "distill.committee_balances", "distill.winners"] * 2
        assert [k["name"] for k in _children(records, inputs)] == [
            "distill.inputs.flags", "distill.inputs.inclusion",
            "distill.committee_balances", "distill.winners"]
        for parent in (distill, context, crosslinks, inputs):
            parts = _children(records, parent)
            assert sum(p["dur"] for p in parts) <= parent["dur"]
            for p in parts:
                assert parent["ts"] <= p["ts"]
                assert p["ts"] + p["dur"] <= parent["ts"] + parent["dur"]
                assert p["req"] == distill["req"] and p["req"] % spe == spe - 1
            assert all(a["ts"] + a["dur"] <= b["ts"]
                       for a, b in zip(parts, parts[1:]))


def test_a_boundary_writes_sixteen_records_more_and_a_slot_and_a_block_none(
        spec, spans):
    """The whole count: a non-boundary slot's tree is 7 records and a
    block's 8 (7 before the deposit list got a span of its own); the boundary
    slot's was 15 and is 30,
    31 since the refresh dispatches its forests in a span of its own, and 32
    since the candidate crosslink groups are formed in one."""
    spe = int(spec.SLOTS_PER_EPOCH)
    core = _sync_core(spec)
    generator = BlockGenerator(spec, SEED, aggregates=8)
    try:
        _sync_slots(spec, core, generator, 2 * spe)
        records = spans()
    finally:
        core._uninstall()

    def tree(root):
        return [root] + _descendants(records, root)
    roots = [r for r in records if r["parent_id"] == 0]
    sizes = {name: {len(tree(r)) for r in roots if r["name"] == name}
             for name in ("resident.slot", "resident.boundary_slot",
                          "resident.block")}
    # a resumed core's first slot root builds the forests under its own span
    assert sizes["resident.slot"] == {7, 8}
    assert sizes["resident.block"] == {8}
    assert sizes["resident.boundary_slot"] == {32}
    assert sum(NEW_RECORDS.values()) == 16
    for root in roots:
        new = Counter(r["name"] for r in tree(root)
                      if r["name"] in NEW_RECORDS)
        assert new == (NEW_RECORDS if root["name"] == "resident.boundary_slot"
                       else {})


# -- the notes -----------------------------------------------------------------

@pytest.mark.parametrize("mix", ["replay", "sync"])
def test_distill_notes_its_rows_and_hashes_no_crosslink_root_singly(
        spec, spans, mix):
    """`pending_rows` is the two lists' lengths as the boundary found them,
    `active_validators` the active set the spec would have counted;
    the context's batch holds every Crosslink root the three passes ask, on
    the replay mix and on the sync mix; the permutations are in the spec's
    cache (the generator's layout, or the epoch's first block, put them
    there), so no shuffle is computed inside distill."""
    spe = int(spec.SLOTS_PER_EPOCH)
    if mix == "replay":
        core, state = _replay_core(spec)
    else:
        core = _sync_core(spec)
        state = core.state
        generator = BlockGenerator(spec, SEED, aggregates=8)
    lengths, active = [], []
    try:
        for epoch in (1, 2, 3):
            if mix == "replay":
                _replay_slots(spec, core, state, epoch * spe - 1)
            else:
                _sync_slots(spec, core, generator, epoch * spe - 1)
                core.process_slots(state, epoch * spe - 1)
            lengths.append(sum(
                len(spec.get_matching_source_attestations(state, e))
                for e in (spec.get_previous_epoch(state),
                          spec.get_current_epoch(state))))
            active.append(len(spec.get_active_validator_indices(
                state, spec.get_current_epoch(state))))
            core.process_slots(state, epoch * spe)
            if mix == "sync":
                core.process_block(state, generator.block(state))
        records = spans()
    finally:
        core.exit() if mix == "replay" else core._uninstall()
    notes = [r["args"] for r in _named(records, DISTILL)]
    assert [n["pending_rows"] for n in notes] == lengths
    assert min(lengths) > 0
    assert [n["active_validators"] for n in notes] == active
    assert [n["crosslink_roots_hashed_singly"] for n in notes] == [0, 0, 0]
    shuffles = [r["args"]["shuffles"] for r in _named(records,
                                                      "distill.layouts")]
    assert shuffles == [0, 0, 0]


def test_a_core_that_takes_bare_slots_shuffles_inside_distill(spec, spans):
    """No generator and no block: nobody asked for the epoch's committees,
    so the boundary's layout is the first to need the permutation, and the
    counter `shuffle.permutations_computed` moves by that one; a context
    built again on the same state finds both in the cache."""
    spe = int(spec.SLOTS_PER_EPOCH)
    core, state = _replay_core(spec)
    computed = telemetry.counter("shuffle.permutations_computed")
    try:
        before = computed.value
        _replay_slots(spec, core, state, 2 * spe, generate=False)
        served = computed.value
        for _ in range(2):      # epoch 2's own permutation, then none
            epoch_soa.build_epoch_context(spec, state, dict(core.mirrors))
        records = spans()
    finally:
        core.exit()
    shuffles = [r["args"]["shuffles"] for r in _named(records,
                                                      "distill.layouts")]
    # epoch 0's boundary builds one layout, every later one two (one cached)
    assert shuffles == [1, 1, 1, 0]
    assert served - before == 2 and computed.value - served == 1


def test_a_crosslink_the_prefill_was_not_given_is_counted(
        spec, spans, monkeypatch):
    """The waste count of the root cache: drop the default Crosslink from
    the batch and the winner passes hash it once, by `_crosslink_root`."""
    spe = int(spec.SLOTS_PER_EPOCH)
    real = epoch_soa._prefill_crosslink_roots
    default = (0, 0, 0, bytes(32), bytes(32))

    def prefill_without_the_default(spec, ctx, state):
        att_keys = real(spec, ctx, state)
        del ctx.cl_roots[default]
        return att_keys
    monkeypatch.setattr(epoch_soa, "_prefill_crosslink_roots",
                        prefill_without_the_default)
    core, state = _replay_core(spec)
    try:
        _replay_slots(spec, core, state, spe)
        records = spans()
    finally:
        core.exit()
    assert [r["args"]["crosslink_roots_hashed_singly"]
            for r in _named(records, DISTILL)] == [1]


# -- the same builders under process_epoch_soa ---------------------------------

def test_epoch_distill_gets_the_same_children(spec, spans):
    state = factories.seed_genesis_state(spec, 2 * spec.SLOTS_PER_EPOCH)
    factories.advance_slots(spec, state, 2)
    epoch_soa.process_epoch_soa(spec, deepcopy(state))
    records = spans()
    cols, inputs = _named(records, "epoch.distill")
    assert _children(records, cols) == []
    kids = _children(records, inputs)
    assert [k["name"] for k in kids] == [
        "distill.context", "distill.crosslinks", "distill.inputs"]
    assert [k["name"] for k in _children(records, kids[0])] == CONTEXT_PARTS
    assert Counter(r["name"] for r in records if r["name"] in NEW_RECORDS) \
        == {k: v for k, v in NEW_RECORDS.items() if k != PLACE}


# -- telemetry off ---------------------------------------------------------------

def _build(spec, state):
    """The three builders on a copy: (facts, the crosslinks written)."""
    state = deepcopy(state)
    spec.clear_caches()
    ctx = epoch_soa.build_epoch_context(spec, state)
    epoch_soa.process_crosslinks_vectorized(spec, state, ctx)
    facts = epoch_soa.build_epoch_inputs_np(spec, state, ctx)
    return facts, [serialize(c, spec.Crosslink)
                   for c in list(state.current_crosslinks)
                   + list(state.previous_crosslinks)]


def test_with_telemetry_off_no_record_and_the_same_facts(spec):
    """The builders return, field for field, what they return with the
    spans on, and write nothing."""
    spe = int(spec.SLOTS_PER_EPOCH)
    state = factories.seed_genesis_state(spec, 8 * spe)
    core = ResidentCore(spec, state, mesh=None)
    try:
        _replay_slots(spec, core, state, 2 * spe - 1)
    finally:
        core.exit()
    assert len(state.previous_epoch_attestations) > 0
    try:
        telemetry.set_enabled(True)
        telemetry.reset()
        facts_on, crosslinks_on = _build(spec, state)
        assert Counter(r["name"] for r in telemetry.ring()) \
            == {k: v for k, v in NEW_RECORDS.items() if k != PLACE}
        telemetry.set_enabled(False)
        telemetry.reset()
        facts_off, crosslinks_off = _build(spec, state)
        assert telemetry.ring() == []
        assert telemetry.snapshot()["spans"] == {}
    finally:
        telemetry.set_enabled(None)
    assert crosslinks_on == crosslinks_off
    assert facts_on._fields == facts_off._fields
    for field in facts_on._fields:
        on, off = getattr(facts_on, field), getattr(facts_off, field)
        assert np.asarray(on).dtype == np.asarray(off).dtype, field
        assert np.array_equal(on, off), field
    assert facts_on.prev_src.any() and facts_on.in_winning.any()
