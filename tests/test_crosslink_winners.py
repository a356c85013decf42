"""Crosslink winner selection over the context's candidate groups, held to
the spec's `get_winning_crosslink_and_attesting_indices` selection by
selection.

One `process_epoch` selects three times: the previous epoch before any
record moved, the current epoch after the previous epoch's updates, the
previous epoch again after both (for the deltas). The unions and balances
of the candidate groups are built once, in `build_epoch_context`; each
selection is recorded with a deep copy of the state as it then stood, and
the spec's function on that copy is what it must equal, committee by
committee: winner, sorted unslashed indices, attesting balance.
"""
from copy import deepcopy

import numpy as np
import pytest

from consensus_specs_tpu import telemetry
from consensus_specs_tpu.crypto import bls
from consensus_specs_tpu.models import phase0
from consensus_specs_tpu.models.phase0 import epoch_soa
from consensus_specs_tpu.testing import factories
from consensus_specs_tpu.utils.ssz.impl import hash_tree_root

COMMITTEE = 16              # validators a committee: eight disjoint pairs
EVERYONE = range(COMMITTEE)
ROOT_A, ROOT_B = b"\xaa" * 32, b"\xbb" * 32


@pytest.fixture
def spec():
    s = phase0.get_spec("minimal")
    old, bls.bls_active = bls.bls_active, False
    s.clear_caches()
    yield s
    s.clear_caches()
    bls.bls_active = old


def _boundary_state(spec):
    """The last slot of epoch 2 with no pending attestation: one committee
    of 16 a slot, every shard in every epoch."""
    state = factories.seed_genesis_state(
        spec, COMMITTEE * spec.SLOTS_PER_EPOCH)
    factories.advance_slots(spec, state, 3 * spec.SLOTS_PER_EPOCH - 1)
    assert spec.get_epoch_committee_count(state, 2) == spec.SHARD_COUNT
    return state


def _candidate(spec, state, lineage, epoch, shard, members, **crosslink):
    """A PendingAttestation of `members` (positions of the committee) whose
    crosslink chains on `lineage[shard]`, its fields overridden."""
    committee = spec.get_crosslink_committee(state, epoch, shard)
    assert len(committee) == COMMITTEE
    bits = np.zeros(COMMITTEE, dtype=bool)
    bits[list(members)] = True
    current = epoch == spec.get_current_epoch(state)
    parent = lineage[shard]
    link = dict(
        shard=shard, parent_root=hash_tree_root(parent),
        start_epoch=parent.end_epoch,
        end_epoch=min(epoch, parent.end_epoch + spec.MAX_EPOCHS_PER_CROSSLINK),
        data_root=spec.ZERO_HASH)
    link.update(crosslink)
    data = spec.AttestationData(
        beacon_block_root=spec.ZERO_HASH,
        source_epoch=(state.current_justified_epoch if current
                      else state.previous_justified_epoch),
        source_root=(state.current_justified_root if current
                     else state.previous_justified_root),
        target_epoch=epoch, target_root=spec.get_block_root(state, epoch),
        crosslink=spec.Crosslink(**link))
    slot = spec.get_attestation_data_slot(state, data)
    if slot < state.slot:
        data.beacon_block_root = spec.get_block_root_at_slot(state, slot)
    return spec.PendingAttestation(
        aggregation_bitfield=np.packbits(bits, bitorder="little").tobytes(),
        data=data, inclusion_delay=spec.MIN_ATTESTATION_INCLUSION_DELAY,
        proposer_index=committee[0])


def _slash(state, indices):
    for i in indices:
        state.validator_registry[i].slashed = True


# -- the cases: (spec, state, epoch, lineage) -> {shard: [candidates in list
# order]}, called for epoch 1 on the records the state holds, then for epoch 2
# on the records as the spec's previous-epoch updates leave them; a shard a
# case does not name gets one full aggregate ----------------------------------

def _case_one_full_aggregate(spec, state, epoch, lineage):
    return {}


def _case_eight_disjoint_aggregates(spec, state, epoch, lineage):
    return {s: [_candidate(spec, state, lineage, epoch, s, (2 * k, 2 * k + 1))
                for k in range(8)]
            for s in range(spec.SHARD_COUNT)}


def _case_overlapping_aggregates_of_one_group(spec, state, epoch, lineage):
    # positions 6-9 attest twice and count once
    return {3: [_candidate(spec, state, lineage, epoch, 3, range(0, 10)),
                _candidate(spec, state, lineage, epoch, 3, range(6, 16))]}


def _case_two_data_roots_the_second_heavier(spec, state, epoch, lineage):
    # the shard's first group loses: the winner comes by the general path
    def one(shard, members, root):
        return _candidate(spec, state, lineage, epoch, shard, members,
                          data_root=root)
    if epoch == 1:
        return {2: [one(2, range(0, 4), ROOT_B), one(2, range(4, 16), ROOT_A)]}
    return {5: [one(5, range(0, 5), ROOT_A), one(5, range(5, 14), ROOT_B),
                one(5, range(12, 16), ROOT_B)]}


def _case_equal_balances_the_greater_data_root_wins(spec, state, epoch, lineage):
    return {4: [_candidate(spec, state, lineage, epoch, 4, range(0, 8),
                           data_root=ROOT_A),
                _candidate(spec, state, lineage, epoch, 4, range(8, 16),
                           data_root=ROOT_B)]}


def _case_equal_balances_and_data_roots_the_first_wins(
        spec, state, epoch, lineage):
    # two records that differ in `end_epoch` alone: neither key is greater
    return {6: [_candidate(spec, state, lineage, epoch, 6, range(8, 16),
                           end_epoch=epoch),
                _candidate(spec, state, lineage, epoch, 6, range(0, 8),
                           end_epoch=epoch - 1)]}


def _case_slashed_attesters_in_the_winning_group(spec, state, epoch, lineage):
    if epoch == 1:
        _slash(state, spec.get_crosslink_committee(state, 1, 1)[2:7])
        _slash(state, spec.get_crosslink_committee(state, 2, 7)[:1])
    return {}


def _case_every_attester_of_the_heavier_group_slashed(
        spec, state, epoch, lineage):
    if epoch == 2:
        return {}
    _slash(state, spec.get_crosslink_committee(state, 1, 2)[:12])
    return {2: [_candidate(spec, state, lineage, 1, 2, range(0, 12),
                           data_root=ROOT_A),
                _candidate(spec, state, lineage, 1, 2, range(12, 16),
                           data_root=ROOT_B)]}


def _case_no_candidate_passes_and_the_default_collects_its_own(
        spec, state, epoch, lineage):
    # shard 0's record moved on, so the candidate equal to `Crosslink()`
    # no longer passes the filter, and the default (whose shard is 0) wins
    # with that candidate's attesters; shard 3's candidates name a parent
    # the state never held, and the default wins there with nobody
    default = dict(parent_root=spec.ZERO_HASH, start_epoch=0, end_epoch=0)

    def one(shard, members, **crosslink):
        return _candidate(spec, state, lineage, epoch, shard, members,
                          **crosslink)
    if epoch == 2:
        return {0: [one(0, EVERYONE, **default)], 3: []}
    state.current_crosslinks[0] = spec.Crosslink(
        shard=0, end_epoch=1, data_root=ROOT_A)
    return {0: [one(0, range(0, 3), parent_root=ROOT_B),
                one(0, range(3, 9), **default),
                one(0, range(7, 12), **default)],
            3: [one(3, EVERYONE, parent_root=ROOT_B)]}


def _case_a_parent_that_matches_only_after_the_first_update(
        spec, state, epoch, lineage):
    # the first selection sees `first` alone and writes it; the third finds
    # `first` by its own root and `child` by its parent, and the heavier wins
    if epoch == 2:
        return {5: []}
    first = _candidate(spec, state, lineage, 1, 5, range(0, 12),
                       data_root=ROOT_A)
    child = _candidate(spec, state, lineage, 1, 5, EVERYONE, data_root=ROOT_B,
                       parent_root=hash_tree_root(first.data.crosslink))
    return {5: [first, child]}


def _case_an_attestation_of_the_other_epoch_in_the_list(
        spec, state, epoch, lineage):
    # its bits lie over the other epoch's committee: no position of this
    # epoch's layout is its, so its group goes through the index arrays
    if epoch == 2:
        return {}
    return {1: [_candidate(spec, state, lineage, 2, 1, range(0, 12))]}


def _random_candidates(seed):
    """Every shard: one to three candidates in one to four aggregates each,
    random members (overlapping within and across groups), parents that
    hold, held or never held, a tenth of the registry slashed."""
    def case(spec, state, epoch, lineage):
        rng = np.random.default_rng(seed + epoch)
        if epoch == 1:
            _slash(state, rng.choice(len(state.validator_registry),
                                     len(state.validator_registry) // 10,
                                     replace=False).tolist())
        named = {}
        for shard in range(spec.SHARD_COUNT):
            candidates = []
            roots = [ROOT_A, ROOT_B, spec.ZERO_HASH]
            for k in rng.permutation(3)[:rng.integers(1, 4)]:
                link = dict(data_root=roots[k])
                if rng.random() < 0.2:
                    link["parent_root"] = ROOT_B
                elif rng.random() < 0.3:
                    link["parent_root"] = hash_tree_root(
                        state.current_crosslinks[shard])
                candidates += [
                    (rng.random(), _candidate(
                        spec, state, lineage, epoch, shard,
                        np.flatnonzero(rng.random(COMMITTEE) < rng.random()),
                        **link))
                    for _ in range(rng.integers(1, 5))]
            named[shard] = [a for _, a in sorted(candidates,
                                                 key=lambda c: c[0])]
        return named
    case.__name__ = f"_case_random_candidates_{seed}"
    return case


def _case_an_empty_epoch(spec, state, epoch, lineage):
    return {s: [] for s in range(spec.SHARD_COUNT)}


CASES = [_case_one_full_aggregate, _case_eight_disjoint_aggregates,
         _case_overlapping_aggregates_of_one_group,
         _case_two_data_roots_the_second_heavier,
         _case_equal_balances_the_greater_data_root_wins,
         _case_equal_balances_and_data_roots_the_first_wins,
         _case_slashed_attesters_in_the_winning_group,
         _case_every_attester_of_the_heavier_group_slashed,
         _case_no_candidate_passes_and_the_default_collects_its_own,
         _case_a_parent_that_matches_only_after_the_first_update,
         _case_an_attestation_of_the_other_epoch_in_the_list,
         _random_candidates(11), _random_candidates(12),
         _random_candidates(13), _case_an_empty_epoch]


def _after_previous_epoch_updates(spec, state):
    """The records as the previous epoch's half of the spec's
    process_crosslinks (:1377-1387) leaves them, on a copy."""
    after = deepcopy(state)
    epoch = spec.get_previous_epoch(after)
    for offset in range(spec.get_epoch_committee_count(after, epoch)):
        shard = (spec.get_epoch_start_shard(after, epoch) + offset) \
            % spec.SHARD_COUNT
        committee = spec.get_crosslink_committee(after, epoch, shard)
        winner, indices = spec.get_winning_crosslink_and_attesting_indices(
            after, epoch, shard)
        if 3 * spec.get_total_balance(after, indices) \
                >= 2 * spec.get_total_balance(after, committee):
            after.current_crosslinks[shard] = winner
    return list(after.current_crosslinks)


def _pending_state(spec, case):
    """The boundary state with the case's two lists. An unnamed shard of
    the current epoch chains on the record the previous epoch's update
    leaves if the shard is odd, and on the record before it if even (a
    candidate the second selection's filter drops where the record moved,
    so that the third selection finds the previous epoch's candidates)."""
    state = _boundary_state(spec)
    lineage = list(state.current_crosslinks)
    for epoch, store in ((1, state.previous_epoch_attestations),
                         (2, state.current_epoch_attestations)):
        named = case(spec, state, epoch, lineage)
        stale = list(state.current_crosslinks)
        for shard in range(spec.SHARD_COUNT):
            for a in named.get(shard, [_candidate(
                    spec, state, lineage if shard % 2 else stale,
                    epoch, shard, EVERYONE)]):
                store.append(a)
        lineage = _after_previous_epoch_updates(spec, state)
    return state


def _three_selections(spec, state, monkeypatch):
    """The three builders on `state`; every `_crosslink_winners` call as
    (epoch, the state as the call found it, what it returned)."""
    calls = []
    real = epoch_soa._crosslink_winners

    def recording(spec, state, ctx, epoch):
        calls.append((epoch, deepcopy(state), real(spec, state, ctx, epoch)))
        return calls[-1][2]
    monkeypatch.setattr(epoch_soa, "_crosslink_winners", recording)
    ctx = epoch_soa.build_epoch_context(spec, state)
    epoch_soa.process_crosslinks_vectorized(spec, state, ctx)
    facts = epoch_soa.build_epoch_inputs_np(spec, state, ctx)
    monkeypatch.undo()
    return ctx, calls, facts


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[6:])
def test_each_selection_equals_the_specs_on_the_state_as_it_stood(
        spec, monkeypatch, case):
    state = _pending_state(spec, case)
    before = deepcopy(state)
    ctx, calls, facts = _three_selections(spec, state, monkeypatch)
    assert [epoch for epoch, _, _ in calls] == [1, 2, 1]
    moved = []
    for epoch, stood, winners in calls:
        lay = ctx.layouts[epoch]
        assert len(winners) == lay.count == spec.SHARD_COUNT
        for off, (winner, group, balance) in enumerate(winners):
            shard = (lay.start_shard + off) % spec.SHARD_COUNT
            want, want_indices = \
                spec.get_winning_crosslink_and_attesting_indices(
                    stood, epoch, shard)
            assert winner == want, (epoch, shard)
            got = epoch_soa._group_indices(ctx, epoch, off, group)
            assert sorted(got.tolist()) == want_indices, (epoch, shard)
            assert balance == spec.get_total_balance(stood, want_indices)
        moved.append([hash_tree_root(c) for c in stood.current_crosslinks])
    # the third selection's facts are the epoch program's
    epoch, stood, winners = calls[2]
    in_winning = np.zeros(len(state.validator_registry), dtype=bool)
    for shard in range(spec.SHARD_COUNT):
        _, indices = spec.get_winning_crosslink_and_attesting_indices(
            stood, epoch, shard)
        in_winning[indices] = True
        assert int(facts.shard_att_balance[shard]) \
            == spec.get_total_balance(stood, indices)
        assert int(facts.shard_comm_balance[shard]) == spec.get_total_balance(
            stood, spec.get_crosslink_committee(stood, epoch, shard))
    assert np.array_equal(facts.in_winning, in_winning)
    # the records the selections stood on moved between them when a case
    # has a winner with two thirds, and the whole transition is the spec's
    if case is not _case_an_empty_epoch:
        assert moved[0] != moved[2]
    if case is _case_an_attestation_of_the_other_epoch_in_the_list:
        first = ctx.winner_groups[1].first[
            (1 - ctx.layouts[1].start_shard) % spec.SHARD_COUNT]
        assert first.indices is not None and len(first.indices) == 12
    want, got = deepcopy(before), deepcopy(before)
    spec.process_epoch(want)
    epoch_soa.process_epoch_soa(spec, got)
    assert hash_tree_root(want) == hash_tree_root(got)


def test_the_case_that_needs_the_update_selects_differently_the_third_time(
        spec, monkeypatch):
    state = _pending_state(
        spec, _case_a_parent_that_matches_only_after_the_first_update)
    ctx, calls, _ = _three_selections(spec, state, monkeypatch)
    off = (5 - ctx.layouts[1].start_shard) % spec.SHARD_COUNT
    assert bytes(calls[0][2][off][0].data_root) == ROOT_A
    assert bytes(calls[2][2][off][0].data_root) == ROOT_B


def test_the_group_facts_are_built_once_and_every_selection_reads_them(
        spec, monkeypatch):
    """No union and no balance sum inside a selection: the counter's delta
    over each `distill.winners` is 0, `distill.winner_groups` formed every
    distinct (epoch, shard, crosslink) of the two lists and computed a
    union a group, and the third selection hands back the very groups the
    first did."""
    state = _pending_state(spec, _case_two_data_roots_the_second_heavier)
    distinct = {(epoch, hash_tree_root(a.data.crosslink))
                for epoch, atts in ((1, state.previous_epoch_attestations),
                                    (2, state.current_epoch_attestations))
                for a in atts}
    assert len(distinct) == 2 * spec.SHARD_COUNT + 2
    assert len(state.current_epoch_attestations) == spec.SHARD_COUNT + 2
    unions = telemetry.counter("distill.winner_unions_computed")
    try:
        telemetry.set_enabled(True)
        telemetry.reset()
        ctx, calls, _ = _three_selections(spec, state, monkeypatch)
        records = telemetry.ring()
    finally:
        telemetry.set_enabled(None)
    (formed,) = [r for r in records if r["name"] == "distill.winner_groups"]
    assert formed["args"] == {"groups": len(distinct),
                              "multi_group_committees": 2}
    assert unions.value == len(distinct)
    passes = [r for r in records if r["name"] == "distill.winners"]
    assert [r["args"] for r in passes] == [{"unions_computed": 0}] * 3
    groups = ctx.winner_groups[1]
    kept = [groups.first[off] for off in range(spec.SHARD_COUNT)] \
        + [g for more in groups.more.values() for g in more]
    for _, _, winners in (calls[0], calls[2]):
        assert all(any(group is k for k in kept)
                   for _, group, _ in winners if group is not None)
    assert sum(group is not None and group.indices is not None
               for _, group, _ in calls[0][2]) == 1
