"""Differential tests: SoA device epoch transition vs. the object-model spec.

Every scenario runs `spec.process_epoch` (reference-semantics Python) and
`process_epoch_soa` (jitted [V]-array program) on deep copies of the same
state and requires identical post-state hash_tree_root — the strongest
whole-state equality the reference itself uses (ssz_typing __eq__ by root).
"""
import random
from copy import deepcopy

import numpy as np
import pytest

from consensus_specs_tpu.crypto import bls
from consensus_specs_tpu.models import phase0
from consensus_specs_tpu.models.phase0.epoch_soa import (
    build_epoch_context, build_epoch_inputs_np, process_epoch_soa,
    proposer_table_capacity)
from consensus_specs_tpu.testing.cases.finality import attested_epoch
from consensus_specs_tpu.testing.factories import (
    advance_epoch as next_epoch,
    seed_genesis_state as create_genesis_state,
    transition_with_empty_block as apply_empty_block,
)
from consensus_specs_tpu.utils.ssz.impl import hash_tree_root


@pytest.fixture(scope="module")
def spec():
    return phase0.get_spec("minimal")


@pytest.fixture(autouse=True)
def _bls_off():
    old = bls.bls_active
    bls.bls_active = False
    yield
    bls.bls_active = old


def assert_same_epoch_transition(spec, state):
    """Run both epoch paths at the end-of-epoch boundary and diff the states."""
    # process_epoch fires inside process_slot when (slot+1) % SLOTS_PER_EPOCH == 0;
    # align to the boundary, then call the sub-transition directly on copies.
    if (state.slot + 1) % spec.SLOTS_PER_EPOCH != 0:
        spec.process_slots(
            state, state.slot + spec.SLOTS_PER_EPOCH - 1 - state.slot % spec.SLOTS_PER_EPOCH)
    ref, soa = deepcopy(state), deepcopy(state)
    spec.process_epoch(ref)
    process_epoch_soa(spec, soa)
    assert hash_tree_root(ref) == hash_tree_root(soa)
    return ref


def test_genesis_epoch_transition(spec):
    state = create_genesis_state(spec, spec.SLOTS_PER_EPOCH * 8)
    assert_same_epoch_transition(spec, state)


def test_empty_epochs(spec):
    state = create_genesis_state(spec, spec.SLOTS_PER_EPOCH * 8)
    for _ in range(3):
        next_epoch(spec, state)
        apply_empty_block(spec, state)
    assert_same_epoch_transition(spec, state)


def test_epochs_with_attestations(spec):
    state = create_genesis_state(spec, spec.SLOTS_PER_EPOCH * 8)
    next_epoch(spec, state)
    apply_empty_block(spec, state)
    for fill_cur, fill_prev in ((True, False), (True, True), (False, True)):
        _, _, state = attested_epoch(spec, state, current=fill_cur, previous=fill_prev)
        assert_same_epoch_transition(spec, deepcopy(state))


def test_justification_and_finalization_parity(spec):
    """Drive enough attested epochs that justification + finalization fire."""
    state = create_genesis_state(spec, spec.SLOTS_PER_EPOCH * 8)
    next_epoch(spec, state)
    apply_empty_block(spec, state)
    for _ in range(4):
        _, _, state = attested_epoch(spec, state, current=True)
        assert_same_epoch_transition(spec, deepcopy(state))
    assert state.finalized_epoch > 0  # the scenario actually exercises finality


def test_slashed_and_ejected_validators(spec):
    state = create_genesis_state(spec, spec.SLOTS_PER_EPOCH * 8)
    next_epoch(spec, state)
    apply_empty_block(spec, state)
    _, _, state = attested_epoch(spec, state, current=True, previous=True)

    rng = random.Random(1234)
    current_epoch = spec.get_current_epoch(state)
    # Slash a few validators the way slash_validator would leave them
    for i in rng.sample(range(len(state.validator_registry)), 4):
        v = state.validator_registry[i]
        v.slashed = True
        v.exit_epoch = current_epoch + 1
        v.withdrawable_epoch = current_epoch + spec.LATEST_SLASHED_EXIT_LENGTH
        state.latest_slashed_balances[current_epoch % spec.LATEST_SLASHED_EXIT_LENGTH] += \
            v.effective_balance
    # One validator mid-way to the slashing-penalty epoch
    v = state.validator_registry[7]
    v.slashed = True
    v.exit_epoch = current_epoch
    v.withdrawable_epoch = current_epoch + spec.LATEST_SLASHED_EXIT_LENGTH // 2
    # Drop some balances below ejection
    for i in rng.sample(range(len(state.validator_registry)), 5):
        if not state.validator_registry[i].slashed:
            state.validator_registry[i].effective_balance = spec.EJECTION_BALANCE
            state.balances[i] = spec.EJECTION_BALANCE
    # Fresh validators waiting on the activation queue
    from consensus_specs_tpu.testing.factories import seed_validator
    for k in range(6):
        nv = seed_validator(spec, len(state.validator_registry), spec.MAX_EFFECTIVE_BALANCE)
        nv.activation_eligibility_epoch = spec.FAR_FUTURE_EPOCH if k % 3 == 0 else current_epoch - k % 2
        state.validator_registry.append(nv)
        state.balances.append(spec.MAX_EFFECTIVE_BALANCE)
    # Scatter balances so hysteresis has work to do
    for i in range(0, len(state.validator_registry), 3):
        state.balances[i] = max(0, state.balances[i] - rng.randrange(0, 3 * 10 ** 9))

    assert_same_epoch_transition(spec, state)


# ---------------------------------------------------------------------------
# The proposer table and the activation cut (PR 29): the epoch program sums
# proposer rewards through a table of the epoch's distinct proposers and
# cuts the activation queue by one threshold element. Each case builds a
# boundary state that drives one corner of either, says so with an assert
# on the distilled inputs, and is held to spec.process_epoch by state root.
# ---------------------------------------------------------------------------

def _attested_state(spec, validators):
    """A boundary-ready state whose two attestation lists blocks filled
    (one proposer a slot)."""
    state = create_genesis_state(spec, validators)
    next_epoch(spec, state)
    apply_empty_block(spec, state)
    _, _, state = attested_epoch(spec, state, current=True, previous=True)
    return state


def _enqueue(spec, state, eligibility_epochs):
    """Fresh validators on the activation queue, one an entry, eligible
    since the given epoch."""
    from consensus_specs_tpu.testing.factories import seed_validator
    for e in eligibility_epochs:
        nv = seed_validator(spec, len(state.validator_registry),
                            spec.MAX_EFFECTIVE_BALANCE)
        nv.activation_eligibility_epoch = e
        state.validator_registry.append(nv)
        state.balances.append(spec.MAX_EFFECTIVE_BALANCE)


def _one_proposer_an_attester(spec, state):
    """Rewrite the previous epoch's attestations as one single-signer
    attestation a committee member, each with a proposer of its own (the
    benchmark generator's shape, taken to its end)."""
    singles = []
    for a in state.previous_epoch_attestations:
        size = len(spec.get_crosslink_committee(
            state, a.data.target_epoch, a.data.crosslink.shard))
        for bit in range(size):
            one = deepcopy(a)
            field = bytearray((size + 7) // 8)
            field[bit // 8] |= 1 << (bit % 8)
            one.aggregation_bitfield = bytes(field)
            one.proposer_index = len(singles)
            one.inclusion_delay = 1 + len(singles) % 3
            singles.append(one)
    state.previous_epoch_attestations = singles


def _case_queue_longer_than_churn_with_ties(preset):
    spec = phase0.get_spec(preset)
    state = _attested_state(spec, spec.SLOTS_PER_EPOCH * 8)
    # churn is 4: the cut falls inside the five rows tied at epoch 2
    _enqueue(spec, state, [3, 2, 2, 2, 2, 2, 1])
    return spec, state, dict(dequeued=4, still_queued=3)


def _case_queue_shorter_than_churn(preset):
    spec = phase0.get_spec(preset)
    state = _attested_state(spec, spec.SLOTS_PER_EPOCH * 8)
    _enqueue(spec, state, [2, 1])
    return spec, state, dict(dequeued=2, still_queued=0)


def _case_queue_empty(preset):
    spec = phase0.get_spec(preset)
    state = _attested_state(spec, spec.SLOTS_PER_EPOCH * 8)
    return spec, state, dict(dequeued=0, still_queued=0)


def _case_churn_at_least_v(preset):
    spec = phase0.get_spec(preset.replace(MIN_PER_EPOCH_CHURN_LIMIT=10_000))
    state = _attested_state(spec, spec.SLOTS_PER_EPOCH * 8)
    _enqueue(spec, state, [3, 2, 2, 2, 2, 2, 1])
    return spec, state, dict(dequeued=7, still_queued=0)


def _case_one_proposer_a_slot(preset):
    spec = phase0.get_spec(preset)
    state = _attested_state(spec, spec.SLOTS_PER_EPOCH * 8)
    rows = len({a.proposer_index for a in state.previous_epoch_attestations})
    assert 1 < rows <= 2 * spec.SLOTS_PER_EPOCH - 1
    return spec, state, dict(proposer_rows=rows,
                             table=proposer_table_capacity(spec))


def _case_one_proposer_an_attestation(preset):
    spec = phase0.get_spec(preset)
    state = _attested_state(spec, spec.SLOTS_PER_EPOCH * 24)
    _one_proposer_an_attester(spec, state)
    # more than one chunk of the table
    return spec, state, dict(proposer_rows=spec.SLOTS_PER_EPOCH * 24,
                             table=proposer_table_capacity(spec))


def _case_more_proposers_than_capacity(preset):
    spec = phase0.get_spec(preset.replace(MAX_ATTESTATIONS=8))
    assert proposer_table_capacity(spec) == 128
    state = _attested_state(spec, spec.SLOTS_PER_EPOCH * 24)
    _one_proposer_an_attester(spec, state)
    return spec, state, dict(proposer_rows=spec.SLOTS_PER_EPOCH * 24,
                             table=2 * proposer_table_capacity(spec))


def _case_proposer_attests_and_proposed_twice(preset):
    spec = phase0.get_spec(preset)
    state = _attested_state(spec, spec.SLOTS_PER_EPOCH * 8)
    atts = state.previous_epoch_attestations
    # a member of the first attestation's committee included it and the next
    member = spec.get_crosslink_committee(
        state, atts[0].data.target_epoch, atts[0].data.crosslink.shard)[0]
    atts[0].proposer_index = atts[1].proposer_index = member
    rows = len({a.proposer_index for a in atts})
    return spec, state, dict(proposer_rows=rows,
                             table=proposer_table_capacity(spec))


@pytest.mark.parametrize("case", [
    _case_queue_longer_than_churn_with_ties,
    _case_queue_shorter_than_churn,
    _case_queue_empty,
    _case_churn_at_least_v,
    _case_one_proposer_a_slot,
    _case_one_proposer_an_attestation,
    _case_more_proposers_than_capacity,
    _case_proposer_attests_and_proposed_twice,
], ids=lambda c: c.__name__[len("_case_"):])
def test_proposer_table_and_activation_cut_parity(case):
    from consensus_specs_tpu.utils.config import load_preset

    spec, state, expect = case(load_preset("minimal"))
    spec.process_slots(state, state.slot + spec.SLOTS_PER_EPOCH - 1
                       - state.slot % spec.SLOTS_PER_EPOCH)
    far = spec.FAR_FUTURE_EPOCH
    waiting = [i for i, v in enumerate(state.validator_registry)
               if v.activation_epoch == far
               and v.activation_eligibility_epoch != far]

    # the case drives what its name says
    facts = build_epoch_inputs_np(spec, deepcopy(state),
                                  build_epoch_context(spec, state))
    if "proposer_rows" in expect:
        assert int(facts.proposer_rows) == expect["proposer_rows"]
        assert facts.proposer_table.shape == (expect["table"],)

    ref = assert_same_epoch_transition(spec, state)
    if "dequeued" in expect:
        left = sum(ref.validator_registry[i].activation_epoch == far
                   for i in waiting)
        assert (len(waiting) - left, left) == (
            expect["dequeued"], expect["still_queued"])


def _scatter_updates(jaxpr):
    """Shapes of the updates operand of every scatter in a jaxpr, its
    sub-jaxprs (loops, calls, branches) included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            found.append((eqn.primitive.name,
                          tuple(eqn.invars[2].aval.shape)))
        for val in eqn.params.values():
            for item in (val if isinstance(val, (tuple, list)) else (val,)):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    found += _scatter_updates(inner)
    return found


def test_epoch_program_scatters_no_row_of_the_validator_axis(spec):
    """The two serial loops cannot come back unnoticed: at two sizes of V
    the traced program holds the same scatters, none with a V-sized
    update (what is left writes one chunk of proposer-table rows and one
    latest_slashed_balances entry), and a state with another number of
    proposers runs the program compiled for the first."""
    import jax
    from functools import partial

    from consensus_specs_tpu.models.phase0.epoch_soa import (
        PROPOSER_CHUNK, EpochConfig, _epoch_transition_traced,
        proposer_table_capacity, proposer_table_np, synthetic_epoch_state)

    cfg = EpochConfig.from_spec(spec)
    program = partial(_epoch_transition_traced, cfg)
    scatters = {}
    for V in (256, 1024):
        args = synthetic_epoch_state(cfg, V, np.random.default_rng(V))
        scatters[V] = _scatter_updates(jax.make_jaxpr(program)(*args).jaxpr)
        assert all(V not in shape for _, shape in scatters[V]), scatters[V]
    assert scatters[256] == scatters[1024]
    assert sorted(shape for _, shape in scatters[256]) \
        == [(), (PROPOSER_CHUNK,)]

    jitted = jax.jit(program)
    cols, scal, inp = synthetic_epoch_state(cfg, 256, np.random.default_rng(1))
    outs = []
    for rows in (3, 2 * PROPOSER_CHUNK + 1):      # one chunk, three chunks
        proposers = np.arange(rows, dtype=np.int32)
        table, n = proposer_table_np(proposers, proposer_table_capacity(cfg))
        outs.append(jitted(cols, scal, inp._replace(
            att_proposer=jax.numpy.asarray(proposers[np.arange(256) % rows]),
            proposer_table=jax.numpy.asarray(table),
            proposer_rows=jax.numpy.asarray(n))))
    assert jitted._cache_size() == 1
    # and the row count is read: the same attesters pay other proposers
    assert not np.array_equal(outs[0][0].balance, outs[1][0].balance)


def test_epoch_transition_donates_column_buffers(spec):
    """The donate_argnums on the epoch program must actually stick: every
    input column buffer is consumed (the 1M-validator epoch program updates
    in place instead of holding input+output copies in HBM) and XLA emits
    no "donated buffer unused" warning. Asserted against the donated jit
    directly — the accelerator production path; the public wrapper pins
    XLA:CPU to the undonated form (persistent-cache-deserialized CPU
    executables intermittently violate donated aliasing)."""
    import warnings

    import jax

    from consensus_specs_tpu.models.phase0.epoch_soa import (
        EpochConfig, _epoch_transition_donated, epoch_transition_device,
        synthetic_epoch_state)

    cfg = EpochConfig.from_spec(spec)
    cols, scal, inp = synthetic_epoch_state(cfg, 256, np.random.default_rng(5))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = jax.block_until_ready(
            _epoch_transition_donated(cfg, cols, scal, inp))
    donation_warnings = [str(w.message) for w in caught
                         if "donated" in str(w.message).lower()]
    assert not donation_warnings, donation_warnings
    # the donation really happened: every input column buffer was consumed
    assert all(getattr(cols, f).is_deleted() for f in cols._fields)
    new_cols = out[0]
    assert not new_cols.balance.is_deleted()
    # undonated args survive
    assert not inp.prev_src.is_deleted() and not scal.slot.is_deleted()

    # the public wrapper keeps CPU on the undonated form: inputs survive
    cols2, scal2, inp2 = synthetic_epoch_state(
        cfg, 256, np.random.default_rng(5))
    jax.block_until_ready(epoch_transition_device(cfg, cols2, scal2, inp2))
    import jax as _jax
    if _jax.default_backend() == "cpu":
        assert not cols2.balance.is_deleted()


def test_wide_math_helpers_exact():
    """muldiv_u64 / isqrt_u64 vs Python bigints on adversarial values."""
    import jax.numpy as jnp
    from consensus_specs_tpu.ops.intmath import isqrt_u64, muldiv_u64

    rng = random.Random(99)
    cases = []
    for _ in range(300):
        a = rng.randrange(0, 1 << 64)
        d = rng.randrange(1, 1 << 63)
        # keep quotient within 64 bits: b <= d * 2^64 / max(a,1) bound via b <= d
        b = rng.randrange(0, d + 1)
        if (a * b) // d < (1 << 64):
            cases.append((a, b, d))
    cases += [(32 * 10 ** 9, 3 * 10 ** 16, 3 * 10 ** 16 + 1), (0, 0, 1), (1 << 63, 2, 1 << 63)]
    a, b, d = (jnp.array([c[i] for c in cases], dtype=jnp.uint64) for i in range(3))
    got = np.asarray(muldiv_u64(a, b, d))
    want = np.array([(x * y) // z for x, y, z in cases], dtype=np.uint64)
    np.testing.assert_array_equal(got, want)

    ns = [rng.randrange(0, 1 << 62) for _ in range(300)]
    ns += [0, 1, 2, 3, 4, (1 << 31) ** 2, (1 << 31) ** 2 - 1, 3 * 10 ** 16]
    ns += [k * k for k in (rng.randrange(1, 1 << 31) for _ in range(50))]
    ns += [k * k - 1 for k in (rng.randrange(2, 1 << 31) for _ in range(50))]
    got = np.asarray(isqrt_u64(jnp.array(ns, dtype=jnp.uint64)))
    import math
    want = np.array([math.isqrt(n) for n in ns], dtype=np.uint64)
    np.testing.assert_array_equal(got, want)


def test_muldiv_hardened_vs_materializing_form():
    """The memory tier's liveness walk flagged two full-width temps in
    muldiv_u64: a broadcast_to that pinned scalar divisors at [V] width
    across the whole 64-step division scan, and jnp's guarded `%` whose
    where(d == 0) select chain is dead under the documented d >= 1
    precondition. This pins the hardened body bit-identical to the old
    materializing formulation — scalar AND vector divisors — and pins
    the prover win itself: a scalar divisor must never re-enter the
    division loop as a full-width constant."""
    import jax
    import jax.numpy as jnp
    from consensus_specs_tpu.ops.intmath import muldiv_u64, mulwide_u64

    def muldiv_materializing(a, b, d):
        # the pre-hardening body, verbatim modulo names
        hi, lo = mulwide_u64(a, b)
        d = jnp.broadcast_to(jnp.asarray(d, dtype=jnp.uint64), hi.shape)

        def step(i, carry):
            rem, quot = carry
            shift = jnp.uint64(63) - jnp.asarray(i, dtype=jnp.uint64)
            bit = (lo >> shift) & jnp.uint64(1)
            top = rem >> jnp.uint64(63)
            rem2 = (rem << jnp.uint64(1)) | bit
            ge = (top == jnp.uint64(1)) | (rem2 >= d)
            rem3 = jnp.where(ge, rem2 - d, rem2)
            quot2 = (quot << jnp.uint64(1)) | ge.astype(jnp.uint64)
            return rem3, quot2

        rem0 = hi % d
        quot0 = jnp.zeros_like(hi)
        _, quot = jax.lax.fori_loop(0, 64, step, (rem0, quot0))
        return quot

    rng = random.Random(1601)
    n = 512
    a = np.array([rng.randrange(0, 1 << 64) for _ in range(n)], np.uint64)
    dv = np.array([rng.randrange(1, 1 << 63) for _ in range(n)], np.uint64)
    b = np.array([rng.randrange(0, int(x) + 1) for x in dv], np.uint64)
    ja, jb, jd = (jnp.asarray(x) for x in (a, b, dv))
    # vector divisor (the crosslink-delta shape)
    np.testing.assert_array_equal(np.asarray(muldiv_u64(ja, jb, jd)),
                                  np.asarray(muldiv_materializing(ja, jb, jd)))
    # scalar divisor (the micro-incentive / slashing shape), d = 1 edge too
    for d_scalar in (jnp.uint64(3 * 10 ** 16 + 1), jnp.uint64(1)):
        bs = jnp.minimum(jb, d_scalar)
        np.testing.assert_array_equal(
            np.asarray(muldiv_u64(ja, bs, d_scalar)),
            np.asarray(muldiv_materializing(ja, bs, d_scalar)))

    # the prover's claim, pinned structurally: in the scalar-divisor
    # jaxpr the division loop's carried/constant operands contain ONE
    # full-width uint64 stream (lo) beyond the two carries — the old
    # body carried the broadcast divisor as a second full-width const
    closed = jax.make_jaxpr(
        lambda x, y: muldiv_u64(x, y, jnp.uint64(7)))(ja, jb)

    def loop_consts(jaxpr):
        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("while", "scan"):
                found.append([tuple(v.aval.shape) for v in eqn.invars
                              if getattr(v, "aval", None) is not None])
            for val in eqn.params.values():
                for item in (val if isinstance(val, (tuple, list)) else (val,)):
                    if hasattr(item, "jaxpr"):
                        found.extend(loop_consts(
                            getattr(item.jaxpr, "jaxpr", item.jaxpr)))
        return found

    loops = loop_consts(closed.jaxpr)
    assert loops, "division loop vanished from muldiv_u64's jaxpr"
    full_width = max(sum(1 for shp in ops if shp == (n,)) for ops in loops)
    assert full_width <= 3, (
        f"scalar-divisor muldiv carries {full_width} full-width loop "
        f"operands (expected lo + rem + quot): the divisor is being "
        f"materialized again")
