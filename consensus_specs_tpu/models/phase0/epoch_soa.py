"""Epoch processing as one jitted program over structure-of-arrays state.

This is the TPU-native redesign of the reference's per-validator Python loops
(/root/reference specs/core/0_beacon-chain.md:1247-1564). The object-model
spec (epoch.py) keeps reference semantics one-to-one; this module runs the
same transition as masked elementwise math over `[V]`-shaped columns:

  - justification/finalization  (:1326-1373)  -> masked balance sums + scalar bit logic
  - attestation deltas          (:1398-1443)  -> flag-masked reward vectors; the
        proposer micro-rewards summed through a table of the epoch's distinct
        proposers (the reference's O(V*A) list membership tests become O(V)
        mask ops)
  - crosslink deltas            (:1445-1463)  -> per-shard balance tables gathered per validator
  - registry updates            (:1479-1503)  -> closed-form exit-queue assignment + stable-sort
        activation queue cut at its churn-th row (the reference's sequential churn loop has a
        closed form: rank r among new exits gets epoch b + (min(c0, churn) + r) // churn)
  - slashings                   (:1507-1524)  -> elementwise, 128-bit exact muldiv
  - final updates               (:1526-1564)  -> hysteresis + rotation (numeric parts)

Byte-rooted pieces (block roots, randao mixes, historical batches, active
index roots) stay on the host in the `process_epoch_soa` wrapper, which is
differentially tested against the object-model path for state-root equality.

Exactness: balances are uint64 Gwei; products that exceed 64 bits go through
ops/intmath.muldiv_u64 (128-bit intermediate), matching Python bigint results
bit-for-bit.
"""
from __future__ import annotations

import itertools
import operator
from functools import partial
from typing import NamedTuple

import numpy as np

from ... import telemetry
from ...ops import intmath  # enables jax_enable_x64 on import
from ...utils.donation import platform_donated_jit
from .helpers import PERMUTATIONS_COMPUTED

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

u64 = jnp.uint64


class EpochConfig(NamedTuple):
    """Static (hashable) constants closed over by the compiled epoch program."""
    SLOTS_PER_EPOCH: int
    GENESIS_EPOCH: int
    FAR_FUTURE_EPOCH: int
    BASE_REWARD_FACTOR: int
    BASE_REWARDS_PER_EPOCH: int
    PROPOSER_REWARD_QUOTIENT: int
    MIN_ATTESTATION_INCLUSION_DELAY: int
    MIN_EPOCHS_TO_INACTIVITY_PENALTY: int
    INACTIVITY_PENALTY_QUOTIENT: int
    MIN_PER_EPOCH_CHURN_LIMIT: int
    CHURN_LIMIT_QUOTIENT: int
    MAX_EFFECTIVE_BALANCE: int
    EJECTION_BALANCE: int
    EFFECTIVE_BALANCE_INCREMENT: int
    ACTIVATION_EXIT_DELAY: int
    MIN_VALIDATOR_WITHDRAWABILITY_DELAY: int
    LATEST_SLASHED_EXIT_LENGTH: int
    MIN_SLASHING_PENALTY_QUOTIENT: int
    SHARD_COUNT: int
    TARGET_COMMITTEE_SIZE: int
    MAX_ATTESTATIONS: int

    @classmethod
    def from_spec(cls, spec) -> "EpochConfig":
        return cls(**{f: int(getattr(spec, f)) for f in cls._fields})


class ValidatorColumns(NamedTuple):
    """SoA layout of the validator registry + balances (reference :525-564)."""
    activation_eligibility_epoch: jnp.ndarray  # [V] uint64
    activation_epoch: jnp.ndarray              # [V] uint64
    exit_epoch: jnp.ndarray                    # [V] uint64
    withdrawable_epoch: jnp.ndarray            # [V] uint64
    slashed: jnp.ndarray                       # [V] bool
    effective_balance: jnp.ndarray             # [V] uint64
    balance: jnp.ndarray                       # [V] uint64


class EpochScalars(NamedTuple):
    slot: jnp.ndarray                      # uint64
    previous_justified_epoch: jnp.ndarray  # uint64
    current_justified_epoch: jnp.ndarray   # uint64
    justification_bitfield: jnp.ndarray    # uint64
    finalized_epoch: jnp.ndarray           # uint64
    latest_start_shard: jnp.ndarray        # uint64
    latest_slashed_balances: jnp.ndarray   # [LATEST_SLASHED_EXIT_LENGTH] uint64


class EpochInputs(NamedTuple):
    """Participation facts distilled from PendingAttestations (host-built).

    Flags are raw membership in the union of attesting indices; slashed
    filtering happens on device (get_unslashed_attesting_indices :1294-1300).
    """
    prev_src: jnp.ndarray        # [V] bool - in prev-epoch matching-source union
    prev_tgt: jnp.ndarray        # [V] bool - matching target
    prev_head: jnp.ndarray       # [V] bool - matching head
    curr_tgt: jnp.ndarray        # [V] bool - current-epoch matching target
    incl_delay: jnp.ndarray      # [V] uint64 - min inclusion delay (1 where unset)
    att_proposer: jnp.ndarray    # [V] int32 - proposer of that min-delay attestation
    v_shard: jnp.ndarray         # [V] int32 - prev-epoch crosslink-committee shard, -1 if none
    in_winning: jnp.ndarray      # [V] bool - in the winning crosslink's attesting set
    shard_att_balance: jnp.ndarray   # [SHARD_COUNT] uint64 (>=1)
    shard_comm_balance: jnp.ndarray  # [SHARD_COUNT] uint64 (>=1)
    proposer_table: jnp.ndarray  # [C] int32 - the distinct values of att_proposer's
    #                              attestations, ascending, then -1 (proposer_table_np)
    proposer_rows: jnp.ndarray   # int32 - how many rows of proposer_table are in use


# The EpochInputs fields that are NOT [V] participation-fact columns: small
# tables and a count, the same on every device of a serving mesh. The one
# list the padding, the mesh placement and the single-device unstaging share.
REPLICATED_INPUT_FIELDS = ("shard_att_balance", "shard_comm_balance",
                           "proposer_table", "proposer_rows")
assert EpochInputs._fields[-len(REPLICATED_INPUT_FIELDS):] \
    == REPLICATED_INPUT_FIELDS

# Rows of the proposer table summed at a time: one lane-width of keys
# against every validator's att_proposer.
PROPOSER_CHUNK = 128


def including_blocks(cfg) -> int:
    """The blocks that can include an attestation whose target is one
    given epoch (`cfg` is an EpochConfig or a spec): process_attestation
    (block.py) takes it at a slot of its own epoch no earlier than
    MIN_ATTESTATION_INCLUSION_DELAY after the epoch's first, and at any
    slot of the next epoch. Each has one proposer."""
    return int(2 * cfg.SLOTS_PER_EPOCH - cfg.MIN_ATTESTATION_INCLUSION_DELAY)


def proposer_table_capacity(cfg) -> int:
    """Rows of `EpochInputs.proposer_table` for a preset: the most
    PendingAttestations a chain of blocks can leave with the previous
    epoch as target, each naming one proposer: including_blocks of
    MAX_ATTESTATIONS each (124 x 128 = 15,872 on mainnet), up to a whole
    number of chunks. One static shape a preset, so every state runs the
    one compiled program: the device loops over the rows in use."""
    rows = int(cfg.MAX_ATTESTATIONS) * including_blocks(cfg)
    return -(-rows // PROPOSER_CHUNK) * PROPOSER_CHUNK


def proposer_table_np(proposers, capacity: int):
    """(table, rows) from proposer indices, duplicates allowed: the
    distinct values ascending in an int32 table padded with -1, and their
    count. `capacity` is the preset's (proposer_table_capacity); more
    distinct values than that (a hand-built state: no chain of blocks
    leaves them) take the next multiple of it, and one more compile."""
    distinct = np.unique(np.asarray(proposers, dtype=np.int32))
    rows = len(distinct)
    table = np.full(max(1, -(-rows // capacity)) * capacity, -1, np.int32)
    table[:rows] = distinct
    return table, np.int32(rows)


class EpochReport(NamedTuple):
    """Scalar decisions the host needs to finish byte-rooted bookkeeping."""
    justified_prev_fired: jnp.ndarray  # bool - bit-1 justification branch taken
    justified_curr_fired: jnp.ndarray  # bool - bit-0 justification branch taken
    finalized_fired: jnp.ndarray       # bool - any finalization branch taken
    justification_active: jnp.ndarray  # bool - epoch > GENESIS + 1


def _total_balance(eff: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """get_total_balance over a mask (reference :933-941): max(sum, 1)."""
    return jnp.maximum(jnp.sum(jnp.where(mask, eff, u64(0))), u64(1))


@jax.jit
def _proposer_chunk_sums(att_proposer: jnp.ndarray, gain: jnp.ndarray,
                         keys: jnp.ndarray) -> jnp.ndarray:
    """sums[k] = the sum of gain[v] over the v with att_proposer[v] ==
    keys[k]: one compare-select-reduce over [V, PROPOSER_CHUNK], which the
    compiler runs inside the reduction's loop (tests/test_tpu_compile.py
    holds the v5e compiler to it: no [V, 128] buffer, 1 GB at V = 1M).
    A named call so that the memory tier can be told so
    (`fused_calls`): its liveness model counts every traced value as a
    buffer."""
    hit = att_proposer[:, None] == keys[None, :]
    return jnp.sum(jnp.where(hit, gain[:, None], u64(0)), axis=0)


def _add_proposer_rewards(rewards: jnp.ndarray, att_proposer: jnp.ndarray,
                          gain: jnp.ndarray, table: jnp.ndarray,
                          rows: jnp.ndarray) -> jnp.ndarray:
    """rewards[p] += the sum of gain[v] over the v with att_proposer[v] == p,
    for every p of table[:rows] (:1416-1421: each source attester pays its
    attestation's proposer).

    A scatter-add of the [V] gains by att_proposer runs one row after
    another on the chip (139 ns a row at V = 1M, PERF.md section 5), and
    all but a few of its million updates land in the same thousand rows.
    So the sum goes the other way round: a chunk of PROPOSER_CHUNK table
    rows at a time is compared with every att_proposer, the hits' gains
    are summed down the validator axis (shard-local under a mesh, the
    chunk's sums combined across shards), and only the chunk's rows are
    written. The loop runs over the rows in use, a traced count, never
    over the table's capacity. Exact: the same uint64 terms in an
    addition that has no order.

    The table's rows are distinct (proposer_table_np), so every row of
    `rewards` is written at most once and the value to add to is the one
    it had before the loop: read from there, the carry's bound does not
    grow with the trip count (the range tier proves the loop without an
    invariant). Padding rows (-1) match no att_proposer and are dropped."""
    V = rewards.shape[0]

    def chunk(c, acc):
        keys = jax.lax.dynamic_slice(
            table, (c * PROPOSER_CHUNK,), (PROPOSER_CHUNK,))
        sums = _proposer_chunk_sums(att_proposer, gain, keys)
        at = jnp.where(keys >= 0, keys, V)      # padding: out of bounds
        return acc.at[at].set(rewards.at[at].get(mode="clip") + sums,
                              mode="drop")

    n_chunks = (rows + (PROPOSER_CHUNK - 1)) // PROPOSER_CHUNK
    return jax.lax.fori_loop(0, n_chunks, chunk, rewards)


def _stage_a_traced(cfg: EpochConfig, cols: ValidatorColumns,
                    scal: EpochScalars, inp: EpochInputs):
    """Justification/finalization + rewards/penalties + registry updates —
    everything BEFORE the phase-1 @process_reveal_deadlines insert point
    (process_epoch order, :1251-1262 + 1_custody-game.md:668-696).
    Returns (cols', scal', report) with balances post-rewards and
    registry epochs post-updates; effective balances, slashed flags, the
    slashed-balance table, and the start shard are untouched here."""
    V = cols.balance.shape[0]
    FAR = u64(cfg.FAR_FUTURE_EPOCH)

    current_epoch = scal.slot // u64(cfg.SLOTS_PER_EPOCH)
    # saturating -1: identical to `current_epoch - 1` on every lane the
    # where() keeps (current != GENESIS implies current >= GENESIS + 1),
    # and provably wrap-free for the range tier (make ranges) even on
    # the unreachable current < 1 lanes the raw subtraction wraps on
    previous_epoch = jnp.where(
        current_epoch == u64(cfg.GENESIS_EPOCH), u64(cfg.GENESIS_EPOCH),
        jnp.maximum(current_epoch, u64(1)) - u64(1))

    active_curr = (cols.activation_epoch <= current_epoch) & (current_epoch < cols.exit_epoch)
    active_prev = (cols.activation_epoch <= previous_epoch) & (previous_epoch < cols.exit_epoch)
    eff = cols.effective_balance
    total_balance = _total_balance(eff, active_curr)
    active_count = jnp.sum(active_curr.astype(jnp.uint64))

    with jax.named_scope("justification"):
        # -- Justification and finalization (:1326-1373) ------------------------
        justification_active = current_epoch > u64(cfg.GENESIS_EPOCH + 1)
        unslashed = ~cols.slashed
        prev_tgt_balance = _total_balance(eff, inp.prev_tgt & unslashed)
        curr_tgt_balance = _total_balance(eff, inp.curr_tgt & unslashed)

        old_prev_just = scal.previous_justified_epoch
        old_curr_just = scal.current_justified_epoch
        new_prev_just = old_curr_just
        bitfield = (scal.justification_bitfield << u64(1))  # uint64 wraps = % 2**64
        just_prev = prev_tgt_balance * u64(3) >= total_balance * u64(2)
        just_curr = curr_tgt_balance * u64(3) >= total_balance * u64(2)
        new_curr_just = jnp.where(just_prev, previous_epoch, old_curr_just)
        bitfield = jnp.where(just_prev, bitfield | u64(2), bitfield)
        new_curr_just = jnp.where(just_curr, current_epoch, new_curr_just)
        bitfield = jnp.where(just_curr, bitfield | u64(1), bitfield)

        new_finalized = scal.finalized_epoch
        fin_fired = jnp.asarray(False)
        # The 2nd/3rd/4th most recent epochs justified, 2nd using 4th as source
        c1 = ((bitfield >> u64(1)) % u64(8) == u64(0b111)) & (old_prev_just + u64(3) == current_epoch)
        new_finalized = jnp.where(c1, old_prev_just, new_finalized)
        # The 2nd/3rd most recent epochs justified, 2nd using 3rd as source
        c2 = ((bitfield >> u64(1)) % u64(4) == u64(0b11)) & (old_prev_just + u64(2) == current_epoch)
        new_finalized = jnp.where(c2, old_prev_just, new_finalized)
        # The 1st/2nd/3rd most recent epochs justified, 1st using 3rd as source
        c3 = ((bitfield >> u64(0)) % u64(8) == u64(0b111)) & (old_curr_just + u64(2) == current_epoch)
        new_finalized = jnp.where(c3, old_curr_just, new_finalized)
        # The 1st/2nd most recent epochs justified, 1st using 2nd as source
        c4 = ((bitfield >> u64(0)) % u64(4) == u64(0b11)) & (old_curr_just + u64(1) == current_epoch)
        new_finalized = jnp.where(c4, old_curr_just, new_finalized)
        fin_fired = c1 | c2 | c3 | c4

        prev_just = jnp.where(justification_active, new_prev_just, old_prev_just)
        curr_just = jnp.where(justification_active, new_curr_just, old_curr_just)
        bitfield = jnp.where(justification_active, bitfield, scal.justification_bitfield)
        finalized = jnp.where(justification_active, new_finalized, scal.finalized_epoch)
        fin_fired = fin_fired & justification_active

    with jax.named_scope("rewards_and_penalties"):
        # -- Rewards and penalties (:1391-1475) ---------------------------------
        rewards_active = current_epoch != u64(cfg.GENESIS_EPOCH)
        sqrt_total = intmath.isqrt_u64(total_balance)
        base_reward = eff * u64(cfg.BASE_REWARD_FACTOR) // sqrt_total // u64(cfg.BASE_REWARDS_PER_EPOCH)

        eligible = active_prev | (cols.slashed & (previous_epoch + u64(1) < cols.withdrawable_epoch))
        rewards = jnp.zeros(V, dtype=jnp.uint64)
        penalties = jnp.zeros(V, dtype=jnp.uint64)

        # Micro-incentives for matching source / target / head (:1398-1414)
        for flag in (inp.prev_src, inp.prev_tgt, inp.prev_head):
            in_set = flag & unslashed
            att_balance = _total_balance(eff, in_set)
            match_reward = intmath.muldiv_u64(base_reward, att_balance, total_balance)
            rewards = rewards + jnp.where(eligible & in_set, match_reward, u64(0))
            penalties = penalties + jnp.where(eligible & ~in_set, base_reward, u64(0))

        # Proposer + inclusion-delay micro-rewards for source attesters (:1416-1429)
        src_set = inp.prev_src & unslashed
        proposer_gain = jnp.where(src_set, base_reward // u64(cfg.PROPOSER_REWARD_QUOTIENT), u64(0))
        rewards = _add_proposer_rewards(
            rewards, inp.att_proposer, proposer_gain,
            inp.proposer_table, inp.proposer_rows)
        delay = jnp.maximum(inp.incl_delay, u64(1))
        rewards = rewards + jnp.where(
            src_set, base_reward * u64(cfg.MIN_ATTESTATION_INCLUSION_DELAY) // delay, u64(0))

        # Inactivity penalty (:1431-1440)
        # saturating: finalized <= previous_epoch is a chain invariant (an
        # epoch finalizes only after it was previous), so the min() changes
        # nothing on reachable states — it makes the inactivity product
        # eff * finality_delay provably wrap-free (make ranges) instead of
        # multiplying by a wrapped ~2^64 delay on a corrupt state
        finality_delay = previous_epoch - jnp.minimum(finalized, previous_epoch)
        inactivity = finality_delay > u64(cfg.MIN_EPOCHS_TO_INACTIVITY_PENALTY)
        tgt_set = inp.prev_tgt & unslashed
        penalties = penalties + jnp.where(
            inactivity & eligible, u64(cfg.BASE_REWARDS_PER_EPOCH) * base_reward, u64(0))
        penalties = penalties + jnp.where(
            inactivity & eligible & ~tgt_set,
            eff * finality_delay // u64(cfg.INACTIVITY_PENALTY_QUOTIENT), u64(0))

    with jax.named_scope("crosslink_deltas"):
        # Crosslink deltas (:1445-1463): per-shard tables gathered per validator
        in_committee = inp.v_shard >= 0
        shard_idx = jnp.maximum(inp.v_shard, 0)
        cl_att = inp.shard_att_balance[shard_idx]
        cl_comm = jnp.maximum(inp.shard_comm_balance[shard_idx], u64(1))
        cl_reward = intmath.muldiv_u64(base_reward, cl_att, cl_comm)
        rewards = rewards + jnp.where(in_committee & inp.in_winning, cl_reward, u64(0))
        penalties = penalties + jnp.where(in_committee & ~inp.in_winning, base_reward, u64(0))

    with jax.named_scope("rewards_and_penalties"):
        # Apply: increase then saturating decrease (:687-705, :1465-1475)
        balance = cols.balance + jnp.where(rewards_active, rewards, u64(0))
        pen = jnp.where(rewards_active, penalties, u64(0))
        balance = jnp.where(pen > balance, u64(0), balance - pen)

    with jax.named_scope("registry_updates"):
        # -- Registry updates (:1479-1503) --------------------------------------
        churn = jnp.maximum(u64(cfg.MIN_PER_EPOCH_CHURN_LIMIT),
                            active_count // u64(cfg.CHURN_LIMIT_QUOTIENT))

        # Activation eligibility
        elig = jnp.where(
            (cols.activation_eligibility_epoch == FAR) & (eff >= u64(cfg.MAX_EFFECTIVE_BALANCE)),
            current_epoch, cols.activation_eligibility_epoch)

        # Ejections -> closed-form exit queue (initiate_validator_exit :1103-1118)
        ejected = active_curr & (eff <= u64(cfg.EJECTION_BALANCE)) & (cols.exit_epoch == FAR)
        delayed_exit = current_epoch + u64(1) + u64(cfg.ACTIVATION_EXIT_DELAY)
        has_exit = cols.exit_epoch != FAR
        base_epoch = jnp.maximum(
            jnp.max(jnp.where(has_exit, cols.exit_epoch, u64(0))), delayed_exit)
        count_at_base = jnp.sum((cols.exit_epoch == base_epoch).astype(jnp.uint64))
        c0 = jnp.minimum(count_at_base, churn)
        rank = jnp.cumsum(ejected.astype(jnp.uint64)) - ejected.astype(jnp.uint64)
        # the has_exit select above already strips the FAR_FUTURE_EPOCH
        # sentinel (2^64-1) from real states, but the interval domain keeps
        # the sentinel in exit_epoch's hull, so the range tier cannot
        # exclude base_epoch ~ 2^64 here; real base_epoch is bounded by the
        # largest genuine exit epoch and the add cannot wrap
        # csa: ignore[CSA1401] -- FAR sentinel lanes are select-masked
        assigned = base_epoch + (c0 + rank) // churn
        exit_epoch = jnp.where(ejected, assigned, cols.exit_epoch)
        withdrawable = jnp.where(
            ejected, assigned + u64(cfg.MIN_VALIDATOR_WITHDRAWABILITY_DELAY), cols.withdrawable_epoch)

        # Activation queue: stable sort by eligibility epoch, dequeue
        # churn-many. A stable sort orders rows by (sort_key, row), so a
        # row is among the first `churn` exactly when its pair is at or
        # before that of the last row to make the cut: no row needs its
        # own position in the order. When churn >= V the cut is the last
        # row and every queued row passes.
        delayed_fin = finalized + u64(1) + u64(cfg.ACTIVATION_EXIT_DELAY)
        queued = (elig != FAR) & (cols.activation_epoch >= delayed_fin)
        sort_key = jnp.where(queued, elig, FAR)
        row = jnp.arange(V, dtype=jnp.int32)
        sorted_key, order = jax.lax.sort(
            (sort_key, row), num_keys=1, is_stable=True)
        # the one element at the cut, picked by a masked sum: under a mesh
        # a shard-local pass and a scalar all-reduce, where an index into
        # the sharded order gathers it
        cut = row.astype(jnp.uint64) + u64(1) == jnp.minimum(churn, u64(V))
        cut_key = jnp.sum(jnp.where(cut, sorted_key, u64(0)))
        cut_row = jnp.sum(jnp.where(cut, order, 0))
        dequeued = queued & ((sort_key < cut_key)
                             | ((sort_key == cut_key) & (row <= cut_row)))
        activation = jnp.where(
            dequeued & (cols.activation_epoch == FAR),
            current_epoch + u64(1) + u64(cfg.ACTIVATION_EXIT_DELAY), cols.activation_epoch)

    mid_cols = ValidatorColumns(
        activation_eligibility_epoch=elig,
        activation_epoch=activation,
        exit_epoch=exit_epoch,
        withdrawable_epoch=withdrawable,
        slashed=cols.slashed,
        effective_balance=eff,
        balance=balance,
    )
    mid_scal = EpochScalars(
        slot=scal.slot,
        previous_justified_epoch=prev_just,
        current_justified_epoch=curr_just,
        justification_bitfield=bitfield,
        finalized_epoch=finalized,
        latest_start_shard=scal.latest_start_shard,
        latest_slashed_balances=scal.latest_slashed_balances,
    )
    report = EpochReport(
        justified_prev_fired=just_prev & justification_active,
        justified_curr_fired=just_curr & justification_active,
        finalized_fired=fin_fired,
        justification_active=justification_active,
    )
    return mid_cols, mid_scal, report


def _stage_b_traced(cfg: EpochConfig, cols: ValidatorColumns,
                    scal: EpochScalars):
    """Slashings + the numeric final updates — everything AFTER the phase-1
    reveal/challenge-deadline inserts (:1507-1564). Reads the columns as
    they stand at its execution point (the inserts may have slashed
    validators and grown the slashed-balance table), exactly like the
    reference's sequential sub-transitions.

    The active set and total balance it recomputes equal stage A's: rewards
    change only balances, registry updates and phase-1 slashings move exit/
    activation epochs strictly beyond the current epoch, and effective
    balances change nowhere before the hysteresis below."""
    eff = cols.effective_balance
    balance = cols.balance
    current_epoch = scal.slot // u64(cfg.SLOTS_PER_EPOCH)
    active_curr = (cols.activation_epoch <= current_epoch) & (current_epoch < cols.exit_epoch)
    total_balance = _total_balance(eff, active_curr)
    active_count = jnp.sum(active_curr.astype(jnp.uint64))

    with jax.named_scope("slashings"):
        # -- Slashings (:1507-1524) ---------------------------------------------
        L = cfg.LATEST_SLASHED_EXIT_LENGTH
        lsb = scal.latest_slashed_balances
        at_start = lsb[(current_epoch + u64(1)) % u64(L)]
        at_end = lsb[current_epoch % u64(L)]
        tp3 = (at_end.astype(jnp.int64) - at_start.astype(jnp.int64)) * 3
        m = jnp.minimum(tp3, total_balance.astype(jnp.int64))
        scaled = jnp.where(m < 0, u64(0),
                           intmath.muldiv_u64(eff, jnp.maximum(m, 0).astype(jnp.uint64), total_balance))
        slash_penalty = jnp.maximum(scaled, eff // u64(cfg.MIN_SLASHING_PENALTY_QUOTIENT))
        slash_now = cols.slashed & (current_epoch == cols.withdrawable_epoch - u64(L // 2))
        slash_penalty = jnp.where(slash_now, slash_penalty, u64(0))
        balance = jnp.where(slash_penalty > balance, u64(0), balance - slash_penalty)

    with jax.named_scope("final_updates"):
        # -- Final updates, numeric parts (:1526-1564) --------------------------
        next_epoch = current_epoch + u64(1)
        half_inc = u64(cfg.EFFECTIVE_BALANCE_INCREMENT // 2)
        stale = (balance < eff) | (eff + u64(3) * half_inc < balance)
        new_eff = jnp.where(
            stale,
            jnp.minimum(balance - balance % u64(cfg.EFFECTIVE_BALANCE_INCREMENT),
                        u64(cfg.MAX_EFFECTIVE_BALANCE)),
            eff)

        # Start shard rotation (get_shard_delta over the *current* epoch :1543-1545)
        committees = jnp.maximum(
            u64(1),
            jnp.minimum(u64(cfg.SHARD_COUNT // cfg.SLOTS_PER_EPOCH),
                        active_count // u64(cfg.SLOTS_PER_EPOCH) // u64(cfg.TARGET_COMMITTEE_SIZE)),
        ) * u64(cfg.SLOTS_PER_EPOCH)
        shard_delta = jnp.minimum(
            committees, u64(cfg.SHARD_COUNT - cfg.SHARD_COUNT // cfg.SLOTS_PER_EPOCH))
        start_shard = (scal.latest_start_shard + shard_delta) % u64(cfg.SHARD_COUNT)

        lsb = lsb.at[next_epoch % u64(L)].set(lsb[current_epoch % u64(L)])

    new_cols = cols._replace(effective_balance=new_eff, balance=balance)
    new_scal = scal._replace(latest_start_shard=start_shard,
                             latest_slashed_balances=lsb)
    return new_cols, new_scal


def _epoch_transition_traced(cfg: EpochConfig, cols: ValidatorColumns,
                             scal: EpochScalars, inp: EpochInputs):
    # named scopes are trace-time only: they put the spec's own names on
    # the device operations of a profiler trace, and change no program
    with jax.named_scope("epoch_stage_a"):
        mid_cols, mid_scal, report = _stage_a_traced(cfg, cols, scal, inp)
    with jax.named_scope("epoch_stage_b"):
        new_cols, new_scal = _stage_b_traced(cfg, mid_cols, mid_scal)
    return new_cols, new_scal, report


# The donated form: every output column matches an input column's
# shape/dtype, so XLA updates the registry in place instead of holding
# input+output copies in HBM (the 1M-validator column set is ~7x8 MB —
# donation halves its footprint during the epoch program). The twins
# come from the shared platform_donated_jit helper (utils/donation.py);
# tests assert the donation sticks (no "donated buffer unused" warnings,
# input buffers consumed) against the donated twin.
_epoch_transition_pd = platform_donated_jit(
    _epoch_transition_traced, static_argnums=(0,), donate_argnums=(1,))
_epoch_transition_donated = _epoch_transition_pd.donated


def epoch_transition_device(cfg: EpochConfig, cols: ValidatorColumns,
                            scal: EpochScalars, inp: EpochInputs):
    """The whole numeric epoch transition, one traced program (the phase-0
    fast path: both stages fuse — XLA sees exactly the pre-split op graph).
    Phase 1 runs the two stages as separate programs with the insert hooks
    between (process_epoch_soa).

    The validator columns are DONATED on accelerator backends; callers must
    not reuse a jnp `cols` after the call (numpy inputs upload to a
    temporary and stay valid) — ResidentCore rebinds `self.cols` to the
    returned columns, and bench/tests chain outputs. XLA:CPU is pinned to
    the undonated form: a donated CPU executable loaded back from the
    persistent compilation cache intermittently ignores its input/output
    aliasing and clobbers a donated input with an intermediate (observed on
    jax 0.4.37 as the balance column coming back as the activation-queue
    iota after the second chained boundary; freshly compiled donated
    executables never reproduced it in stress runs). The tests differential
    against the object model on CPU, so correctness there must not depend
    on cache temperature."""
    return _epoch_transition_jit()(cfg, cols, scal, inp)


def _epoch_transition_jit():
    """The backend-selected jitted epoch program (donated off-CPU) — the
    dispatch point the retrace watchdog wraps (resident.py passes it to
    telemetry.watchdog.dispatch with a shape-pinned key)."""
    return _epoch_transition_pd.resolve()


_stage_a_jit = partial(jax.jit, static_argnums=(0,))(_stage_a_traced)
_stage_b_jit = partial(jax.jit, static_argnums=(0,))(_stage_b_traced)


# ---------------------------------------------------------------------------
# Inert validator padding (the sharded serving layout)
#
# jax pins shard sizes at placement time, so a `[V]` column sharded over the
# serving mesh must have V divisible by the mesh size. The serving path pads
# with INERT rows instead: a never-eligible, never-active, zero-balance
# validator every mask in the traced program excludes —
#   * active/eligible masks are False (activation == exit == FAR_FUTURE),
#   * uint64 balance sums gain exact zeros (order-independent),
#   * the activation-queue stable sort keys padding at FAR_FUTURE behind
#     every real row (padding indices are the largest), so queued positions
#     are unchanged,
#   * the exit-queue base/count scans see exit_epoch == FAR (excluded), and
#   * the proposer sums gain an exact zero (att_proposer 0, gain 0).
# The `[V]` prefix of the padded program's outputs is therefore
# bit-identical to the unpadded program (asserted differentially in
# tests/test_multichip.py, including a non-divisible V).
# ---------------------------------------------------------------------------

def inert_column_tail(field: str, k: int, far: int) -> np.ndarray:
    """[k] inert-validator rows for one ValidatorColumns field."""
    if field in ("activation_eligibility_epoch", "activation_epoch",
                 "exit_epoch", "withdrawable_epoch"):
        return np.full(k, far, dtype=np.uint64)
    if field == "slashed":
        return np.zeros(k, dtype=bool)
    return np.zeros(k, dtype=np.uint64)   # effective_balance, balance


def pad_validator_columns(cols: ValidatorColumns, vp: int,
                          far: int) -> ValidatorColumns:
    """Pad [V] columns to [vp] rows with inert validators (see above)."""
    V = int(cols.balance.shape[0])
    k = vp - V
    assert k >= 0, (vp, V)
    if k == 0:
        return cols
    return ValidatorColumns(**{
        f: jnp.concatenate([getattr(cols, f),
                            jnp.asarray(inert_column_tail(f, k, far))])
        for f in ValidatorColumns._fields})


def pad_epoch_inputs(inp: EpochInputs, vp: int) -> EpochInputs:
    """Pad the [V] participation facts to [vp] rows with the neutral
    values build_epoch_inputs uses for non-participants (flags False,
    inclusion delay 1, proposer 0, no crosslink committee); the
    REPLICATED_INPUT_FIELDS pass through. Host facts
    (build_epoch_inputs_np) pad on the host and stay there, so that they
    can go from the host straight to their shards."""
    V = int(inp.prev_src.shape[0])
    k = vp - V
    assert k >= 0, (vp, V)
    if k == 0:
        return inp
    xp = np if isinstance(inp.prev_src, np.ndarray) else jnp
    f_bool = xp.zeros(k, dtype=bool)
    return inp._replace(
        prev_src=xp.concatenate([inp.prev_src, f_bool]),
        prev_tgt=xp.concatenate([inp.prev_tgt, f_bool]),
        prev_head=xp.concatenate([inp.prev_head, f_bool]),
        curr_tgt=xp.concatenate([inp.curr_tgt, f_bool]),
        incl_delay=xp.concatenate(
            [inp.incl_delay, xp.ones(k, dtype=xp.uint64)]),
        att_proposer=xp.concatenate(
            [inp.att_proposer, xp.zeros(k, dtype=xp.int32)]),
        v_shard=xp.concatenate(
            [inp.v_shard, xp.full(k, -1, dtype=xp.int32)]),
        in_winning=xp.concatenate([inp.in_winning, f_bool]),
    )


# ===========================================================================
# Host bridge: object-model state <-> SoA columns, input distillation
# ===========================================================================

def columns_np_from_state(state) -> dict:
    """Numpy SoA extraction of the registry (shared by the device upload and
    the vectorized input distillation, so the registry is walked once)."""
    vr = state.validator_registry
    n = len(vr)

    def col(f, dtype=np.uint64):
        # map(attrgetter) beats a genexpr ~30% at registry scale (no
        # per-element generator frame) — this walk is the distill floor
        return np.fromiter(map(operator.attrgetter(f), vr), dtype=dtype,
                           count=n)

    return {
        "activation_eligibility_epoch": col("activation_eligibility_epoch"),
        "activation_epoch": col("activation_epoch"),
        "exit_epoch": col("exit_epoch"),
        "withdrawable_epoch": col("withdrawable_epoch"),
        "slashed": col("slashed", dtype=np.bool_),
        "effective_balance": col("effective_balance"),
        "balance": np.fromiter((b for b in state.balances), dtype=np.uint64, count=n),
    }


def columns_from_state(state, np_cols: dict = None) -> ValidatorColumns:
    np_cols = np_cols if np_cols is not None else columns_np_from_state(state)
    return ValidatorColumns(**{f: jnp.asarray(np_cols[f])
                               for f in ValidatorColumns._fields})


def scalars_np_from_state(state) -> EpochScalars:
    """The epoch scalars as host values: what `scalars_from_state` uploads,
    before it does (a serving mesh places them itself, replicated)."""
    return EpochScalars(
        slot=np.uint64(state.slot),
        previous_justified_epoch=np.uint64(state.previous_justified_epoch),
        current_justified_epoch=np.uint64(state.current_justified_epoch),
        justification_bitfield=np.uint64(state.justification_bitfield),
        finalized_epoch=np.uint64(state.finalized_epoch),
        latest_start_shard=np.uint64(state.latest_start_shard),
        latest_slashed_balances=np.array(
            [int(x) for x in state.latest_slashed_balances], dtype=np.uint64),
    )


def scalars_from_state(state) -> EpochScalars:
    return EpochScalars(*map(jnp.asarray, scalars_np_from_state(state)))


# ---------------------------------------------------------------------------
# Vectorized input distillation
#
# The former implementation looped `get_attesting_indices` per attestation
# and `get_winning_crosslink_and_attesting_indices` per shard — O(V·A) host
# Python at 1M validators. This layer computes each epoch's committee layout
# ONCE as numpy arrays (the batched swap-or-not permutation already exists
# behind get_shuffle_permutation), decodes every attestation bitfield ONCE
# with np.unpackbits, and reduces winners/balances with array ops. Reference
# semantics it must reproduce exactly: get_attesting_indices
# (0_beacon-chain.md:905-917), the matching-attestation filters (:1266-1322),
# min-inclusion-delay first-tie order (:1423-1429), and crosslink winner
# selection incl. ties + the default-Crosslink edge (:1308-1322).
# ---------------------------------------------------------------------------

class _Layout(NamedTuple):
    """One epoch's committee layout: committee `off` of `count` is
    shuffled[bounds[off]:bounds[off+1]] (compute_committee :884-891)."""
    epoch: int
    shuffled: np.ndarray     # [A] int64 - active indices in shuffled order
    bounds: np.ndarray       # [count+1] int64
    count: int
    start_shard: int


class EpochContext(NamedTuple):
    """Everything the host distillation derives from the object state."""
    n: int
    np_cols: dict
    layouts: dict            # epoch -> _Layout
    prev_atts: list          # PendingAttestation (previous epoch list)
    curr_atts: list
    prev_parts: list         # [len(prev_atts)] np.ndarray participant indices
    curr_parts: list
    cl_roots: dict           # content tuple -> hash_tree_root(Crosslink)
    eff_shuffled: dict       # epoch -> [A] int64 effective_balance[lay.shuffled]
    winner_groups: dict      # epoch -> _WinnerGroups


def _crosslink_key(c) -> tuple:
    """A Crosslink's content: what keys its root and its candidate group."""
    return (int(c.shard), int(c.start_epoch), int(c.end_epoch),
            bytes(c.parent_root), bytes(c.data_root))


def _crosslink_root(spec, ctx: "EpochContext", c) -> bytes:
    """hash_tree_root(Crosslink) through a content-keyed cache.

    build_epoch_context pre-fills the cache in one vectorized batch
    (_prefill_crosslink_roots: without it these tiny-container
    merkleizations were >half of the 1M-validator distill wall-clock);
    this per-record path is the fallback for a record the batch did not
    hold."""
    key = _crosslink_key(c)
    r = ctx.cl_roots.get(key)
    if r is None:
        r = ctx.cl_roots[key] = spec.hash_tree_root(c)
    return r


def _prefill_crosslink_roots(spec, ctx: "EpochContext", state) -> tuple:
    """Batch every Crosslink merkleization the winner selection will
    query — the state's records + each attestation's candidate + the
    default — into ONE [N, 8, 32] subtree_roots_batch call instead of ~2k
    recursive per-container hash_tree_root walks (those were ~1.2 s of the
    1M-validator distill). Chunk layout per container Merkleization rules
    (simple-serialize.md:134-145): 5 field leaves (three uint64, two
    Bytes32) padded to the next power of two.

    Returns (contents, prev_ids, curr_ids): the distinct contents the walk
    met, in the order it met them, and for every attestation of
    `ctx.prev_atts` and of `ctx.curr_atts` the place of its crosslink's
    content among them — what the candidate groups are keyed by, so that
    no selection builds an attestation's key again."""
    from ...utils.ssz import bulk
    ids = {}
    walked = []
    # (the key inline: a call an attestation is a tenth of this walk)
    for c in itertools.chain(
            state.current_crosslinks,
            (a.data.crosslink for a in ctx.prev_atts),
            (a.data.crosslink for a in ctx.curr_atts),
            (spec.Crosslink(),)):
        key = (int(c.shard), int(c.start_epoch), int(c.end_epoch),
               bytes(c.parent_root), bytes(c.data_root))
        i = ids.get(key)
        if i is None:
            i = ids[key] = len(ids)
        walked.append(i)
    n_records = len(state.current_crosslinks)
    prev_ids, curr_ids = np.split(
        np.array(walked[n_records:-1], dtype=np.int64), [len(ctx.prev_atts)])
    contents = list(ids)
    ks = [k for k in contents if k not in ctx.cl_roots]
    if ks:
        n = len(ks)
        leaves = np.zeros((n, 8, 32), dtype=np.uint8)
        u64s = np.array([(k[0], k[1], k[2]) for k in ks], dtype="<u8")
        leaves[:, 0:3, :8] = u64s.view(np.uint8).reshape(n, 3, 8)
        leaves[:, 3, :] = np.frombuffer(b"".join(k[3] for k in ks),
                                        np.uint8).reshape(n, 32)
        leaves[:, 4, :] = np.frombuffer(b"".join(k[4] for k in ks),
                                        np.uint8).reshape(n, 32)
        roots = bulk.subtree_roots_batch(leaves)
        for i, k in enumerate(ks):
            ctx.cl_roots[k] = roots[i].tobytes()
    return contents, prev_ids, curr_ids


def _committee_count_for_active(spec, active_count: int) -> int:
    return max(1, min(spec.SHARD_COUNT // spec.SLOTS_PER_EPOCH,
                      active_count // spec.SLOTS_PER_EPOCH
                      // spec.TARGET_COMMITTEE_SIZE)) * spec.SLOTS_PER_EPOCH


def _active_count_np(np_cols: dict, epoch: int) -> int:
    return int(np.count_nonzero(
        (np_cols["activation_epoch"] <= np.uint64(epoch))
        & (np.uint64(epoch) < np_cols["exit_epoch"])))


def _start_shard_np(spec, state, np_cols: dict, epoch: int) -> int:
    """get_epoch_start_shard (:741-745) with active counts from columns
    (the helper recomputes the O(V) active list per shard-delta call)."""
    current_epoch = spec.get_current_epoch(state)
    assert epoch <= current_epoch + 1

    def delta(e):
        return min(_committee_count_for_active(spec, _active_count_np(np_cols, e)),
                   spec.SHARD_COUNT - spec.SHARD_COUNT // spec.SLOTS_PER_EPOCH)

    check_epoch = current_epoch + 1
    shard = (state.latest_start_shard + delta(current_epoch)) % spec.SHARD_COUNT
    while check_epoch > epoch:
        check_epoch -= 1
        shard = (shard + spec.SHARD_COUNT - delta(check_epoch)) % spec.SHARD_COUNT
    return shard


def _epoch_layout(spec, state, np_cols: dict, epoch: int) -> _Layout:
    active = np.nonzero(
        (np_cols["activation_epoch"] <= np.uint64(epoch))
        & (np.uint64(epoch) < np_cols["exit_epoch"]))[0].astype(np.int64)
    seed = spec.generate_seed(state, epoch)
    perm = spec.get_shuffle_permutation(len(active), seed)
    shuffled = active[perm] if len(active) else active
    count = _committee_count_for_active(spec, len(active))
    bounds = (len(active) * np.arange(count + 1, dtype=np.int64)) // count
    return _Layout(epoch=epoch, shuffled=shuffled, bounds=bounds, count=count,
                   start_shard=_start_shard_np(spec, state, np_cols, epoch))


class _Bitfields(NamedTuple):
    """One attestation list's aggregation bitfields, unpacked once:
    attestation j's bits are allbits[starts[j]:starts[j] + sizes[j]], over
    the positions lo[j]... of its target epoch's layout."""
    shards: np.ndarray       # [n] int64
    epochs: np.ndarray       # [n] int64 target epoch
    allbits: np.ndarray      # [sum(8 * len(bitfield))] bool
    starts: np.ndarray       # [n + 1] int64
    sizes: np.ndarray        # [n] int64 committee sizes
    lo: np.ndarray           # [n] int64 the committee's first position


def _decode_participants(spec, layouts: dict, atts) -> tuple:
    """Per attestation: participant validator indices
    (get_attesting_indices :905-917; order is irrelevant downstream, so the
    reference's sorted() is dropped), and the unpacked bitfields they were
    read from (None for an empty list).

    Batched: every aggregation bitfield decodes through ONE concatenated
    unpackbits and the committee bounds resolve as one vectorized pass per
    epoch — at a full mainnet epoch (~2k attestations) the per-attestation
    loop below does only the two ragged ops (slice + boolean gather)."""
    if not atts:
        return [], None
    n = len(atts)
    shards = np.fromiter((int(a.data.crosslink.shard) for a in atts),
                         np.int64, n)
    epochs = np.fromiter((int(a.data.target_epoch) for a in atts),
                         np.int64, n)
    bfs = [bytes(a.aggregation_bitfield) for a in atts]
    lo = np.full(n, -1, np.int64)
    hi = np.full(n, -1, np.int64)
    for e, lay in layouts.items():
        m = epochs == e
        if not m.any():
            continue
        offs = (shards[m] + spec.SHARD_COUNT - lay.start_shard) % spec.SHARD_COUNT
        lo[m] = lay.bounds[offs]
        hi[m] = lay.bounds[offs + 1]
    # deterministic diagnostic (the old per-attestation dict lookup raised
    # KeyError) if a target epoch ever escapes build_epoch_context's union
    assert (lo >= 0).all(), "attestation target epoch missing from layouts"
    sizes = hi - lo
    blens = np.fromiter((len(b) for b in bfs), np.int64, n)
    assert (blens == (sizes + 7) // 8).all()  # verify_bitfield :355-361
    allbits = np.unpackbits(np.frombuffer(b"".join(bfs), np.uint8),
                            bitorder="little").astype(bool)
    starts = np.concatenate([[0], np.cumsum(blens * 8)])
    parts = []
    for j in range(n):
        lay = layouts[int(epochs[j])]
        bits = allbits[starts[j]:starts[j] + sizes[j]]
        parts.append(lay.shuffled[lo[j]:hi[j]][bits])
    return parts, _Bitfields(shards=shards, epochs=epochs, allbits=allbits,
                             starts=starts, sizes=sizes, lo=lo)


def build_epoch_context(spec, state, np_cols: dict = None) -> EpochContext:
    """The epoch's layouts, decoded participants, crosslink roots and
    candidate crosslink groups, under "distill.context" with a span a part
    (".layouts", which notes the permutations the shuffle really computed
    inside it, ".participants", ".crosslink_roots", ".winner_groups", which
    notes the groups it formed and the committees with more than one)."""
    with telemetry.span("distill.context"):
        np_cols = np_cols if np_cols is not None else columns_np_from_state(state)
        current_epoch = spec.get_current_epoch(state)
        previous_epoch = spec.get_previous_epoch(state)
        prev_atts = list(spec.get_matching_source_attestations(state, previous_epoch))
        curr_atts = list(spec.get_matching_source_attestations(state, current_epoch))
        layouts = {}
        with telemetry.span("distill.layouts") as sp_lay:
            computed0 = PERMUTATIONS_COMPUTED.value
            for e in {previous_epoch, current_epoch}.union(
                    int(a.data.target_epoch) for a in prev_atts + curr_atts):
                layouts[e] = _epoch_layout(spec, state, np_cols, e)
            # a permutation the cache did not hold is a shuffle (on the
            # device, where the kernel serves) waited for inside distill;
            # the counter is the process's: one host thread is assumed
            sp_lay.note(shuffles=PERMUTATIONS_COMPUTED.value - computed0)
        with telemetry.span("distill.participants"):
            prev_parts, prev_bits = _decode_participants(spec, layouts, prev_atts)
            curr_parts, curr_bits = _decode_participants(spec, layouts, curr_atts)
        ctx = EpochContext(
            # column length, not len(validator_registry): identical for object
            # states, and checkpoint-resumed resident states keep the registry
            # as columns without materializing objects (resident.py)
            n=len(np_cols["slashed"]), np_cols=np_cols, layouts=layouts,
            prev_atts=prev_atts, curr_atts=curr_atts,
            prev_parts=prev_parts, curr_parts=curr_parts,
            cl_roots={}, eff_shuffled={}, winner_groups={},
        )
        with telemetry.span("distill.crosslink_roots"):
            contents, prev_ids, curr_ids = _prefill_crosslink_roots(
                spec, ctx, state)
        with telemetry.span("distill.winner_groups") as sp_groups:
            # (in the genesis epoch the two epochs, and the two lists, are one)
            for epoch in dict.fromkeys((previous_epoch, current_epoch)):
                ctx.winner_groups[epoch] = _winner_groups(
                    spec, ctx, epoch, contents,
                    *((curr_atts, curr_parts, curr_ids, curr_bits)
                      if epoch == current_epoch else
                      (prev_atts, prev_parts, prev_ids, prev_bits)))
            formed = ctx.winner_groups.values()
            sp_groups.note(
                groups=sum(g.formed for g in formed),
                multi_group_committees=sum(len(g.more) for g in formed))
    return ctx


def _union_flags(n: int, parts_iter) -> np.ndarray:
    flags = np.zeros(n, dtype=bool)
    chunks = list(parts_iter)
    if chunks:
        flags[np.concatenate(chunks)] = True
    return flags


def _unslashed_union(ctx: EpochContext, parts_list) -> np.ndarray:
    """get_unslashed_attesting_indices (:1294-1300) as an index array."""
    if not parts_list:
        return np.empty(0, dtype=np.int64)
    if len(parts_list) == 1:
        # the common shape (one candidate attestation per group): bitfield
        # decode already yields unique indices, so the dedupe sort is pure
        # overhead — it was ~half the winner-selection time at 1M
        idx = parts_list[0]
    else:
        idx = np.unique(np.concatenate(parts_list))
    return idx[~ctx.np_cols["slashed"][idx]]


def _balance_of(ctx: EpochContext, idx: np.ndarray) -> int:
    """get_total_balance (:933-941): max(sum of effective balances, 1)."""
    return max(int(ctx.np_cols["effective_balance"][idx].sum()), 1)


def _attestation_data_slot(spec, lay: _Layout, data) -> int:
    """get_attestation_data_slot (:747-754) from the cached layout."""
    off = (int(data.crosslink.shard) + spec.SHARD_COUNT
           - lay.start_shard) % spec.SHARD_COUNT
    return (spec.get_epoch_start_slot(lay.epoch)
            + off // (lay.count // spec.SLOTS_PER_EPOCH))


def _eff_shuffled(ctx: EpochContext, lay: _Layout) -> np.ndarray:
    """[A] effective balances along the layout, gathered once a layout
    and kept on the context: the committees' sums and the candidate
    groups' sums are segment sums of it."""
    eff = ctx.eff_shuffled.get(lay.epoch)
    if eff is None:
        # (uint64 Gwei, far below 2**63: read in place as the sums' type;
        # `clip`: with `raise` numpy gathers into a temporary of its own,
        # and a layout's rows are this registry's by construction)
        eff = ctx.eff_shuffled[lay.epoch] = np.take(
            ctx.np_cols["effective_balance"].view(np.int64), lay.shuffled,
            mode="clip")
    return eff


def _segment_sums(values: np.ndarray, bounds: np.ndarray) -> np.ndarray:
    """[count] int64 sums of values[bounds[k]:bounds[k + 1]], exact; an
    empty segment sums to 0 (`reduceat` alone gives it the element it
    starts at, and has nothing to start at in an empty array)."""
    if len(values) == 0:
        return np.zeros(len(bounds) - 1, np.int64)
    sums = np.add.reduceat(values, bounds[:-1], dtype=np.int64)
    sums[bounds[1:] == bounds[:-1]] = 0
    return sums


class _Group(NamedTuple):
    """One candidate crosslink of one shard with its attestations' facts:
    what get_winning_crosslink_and_attesting_indices (:1308-1322) computes
    a candidate, none of which depends on state.current_crosslinks."""
    crosslink: object        # the first attestation's record
    root: bytes
    parent_root: bytes
    data_root: bytes
    balance: int             # get_attesting_balance: max(sum, 1)
    # unslashed attesting indices; None: the committee's positions set in
    # `_WinnerGroups.attested` (see `_group_indices`)
    indices: np.ndarray


class _WinnerGroups(NamedTuple):
    """One epoch's candidate groups, by committee offset, in the order the
    shard's attestations first name them."""
    first: list              # [count] _Group or None
    more: dict               # off -> [_Group, ...]: a shard's further candidates
    attested: np.ndarray     # [A] bool over lay.shuffled: unslashed attesters
    #                          of every first group in array form

    @property
    def formed(self) -> int:
        return (sum(g is not None for g in self.first)
                + sum(map(len, self.more.values())))


# Participant unions (with their balance sums) really computed: one a
# candidate group, all inside "distill.winner_groups"; a selection pass
# notes the delta over itself, which is 0.
UNIONS_COMPUTED = telemetry.counter("distill.winner_unions_computed")


def _winner_groups(spec, ctx: EpochContext, epoch: int, contents: list,
                   atts, parts, ids: np.ndarray,
                   bits: _Bitfields) -> _WinnerGroups:
    """The candidate groups of every shard of `epoch`: the attestations of
    one list grouped by their crosslink's content (`ids` into `contents`),
    each group's unslashed attesters and attesting balance. A committee's
    first group is taken in position space: its members' bitfields are
    masks over the committee's slice of the layout, so the union is an OR
    into one [A] mask, and the balances of all of them one masked segment
    sum. A shard's further groups (and a group holding an attestation of
    another target epoch, whose bits lie over another layout) go group by
    group through the index arrays."""
    lay = ctx.layouts[epoch]
    first = [None] * lay.count
    more: dict = {}
    attested = np.zeros(len(lay.shuffled), dtype=bool)
    if not atts:
        return _WinnerGroups(first, more, attested)

    # groups numbered in the order the list first names them
    content, first_j, inverse = np.unique(ids, return_index=True,
                                          return_inverse=True)
    by_first = np.argsort(first_j)
    n_groups = len(by_first)
    number = np.empty(n_groups, np.int64)
    number[by_first] = np.arange(n_groups)
    gids = number[inverse]
    content, first_j = content[by_first], first_j[by_first]
    # a group's members, ascending: by_group[cuts[g]:cuts[g + 1]]
    by_group = np.argsort(gids, kind="stable")
    cuts = np.concatenate([[0], np.cumsum(np.bincount(gids))])
    offs = (bits.shards[first_j] + spec.SHARD_COUNT
            - lay.start_shard) % spec.SHARD_COUNT
    # (a shard the epoch has no committee for is never selected for)
    asked = offs < lay.count
    first_of = np.full(lay.count, -1, np.int64)
    backwards = np.flatnonzero(asked)[::-1]
    first_of[offs[backwards]] = backwards    # the last write is the first group
    is_first = np.zeros(n_groups, dtype=bool)
    is_first[first_of[first_of >= 0]] = True
    in_array_form = is_first.copy()
    in_array_form[gids[bits.epochs != epoch]] = False
    # members that lie one after the other in the list are rows of one
    # block of `allbits` (one committee, so one bitfield length)
    in_a_row = (by_group[cuts[1:] - 1] - first_j == np.diff(cuts) - 1).tolist()

    allbits = bits.allbits
    starts, sizes, lo, first_j, members_of = (x.tolist() for x in (
        bits.starts, bits.sizes, bits.lo, first_j, np.diff(cuts)))
    for g in np.flatnonzero(in_array_form).tolist():
        j, members = first_j[g], members_of[g]
        size = sizes[j]
        if members == 1:
            union = allbits[starts[j]:starts[j] + size]
        elif in_a_row[g]:
            union = allbits[starts[j]:starts[j + members]].reshape(
                members, -1).any(axis=0)[:size]
        else:
            union = allbits[starts[j]:starts[j] + size].copy()
            for k in by_group[cuts[g] + 1:cuts[g + 1]].tolist():
                union |= allbits[starts[k]:starts[k] + size]
        attested[lo[j]:lo[j] + size] = union
    if in_array_form.any():
        slashed = ctx.np_cols["slashed"]
        if slashed.any():
            attested &= ~slashed[lay.shuffled]
        balances = np.maximum(_segment_sums(
            _eff_shuffled(ctx, lay) * attested, lay.bounds), 1).tolist()
        UNIONS_COMPUTED.inc(int(np.count_nonzero(in_array_form)))

    content, offs = content.tolist(), offs.tolist()
    for g in np.flatnonzero(asked).tolist():
        key, off = contents[content[g]], offs[g]
        c = atts[first_j[g]].data.crosslink
        if in_array_form[g]:
            balance, idx = balances[off], None
        else:
            idx = _unslashed_union(ctx, [
                parts[j] for j in by_group[cuts[g]:cuts[g + 1]].tolist()])
            balance = _balance_of(ctx, idx)
            UNIONS_COMPUTED.inc()
        root = ctx.cl_roots.get(key) or _crosslink_root(spec, ctx, c)
        group = _Group(c, root, key[3], key[4], balance, idx)
        if is_first[g]:
            first[off] = group
        else:
            more.setdefault(off, []).append(group)
    return _WinnerGroups(first, more, attested)


def _group_indices(ctx: EpochContext, epoch: int, off: int,
                   group: _Group) -> np.ndarray:
    """A selected group's unslashed attesting indices
    (get_unslashed_attesting_indices :1294-1300, unsorted); `None`, the
    default crosslink with no attestation of its own, has none."""
    if group is None:
        return np.empty(0, dtype=np.int64)
    if group.indices is not None:
        return group.indices
    lay = ctx.layouts[epoch]
    lo, hi = lay.bounds[off], lay.bounds[off + 1]
    return lay.shuffled[lo:hi][ctx.winner_groups[epoch].attested[lo:hi]]


def _crosslink_winners(spec, state, ctx: EpochContext, epoch: int) -> list:
    """Per committee offset of `epoch`: (winning_crosslink, its group or
    None, attesting_balance) — the selection of
    get_winning_crosslink_and_attesting_indices (:1308-1322) over the
    context's candidate groups, evaluated against the CURRENT
    state.current_crosslinks (callers control ordering vs record mutation,
    exactly like the reference's sequential loops; it runs three times a
    transition, mirroring process_epoch's ordering :1251-1262, and only the
    filter and the max can differ between the three). `_group_indices`
    gives a group's unslashed attesting indices."""
    with telemetry.span("distill.winners") as sp:
        unions0 = UNIONS_COMPUTED.value
        lay = ctx.layouts[epoch]
        groups = ctx.winner_groups[epoch]
        default_cl = spec.Crosslink()
        default_root = _crosslink_root(spec, ctx, default_cl)
        out = []
        for off, group in enumerate(groups.first):
            if group is None:
                out.append((default_cl, None, 1))
                continue
            shard = (lay.start_shard + off) % spec.SHARD_COUNT
            current_root = _crosslink_root(spec, ctx,
                                           state.current_crosslinks[shard])
            candidates = (group, *groups.more.get(off, ()))
            # the root filter is `current_root in (c.parent_root,
            # hash_tree_root(c))`; strict >: the first maximum wins, like max()
            best = None
            for g in candidates:
                if current_root != g.parent_root and current_root != g.root:
                    continue
                if best is None or ((g.balance, g.data_root)
                                    > (best.balance, best.data_root)):
                    best = g
            if best is not None:
                out.append((best.crosslink, best, best.balance))
                continue
            # max(..., default=Crosslink()): the default still collects
            # attestations whose crosslink equals it (:1318-1321)
            own = next((g for g in candidates if g.root == default_root), None)
            out.append((default_cl, own, 1 if own is None else own.balance))
        sp.note(unions_computed=UNIONS_COMPUTED.value - unions0)
        return out


def _committee_balances(ctx: EpochContext, lay: _Layout) -> np.ndarray:
    """[count] committee effective-balance sums (>=1 each): segment sums
    of the layout's gathered balances."""
    with telemetry.span("distill.committee_balances"):
        return np.maximum(_segment_sums(_eff_shuffled(ctx, lay), lay.bounds),
                          1).astype(np.uint64)


def process_crosslinks_vectorized(spec, state, ctx: EpochContext) -> None:
    """process_crosslinks (:1377-1387) on the decoded context.

    The reference mutates state.current_crosslinks[shard] as it loops
    (epoch, offset) — but within one epoch each offset touches a DISTINCT
    shard (count <= SHARD_COUNT consecutive shards) and selection for a
    shard reads only that shard's record, so the epoch's winners can be
    batch-computed before its updates. Across epochs the sequencing is
    preserved: the current epoch's winners are selected against the
    previous epoch's updated records."""
    with telemetry.span("distill.crosslinks"):
        state.previous_crosslinks = [c for c in state.current_crosslinks]
        for epoch in (spec.get_previous_epoch(state),
                      spec.get_current_epoch(state)):
            lay = ctx.layouts[epoch]
            comm_bal = _committee_balances(ctx, lay).tolist()
            winners = _crosslink_winners(spec, state, ctx, epoch)
            for off, (winner, _, att_bal) in enumerate(winners):
                shard = (lay.start_shard + off) % spec.SHARD_COUNT
                if 3 * att_bal >= 2 * comm_bal[off]:
                    state.current_crosslinks[shard] = winner


def build_epoch_inputs(spec, state, ctx: EpochContext = None) -> EpochInputs:
    """Distill PendingAttestations + committee layout into device arrays
    (on the default device; a serving mesh takes build_epoch_inputs_np's
    host arrays and places them itself)."""
    return EpochInputs(*map(jnp.asarray,
                            build_epoch_inputs_np(spec, state, ctx)))


def build_epoch_inputs_np(spec, state,
                          ctx: EpochContext = None) -> EpochInputs:
    """Distill PendingAttestations + committee layout into host arrays.

    Must be called AFTER process_crosslinks has run on `state` (winner
    selection for deltas reads the updated current_crosslinks, matching the
    reference's process_epoch ordering :1251-1262).
    """
    ctx = ctx if ctx is not None else build_epoch_context(spec, state)
    with telemetry.span("distill.inputs"):
        n = ctx.n
        current_epoch = spec.get_current_epoch(state)
        previous_epoch = spec.get_previous_epoch(state)
        prev_lay = ctx.layouts[previous_epoch]

        # Matching filters (:1266-1290) — cheap per-attestation byte compares
        with telemetry.span("distill.inputs.flags"):
            prev_target_root = spec.get_block_root(state, previous_epoch)
            prev_src = _union_flags(n, ctx.prev_parts)
            prev_tgt = _union_flags(n, (
                p for a, p in zip(ctx.prev_atts, ctx.prev_parts)
                if bytes(a.data.target_root) == prev_target_root))
            prev_head = _union_flags(n, (
                p for a, p in zip(ctx.prev_atts, ctx.prev_parts)
                if bytes(a.data.beacon_block_root) == spec.get_block_root_at_slot(
                    state, _attestation_data_slot(
                        spec, ctx.layouts[int(a.data.target_epoch)], a.data))))
            curr_target_root = spec.get_block_root(state, current_epoch)
            curr_tgt = _union_flags(n, (
                p for a, p in zip(ctx.curr_atts, ctx.curr_parts)
                if bytes(a.data.target_root) == curr_target_root))

        # Min-inclusion-delay attestation per source attester (:1423-1429);
        # python min() keeps the first minimum, so strict < preserves tie order.
        with telemetry.span("distill.inputs.inclusion"):
            incl_delay = np.ones(n, dtype=np.uint64)
            best = np.full(n, np.iinfo(np.uint64).max, dtype=np.uint64)
            att_proposer = np.zeros(n, dtype=np.int32)
            for a, idxs in zip(ctx.prev_atts, ctx.prev_parts):
                better = a.inclusion_delay < best[idxs]
                upd = idxs[better]
                best[upd] = a.inclusion_delay
                incl_delay[upd] = a.inclusion_delay
                att_proposer[upd] = a.proposer_index

        # Crosslink-committee layout + winners for the previous epoch (:1445-1463)
        v_shard = np.full(n, -1, dtype=np.int32)
        shards = ((prev_lay.start_shard + np.arange(prev_lay.count))
                  % spec.SHARD_COUNT).astype(np.int32)
        v_shard[prev_lay.shuffled] = np.repeat(shards, np.diff(prev_lay.bounds))
        shard_att_balance = np.ones(spec.SHARD_COUNT, dtype=np.uint64)
        shard_comm_balance = np.ones(spec.SHARD_COUNT, dtype=np.uint64)
        shard_comm_balance[shards] = _committee_balances(ctx, prev_lay)
        winners = _crosslink_winners(spec, state, ctx, previous_epoch)
        shard_att_balance[shards] = np.array(
            [att_bal for _, _, att_bal in winners], dtype=np.uint64)
        # the winners' attesters: the array-form groups' positions, one
        # scatter through the layout, then the few kept as index arrays
        in_array_form = np.fromiter(
            (g is not None and g.indices is None for _, g, _ in winners),
            bool, prev_lay.count)
        attested = ctx.winner_groups[previous_epoch].attested
        if not in_array_form.all():
            attested = attested & np.repeat(in_array_form,
                                            np.diff(prev_lay.bounds))
        in_winning = np.zeros(n, dtype=bool)
        in_winning[prev_lay.shuffled] = attested
        for _, g, _ in winners:
            if g is not None and g.indices is not None:
                in_winning[g.indices] = True

        # every value att_proposer holds is some attestation's proposer_index
        proposer_table, proposer_rows = proposer_table_np(
            [a.proposer_index for a in ctx.prev_atts],
            proposer_table_capacity(spec))

        return EpochInputs(
            prev_src=prev_src, prev_tgt=prev_tgt, prev_head=prev_head,
            curr_tgt=curr_tgt, incl_delay=incl_delay, att_proposer=att_proposer,
            v_shard=v_shard, in_winning=in_winning,
            shard_att_balance=shard_att_balance,
            shard_comm_balance=shard_comm_balance,
            proposer_table=proposer_table, proposer_rows=proposer_rows)


def process_epoch_soa(spec, state, timings: dict = None):
    """Drop-in replacement for spec.process_epoch using the device program.

    Host handles the byte-rooted bookkeeping (justified/finalized roots,
    randao/index-root/historical rotations, attestation rotation) in the
    reference's exact write order; the device handles every [V]-shaped loop.
    Phase-1 insert hooks (epoch.py:21-26) run at the same points as in
    process_epoch.

    Returns the post-transition device columns (still device-resident) so
    production callers can chain the device state root without a re-upload.
    Stages run under telemetry spans ("epoch.distill", "epoch.perm",
    "epoch.device", "epoch.writeback"; the second "epoch.distill" holds
    the builders' own "distill.*" spans) with honest fences at span exit
    only; when `timings` is given, the span durations are mirrored into it
    under the historical keys ("distill", "perm", "device", "writeback")
    so bench JSON stays comparable — zeros when CSTPU_TELEMETRY=0
    (phase-1's staged path below leaves `timings` untouched).
    """
    if spec._insert_after_registry_updates or spec._insert_after_final_updates:
        # Phase-1 hooks splice between the two fused stages: run the device
        # program staged around them, preserving exact insert ordering.
        return process_epoch_soa_staged(spec, state)

    with telemetry.span("epoch.distill") as sp_cols:
        cfg = EpochConfig.from_spec(spec)
        np_cols = columns_np_from_state(state)
        cols = columns_from_state(state, np_cols)
        scal = scalars_from_state(state)

        current_epoch = spec.get_current_epoch(state)
        previous_epoch = spec.get_previous_epoch(state)

    if timings is not None:
        # The two layout permutations are DEVICE compute (the swap-or-not
        # kernel), not host distillation: warm them into the spec's perm
        # cache under their own span so "epoch.distill" reports host-only
        # work (a resident pipeline reuses the epoch's cached perms).
        with telemetry.span("epoch.perm") as sp_perm:
            for e in (previous_epoch, current_epoch):
                spec.get_shuffle_permutation(
                    _active_count_np(np_cols, e), spec.generate_seed(state, e))
        timings["perm"] = sp_perm.duration

    with telemetry.span("epoch.distill") as sp_inp:
        # Crosslink record updates run on host (byte roots), before input
        # distillation — same order as process_epoch (:1251-1262).
        ctx = build_epoch_context(spec, state, np_cols)
        process_crosslinks_vectorized(spec, state, ctx)
        inp = build_epoch_inputs(spec, state, ctx)
        if timings is not None:
            # fence the async uploads at span exit so transfer cost lands
            # in "epoch.distill", not in the device-program span (tiny
            # per-array fetches). Opt-in exactly as before: a caller that asked for
            # no timings must not pay the per-leaf round trips.
            sp_inp.fence(cols, scal, inp)

    with telemetry.span("epoch.device") as sp_dev:
        dev_cols, dev_scal, dev_report = epoch_transition_device(
            cfg, cols, scal, inp)
        sp_dev.fence(dev_cols.balance)

    with telemetry.span("epoch.writeback") as sp_wb:
        new_cols, new_scal, report = jax.device_get(
            (dev_cols, dev_scal, dev_report))

        _apply_justification(spec, state, new_scal, report,
                             previous_epoch, current_epoch)
        _apply_validator_columns(state, new_cols)
        state.latest_slashed_balances = [
            int(x) for x in np.asarray(new_scal.latest_slashed_balances)]
        state.latest_start_shard = int(new_scal.latest_start_shard)

        # Host-side final updates (:1526-1564), byte-rooted (shared helper)
        spec.final_updates_byte_rooted(state)

    if timings is not None:
        timings["distill"] = sp_cols.duration + sp_inp.duration
        timings["device"] = sp_dev.duration
        timings["writeback"] = sp_wb.duration
    return dev_cols, dev_scal


def _apply_justification(spec, state, new_scal, report,
                         previous_epoch, current_epoch) -> None:
    """Justification scalars + the root writes they gate (:1326-1373)."""
    if bool(report.justification_active):
        state.previous_justified_root = state.current_justified_root
        state.previous_justified_epoch = int(new_scal.previous_justified_epoch)
        state.current_justified_epoch = int(new_scal.current_justified_epoch)
        state.justification_bitfield = int(new_scal.justification_bitfield)
        if bool(report.justified_prev_fired):
            state.current_justified_root = spec.get_block_root(state, previous_epoch)
        if bool(report.justified_curr_fired):
            state.current_justified_root = spec.get_block_root(state, current_epoch)
        state.finalized_epoch = int(new_scal.finalized_epoch)
        if bool(report.finalized_fired):
            state.finalized_root = spec.get_block_root(state, state.finalized_epoch)


def _apply_validator_columns(state, new_cols) -> None:
    """Device columns -> object registry (.tolist() yields python ints ~10x
    faster than per-element int() casts at registry scale); `slashed` is
    excluded — the numeric epoch stages never change it."""
    arrs = {f: np.asarray(getattr(new_cols, f)).tolist()
            for f in ValidatorColumns._fields if f != "slashed"}
    for v, elig, act, exit_ep, wd, eff in zip(
            state.validator_registry, arrs["activation_eligibility_epoch"],
            arrs["activation_epoch"], arrs["exit_epoch"],
            arrs["withdrawable_epoch"], arrs["effective_balance"]):
        v.activation_eligibility_epoch = elig
        v.activation_epoch = act
        v.exit_epoch = exit_ep
        v.withdrawable_epoch = wd
        v.effective_balance = eff
    state.balances = arrs["balance"]


def process_epoch_soa_staged(spec, state):
    """The device epoch path for specs WITH phase-1 insert hooks:
    stage A (justification/rewards/registry) runs as one
    device program, its results materialize to the object state, the
    @process_reveal_deadlines/@process_challenge_deadlines hooks run on
    that state (they slash validators and grow the slashed-balance table),
    then stage B (slashings/final updates) re-distills the mutated columns
    and runs as a second device program — the exact insert ordering of the
    reference's process_epoch (1_custody-game.md:668-716). Differentially
    tested against Phase1Spec.process_epoch in tests/test_phase1.py."""
    cfg = EpochConfig.from_spec(spec)
    np_cols = columns_np_from_state(state)
    cols = columns_from_state(state, np_cols)
    scal = scalars_from_state(state)
    current_epoch = spec.get_current_epoch(state)
    previous_epoch = spec.get_previous_epoch(state)

    ctx = build_epoch_context(spec, state, np_cols)
    process_crosslinks_vectorized(spec, state, ctx)
    inp = build_epoch_inputs(spec, state, ctx)

    mid = jax.device_get(_stage_a_jit(cfg, cols, scal, inp))
    mid_cols, mid_scal, report = mid
    _apply_justification(spec, state, mid_scal, report,
                         previous_epoch, current_epoch)
    _apply_validator_columns(state, mid_cols)

    for hook in spec._insert_after_registry_updates:
        hook(state)

    cols2 = columns_from_state(state)
    scal2 = scalars_from_state(state)
    dev_cols, dev_scal = _stage_b_jit(cfg, cols2, scal2)
    b_cols, b_scal = jax.device_get((dev_cols, dev_scal))
    _apply_validator_columns(state, b_cols)
    state.latest_slashed_balances = [int(x) for x in np.asarray(b_scal.latest_slashed_balances)]
    state.latest_start_shard = int(b_scal.latest_start_shard)

    spec.final_updates_byte_rooted(state)
    for hook in spec._insert_after_final_updates:
        hook(state)
    return dev_cols, dev_scal


def synthetic_epoch_state(cfg: EpochConfig, V: int, rng,
                          slashed_p: float = 0.05,
                          incl_delay_max: int = 8,
                          random_eligibility: bool = False,
                          random_slashed_balances: bool = False):
    """Plausible random (cols, scal, inp) for dryruns and mesh tests —
    the ONE example-state builder shared by __graft_entry__ and
    tests/test_multichip.py so placement/shape drift cannot split them.
    Proposers are a block chain's: a few validators (one an including
    block) listed in the proposer table, att_proposer drawn from them."""
    FAR = cfg.FAR_FUTURE_EPOCH
    MAX_EB = 32_000_000_000
    if random_eligibility:
        elig = jnp.asarray(np.where(rng.random(V) < 0.1, FAR, 0).astype(np.uint64))
        act = jnp.asarray(np.where(rng.random(V) < 0.1, FAR, 0).astype(np.uint64))
    else:
        elig = jnp.zeros(V, jnp.uint64)
        act = jnp.zeros(V, jnp.uint64)
    cols = ValidatorColumns(
        activation_eligibility_epoch=elig,
        activation_epoch=act,
        exit_epoch=jnp.full(V, FAR, jnp.uint64),
        withdrawable_epoch=jnp.full(V, FAR, jnp.uint64),
        slashed=jnp.asarray(rng.random(V) < slashed_p),
        effective_balance=jnp.full(V, MAX_EB, jnp.uint64),
        balance=jnp.asarray(
            rng.integers(MAX_EB - 10 ** 9, MAX_EB + 10 ** 9, V).astype(np.uint64)),
    )
    if random_slashed_balances:
        lsb = jnp.asarray(rng.integers(
            0, 10 ** 12, cfg.LATEST_SLASHED_EXIT_LENGTH).astype(np.uint64))
    else:
        lsb = jnp.zeros(cfg.LATEST_SLASHED_EXIT_LENGTH, jnp.uint64)
    scal = EpochScalars(
        slot=jnp.uint64(10 * cfg.SLOTS_PER_EPOCH - 1),
        previous_justified_epoch=jnp.uint64(7),
        current_justified_epoch=jnp.uint64(8),
        justification_bitfield=jnp.uint64(0b1111),
        finalized_epoch=jnp.uint64(7),
        latest_start_shard=jnp.uint64(0),
        latest_slashed_balances=lsb,
    )
    comm_bal = np.maximum(
        np.full(cfg.SHARD_COUNT, (V // max(1, cfg.SHARD_COUNT)) * MAX_EB,
                dtype=np.uint64), 1)
    # proposers as a chain of blocks leaves them: every attester's
    # att_proposer is one of the few validators that proposed an including
    # block, and the table lists exactly those
    proposers = rng.choice(V, size=min(V, including_blocks(cfg)),
                           replace=False)
    proposer_table, proposer_rows = proposer_table_np(
        proposers, proposer_table_capacity(cfg))
    inp = EpochInputs(
        prev_src=jnp.asarray(rng.random(V) < 0.95),
        prev_tgt=jnp.asarray(rng.random(V) < 0.90),
        prev_head=jnp.asarray(rng.random(V) < 0.85),
        curr_tgt=jnp.asarray(rng.random(V) < 0.90),
        incl_delay=jnp.asarray(
            rng.integers(1, incl_delay_max + 1, V).astype(np.uint64)),
        att_proposer=jnp.asarray(rng.choice(proposers, V).astype(np.int32)),
        v_shard=jnp.asarray(rng.integers(0, cfg.SHARD_COUNT, V).astype(np.int32)),
        in_winning=jnp.asarray(rng.random(V) < 0.90),
        shard_att_balance=jnp.asarray((comm_bal * 9) // 10 + 1),
        shard_comm_balance=jnp.asarray(comm_bal),
        proposer_table=jnp.asarray(proposer_table),
        proposer_rows=jnp.asarray(proposer_rows),
    )
    return cols, scal, inp


# ---------------------------------------------------------------------------
# Trace-tier kernel contract (tools/analysis/trace/, `make contracts`)
# ---------------------------------------------------------------------------
# The fused epoch program at a canonical minimal-preset shape: graph-size
# ratchet, f64/callback/transfer hygiene, and — the resident epoch
# boundary's buffer-reuse guarantee — every ValidatorColumns input's
# donation must survive lowering of the donated form (the variant
# accelerator backends dispatch; CPU runs undonated for the persistent-
# cache aliasing reason documented at epoch_transition_device).

def _epoch_contract_build():
    from . import get_spec
    cfg = EpochConfig.from_spec(get_spec("minimal"))
    cols, scal, inp = synthetic_epoch_state(
        cfg, 64, np.random.default_rng(1))
    return dict(
        fn=_epoch_transition_traced,
        args=(cfg, cols, scal, inp),
        jit_kwargs=dict(static_argnums=(0,), donate_argnums=(1,)))


TRACE_CONTRACTS = [
    dict(
        name="models.phase0.epoch_soa.epoch_transition",
        build=_epoch_contract_build,
        # f64_ops pinned at exactly 2: ops/intmath.isqrt_u64's deliberate
        # float64 Newton seed (exact for n < 2^63, one-step corrected).
        # Any OTHER float64 creeping into the uint64 Gwei math fails.
        budgets={"jaxpr_eqns": 2_000, "f64_ops": 2},
        exact=("f64_ops",),
        forbid=("callback", "device_put"),
        donate_min=len(ValidatorColumns._fields),
    ),
]


# ---------------------------------------------------------------------------
# Value-range contract (tools/analysis/ranges/, `make ranges`)
# ---------------------------------------------------------------------------
# The uint64 Gwei/index arithmetic of the WHOLE epoch transition at the
# 10M-validator ceiling, mainnet constants, traced over
# ShapeDtypeStructs (nothing allocates 10M-row columns). What is
# proven: effective-balance sums (10^7 * MAX_EFFECTIVE_BALANCE < 2^58),
# base-reward products, the proposer sums with every attester paying ONE
# table row (a chunk's masked sum over all V gains, added to the reward
# the row had before the loop; the loop's traced trip count, at most
# capacity / PROPOSER_CHUNK = 124 < the unroll window, is proven by
# joining the carries of every turn it may leave at, with no declared
# invariant), exit-queue counts and the activation cut's masked picks,
# the int32 att_proposer / proposer_table index at V = 10^7, and the
# slashing table's int64 3x window — none of it can wrap
# uint64/int64/int32. The declared inputs: proposer_table in [-1, V - 1]
# (-1 is the padding), proposer_rows in [0, capacity]. What is DECLARED
# rather than proven:
# saturating subtractions (`uint64:sub` — the where-masked balance
# decrease idiom), the justification bitfield's shifted-out bit
# (`uint64:shl`), ops/intmath.py's documented 128-bit wrap machinery
# (replaced by exact summaries via `wrap_ok_sources`), and the
# FAR_FUTURE_EPOCH sentinel add inline-suppressed at its site above.

def _epoch_ranges_build(V: int = 10_000_000):
    import jax as _jax
    from . import get_spec
    cfg = EpochConfig.from_spec(get_spec("mainnet"))
    S = _jax.ShapeDtypeStruct
    b = S((V,), jnp.bool_)
    u = S((V,), jnp.uint64)
    cols = ValidatorColumns(u, u, u, u, b, u, u)
    scal = EpochScalars(*([S((), jnp.uint64)] * 6),
                        S((cfg.LATEST_SLASHED_EXIT_LENGTH,), jnp.uint64))
    rows = proposer_table_capacity(cfg)
    inp = EpochInputs(b, b, b, b, u, S((V,), jnp.int32), S((V,), jnp.int32),
                      b, S((cfg.SHARD_COUNT,), jnp.uint64),
                      S((cfg.SHARD_COUNT,), jnp.uint64),
                      S((rows,), jnp.int32), S((), jnp.int32))
    far = {"lo": 0, "hi": cfg.FAR_FUTURE_EPOCH}
    flag = {"lo": 0, "hi": 1}
    epoch = {"lo": 0, "hi": 1 << 19}          # ~12k years of epochs
    ranges = (
        ValidatorColumns(
            activation_eligibility_epoch=far, activation_epoch=far,
            exit_epoch=far, withdrawable_epoch=far, slashed=flag,
            effective_balance={"lo": 0, "hi": cfg.MAX_EFFECTIVE_BALANCE},
            balance={"lo": 0, "hi": 1 << 45}),
        EpochScalars(
            slot={"lo": 0, "hi": 1 << 24},
            previous_justified_epoch=epoch, current_justified_epoch=epoch,
            justification_bitfield={"lo": 0, "hi": (1 << 64) - 1},
            finalized_epoch=epoch,
            latest_start_shard={"lo": 0, "hi": cfg.SHARD_COUNT - 1},
            latest_slashed_balances={"lo": 0, "hi": 1 << 59}),
        EpochInputs(
            prev_src=flag, prev_tgt=flag, prev_head=flag, curr_tgt=flag,
            incl_delay={"lo": 1, "hi": 1 << 24},
            att_proposer={"lo": 0, "hi": V - 1},
            v_shard={"lo": -1, "hi": cfg.SHARD_COUNT - 1}, in_winning=flag,
            shard_att_balance={"lo": 1, "hi": 1 << 58},
            shard_comm_balance={"lo": 1, "hi": 1 << 58},
            proposer_table={"lo": -1, "hi": V - 1},
            proposer_rows={"lo": 0, "hi": rows}),
    )
    return dict(
        fn=lambda c, s, i: _epoch_transition_traced(cfg, c, s, i),
        args=(cols, scal, inp), ranges=ranges)


RANGE_CONTRACTS = [
    dict(
        name="models.phase0.epoch_soa.epoch_ceiling",
        build=_epoch_ranges_build,
        wrap_ok=("uint64:sub", "uint64:shl"),
        wrap_ok_sources=("ops/intmath.py",),
    ),
]


# ---------------------------------------------------------------------------
# Memory contract (tools/analysis/memory/, `make memory`)
# ---------------------------------------------------------------------------
# Peak HBM of the WHOLE epoch transition at the 10M-validator mainnet
# ceiling, modeled by the liveness walk over the same ShapeDtypeStruct
# trace the range contract uses (nothing allocates 10M-row columns).
# The resident-boundary donation (ValidatorColumns in-place, the trace
# tier's donate_min pin) is part of the model: the seven donated [V]
# columns alias their outputs and count ONCE. The declared budget is
# the capacity argument ROADMAP item 4's pod-scale path rests on: the
# single-device peak must clear a 16 GB HBM with the room the serving
# loop needs, and the scaling probes pin the O(V) order so a V^2 temp
# (a [V, V] outer product creeping into the reward math) fails loudly.
# The compiled cross-check runs at a 2^18-validator probe shape — big
# enough that every [V] buffer dominates alignment slack, small enough
# that XLA:CPU compiles it in seconds.

def _epoch_mem_build(V: int = 10_000_000):
    spec = _epoch_ranges_build(V)
    return dict(fn=spec["fn"], args=spec["args"], donate_argnums=(0,),
                fused_calls=(_proposer_chunk_sums.__name__,))


MEM_CONTRACTS = [
    dict(
        name="models.phase0.epoch_soa.epoch_hbm_ceiling",
        build=_epoch_mem_build,
        budget_bytes=4 << 30,          # 4 GiB of a 16 GB HBM at V = 10^7
        scaling=dict(ns=[100_000, 1_000_000, 10_000_000],
                     build=_epoch_mem_build,
                     metric="peak_bytes", max_order=1.0),
        compiled=dict(build=lambda: _epoch_mem_build(1 << 18)),
    ),
]
