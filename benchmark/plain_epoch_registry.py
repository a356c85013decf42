"""The plain reference for guarantee 2 on a registry that MOVES: one epoch
boundary in numpy and Python integers for columns with exiting, exited and
slashed validators, from ethereum/consensus-specs v0.6.x
specs/core/0_beacon-chain.md ("Epoch processing": justification and
finalization, crosslinks, rewards and penalties, registry updates,
slashings, final updates; "get_crosslink_committee",
"get_epoch_start_shard"). `plain_epoch.boundary` covers the registries in
which every validator is plainly active and raises `Unsupported` on any
other; this one is written for those others and shares its helpers (the
swap-or-not shuffle, the exact per-effective-balance products).

It imports nothing of the program and takes nothing the program has made
but the boundary's inputs: the validator columns as the reference itself
last left them and the state's small fields as `plain_ssz.read_state`
copied them.

What differs from the plainly active case, each as the spec writes it:
the active set is a mask by epoch (`activation_epoch <= e < exit_epoch`),
so an epoch's committees are a shuffle over ITS active indices and the
start shard walks back over each epoch's own shard delta; attesting
balances and crosslink winners count unslashed attesters only; the deltas
go to the eligible validators (active in the previous epoch, or slashed
and not yet withdrawable); `process_registry_updates` ejects and dequeues
(pending activations are `Unsupported`: this mix has none, and the churn
arithmetic of the queue would be untested here); `process_slashings`
charges the slashed at the midpoint of their withdrawal delay; the final
updates write the active-index root of the epoch the delay reaches, which
the comparison holds to hashlib, and roll the slashed balances on.
"""
from __future__ import annotations

import hashlib

import numpy as np

from benchmark import plain_epoch, plain_ssz
from benchmark.plain_epoch import Unsupported, integer_squareroot

_per_effective_balance = plain_epoch._per_effective_balance
_crosslink_root = plain_epoch._crosslink_root


def _sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def active_mask(cols: dict, epoch: int) -> np.ndarray:
    e = np.uint64(epoch)
    return (cols["activation_epoch"] <= e) & (e < cols["exit_epoch"])


def committee_count(C: dict, active: int) -> int:
    spe = C["SLOTS_PER_EPOCH"]
    return max(1, min(C["SHARD_COUNT"] // spe,
                      active // spe // C["TARGET_COMMITTEE_SIZE"])) * spe


def shard_delta(C: dict, active: int) -> int:
    return min(committee_count(C, active),
               C["SHARD_COUNT"] - C["SHARD_COUNT"] // C["SLOTS_PER_EPOCH"])


def start_shard(C: dict, pre: dict, cols: dict, epoch: int, current: int) -> int:
    """get_epoch_start_shard, every epoch by its own active count."""
    if epoch > current + 1:
        raise Unsupported("a start shard beyond the next epoch")
    shards = C["SHARD_COUNT"]

    def delta(e: int) -> int:
        return shard_delta(C, int(np.count_nonzero(active_mask(cols, e))))
    shard = (pre["latest_start_shard"] + delta(current)) % shards
    check = current + 1
    while check > epoch:
        check -= 1
        shard = (shard + shards - delta(check)) % shards
    return shard


class Committees:
    """The crosslink committees of one epoch over its own active set; what
    `plain_block.process_block` and `proposer_index` ask of
    `plain_epoch.Committees`, answered for a registry that moves."""

    def __init__(self, C: dict, pre: dict, cols: dict, epoch: int,
                 current_epoch: int):
        self.active = np.flatnonzero(active_mask(cols, epoch))
        n = int(self.active.shape[0])
        self.count = committee_count(C, n)
        self.delta = shard_delta(C, n)
        self.shards = C["SHARD_COUNT"]
        self.start_shard = start_shard(C, pre, cols, epoch, current_epoch)
        mixes = pre["latest_randao_mixes"]
        roots = pre["latest_active_index_roots"]
        self.seed = _sha(
            mixes[(epoch + len(mixes) - C["MIN_SEED_LOOKAHEAD"]) % len(mixes)]
            + roots[epoch % len(roots)] + epoch.to_bytes(32, "little"))
        self.perm = plain_epoch.shuffle_permutation(
            n, self.seed, C["SHUFFLE_ROUND_COUNT"])
        self.bounds = [n * i // self.count for i in range(self.count + 1)]

    def offset_of(self, shard: int) -> int:
        return (shard + self.shards - self.start_shard) % self.shards

    def members(self, offset: int) -> np.ndarray:
        return self.active[self.perm[self.bounds[offset]:self.bounds[offset + 1]]]

    def attesting(self, att: dict) -> np.ndarray:
        committee = self.members(self.offset_of(att["data"]["crosslink"]["shard"]))
        bits = np.unpackbits(
            np.frombuffer(att["aggregation_bitfield"], np.uint8),
            bitorder="little")[:len(committee)]
        return committee[bits.astype(bool)]


class Shuffles:
    """`Committees` by epoch for the blocks of a chain (what
    `plain_block.Shuffles` is where nobody leaves): kept while the epoch's
    seed stays what it was; the start shard is taken anew from where the
    state stands. `cols` is the reference's own columns, which the caller
    keeps current: an exit written in epoch e leaves the active sets of e
    and e + 1 alone (ACTIVATION_EXIT_DELAY), so a kept shuffle stays true."""

    def __init__(self, C: dict, cols: dict):
        self.C, self.cols = C, cols
        self._kept: dict = {}

    def committees(self, pre: dict, epoch: int, current: int):
        C = self.C
        mixes, roots = pre["latest_randao_mixes"], pre["latest_active_index_roots"]
        key = (mixes[(epoch + len(mixes) - C["MIN_SEED_LOOKAHEAD"]) % len(mixes)],
               roots[epoch % len(roots)])
        com = self._kept.get(epoch)
        if com is None or com.key != key:
            if len(self._kept) > 4:
                self._kept.clear()
            com = Committees(C, pre, self.cols, epoch, current)
            com.key = key
            self._kept[epoch] = com
        else:
            com.start_shard = start_shard(C, pre, self.cols, epoch, current)
        return com


def uint64_list_root(values: np.ndarray) -> bytes:
    """hash_tree_root(List[uint64]) by hashlib: packed chunks, zero chunks
    up to the next power of two, the length mixed in."""
    n = int(values.shape[0])
    data = np.asarray(values, "<u8").tobytes()
    data += b"\x00" * (-len(data) % 32)
    level = [data[i:i + 32] for i in range(0, len(data), 32)] or [plain_ssz.ZERO]
    zero = plain_ssz.ZERO
    while len(level) > 1:
        if len(level) % 2:
            level.append(zero)
        level = [_sha(level[i] + level[i + 1]) for i in range(0, len(level), 2)]
        zero = _sha(zero + zero)
    return plain_ssz.mix_in_length(level[0], n)


def churn_limit(C: dict, cols: dict, epoch: int) -> int:
    return max(C["MIN_PER_EPOCH_CHURN_LIMIT"],
               int(np.count_nonzero(active_mask(cols, epoch)))
               // C["CHURN_LIMIT_QUOTIENT"])


def initiate_validator_exit(C: dict, cols: dict, index: int, current: int) -> None:
    """The spec's, with its two scans of the registry as it writes them."""
    far = np.uint64(C["FAR_FUTURE_EPOCH"])
    if cols["exit_epoch"][index] != far:
        return
    exit_epochs = cols["exit_epoch"][cols["exit_epoch"] != far]
    queue_epoch = current + 1 + C["ACTIVATION_EXIT_DELAY"]
    if exit_epochs.size:
        queue_epoch = max(queue_epoch, int(exit_epochs.max()))
    churn = int(np.count_nonzero(cols["exit_epoch"] == np.uint64(queue_epoch)))
    if churn >= churn_limit(C, cols, current):
        queue_epoch += 1
    cols["exit_epoch"][index] = queue_epoch
    cols["withdrawable_epoch"][index] = \
        queue_epoch + C["MIN_VALIDATOR_WITHDRAWABILITY_DELAY"]


def boundary(C: dict, pre: dict, cols: dict) -> dict:
    """process_epoch on `cols` (numpy columns before the boundary) and `pre`
    (the small fields at the epoch's last slot, before its process_slots).
    Returns the seven columns and the small fields it writes, as they must
    be after the boundary."""
    spe = C["SLOTS_PER_EPOCH"]
    v = int(cols["balance"].shape[0])
    current = pre["slot"] // spe
    previous = max(current - 1, C["GENESIS_EPOCH"])
    if pre["slot"] % spe != spe - 1 or current <= C["GENESIS_EPOCH"] + 1:
        raise Unsupported("not the last slot of an epoch past the second")
    far = np.uint64(C["FAR_FUTURE_EPOCH"])
    if np.any((cols["activation_epoch"] == far)
              & (cols["activation_eligibility_epoch"] != far)) \
            or np.any((cols["activation_eligibility_epoch"] == far)
                      & (cols["effective_balance"]
                         >= np.uint64(C["MAX_EFFECTIVE_BALANCE"]))):
        raise Unsupported("a validator waits to be activated")
    cols = {k: np.array(a, copy=True) for k, a in cols.items()}
    eff = cols["effective_balance"]
    slashed = np.asarray(cols["slashed"], bool)
    active = {e: active_mask(cols, e) for e in {previous, current}}
    total = max(int(eff[active[current]].sum(dtype=np.uint64)), 1)
    block_roots = pre["latest_block_roots"]

    def block_root_at(slot: int) -> bytes:
        return block_roots[slot % len(block_roots)]

    committees = {e: Committees(C, pre, cols, e, current)
                  for e in {previous, current}}
    lists = {previous: pre["previous_epoch_attestations"],
             current: pre["current_epoch_attestations"]}
    attesting = {e: [committees[e].attesting(a) for a in lists[e]]
                 for e in lists}

    def unslashed(members: np.ndarray) -> np.ndarray:
        return members[~slashed[members]]

    def mask_of(epoch: int, keep) -> np.ndarray:
        """get_unslashed_attesting_indices of the epoch's attestations
        that `keep` takes, as a mask."""
        mask = np.zeros(v, bool)
        for a, members in zip(lists[epoch], attesting[epoch]):
            if keep(a):
                mask[members] = True
        return mask & ~slashed

    def balance_of(mask_or_members) -> int:
        return max(int(eff[mask_or_members].sum(dtype=np.uint64)), 1)

    def target_matches(epoch: int):
        want = block_root_at(epoch * spe)
        return lambda a: a["data"]["target_root"] == want

    # -- justification and finalization ---------------------------------
    old_previous = pre["previous_justified_epoch"]
    old_current = pre["current_justified_epoch"]
    out = {"previous_justified_epoch": old_current,
           "previous_justified_root": pre["current_justified_root"],
           "current_justified_epoch": old_current,
           "current_justified_root": pre["current_justified_root"],
           "finalized_epoch": pre["finalized_epoch"],
           "finalized_root": pre["finalized_root"]}
    bitfield = (pre["justification_bitfield"] << 1) % 2 ** 64
    for epoch, bit in ((previous, 1), (current, 0)):
        if balance_of(mask_of(epoch, target_matches(epoch))) * 3 >= total * 2:
            out["current_justified_epoch"] = epoch
            out["current_justified_root"] = block_root_at(epoch * spe)
            bitfield |= 1 << bit
    out["justification_bitfield"] = bitfield
    for shift, window, old, back in ((1, 0b111, old_previous, 3),
                                     (1, 0b11, old_previous, 2),
                                     (0, 0b111, old_current, 2),
                                     (0, 0b11, old_current, 1)):
        if (bitfield >> shift) % (window + 1) == window \
                and old + back == current:
            out["finalized_epoch"] = old
            out["finalized_root"] = block_root_at(old * spe)

    # -- crosslinks --------------------------------------------------------
    crosslinks = [dict(c) for c in pre["current_crosslinks"]]
    out["previous_crosslinks"] = [dict(c) for c in crosslinks]
    by_shard = {e: {} for e in lists}
    for e in lists:
        for a, m in zip(lists[e], attesting[e]):
            by_shard[e].setdefault(a["data"]["crosslink"]["shard"], []).append((a, m))

    def winning(epoch: int, shard: int):
        """get_winning_crosslink_and_attesting_indices, against the
        crosslinks as they stand when it is called."""
        here = by_shard[epoch].get(shard, [])
        current_root = _crosslink_root(crosslinks[shard])
        candidates = [a["data"]["crosslink"] for a, _ in here
                      if current_root in (a["data"]["crosslink"]["parent_root"],
                                          _crosslink_root(a["data"]["crosslink"]))]
        if not candidates:
            return None, np.zeros(0, np.int64)

        def members_for(c):
            return unslashed(np.unique(np.concatenate(
                [m for a, m in here if a["data"]["crosslink"] == c])))
        best = max(candidates,
                   key=lambda c: (balance_of(members_for(c)), c["data_root"]))
        return best, members_for(best)

    for epoch in (previous, current):
        com = committees[epoch]
        for offset in range(com.count):
            shard = (com.start_shard + offset) % com.shards
            best, members = winning(epoch, shard)
            if best is not None and 3 * balance_of(members) \
                    >= 2 * balance_of(com.members(offset)):
                crosslinks[shard] = dict(best)
    out["current_crosslinks"] = crosslinks

    # -- rewards and penalties -----------------------------------------------
    root = integer_squareroot(total)

    def base_reward(e: int) -> int:
        return e * C["BASE_REWARD_FACTOR"] // root // C["BASE_REWARDS_PER_EPOCH"]
    base = _per_effective_balance(eff, base_reward)
    rewards = np.zeros(v, np.uint64)
    penalties = np.zeros(v, np.uint64)
    com = committees[previous]
    eligible = active[previous] | (
        slashed & (np.uint64(previous + 1) < cols["withdrawable_epoch"]))

    def head_matches(a) -> bool:
        slot = previous * spe + com.offset_of(
            a["data"]["crosslink"]["shard"]) // (com.count // spe)
        return a["data"]["beacon_block_root"] == block_root_at(slot)

    source_mask = mask_of(previous, lambda a: True)
    target_mask = mask_of(previous, target_matches(previous))
    for mask in (source_mask, target_mask, mask_of(previous, head_matches)):
        share = balance_of(mask)
        paid = eligible & mask
        rewards[paid] += _per_effective_balance(
            eff, lambda e: base_reward(e) * share // total)[paid]
        unpaid = eligible & ~mask
        penalties[unpaid] += base[unpaid]

    # proposer and inclusion delay: each unslashed attester's earliest inclusion
    taken = np.zeros(v, bool)
    for i in sorted(range(len(lists[previous])),
                    key=lambda i: lists[previous][i]["inclusion_delay"]):
        att, members = lists[previous][i], unslashed(attesting[previous][i])
        mine = members[~taken[members]]
        taken[mine] = True
        rewards[att["proposer_index"]] += (
            base[mine] // np.uint64(C["PROPOSER_REWARD_QUOTIENT"])
        ).sum(dtype=np.uint64)
        rewards[mine] += (base[mine]
                          * np.uint64(C["MIN_ATTESTATION_INCLUSION_DELAY"])
                          // np.uint64(att["inclusion_delay"]))

    finality_delay = previous - out["finalized_epoch"]
    if finality_delay > C["MIN_EPOCHS_TO_INACTIVITY_PENALTY"]:
        penalties[eligible] += np.uint64(C["BASE_REWARDS_PER_EPOCH"]) * base[eligible]
        late = eligible & ~target_mask
        penalties[late] += _per_effective_balance(
            eff, lambda e: e * finality_delay
            // C["INACTIVITY_PENALTY_QUOTIENT"])[late]

    # crosslink deltas, against the crosslinks just updated
    for offset in range(com.count):
        shard = (com.start_shard + offset) % com.shards
        committee = com.members(offset)
        _, members = winning(previous, shard)
        attesting_balance = balance_of(members)
        committee_balance = balance_of(committee)
        inside = np.isin(committee, members)
        paid = committee[inside]
        assert int(base.max()) * attesting_balance < 2 ** 64
        rewards[paid] += (base[paid] * np.uint64(attesting_balance)
                          // np.uint64(committee_balance))
        unpaid = committee[~inside]
        penalties[unpaid] += base[unpaid]

    balance = np.asarray(cols["balance"], np.uint64) + rewards
    balance = np.where(penalties > balance, np.uint64(0), balance - penalties)
    cols["balance"] = balance

    # -- registry updates ------------------------------------------------------
    ejected = np.flatnonzero(
        active[current] & (eff <= np.uint64(C["EJECTION_BALANCE"])))
    for index in ejected:
        initiate_validator_exit(C, cols, int(index), current)
    # the activation queue is empty: no validator waits (checked on entry)

    # -- slashings ---------------------------------------------------------------
    total_now = max(int(eff[active_mask(cols, current)].sum(dtype=np.uint64)), 1)
    table = pre["latest_slashed_balances"]
    length = C["LATEST_SLASHED_EXIT_LENGTH"]
    total_penalties = table[current % length] - table[(current + 1) % length]
    midway = slashed & (np.uint64(current)
                        == cols["withdrawable_epoch"] - np.uint64(length // 2))
    for index in np.flatnonzero(midway):
        e = int(eff[index])
        penalty = max(e * min(total_penalties * 3, total_now) // total_now,
                      e // C["MIN_SLASHING_PENALTY_QUOTIENT"])
        b = int(cols["balance"][index])
        cols["balance"][index] = 0 if penalty > b else b - penalty

    # -- final updates ------------------------------------------------------
    balance = cols["balance"]
    inc = np.uint64(C["EFFECTIVE_BALANCE_INCREMENT"])
    half = inc // np.uint64(2)
    move = (balance < eff) | (eff + np.uint64(3) * half < balance)
    cols["effective_balance"] = np.where(
        move, np.minimum(balance - balance % inc,
                         np.uint64(C["MAX_EFFECTIVE_BALANCE"])), eff)
    out.update(cols)
    out["latest_start_shard"] = (pre["latest_start_shard"]
                                 + committees[current].delta) % C["SHARD_COUNT"]
    reach = current + 1 + C["ACTIVATION_EXIT_DELAY"]
    roots = list(pre["latest_active_index_roots"])
    roots[reach % len(roots)] = uint64_list_root(
        np.flatnonzero(active_mask(cols, reach)).astype(np.uint64))
    out["latest_active_index_roots"] = roots
    table = list(table)
    table[(current + 1) % length] = table[current % length]
    out["latest_slashed_balances"] = table
    return out
