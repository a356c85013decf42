"""The plain reference for guarantee 2: one epoch boundary in numpy and
Python integers, from ethereum/consensus-specs v0.6.x
specs/core/0_beacon-chain.md ("Epoch processing", "get_shuffled_index",
"get_crosslink_committee"). It imports nothing of the program and takes
nothing the program has made but the boundary's inputs: the validator
columns fetched before the boundary and the state's small fields as
`plain_ssz.read_state` copied them. The committees come from its own
swap-or-not shuffle (hashlib), so the program's shuffle and the generator's
committee layout are held to it at the cell's full size.

Exact: every product that can pass 64 bits is taken in Python integers,
once per distinct effective balance (there are at most 33 of them).

It covers the deployments the configurations state: every validator active
and none slashed, so registry updates and slashings change nothing. Columns
that say otherwise raise `Unsupported`.
"""
from __future__ import annotations

import hashlib

import numpy as np

from benchmark import plain_ssz


class Unsupported(Exception):
    """The columns describe a registry this reference does not cover."""


def _sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


# -- committees ---------------------------------------------------------------

def shuffle_permutation(n: int, seed: bytes, rounds: int) -> np.ndarray:
    """perm[i] = get_shuffled_index(i, n, seed) for every i: swap-or-not,
    all positions at once, one hash per block of 256 positions a round."""
    assert n < 2 ** 31
    idx = np.arange(n, dtype=np.int32)
    blocks = (n + 255) // 256
    for r in range(rounds):
        rb = bytes([r])
        pivot = int.from_bytes(_sha(seed + rb)[:8], "little") % n
        flip = np.int32(pivot) - idx
        flip += np.where(flip < 0, np.int32(n), np.int32(0))
        position = np.maximum(idx, flip)
        source = np.unpackbits(np.frombuffer(b"".join(
            _sha(seed + rb + b.to_bytes(4, "little")) for b in range(blocks)),
            np.uint8), bitorder="little")    # bit p of the round, p < 256 * blocks
        idx = np.where(source[position].view(bool), flip, idx)
    return idx


class Committees:
    """The crosslink committees of one epoch, every validator active."""

    def __init__(self, C: dict, pre: dict, validators: int, epoch: int,
                 current_epoch: int):
        spe, shards = C["SLOTS_PER_EPOCH"], C["SHARD_COUNT"]
        self.count = max(1, min(
            shards // spe,
            validators // spe // C["TARGET_COMMITTEE_SIZE"])) * spe
        self.delta = min(self.count, shards - shards // spe)
        # get_epoch_start_shard: every epoch has the same delta here
        self.start_shard = (pre["latest_start_shard"] + shards
                            - self.delta * (current_epoch - epoch)) % shards
        mixes = pre["latest_randao_mixes"]
        roots = pre["latest_active_index_roots"]
        seed = _sha(
            mixes[(epoch + len(mixes) - C["MIN_SEED_LOOKAHEAD"]) % len(mixes)]
            + roots[epoch % len(roots)] + epoch.to_bytes(32, "little"))
        self.perm = shuffle_permutation(validators, seed,
                                        C["SHUFFLE_ROUND_COUNT"])
        self.bounds = [validators * i // self.count
                       for i in range(self.count + 1)]
        self.shards = shards

    def offset_of(self, shard: int) -> int:
        return (shard + self.shards - self.start_shard) % self.shards

    def members(self, offset: int) -> np.ndarray:
        return self.perm[self.bounds[offset]:self.bounds[offset + 1]]

    def attesting(self, att: dict) -> np.ndarray:
        """get_attesting_indices: the committee's members whose bit is set."""
        committee = self.members(self.offset_of(att["data"]["crosslink"]["shard"]))
        bits = np.unpackbits(
            np.frombuffer(att["aggregation_bitfield"], np.uint8),
            bitorder="little")[:len(committee)]
        return committee[bits.astype(bool)]


# -- the boundary --------------------------------------------------------------

def _crosslink_root(c: dict) -> bytes:
    return plain_ssz.root_of(c, "Crosslink")


def _per_effective_balance(eff: np.ndarray, fn) -> np.ndarray:
    """fn(effective_balance: int) -> int, in Python integers, once per
    distinct value; the result as a uint64 column."""
    values, inverse = np.unique(eff, return_inverse=True)
    table = np.array([fn(int(v)) for v in values], dtype=np.uint64)
    return table[inverse]


def integer_squareroot(n: int) -> int:
    x, y = n, (n + 1) // 2
    while y < x:
        x, y = y, (y + n // y) // 2
    return x


def boundary(C: dict, pre: dict, cols: dict) -> dict:
    """process_epoch on `cols` (numpy columns before the boundary) and `pre`
    (the small fields at the epoch's last slot, before its process_slots).
    Returns the columns and the small fields it changes, as they must be
    after the boundary."""
    spe = C["SLOTS_PER_EPOCH"]
    v = int(cols["balance"].shape[0])
    current = pre["slot"] // spe
    previous = max(current - 1, C["GENESIS_EPOCH"])
    if pre["slot"] % spe != spe - 1 or current <= C["GENESIS_EPOCH"] + 1:
        raise Unsupported("not the last slot of an epoch past the second")
    far = np.uint64(C["FAR_FUTURE_EPOCH"])
    if (np.any(cols["activation_epoch"] > np.uint64(previous))
            or np.any(cols["exit_epoch"] != far)
            or np.any(cols["withdrawable_epoch"] != far)
            or np.any(cols["activation_eligibility_epoch"] == far)
            or np.any(cols["slashed"])
            or np.any(cols["effective_balance"]
                      <= np.uint64(C["EJECTION_BALANCE"]))):
        raise Unsupported("a validator is not plainly active")
    eff = np.asarray(cols["effective_balance"], np.uint64)
    total = max(int(eff.sum(dtype=np.uint64)), 1)
    block_roots = pre["latest_block_roots"]

    def block_root_at(slot: int) -> bytes:
        return block_roots[slot % len(block_roots)]

    committees = {e: Committees(C, pre, v, e, current)
                  for e in {previous, current}}
    lists = {previous: pre["previous_epoch_attestations"],
             current: pre["current_epoch_attestations"]}
    attesting = {e: [committees[e].attesting(a) for a in lists[e]]
                 for e in lists}

    def mask_of(epoch: int, keep) -> np.ndarray:
        mask = np.zeros(v, bool)
        for a, members in zip(lists[epoch], attesting[epoch]):
            if keep(a):
                mask[members] = True
        return mask

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
            return np.unique(np.concatenate(
                [m for a, m in here if a["data"]["crosslink"] == c]))
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

    def head_matches(a) -> bool:
        slot = previous * spe + com.offset_of(
            a["data"]["crosslink"]["shard"]) // (com.count // spe)
        return a["data"]["beacon_block_root"] == block_root_at(slot)

    source_mask = mask_of(previous, lambda a: True)
    target_mask = mask_of(previous, target_matches(previous))
    for mask in (source_mask, target_mask, mask_of(previous, head_matches)):
        share = balance_of(mask)
        rewards[mask] += _per_effective_balance(
            eff, lambda e: base_reward(e) * share // total)[mask]
        penalties[~mask] += base[~mask]

    # proposer and inclusion delay: each attester's earliest inclusion
    taken = np.zeros(v, bool)
    for i in sorted(range(len(lists[previous])),
                    key=lambda i: lists[previous][i]["inclusion_delay"]):
        att, members = lists[previous][i], attesting[previous][i]
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
        penalties += np.uint64(C["BASE_REWARDS_PER_EPOCH"]) * base
        late = ~target_mask
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

    # -- final updates ------------------------------------------------------
    inc = np.uint64(C["EFFECTIVE_BALANCE_INCREMENT"])
    half = inc // np.uint64(2)
    move = (balance < eff) | (eff + np.uint64(3) * half < balance)
    out["effective_balance"] = np.where(
        move, np.minimum(balance - balance % inc,
                         np.uint64(C["MAX_EFFECTIVE_BALANCE"])), eff)
    out["balance"] = balance
    out["latest_start_shard"] = (pre["latest_start_shard"]
                                 + committees[current].delta) % C["SHARD_COUNT"]
    return out
