"""The plain reference for guarantee 2 on a registry with an ACTIVATION
QUEUE: one epoch boundary in numpy and Python integers for columns in which
validators wait to be activated, from ethereum/consensus-specs v0.6.x
specs/core/0_beacon-chain.md ("Registry updates": the eligibility pass, the
queue sorted by eligibility epoch and cut at the churn limit;
"get_churn_limit", "get_delayed_activation_exit_epoch").
`plain_epoch_registry.boundary` covers registries that move by exits,
ejections and slashings and raises `Unsupported` when a validator waits;
this one is written for those.

A validator that waits (no activation epoch yet) takes part in nothing else
of the boundary: it is active in neither epoch, so it is in no committee,
no total balance, no reward, no penalty, no ejection and no slashing. So
`plain_epoch_registry.boundary` runs on columns in which the waiting rows
are blank (never eligible, no balance), which it covers, and what the spec
does to a waiting row is done here, in its order:

- the eligibility pass: a row with no eligibility epoch whose effective
  balance (as the boundary found it) has reached MAX_EFFECTIVE_BALANCE is
  eligible from the current epoch;
- the queue: every row with an eligibility epoch whose activation epoch is
  not before the delayed exit epoch of the finalized epoch (as THIS
  boundary's justification leaves it), so rows that were given their
  activation epoch lately still hold their places; sorted by eligibility
  epoch, then index; the first churn-limit many, where they have no
  activation epoch, get the delayed exit epoch of the current one;
- the final updates: the waiting rows' effective balances by the same
  hysteresis as everybody's, and the active-index root of the epoch the
  delay reaches over the activation epochs as they now are.

It imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

from benchmark import plain_epoch_registry
from benchmark.plain_epoch_registry import active_mask, churn_limit, uint64_list_root

COLUMNS = ("activation_eligibility_epoch", "activation_epoch", "exit_epoch",
           "withdrawable_epoch", "slashed", "effective_balance", "balance")


def boundary(C: dict, pre: dict, cols: dict) -> dict:
    """process_epoch on `cols` (numpy columns before the boundary) and `pre`
    (the small fields at the epoch's last slot, before its process_slots):
    the seven columns and the small fields it writes, as they must be
    after the boundary."""
    far = np.uint64(C["FAR_FUTURE_EPOCH"])
    cap = np.uint64(C["MAX_EFFECTIVE_BALANCE"])
    current = pre["slot"] // C["SLOTS_PER_EPOCH"]
    cols = {f: np.array(cols[f]) for f in COLUMNS}
    waiting = cols["activation_epoch"] == far
    blank = {f: a.copy() for f, a in cols.items()}
    blank["activation_eligibility_epoch"][waiting] = far
    blank["effective_balance"][waiting] = 0
    blank["balance"][waiting] = 0
    out = plain_epoch_registry.boundary(C, pre, blank)
    # a waiting row is in no epoch's active set: nothing above moved it
    for f in COLUMNS:
        assert (out[f][waiting] == blank[f][waiting]).all(), f
        out[f] = np.where(waiting, cols[f], out[f])

    # -- registry updates, for the rows that wait ---------------------------
    eligibility = out["activation_eligibility_epoch"]
    eligibility[(eligibility == far) & (cols["effective_balance"] >= cap)] = current
    delay = C["ACTIVATION_EXIT_DELAY"]
    activation = out["activation_epoch"]
    queued = np.flatnonzero(
        (eligibility != far)
        & (activation >= np.uint64(out["finalized_epoch"] + 1 + delay)))
    queue = queued[np.argsort(eligibility[queued], kind="stable")]
    for index in queue[:churn_limit(C, cols, current)]:
        if activation[index] == far:
            activation[index] = current + 1 + delay

    # -- final updates, for the rows that wait -------------------------------
    balance, eff = cols["balance"], cols["effective_balance"]
    inc = np.uint64(C["EFFECTIVE_BALANCE_INCREMENT"])
    half = inc // np.uint64(2)
    move = waiting & ((balance < eff) | (eff + np.uint64(3) * half < balance))
    out["effective_balance"] = np.where(
        move, np.minimum(balance - balance % inc, cap), out["effective_balance"])
    reach = current + 1 + delay
    roots = list(out["latest_active_index_roots"])
    roots[reach % len(roots)] = uint64_list_root(
        np.flatnonzero(active_mask(out, reach)).astype(np.uint64))
    out["latest_active_index_roots"] = roots
    return out
