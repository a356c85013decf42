"""Bit-equality gate for the state-to-state config-5 path: vectorized
distillation -> one-program device epoch -> device registry/balances roots
from the still-resident output columns, at reduced V on the mainnet preset:
  1. post-state hash_tree_root == the object-model spec.process_epoch
  2. the device roots from post-transition columns == the recursive oracle
     roots of the written-back registry/balances
"""
from copy import deepcopy

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # pairing compiles dominate suite wall-clock

from consensus_specs_tpu.crypto import bls
from consensus_specs_tpu.models import phase0
from consensus_specs_tpu.models.phase0.epoch_soa import process_epoch_soa
from consensus_specs_tpu.testing.states import build_baseline_state
from consensus_specs_tpu.utils.ssz import bulk
from consensus_specs_tpu.utils.ssz.impl import hash_tree_root
from consensus_specs_tpu.utils.ssz.typing import List as SSZList, uint64

V = 256


@pytest.fixture(autouse=True)
def _bls_off():
    old = bls.bls_active
    bls.bls_active = False
    yield
    bls.bls_active = old


def test_state_to_state_path_matches_object_model():
    spec = phase0.get_spec("mainnet")
    spec.clear_caches()
    state = build_baseline_state(spec, V)
    ref = deepcopy(state)

    tm = {}
    dev_cols, _ = process_epoch_soa(spec, state, timings=tm)
    spec.process_epoch(ref)
    assert hash_tree_root(state) == hash_tree_root(ref)
    assert set(tm) == {"distill", "perm", "device", "writeback"}

    # Device roots from the post-transition columns == recursive oracle
    pk = np.zeros((V, 48), np.uint8)
    pk[:, :8] = np.arange(V, dtype=np.uint64).astype(
        "<u8").view(np.uint8).reshape(V, 8)
    wc = np.zeros((V, 32), np.uint8)
    reg_root, bal_root = bulk.registry_and_balances_roots_device(
        pk, wc, dev_cols.activation_eligibility_epoch,
        dev_cols.activation_epoch, dev_cols.exit_epoch,
        dev_cols.withdrawable_epoch, dev_cols.slashed,
        dev_cols.effective_balance, dev_cols.balance)
    assert reg_root == hash_tree_root(
        state.validator_registry, SSZList[spec.Validator])
    assert bal_root == hash_tree_root(state.balances, SSZList[uint64])
