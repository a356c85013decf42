"""The bulk-Merkleizer state-root hook vs the recursive oracle.

process_slot's full-state hash_tree_root (the reference's
hottest loop, 0_beacon-chain.md:1232-1245) must actually route through
utils/ssz/bulk.py when installed. These tests install the hook and drive
real transitions, requiring bit-identical states against the un-hooked
recursive path at every step.
"""
from copy import deepcopy

import pytest

from consensus_specs_tpu.crypto import bls
from consensus_specs_tpu.models import phase0
from consensus_specs_tpu.models.phase0 import helpers
from consensus_specs_tpu.testing.cases.finality import attested_epoch
from consensus_specs_tpu.testing.factories import (
    advance_epoch,
    advance_slots,
    empty_block_next,
    new_attestation,
    seed_genesis_state,
    transition_with_empty_block,
)
from consensus_specs_tpu.utils.ssz.impl import hash_tree_root


@pytest.fixture(scope="module")
def spec():
    return phase0.get_spec("minimal")


@pytest.fixture(autouse=True)
def _bls_off_and_hook():
    old = bls.bls_active
    bls.bls_active = False
    helpers.install_bulk_state_root()
    yield
    helpers.set_state_root_backend(None)
    bls.bls_active = old


def test_hook_returns_oracle_root(spec):
    state = seed_genesis_state(spec, spec.SLOTS_PER_EPOCH * 8)
    hooked = spec.hash_tree_root(state)
    helpers.set_state_root_backend(None)
    assert hooked == spec.hash_tree_root(state) == hash_tree_root(state)


def test_hook_is_actually_consulted(spec):
    state = seed_genesis_state(spec, 8)
    seen = []

    def probe(s):
        seen.append(s)
        return None  # decline -> fall back to oracle

    helpers.set_state_root_backend(probe)
    root = spec.hash_tree_root(state)
    assert seen == [state]
    assert root == hash_tree_root(state)


def test_transitions_identical_with_and_without_hook(spec):
    """Blocks, attestations, and epoch boundaries under the hooked root."""
    base = seed_genesis_state(spec, spec.SLOTS_PER_EPOCH * 8)
    plain = deepcopy(base)

    def script(state):
        advance_epoch(spec, state)
        transition_with_empty_block(spec, state)
        att = new_attestation(spec, state)
        advance_slots(spec, state, spec.MIN_ATTESTATION_INCLUSION_DELAY)
        block = empty_block_next(spec, state)
        block.body.attestations.append(att)
        spec.state_transition(state, block)
        _, _, state = attested_epoch(spec, state, current=True)
        return state

    state = script(base)               # hooked run
    helpers.set_state_root_backend(None)
    plain = script(plain)              # un-hooked run, same script

    assert hash_tree_root(state) == hash_tree_root(plain)


def test_hook_covers_nonempty_operations_state(spec):
    """A state dirtied by slashings/exits still roots identically."""
    state = seed_genesis_state(spec, spec.SLOTS_PER_EPOCH * 8)
    advance_epoch(spec, state)
    transition_with_empty_block(spec, state)
    current_epoch = spec.get_current_epoch(state)
    for i in (1, 5):
        v = state.validator_registry[i]
        v.slashed = True
        v.exit_epoch = current_epoch + 1
        v.withdrawable_epoch = current_epoch + spec.LATEST_SLASHED_EXIT_LENGTH
    state.validator_registry[2].exit_epoch = current_epoch + 4
    hooked = spec.hash_tree_root(state)
    helpers.set_state_root_backend(None)
    assert hooked == spec.hash_tree_root(state)
