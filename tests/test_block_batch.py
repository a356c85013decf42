"""End-to-end config-3: a block of real attestations verified on device
through ONE batched pipeline.

process_operations collapses the attestation family's signature checks into
JaxBackend.verify_indexed_batch (grouped G1 decompress+aggregate, batched
G2 decompress, batched hash_to_G2, one grouped pairing program). These
tests pin it to the sequential bignum oracle: same post-states, same
failures, under always-on BLS.
"""
from copy import deepcopy

import pytest

from consensus_specs_tpu.crypto import bls
from consensus_specs_tpu.models import phase0
from consensus_specs_tpu.models.phase0 import block as block_mod
from consensus_specs_tpu.testing.states import build_config3_state_and_block
from consensus_specs_tpu.utils.ssz.impl import hash_tree_root

N_KEYS = 8


@pytest.fixture(autouse=True)
def _bls_on():
    old_active, old_batching = bls.bls_active, block_mod._batching_enabled
    bls.bls_active = True
    yield
    bls.bls_active = old_active
    bls.set_backend("python")
    block_mod.set_attestation_batching(old_batching)


def _build(spec, v, n_atts):
    bls.set_backend("python")  # stage signatures with the bignum oracle
    return build_config3_state_and_block(spec, v, n_atts, n_keys=N_KEYS)


def test_batched_block_matches_sequential_oracle():
    """jax-batched process_block == python-sequential on the same block."""
    spec = phase0.get_spec("minimal")
    state, block = _build(spec, 8 * spec.SLOTS_PER_EPOCH, 4)

    ref = deepcopy(state)
    bls.set_backend("python")  # no verify_indexed_batch -> sequential path
    spec.state_transition(ref, block)

    bls.set_backend("jax")
    spec.state_transition(state, block)
    assert hash_tree_root(state) == hash_tree_root(ref)
    assert len(state.previous_epoch_attestations) == 4


def test_batched_equals_forced_sequential_same_backend():
    spec = phase0.get_spec("minimal")
    state, block = _build(spec, 8 * spec.SLOTS_PER_EPOCH, 3)
    bls.set_backend("jax")

    seq = deepcopy(state)
    block_mod.set_attestation_batching(False)
    spec.state_transition(seq, deepcopy(block))
    block_mod.set_attestation_batching(True)
    spec.state_transition(state, block)
    assert hash_tree_root(state) == hash_tree_root(seq)


@pytest.mark.parametrize("backend", ["python", "jax"])
def test_invalid_signature_fails_block(backend):
    spec = phase0.get_spec("minimal")
    state, block = _build(spec, 8 * spec.SLOTS_PER_EPOCH, 3)
    # corrupt the middle attestation's signature (swap with another's)
    block.body.attestations[1].signature = block.body.attestations[2].signature
    bls.set_backend(backend)
    with pytest.raises(AssertionError):
        spec.state_transition(deepcopy(state), block)


def test_wrong_participants_fail_batched():
    """A bitfield naming a non-signer must fail the grouped check."""
    spec = phase0.get_spec("minimal")
    state, block = _build(spec, 8 * spec.SLOTS_PER_EPOCH, 3)
    att = block.body.attestations[0]
    bf = bytearray(att.aggregation_bitfield)
    bf[0] ^= 0x01  # drop one signer from the claimed set
    att.aggregation_bitfield = bytes(bf)
    bls.set_backend("jax")
    with pytest.raises(AssertionError):
        spec.state_transition(deepcopy(state), block)


def test_mainnet_preset_batched_block():
    """always_bls, mainnet preset, jax backend: the batched-block gate."""
    spec = phase0.get_spec("mainnet")
    state, block = _build(spec, 4 * spec.SLOTS_PER_EPOCH, 4)
    bls.set_backend("jax")
    spec.state_transition(state, block)
    assert len(state.previous_epoch_attestations) == 4


def test_state_builders_live_in_the_package():
    """The two whole-state builders moved out of the retired bench.py
    (PR 30): they import from the package, and no top-level script of
    that name is left for a test to lean on."""
    import importlib

    from consensus_specs_tpu.testing import states
    assert states.build_config3_state_and_block is \
        build_config3_state_and_block
    assert callable(states.build_baseline_state)
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("bench")
