"""Device swap-or-not kernel vs the one-point spec oracle and the numpy path."""
import hashlib

import numpy as np
import pytest

from consensus_specs_tpu.models.phase0 import helpers
from consensus_specs_tpu.models.phase0.spec import get_spec
from consensus_specs_tpu.ops.shuffle import shuffle_permutation_device


@pytest.mark.parametrize("n", [1, 2, 7, 100, 256, 257, 1000])
@pytest.mark.parametrize("seed_byte", [0, 0xAA])
def test_device_matches_point_oracle(n, seed_byte):
    spec = get_spec("minimal")  # 10 rounds
    seed = bytes([seed_byte]) * 32
    perm = shuffle_permutation_device(seed, n, spec.SHUFFLE_ROUND_COUNT)
    assert sorted(perm.tolist()) == list(range(n))
    for i in range(n):
        assert perm[i] == spec.get_shuffled_index(i, n, seed)


def test_device_matches_numpy_mainnet_rounds():
    spec = get_spec("mainnet")  # 90 rounds
    seed = hashlib.sha256(b"shuffle kernel").digest()
    n = 2048
    device = shuffle_permutation_device(seed, n, spec.SHUFFLE_ROUND_COUNT)
    spec.clear_caches()
    host = spec.get_shuffle_permutation(n, seed)
    assert np.array_equal(device, np.asarray(host))


def test_backend_hook_used_and_cached():
    spec = get_spec("minimal")
    spec.clear_caches()
    calls = []

    def backend(seed, n, rounds):
        if n < 50:
            return None
        calls.append((seed, n, rounds))
        return shuffle_permutation_device(seed, n, rounds)

    helpers.set_shuffle_backend(backend)
    try:
        seed = b"\x01" * 32
        p1 = spec.get_shuffle_permutation(100, seed)
        p2 = spec.get_shuffle_permutation(100, seed)  # cache hit
        assert len(calls) == 1 and p1 is p2
        spec.clear_caches()
        small = spec.get_shuffle_permutation(10, seed)  # backend declined -> host
        assert sorted(np.asarray(small).tolist()) == list(range(10))
        assert len(calls) == 1
    finally:
        helpers.set_shuffle_backend(None)
        spec.clear_caches()


@pytest.mark.parametrize("n", [1, 7, 256, 1000, 2048])
def test_stacked_variant_bit_equal(n):
    """The [2, n] stacked-movement A/B variant == the reference kernel."""
    import jax.numpy as jnp

    from consensus_specs_tpu.ops.shuffle import (
        _shuffle_rounds_stacked, host_pivots, shuffle_permutation_on_device)
    from consensus_specs_tpu.ops.sha256 import bytes_to_words

    seed = hashlib.sha256(b"stacked shuffle").digest()
    rounds = 90
    base = np.asarray(shuffle_permutation_on_device(seed, n, rounds))
    seed_words = jnp.asarray(bytes_to_words(np.frombuffer(seed, dtype=np.uint8)))
    stacked = np.asarray(_shuffle_rounds_stacked(
        seed_words, jnp.asarray(host_pivots(seed, n, rounds)), n, rounds))
    assert np.array_equal(base, stacked)
