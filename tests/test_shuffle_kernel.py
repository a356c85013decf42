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


# -- the traced count: one program for an active set that shrinks -------------------

def _hashlib_permutation(n: int, seed: bytes, rounds: int) -> np.ndarray:
    import sys
    from pathlib import Path
    repo = str(Path(__file__).resolve().parents[1])
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark.plain_epoch import shuffle_permutation
    return np.asarray(shuffle_permutation(n, seed, rounds))


@pytest.mark.parametrize("top,rounds", [(1020, 10), (1024, 10), (8192, 90),
                                        (300_000, 10)])
def test_consecutive_counts_below_a_capacity_share_one_program(top, rounds):
    """The counts a shrinking active set takes, `top` down to `top - 15`
    (one churn limit at a million validators) and a few further down: each
    permutation equals the hashlib swap-or-not, all at one padded length
    and through ONE compiled program (the count is a traced scalar)."""
    from consensus_specs_tpu.ops.shuffle import _shuffle_rounds, shuffle_capacity
    seed = hashlib.sha256(b"traced count %d" % top).digest()
    capacity = shuffle_capacity(top)
    counts = [n for n in [top - k for k in range(16)] + [top - 15 * k for k in (2, 3)]
              if shuffle_capacity(n) == capacity]
    assert len(counts) >= 16
    assert capacity - top < max(top // 16, 1)
    shuffle_permutation_device(seed, counts[0], rounds)      # the one compile
    before = _shuffle_rounds._cache_size()
    for n in counts:
        got = shuffle_permutation_device(seed, n, rounds)
        assert got.shape == (n,)
        if n <= 8192 or n == top:
            assert np.array_equal(got, _hashlib_permutation(n, seed, rounds)), n
        else:
            assert sorted(got[:2048].tolist()) != got[:2048].tolist() \
                and np.array_equal(np.sort(got), np.arange(n))
    assert _shuffle_rounds._cache_size() == before


@pytest.mark.parametrize("devices", [1, 4], ids=["one-device", "mesh-of-four"])
def test_a_core_whose_active_set_shrinks_compiles_the_shuffle_once(devices):
    """A checkpoint-resumed core (on one device, and with its columns over
    a mesh of four) takes nine epochs of blocks with exits and slashings
    with the device shuffler answering every committee layout: from 1,024
    validators the counts fall by the churn limit's floor an epoch, inside
    one capacity step (32 at this size, 32,768 at a million), every permutation it served
    equals the hashlib swap-or-not, and the shuffle compiled once."""
    import json
    import sys
    from pathlib import Path
    repo = Path(__file__).resolve().parents[1]
    if str(repo) not in sys.path:
        sys.path.insert(0, str(repo))
    from benchmark import seeded_mature
    from benchmark.ops_generator import OpsBlockGenerator
    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    from consensus_specs_tpu.ops import shuffle as shuffle_mod
    from consensus_specs_tpu.parallel.sharding import ServingMesh

    bls.bls_active = False
    spec = get_spec("minimal")
    spec.clear_caches()
    mix = dict(json.loads((repo / "benchmark/traffic/dirty-slots.json").read_text()),
               exits_per_block=1, proposer_slashing_every=4,
               attester_slashing_at=5, attester_slashing_indices=2)
    served = []
    real = shuffle_mod.shuffle_permutation_device

    def backend(seed, n, rounds):
        perm = real(seed, n, rounds)
        served.append((seed, n, rounds, perm))
        return perm
    helpers.set_shuffle_backend(backend)
    telemetry.set_enabled(True)
    retraces = telemetry.counter("watchdog.retrace_events")
    data = seeded_mature.seeded_mature_checkpoint(spec, 1024, 2**31 + 7)
    core = ResidentCore.from_checkpoint(
        spec, data, mesh=None if devices == 1 else ServingMesh.create(devices))
    try:
        generator = OpsBlockGenerator(spec, 2**31 + 7, mix, 1024)
        state = core.state
        core.process_slots(state, int(state.slot) + 1)      # every first compile
        retraces0, programs0 = retraces.value, shuffle_mod._shuffle_rounds._cache_size()
        for _ in range(9 * int(spec.SLOTS_PER_EPOCH)):
            block = generator.block(state)
            if block is not None:
                core.process_block(state, block)
            core.process_slots(state, int(state.slot) + 1)
        counts = sorted({n for _, n, _, _ in served}, reverse=True)
        assert counts[0] == 1024 and len(counts) >= 4
        assert all(a - b == 4 for a, b in zip(counts, counts[1:]))
        assert {shuffle_mod.shuffle_capacity(n) for n in counts} == {1024}
        for seed, n, rounds, perm in served:
            assert np.array_equal(perm, _hashlib_permutation(n, seed, rounds))
        assert shuffle_mod._shuffle_rounds._cache_size() == programs0
        assert retraces.value == retraces0
    finally:
        core._uninstall()
        helpers.set_shuffle_backend(None)
        telemetry.set_enabled(None)
        spec.clear_caches()
