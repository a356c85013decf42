"""The main path's kernels compile for the chip — checked WITHOUT the chip.

The TPU compiler is installed here and compiles for a v5e that is
described, not attached (`jax.experimental.topologies`). Nothing runs, so
these say nothing about results or times; they catch what the chip's
compiler refuses — a kernel Mosaic cannot lower, a program whose tiling
blows the 16 GB of HBM — before a chip call is spent on it. The benchmark
(benchmark/run.py) is what runs them; chip_smoke.py runs the Mosaic one.

Code that asks `jax.default_backend()` sees the CPU here and would take
its CPU branch, so each test hands the kernel its TPU-side choices itself
(`unroll=True`, `interpret=False`). Every program in this file compiles in
about a quarter of a minute or less; the slow ones (the donating epoch
program, the 1M registry leaf and forest-build programs) are timed in
CHANGES.md instead.

The topology is described inside a module-scoped fixture — never at
import — and the persistent compile cache is off around these compiles
(an entry written for a described device cannot be read back without one).
"""
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

V = 1_000_000
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn, static_argnames=tuple(static)) \
        .lower(*args, **static).compile()
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes
            - ma.alias_size_in_bytes)
    assert need < HBM_BYTES, f"{need / 2**30:.1f} GiB does not fit a v5e"
    return compiled


@pytest.mark.parametrize("lanes", [1 << 16, 1 << 20])
def test_mosaic_pair_hash_kernel_compiles(one_chip, lanes):
    """The Mosaic body (`_sha256_pairs_kernel`, never the interpreter's
    fori body) at block_lanes=512 — with 64-bit types on, as in every
    serving process (ops/intmath.py turns them on): the chip refused the
    kernel's index maps when their literal 0 traced as an i64 next to
    the i32 grid index."""
    from consensus_specs_tpu.ops import intmath  # noqa: F401 - enables x64
    from consensus_specs_tpu.ops.sha256_pallas import _pairs_transposed
    assert jax.config.jax_enable_x64
    compiled = _compile(_pairs_transposed, one_chip,
                        ((16, lanes), jnp.uint32),
                        block_lanes=512, interpret=False)
    assert "tpu_custom_call" in compiled.as_text()


def test_unrolled_pair_hash_level_compiles(one_chip):
    """One Merkle level of the XLA kernel in the form the chip gets: 64
    statically unrolled rounds (the CPU is pinned to the fori form, so
    no CPU test ever compiles this program)."""
    from consensus_specs_tpu.ops.sha256 import sha256_pairs_inner
    _compile(sha256_pairs_inner, one_chip, ((1 << 19, 16), jnp.uint32),
             unroll=True)


def test_unrolled_pair_hash_equals_hashlib():
    """The unrolled body evaluated eagerly (XLA:CPU cannot compile it):
    the word-major rounds are the same function as hashlib's."""
    from consensus_specs_tpu.ops import sha256 as S
    words = np.random.default_rng(5).integers(
        0, 1 << 32, (5, 16), dtype=np.uint32)
    with jax.disable_jit():
        got = np.asarray(S.sha256_pairs_inner(jnp.asarray(words),
                                              unroll=True))
    raw = S.words_to_bytes(words)
    for i in range(words.shape[0]):
        assert S.words_to_bytes(got[i]).tobytes() \
            == hashlib.sha256(raw[i].tobytes()).digest()


def test_forest_dirty_update_level_compiles(one_chip):
    """One level of the incremental forest's dirty-path re-hash at the 1M
    registry shape: gather 64 sibling pairs out of the resident level,
    pair-hash them, scatter the digests into the parent level (the
    donating scatter, as the chip dispatches it)."""
    from consensus_specs_tpu.ops.sha256 import sha256_pairs_inner
    from consensus_specs_tpu.utils.ssz.incremental import _scatter_rows_pd

    def rehash_level(level, lanes):
        left, right = level[lanes * 2], level[lanes * 2 + 1]
        return sha256_pairs_inner(jnp.concatenate([left, right], axis=1))

    _compile(rehash_level, one_chip,
             ((V, 8), jnp.uint32), ((64,), jnp.int32))
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((V // 2, 8), jnp.uint32), ((64,), jnp.int32), ((64, 8), jnp.uint32))]
    compiled = _scatter_rows_pd.donated.lower(*args).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= V // 2 * 32, \
        "the level scatter no longer updates the resident level in place"


@pytest.mark.parametrize("leaves", [V, V // 4], ids=["registry", "balances"])
def test_forest_bucket_update_compiles_and_updates_in_place(one_chip, leaves):
    """The serving loop's per-slot forest update at the 1M shapes (the
    registry forest's 20 levels, the balances forest's 18): the scatter of
    a bucket of 32 dirty leaves and every level of their paths in one
    program, the unrolled pair hash inside its scan, every level donated."""
    from consensus_specs_tpu.utils.ssz.incremental import _update_bucket_traced
    rows, shapes = leaves, []
    while True:
        shapes.append(jax.ShapeDtypeStruct((rows, 8), jnp.uint32,
                                           sharding=one_chip))
        if rows == 1:
            break
        rows = -(-rows // 2)
    compiled = jax.jit(
        _update_bucket_traced, static_argnames=("unroll",),
        donate_argnums=(0,)).lower(
        tuple(shapes),
        jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((32, 8), jnp.uint32, sharding=one_chip),
        unroll=True).compile()
    ma = compiled.memory_analysis()
    stored = sum(s.shape[0] for s in shapes) * 32
    assert ma.alias_size_in_bytes >= stored, \
        "the bucket update no longer rewrites the resident levels in place"
    assert ma.temp_size_in_bytes < HBM_BYTES // 16


def test_shuffle_program_compiles(one_chip):
    """shuffle_permutation_on_device's program: the whole registry, the
    mainnet round count."""
    from consensus_specs_tpu.ops.shuffle import (_shuffle_rounds,
                                                 shuffle_capacity)
    _compile(_shuffle_rounds, one_chip,
             ((8,), jnp.uint32), ((90,), jnp.int32), ((), jnp.int32),
             capacity=shuffle_capacity(V), rounds=90)


def test_proposer_sum_holds_no_wide_buffer(one_chip):
    """The epoch program's proposer sum (epoch_soa._add_proposer_rewards)
    at the 1M registry and the mainnet table: the compare-select-reduce
    over [V, 128] must stay inside the reduction's fusion. One such
    operand materialised is 128 MB as bool and 1 GB as uint64 a chunk;
    the memory tier's contract declares the call fused (`fused_calls`)
    on the strength of this compile."""
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        PROPOSER_CHUNK, _add_proposer_rewards, proposer_table_capacity)
    rows = proposer_table_capacity(phase0.get_spec("mainnet"))
    assert rows == 15_872 and rows % PROPOSER_CHUNK == 0
    compiled = _compile(_add_proposer_rewards, one_chip,
                        ((V,), jnp.uint64), ((V,), jnp.int32),
                        ((V,), jnp.uint64), ((rows,), jnp.int32),
                        ((), jnp.int32))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < V * PROPOSER_CHUNK // 8, \
        f"{temp / 2**20:.0f} MiB of temporaries: a [V, 128] operand is held"
    assert " while(" in compiled.as_text()      # the traced trip count
