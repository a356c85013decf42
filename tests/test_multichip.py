"""Multi-device sharding correctness: sharded == single-device, bit for bit.

The protocol's data-parallel axis is the validator registry (SURVEY.md §2c);
these tests jit the SAME epoch program once per placement — all inputs on
one device vs `[V]` columns sharded over an explicit 8-device Mesh — and
require bit-identical outputs. XLA inserts the cross-shard collectives
(balance-sum reductions, the proposer sums, the activation-queue sort);
equality proves the sharded program is semantically the single-chip one.

Runs on the virtual 8-device CPU mesh the conftest pins; the driver's
dryrun_multichip does the same check at entry level.
"""
import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from consensus_specs_tpu.models import phase0
from consensus_specs_tpu.parallel import (
    shard_epoch_state, trees_bitwise_equal, validator_mesh)
from consensus_specs_tpu.models.phase0.epoch_soa import (
    EpochConfig, epoch_transition_device, synthetic_epoch_state)

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < N_DEV:
        pytest.skip(f"needs {N_DEV} devices, have {len(jax.devices())}")
    return validator_mesh(n=N_DEV)


@pytest.mark.parametrize("seed", [0, 3])
def test_epoch_transition_sharded_equals_single(mesh, seed):
    spec = phase0.get_spec("minimal")
    cfg = EpochConfig.from_spec(spec)
    V = 64 * N_DEV
    cols, scal, inp = synthetic_epoch_state(
        cfg, V, np.random.default_rng(seed), random_eligibility=True,
        random_slashed_balances=True)

    # shard (device_put copies) BEFORE the single-device run: the direct
    # epoch_transition_device call donates `cols`
    cols_s, scal_s, inp_s = shard_epoch_state(mesh, cols, scal, inp)
    single = epoch_transition_device(cfg, cols, scal, inp)
    jax.block_until_ready(single)

    sharded = jax.jit(
        lambda c, s, i: epoch_transition_device(cfg, c, s, i)
    )(cols_s, scal_s, inp_s)
    jax.block_until_ready(sharded)

    assert trees_bitwise_equal(single, sharded)


def test_grouped_pairing_sharded_equals_single(mesh):
    """The attestation axis (SURVEY §2c axis #1): a batch of aggregate-
    verify pair groups sharded over the mesh must give the single-device
    verdicts bit-for-bit. Groups are independent pair products, so the
    sharded program is embarrassingly parallel until the verdict gather."""
    import jax.numpy as jnp
    from consensus_specs_tpu.ops.bls_jax import (
        grouped_pairing_check, stage_example_groups)
    from consensus_specs_tpu.parallel import shard_leading_axis

    g1, g2 = stage_example_groups(N_DEV)
    single = np.asarray(grouped_pairing_check(jnp.asarray(g1),
                                                   jnp.asarray(g2)))
    assert single.all(), "staged groups must verify"
    g1_s, g2_s = shard_leading_axis(mesh, (jnp.asarray(g1), jnp.asarray(g2)))
    sharded = np.asarray(grouped_pairing_check(g1_s, g2_s))
    np.testing.assert_array_equal(single, sharded)

    # and a failing group must fail identically under sharding
    g1_bad = g1.copy()
    g1_bad[3, 1] = g1_bad[3, 2]   # swap in the wrong pubkey
    single = np.asarray(grouped_pairing_check(jnp.asarray(g1_bad),
                                                   jnp.asarray(g2)))
    g1_s, g2_s = shard_leading_axis(mesh, (jnp.asarray(g1_bad),
                                           jnp.asarray(g2)))
    sharded = np.asarray(grouped_pairing_check(g1_s, g2_s))
    assert not single[3] and not sharded[3]
    np.testing.assert_array_equal(single, sharded)


def test_bulk_merkleizer_sharded_equals_single(mesh):
    """The Merkle leaf axis (SURVEY §2c axis #4): registry + balances roots
    from columns sharded over the mesh == single-device == byte-identical
    roots (the tree reduction crosses shards as the levels shrink)."""
    import jax.numpy as jnp
    from consensus_specs_tpu.parallel import shard_leading_axis
    from consensus_specs_tpu.utils.ssz import bulk

    rng = np.random.default_rng(11)
    V = 256 * N_DEV
    cols = (
        rng.integers(0, 256, (V, 48), dtype=np.uint8),           # pubkeys
        rng.integers(0, 256, (V, 32), dtype=np.uint8),           # wc
        np.zeros(V, np.uint64), np.zeros(V, np.uint64),
        np.zeros(V, np.uint64), np.zeros(V, np.uint64),
        rng.random(V) < 0.01,                                    # slashed
        np.full(V, 32_000_000_000, np.uint64),
        rng.integers(31_000_000_000, 33_000_000_000, V).astype(np.uint64),
    )
    single = bulk.registry_and_balances_roots_device(*cols)
    sharded_cols = shard_leading_axis(mesh, tuple(jnp.asarray(c) for c in cols))
    sharded = bulk.registry_and_balances_roots_device(*sharded_cols)
    assert single == sharded


def test_sharded_output_stays_sharded(mesh):
    """With output shardings left to propagation, the result's [V] columns
    must come back sharded over the mesh — i.e. the partitioner kept the
    program SPMD instead of gathering to one device."""
    spec = phase0.get_spec("minimal")
    cfg = EpochConfig.from_spec(spec)
    cols, scal, inp = synthetic_epoch_state(
        cfg, 64 * N_DEV, np.random.default_rng(1), random_eligibility=True)
    cols_s, scal_s, inp_s = shard_epoch_state(mesh, cols, scal, inp)
    out_cols, _, _ = jax.jit(
        lambda c, s, i: epoch_transition_device(cfg, c, s, i)
    )(cols_s, scal_s, inp_s)
    jax.block_until_ready(out_cols)
    shard_v = NamedSharding(mesh, P("v"))
    assert out_cols.balance.sharding.is_equivalent_to(shard_v, out_cols.balance.ndim)


@pytest.fixture(scope="module")
def serving_mesh():
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    if len(jax.devices()) < N_DEV:
        pytest.skip(f"needs {N_DEV} devices, have {len(jax.devices())}")
    return ServingMesh.create(N_DEV)


def test_sharded_forest_matches_single(serving_mesh):
    """The incremental forest under the ServingMesh: per-shard subtree
    levels sharded over "v", replicated cap tree, and every root — build,
    scattered update, a list that grows inside the capacity across both a
    power of two AND a shard boundary — bit-identical to the single-device tree, at
    the same O(dirty·log V) pair-lane bound."""
    import jax.numpy as jnp
    from consensus_specs_tpu.utils.ssz.incremental import (
        IncrementalMerkleTree, ShardedIncrementalMerkleTree)

    mesh = serving_mesh
    rng = np.random.default_rng(21)
    V = 100                         # deliberately not pow2, not 8-divisible
    leaves = rng.integers(0, 2 ** 32, (V, 8), dtype=np.uint32)
    single = IncrementalMerkleTree(leaves.copy())
    shard = ShardedIncrementalMerkleTree(jnp.asarray(leaves), mesh)
    assert shard.root() == single.root()
    assert shard.n == single.n == V
    assert shard.depth == single.depth
    # materialized pow2 level 0 shards over "v"; the cap levels replicate
    assert shard.levels[0].shape == (128, 8)
    assert shard.levels[0].sharding.is_equivalent_to(mesh.shard_v, 2)
    assert shard.levels[-1].sharding.is_equivalent_to(mesh.replicated, 2)

    # scattered update: same dirty set, same roots, layout preserved
    idx = np.array([0, 5, 63, 99], np.int32)
    rows = rng.integers(0, 2 ** 32, (4, 8), dtype=np.uint32)
    single.update(idx, rows.copy())
    shard.update(idx, rows)
    assert shard.root() == single.root()
    assert shard.last_pairs_per_level == single.last_pairs_per_level
    assert sum(shard.last_pairs_per_level) <= 2 * 4 * shard.depth
    assert shard.levels[0].sharding.is_equivalent_to(mesh.shard_v, 2)

    # the serving loop's bucket update: one program, every level stays on
    # the placement it had, the roots the single-device tree's
    from consensus_specs_tpu.utils.ssz.incremental import bucket_indices
    placed = [level.sharding for level in shard.levels]
    for dirty in ([99], [0, 1, 64, 65, 98], list(range(20, 41))):
        bucket = bucket_indices(np.array(dirty))
        rows = rng.integers(0, 2 ** 32, (len(bucket), 8), dtype=np.uint32)
        rows[len(dirty):] = rows[len(dirty) - 1]    # repeats repeat their rows
        single.update_bucket(bucket, rows.copy())
        shard.update_bucket(bucket, rows)
        assert shard.root() == single.root()
        assert shard.last_pairs_per_level == [len(bucket)] * shard.depth
        for level, was in zip(shard.levels, placed):
            assert level.sharding.is_equivalent_to(was, 2)

    # a list that grows inside the capacity: 100 -> 140 crosses the 128
    # pow2 (and, at 8 devices, a per-shard row boundary) inside trees laid
    # out with 256 rows of room; the bucket program takes the new leaves
    # with the new logical length, no level changes shape or placement
    room = np.zeros((256, 8), np.uint32)
    room[:V] = np.asarray(single.levels[0])[:V]
    single = IncrementalMerkleTree(room.copy(), logical_n=V)
    shard = ShardedIncrementalMerkleTree(jnp.asarray(room), mesh, logical_n=V)
    assert shard.root() == single.root()
    placed = [level.sharding for level in shard.levels]
    bucket = bucket_indices(np.arange(100, 140))
    rows2 = rng.integers(0, 2 ** 32, (len(bucket), 8), dtype=np.uint32)
    rows2[40:] = rows2[39]
    single.update_bucket(bucket, rows2.copy(), logical_n=140)
    shard.update_bucket(bucket, rows2, logical_n=140)
    room[100:140] = rows2[:40]
    assert shard.root() == single.root() \
        == IncrementalMerkleTree(room[:140].copy()).root()
    assert shard.n == single.n == 140
    assert shard.levels[0].shape == (256, 8)
    for level, was in zip(shard.levels, placed):
        assert level.sharding.is_equivalent_to(was, 2)
    assert shard.builds == single.builds == 1   # never a full rebuild


def test_serving_mesh_epoch_padded_equals_single(serving_mesh):
    """The serving layout's inert validator padding is bit-neutral: the
    epoch program over [Vp]-padded sharded columns (V NOT divisible by the
    mesh — the deposit-grown shape) returns the single-device outputs on
    the [V] prefix, replicated scalars equal, and the padding rows stay
    inert for the NEXT boundary too (chained call, zero re-layout)."""
    import jax.numpy as jnp
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        pad_epoch_inputs, pad_validator_columns)
    from consensus_specs_tpu.parallel import trees_bitwise_equal

    mesh = serving_mesh
    spec = phase0.get_spec("minimal")
    cfg = EpochConfig.from_spec(spec)
    V = 64 * N_DEV + 3              # padding must cover 5 inert rows
    cols, scal, inp = synthetic_epoch_state(
        cfg, V, np.random.default_rng(17), random_eligibility=True,
        random_slashed_balances=True)
    vp = mesh.pad_rows(V)
    cols_p = pad_validator_columns(cols, vp, cfg.FAR_FUTURE_EPOCH)
    inp_p = pad_epoch_inputs(inp, vp)

    single = epoch_transition_device(cfg, cols, scal, inp)
    jax.block_until_ready(single)
    sh_cols, sh_scal, sh_rep = mesh.epoch_transition(cfg, cols_p, scal, inp_p)
    jax.block_until_ready(sh_cols)
    trim = type(sh_cols)(*[x[:V] for x in sh_cols])
    assert trees_bitwise_equal(single[0], trim)
    assert trees_bitwise_equal(single[1], sh_scal)
    assert trees_bitwise_equal(single[2], sh_rep)
    # out_shardings matched in_shardings: outputs come back sharded and
    # chain straight into the next boundary without re-layout
    assert sh_cols.balance.sharding.is_equivalent_to(mesh.shard_v, 1)
    next_scal = sh_scal._replace(
        slot=sh_scal.slot + jnp.uint64(cfg.SLOTS_PER_EPOCH))
    sh2_cols, _, _ = mesh.epoch_transition(cfg, sh_cols, next_scal, inp_p)
    single2 = epoch_transition_device(
        cfg, single[0], single[1]._replace(
            slot=single[1].slot + jnp.uint64(cfg.SLOTS_PER_EPOCH)), inp)
    assert trees_bitwise_equal(
        single2[0], type(sh2_cols)(*[x[:V] for x in sh2_cols]))
    assert sh2_cols.balance.sharding.is_equivalent_to(mesh.shard_v, 1)


def test_mesh4_proposer_table_and_activation_cut_equal_single():
    """PR 29's two mechanisms across shards, on the four-device layout of
    the benchmark's mesh cell: a V that is no multiple of four (inert
    rows), proposers on every shard and more of them than one chunk of the
    table, an activation queue longer than the churn whose tied rows lie
    on several shards. The sharded boundary is the one-device boundary,
    bit for bit, and so is the next one chained onto it."""
    import jax.numpy as jnp
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        PROPOSER_CHUNK, pad_epoch_inputs, pad_validator_columns,
        proposer_table_capacity, proposer_table_np)
    from consensus_specs_tpu.parallel import ServingMesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = ServingMesh.create(4)
    cfg = EpochConfig.from_spec(phase0.get_spec("minimal"))
    V = 4 * 150 + 2
    rng = np.random.default_rng(29)
    cols, scal, inp = synthetic_epoch_state(
        cfg, V, rng, random_eligibility=True, random_slashed_balances=True)
    vp = mesh.pad_rows(V)
    proposers = rng.choice(V, size=2 * PROPOSER_CHUNK + 9, replace=False)
    assert set(proposers // (vp // 4)) == {0, 1, 2, 3}
    table, rows = proposer_table_np(proposers, proposer_table_capacity(cfg))
    inp = inp._replace(
        att_proposer=jnp.asarray(rng.choice(proposers, V).astype(np.int32)),
        proposer_table=jnp.asarray(table), proposer_rows=jnp.asarray(rows))
    queued = (np.asarray(cols.activation_eligibility_epoch) == 0) \
        & (np.asarray(cols.activation_epoch) == cfg.FAR_FUTURE_EPOCH)
    assert queued.sum() > cfg.MIN_PER_EPOCH_CHURN_LIMIT
    assert len(set(np.nonzero(queued)[0] // (vp // 4))) > 1

    cols_p = pad_validator_columns(cols, vp, cfg.FAR_FUTURE_EPOCH)
    inp_p = pad_epoch_inputs(inp, vp)
    single = epoch_transition_device(cfg, cols, scal, inp)
    # the cut admitted exactly the churn, the rest of the queue waits
    admitted = queued & (np.asarray(single[0].activation_epoch)
                         != cfg.FAR_FUTURE_EPOCH)
    assert admitted.sum() == cfg.MIN_PER_EPOCH_CHURN_LIMIT

    sh_cols, sh_scal, sh_rep = mesh.epoch_transition(cfg, cols_p, scal, inp_p)
    assert trees_bitwise_equal(single[0],
                               type(sh_cols)(*[x[:V] for x in sh_cols]))
    assert trees_bitwise_equal(single[1:], (sh_scal, sh_rep))
    nxt = jnp.uint64(cfg.SLOTS_PER_EPOCH)
    sh2_cols, _, _ = mesh.epoch_transition(
        cfg, sh_cols, sh_scal._replace(slot=sh_scal.slot + nxt), inp_p)
    single2 = epoch_transition_device(
        cfg, single[0], single[1]._replace(slot=single[1].slot + nxt), inp)
    assert trees_bitwise_equal(
        single2[0], type(sh2_cols)(*[x[:V] for x in sh2_cols]))


def test_serving_mesh_forest_leaf_builders_match_oracle(serving_mesh):
    """registry_forest_leaves / balances_forest_chunks: inert padding rows
    mask to the SSZ virtual-zero rows, real rows equal the single-device
    builders, output placed per row_sharding — and the traced v_count
    means a registry grown INSIDE the same padding reuses the program."""
    import jax.numpy as jnp
    from consensus_specs_tpu.utils.ssz import bulk

    mesh = serving_mesh
    rng = np.random.default_rng(29)
    V, vp = 100, mesh.pad_rows(100)
    pk = rng.integers(0, 256, (vp, 48), dtype=np.uint8)
    wc = rng.integers(0, 256, (vp, 32), dtype=np.uint8)
    epochs = [rng.integers(0, 50, vp).astype(np.uint64) for _ in range(4)]
    slashed = rng.random(vp) < 0.1
    eff = rng.integers(1, 2 ** 35, vp).astype(np.uint64)
    bal = np.where(np.arange(vp) < V,
                   rng.integers(1, 2 ** 35, vp), 0).astype(np.uint64)
    args = [jax.device_put(jnp.asarray(a), mesh.shard_v)
            for a in (pk, wc, *epochs, slashed, eff)]
    leaves = mesh.registry_forest_leaves(*args, v_count=V)
    assert leaves.shape == (128, 8)     # pow2 of the LOGICAL count
    assert leaves.sharding.is_equivalent_to(mesh.shard_v, 2)
    want = np.asarray(bulk.registry_leaf_words_device(
        pk[:V], wc[:V], *[e[:V] for e in epochs], slashed[:V], eff[:V]))
    got = np.asarray(leaves)
    np.testing.assert_array_equal(got[:V], want)
    assert not got[V:].any()            # virtual-zero padding rows

    chunks = mesh.balances_forest_chunks(
        jax.device_put(jnp.asarray(bal), mesh.shard_v), V)
    want_c = np.asarray(bulk.balances_chunk_words_device(bal[:V]))
    assert chunks.shape[0] == 32        # pow2 of ceil(100/4)
    np.testing.assert_array_equal(np.asarray(chunks)[:want_c.shape[0]], want_c)
    assert not np.asarray(chunks)[want_c.shape[0]:].any()


def test_hierarchical_mesh_epoch_equals_single():
    """Multi-host shape: 8 virtual devices arranged as 2 hosts x 4 ICI
    devices (the DCN-outer/ICI-inner mesh of parallel/sharding.py). The
    epoch program over the flattened ("host", "v") sharding must stay
    bit-equal to single-device — the multi-host counterpart of the
    NCCL/MPI backend, expressed as placement."""
    import jax.numpy as jnp

    from consensus_specs_tpu.parallel.sharding import (
        hierarchical_mesh, shard_hierarchical)
    if len(jax.devices()) < N_DEV:
        pytest.skip(f"needs {N_DEV} devices")
    hmesh = hierarchical_mesh(jax.devices()[:N_DEV], hosts=2)
    assert hmesh.devices.shape == (2, 4)

    spec = phase0.get_spec("minimal")
    cfg = EpochConfig.from_spec(spec)
    cols, scal, inp = synthetic_epoch_state(
        cfg, 64 * N_DEV, np.random.default_rng(9), random_eligibility=True)
    # shard first: the direct single-device call donates `cols`
    cols_s = shard_hierarchical(hmesh, cols)
    scal_s = shard_hierarchical(hmesh, scal)  # 0-d scalars replicate
    single = jax.device_get(epoch_transition_device(cfg, cols, scal, inp))
    # per-shard tables replicate; [V] facts shard with the columns
    import jax as _jax
    from jax.sharding import NamedSharding, PartitionSpec
    repl = NamedSharding(hmesh, PartitionSpec())
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        REPLICATED_INPUT_FIELDS)
    flat = NamedSharding(hmesh, PartitionSpec(("host", "v")))
    inp_s = inp._replace(**{
        f: _jax.device_put(getattr(inp, f),
                           repl if f in REPLICATED_INPUT_FIELDS else flat)
        for f in inp._fields})
    sharded = jax.device_get(epoch_transition_device(cfg, cols_s, scal_s, inp_s))
    assert trees_bitwise_equal(single, sharded)


# -- the resident core on a serving mesh against the one-device core -----------

MESH4_V = 4 * 8 + 2     # minimal preset; no multiple of four: two inert rows


def _epoch_avals(mesh):
    """Shapes of the mesh epoch program's (cols, scal, inp) at MESH4_V."""
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        pad_epoch_inputs, pad_validator_columns)
    cfg = EpochConfig.from_spec(phase0.get_spec("minimal"))
    cols, scal, inp = synthetic_epoch_state(
        cfg, MESH4_V, np.random.default_rng(0))
    vp = mesh.pad_rows(MESH4_V)
    return jax.eval_shape(lambda: (
        pad_validator_columns(cols, vp, cfg.FAR_FUTURE_EPOCH), scal,
        pad_epoch_inputs(inp, vp)))


def _resident_run(mesh, blocks, data, on_dispatch=None):
    """Drive a checkpoint-free ResidentCore through `blocks` and take, at
    every epoch boundary it crosses, the logical columns, both forest roots
    and the state root; then the `resident.device` records."""
    from consensus_specs_tpu import telemetry
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    from consensus_specs_tpu.utils.ssz.impl import deserialize

    spec = phase0.get_spec("minimal")
    spec.clear_caches()
    state = deserialize(data, spec.BeaconState)
    telemetry.set_enabled(True)
    telemetry.reset()
    core = ResidentCore(spec, state, mesh=mesh)
    taken = []
    try:
        if on_dispatch is not None:
            real = core._epoch_dispatch

            def dispatch(scal, inp):
                on_dispatch(core, scal, inp)
                return real(scal, inp)
            core._epoch_dispatch = dispatch
        epoch = int(spec.get_current_epoch(state))
        for block in blocks:
            core.state_transition(state, block)
            if int(spec.get_current_epoch(state)) != epoch:
                epoch = int(spec.get_current_epoch(state))
                taken.append((core._materialize_np_cols(),
                              core._registry_balances_roots(),
                              bytes(core._state_root(state))))
        records = [s for s in telemetry.ring() if s["name"] == "resident.device"]
    finally:
        core.exit()
        telemetry.set_enabled(None)
        spec.clear_caches()
    return taken, records


@pytest.fixture(scope="module")
def attested_blocks():
    """Attestation-carrying blocks over two epoch boundaries, built on the
    object model, and the serialized state they start from."""
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.testing import factories
    from consensus_specs_tpu.utils.ssz.impl import serialize

    bls.bls_active = False
    spec = phase0.get_spec("minimal")
    spec.clear_caches()
    state = factories.seed_genesis_state(spec, MESH4_V)
    factories.advance_slots(spec, state, 2)
    data = serialize(state, spec.BeaconState)
    blocks = []
    while int(spec.get_current_epoch(state)) < 2:
        att = factories.new_attestation(spec, state)
        block = factories.empty_block_next(spec, state)
        block.slot = state.slot + spec.MIN_ATTESTATION_INCLUSION_DELAY
        block.body.attestations.append(att)
        spec.state_transition(state, block)
        blocks.append(block)
    spec.clear_caches()
    return data, blocks


def test_resident_mesh_equals_single_over_two_chained_boundaries(
        attested_blocks):
    """Every column, both forest roots and the state root of the sharded
    core equal the one-device core's after each of two chained boundaries
    that hold attestations; and the inputs of each dispatch sit where the
    mesh program takes them once `resident.stage.upload` has closed."""
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    if len(jax.devices()) < 4:
        pytest.skip(f"needs 4 devices, have {len(jax.devices())}")
    data, blocks = attested_blocks
    mesh = ServingMesh.create(4)
    staged = []

    def placement(core, scal, inp):
        _, scal_sh, inp_sh = mesh.epoch_shardings()
        rows = int(core.cols.balance.shape[0])
        assert rows == mesh.pad_rows(MESH4_V) == MESH4_V + 2
        for x, want in zip(tuple(scal) + tuple(inp),
                           tuple(scal_sh) + tuple(inp_sh)):
            assert x.sharding.is_equivalent_to(want, x.ndim), x.sharding
            assert x.committed
        v_cols = [x for x, sh in zip(inp, inp_sh) if sh == mesh.shard_v]
        assert len(v_cols) == 8 and all(x.shape == (rows,) for x in v_cols)
        assert inp.shard_att_balance.sharding.is_fully_replicated
        assert all(x.sharding.is_fully_replicated for x in scal)
        staged.append(rows)

    single, single_records = _resident_run(None, blocks, data)
    sharded, mesh_records = _resident_run(mesh, blocks, data,
                                          on_dispatch=placement)
    assert len(single) == len(sharded) == 2 == len(staged)
    for (cols_1, roots_1, root_1), (cols_4, roots_4, root_4) in zip(
            single, sharded):
        assert set(cols_1) == set(cols_4)
        for f in cols_1:
            assert cols_1[f].shape == (MESH4_V,) == cols_4[f].shape
            assert (cols_1[f] == cols_4[f]).all(), f
        assert roots_1 == roots_4 and root_1 == root_4
    # the mesh program compiles under the function's name (the XLA module a
    # device trace is read by), not as a partial's `jit__unknown`
    (program,) = [pd for key, pd in mesh._jits.items() if key[0] == "epoch"]
    lowered = program.resolve().lower(*_epoch_avals(mesh))
    assert "module @jit__epoch_transition_traced " in lowered.as_text()[:200]
    # the layout that served each boundary rides on its record
    assert [r["args"]["mesh_size"] for r in mesh_records] == [4, 4]
    assert [r["args"]["mesh_size"] for r in single_records] == [1, 1]


def test_resident_device_notes_one_after_the_ladder_leaves_the_mesh(
        attested_blocks):
    """A dispatch that keeps failing before it runs walks the ladder down
    to `degrade_to_single_device`: the inputs staged for the mesh are taken
    to the one device, the boundary is served there, bit-identical, and its
    record says so."""
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    from consensus_specs_tpu.resilience import dispatch as rdispatch, faults
    if len(jax.devices()) < 4:
        pytest.skip(f"needs 4 devices, have {len(jax.devices())}")
    data, blocks = attested_blocks
    single, _ = _resident_run(None, blocks, data)
    faults.set_schedule("seed=7;dispatch:*mesh.epoch*@1-99=raise")
    try:
        degraded, records = _resident_run(ServingMesh.create(4), blocks, data)
    finally:
        faults.set_schedule(None)
        rdispatch.ladder().reset()
    assert [r["args"]["mesh_size"] for r in records] == [1, 1]
    for (cols_1, roots_1, root_1), (cols_d, roots_d, root_d) in zip(
            single, degraded):
        assert all((cols_1[f] == cols_d[f]).all() for f in cols_1)
        assert roots_1 == roots_d and root_1 == root_d
