"""parallel/sharding.py unit coverage on the virtual 8-device CPU mesh.

Direct tests for the placement helpers that previously only ran inside
the multichip dry-run: mesh construction, leading-axis round trips
(values must be bitwise-unchanged by placement), hierarchical mesh
shapes, and the unequal-tree detector the dry-run relies on for its
bitwise verdicts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from consensus_specs_tpu.parallel.sharding import (
    hierarchical_mesh, shard_hierarchical, shard_leading_axis,
    trees_bitwise_equal, validator_mesh)


def _tree():
    return {
        "cols": jnp.arange(64, dtype=jnp.uint64).reshape(8, 8),
        "flat": jnp.arange(16, dtype=jnp.uint32),
        "scalar": jnp.uint64(7),
    }


def test_validator_mesh_uses_all_devices():
    mesh = validator_mesh()
    assert mesh.axis_names == ("v",)
    assert mesh.devices.shape == (len(jax.devices()),)


def test_validator_mesh_subset_and_overask():
    assert validator_mesh(n=4).devices.shape == (4,)
    with pytest.raises(AssertionError):
        validator_mesh(n=len(jax.devices()) + 1)


def test_shard_leading_axis_roundtrip_bitwise():
    mesh = validator_mesh()
    tree = _tree()
    sharded = shard_leading_axis(mesh, tree)
    # placement must not change a single bit
    assert trees_bitwise_equal(tree, sharded)
    # array leaves shard their leading axis over "v"
    assert sharded["cols"].sharding == NamedSharding(mesh, P("v"))
    assert sharded["flat"].sharding == NamedSharding(mesh, P("v"))
    # 0-d leaves replicate
    assert sharded["scalar"].sharding == NamedSharding(mesh, P())
    # every device owns a distinct shard of the leading axis
    devs = {s.device for s in sharded["cols"].addressable_shards}
    assert len(devs) == len(jax.devices())


def test_hierarchical_mesh_shapes():
    assert hierarchical_mesh(hosts=2).devices.shape == (2, 4)
    assert hierarchical_mesh(hosts=4).devices.shape == (4, 2)
    assert hierarchical_mesh(hosts=2).axis_names == ("host", "v")
    with pytest.raises(AssertionError):
        hierarchical_mesh(hosts=3)   # 8 devices don't tile 3 hosts


def test_shard_hierarchical_roundtrip_bitwise():
    mesh = hierarchical_mesh(hosts=2)
    tree = _tree()
    sharded = shard_hierarchical(mesh, tree)
    assert trees_bitwise_equal(tree, sharded)
    # flattened (host, v) product: all 8 devices own leading-axis shards
    assert sharded["cols"].sharding == NamedSharding(mesh, P(("host", "v")))
    devs = {s.device for s in sharded["cols"].addressable_shards}
    assert len(devs) == len(jax.devices())


def test_trees_bitwise_equal_detects_value_drift():
    a = _tree()
    b = _tree()
    assert trees_bitwise_equal(a, b)
    b["flat"] = b["flat"].at[3].set(99)
    assert not trees_bitwise_equal(a, b)


def test_trees_bitwise_equal_detects_dtype_shape_and_arity():
    a = _tree()
    narrower = dict(a, cols=a["cols"].astype(jnp.uint32))
    assert not trees_bitwise_equal(a, narrower)
    reshaped = dict(a, cols=a["cols"].reshape(4, 16))
    assert not trees_bitwise_equal(a, reshaped)
    pruned = {k: v for k, v in a.items() if k != "scalar"}
    assert not trees_bitwise_equal(a, pruned)


def test_trees_bitwise_equal_mixed_host_device_leaves():
    # host compare: numpy and device arrays with identical bits are equal
    a = {"x": np.arange(8, dtype=np.uint64)}
    b = {"x": jnp.arange(8, dtype=jnp.uint64)}
    assert trees_bitwise_equal(a, b)


def test_shard_leading_axis_rejects_non_divisible_axis():
    """A leading axis that does not tile the mesh must raise up front —
    naming the axis size, the mesh size, and the pow2-pad helper — instead
    of letting pjit pad (or reject) unpredictably per jax version."""
    mesh = validator_mesh()
    bad = {"cols": jnp.arange(33, dtype=jnp.uint32)}
    with pytest.raises(ValueError) as exc:
        shard_leading_axis(mesh, bad)
    msg = str(exc.value)
    assert "33" in msg and "8-device" in msg
    assert "pad_leading_pow2" in msg and "64" in msg


def test_pad_leading_pow2_makes_axis_shardable():
    from consensus_specs_tpu.parallel.sharding import pad_leading_pow2
    mesh = validator_mesh()
    x = jnp.arange(33, dtype=jnp.uint32)
    padded = pad_leading_pow2(x, mesh)
    assert padded.shape == (64,)
    assert (np.asarray(padded)[:33] == np.arange(33)).all()
    assert not np.asarray(padded)[33:].any()
    sharded = shard_leading_axis(mesh, padded)   # now accepted
    assert sharded.sharding == NamedSharding(mesh, P("v"))
    # already-divisible axes pass through untouched
    y = jnp.arange(16, dtype=jnp.uint32)
    assert pad_leading_pow2(y, mesh) is y


def test_serving_mesh_from_env(monkeypatch):
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    for off in ("", "0", "off"):
        monkeypatch.setenv("CSTPU_SERVING_MESH", off)
        assert ServingMesh.from_env() is None
    monkeypatch.setenv("CSTPU_SERVING_MESH", "1")
    assert ServingMesh.from_env() is None        # nothing to shard
    monkeypatch.setenv("CSTPU_SERVING_MESH", "4")
    m = ServingMesh.from_env()
    assert m is not None and m.size == 4
    monkeypatch.setenv("CSTPU_SERVING_MESH", "all")
    # "all" rounds DOWN to a power of two (8 virtual devices here)
    assert ServingMesh.from_env().size == 8
    # explicit asks are refused with a clear message, never rounded
    monkeypatch.setenv("CSTPU_SERVING_MESH", "6")
    with pytest.raises(ValueError, match="power of two"):
        ServingMesh.from_env()
    monkeypatch.setenv("CSTPU_SERVING_MESH", "six")
    with pytest.raises(ValueError, match="CSTPU_SERVING_MESH"):
        ServingMesh.from_env()


def test_serving_mesh_padding_and_row_sharding():
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    mesh = ServingMesh.create(8)
    assert mesh.pad_rows(0) == 0
    assert mesh.pad_rows(1) == 8
    assert mesh.pad_rows(32) == 32
    assert mesh.pad_rows(33) == 40
    # forest levels shard while their rows tile the mesh; the cap replicates
    assert mesh.row_sharding(64) == mesh.shard_v
    assert mesh.row_sharding(8) == mesh.shard_v
    assert mesh.row_sharding(4) == mesh.replicated
    assert mesh.row_sharding(1) == mesh.replicated
    with pytest.raises(AssertionError):
        ServingMesh.create(3)                    # mesh size must be pow2


def test_serving_mesh_places_epoch_inputs_from_the_host():
    """Host facts, padded on the host, land in `epoch_shardings()`'s
    placement with their values unchanged: every `[Vp]` fact a quarter a
    device, the scalars and the two shard tables whole on each."""
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        EpochConfig, pad_epoch_inputs, synthetic_epoch_state)
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    mesh = ServingMesh.create(4)
    cfg = EpochConfig.from_spec(phase0.get_spec("minimal"))
    V = 4 * 16 + 3
    _, scal, inp = jax.device_get(
        synthetic_epoch_state(cfg, V, np.random.default_rng(5)))
    padded = pad_epoch_inputs(inp, mesh.pad_rows(V))
    assert all(isinstance(x, np.ndarray) for x in padded)   # still the host's
    assert padded.prev_src.shape == (V + 1,) and not padded.prev_src[V]
    assert padded.v_shard[V] == -1 and padded.incl_delay[V] == 1
    scal_d, inp_d = mesh.place_epoch_inputs(scal, padded)
    _, scal_sh, inp_sh = mesh.epoch_shardings()
    for got, want in zip(tuple(scal_d) + tuple(inp_d),
                         tuple(scal_sh) + tuple(inp_sh)):
        assert got.sharding.is_equivalent_to(want, got.ndim)
    assert [s.data.shape for s in inp_d.prev_src.addressable_shards] \
        == [((V + 1) // 4,)] * 4
    assert len(inp_d.shard_att_balance.addressable_shards) == 4
    assert inp_d.shard_att_balance.sharding.is_fully_replicated
    assert trees_bitwise_equal((scal_d, inp_d), (scal, padded))
