"""The cell `mainnet-1m-ops.dirty-slots`, as BENCHMARK.json commits it, on the
CPU at a test's size; its mix on four virtual devices; the plain references
of the operations and of the boundary on a moving registry against the
object model.

At V = 16,384 the mainnet preset has two committees of 128 a slot, so a
block carries two aggregates of 128 bits, 16 exits and now and then a
slashing, and the churn limit is its floor of 4 (15 at 1,000,000
validators). The size is twice the other cells' tests': the mix exits 1,024
validators an epoch, and a run of the cell is a dozen epochs. The controls
are control_dirty_slots_on_chip.py's, run here at this size. No timing read
here means anything.
"""
from __future__ import annotations

import json
import sys
from copy import deepcopy
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
for p in (REPO, HERE):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmark import (plain_block, plain_epoch_registry,  # noqa: E402
                       plain_operations, plain_ssz, run)
from benchmark.ops_generator import OpsBlockGenerator  # noqa: E402
import control_dirty_slots_on_chip as controls  # noqa: E402
from test_benchmark_harness import (  # noqa: E402,F401  (fixtures)
    MESH_CELLS, SEED, TINY_V, _before_compare, _failed, drive,
    with_mesh_cells)

CELL = "mainnet-1m-ops.dirty-slots"
SYNC = "mainnet-1m-blocks.sync"
REPLAY = "mainnet-1m.replay"
BROUGHT = ["block_exits_ms", "block_slashings_ms", "registry_write_ms",
           "forest_update_ms", "forest_update_registry_leaves",
           "active_validators_per_epoch", "block_ms.ops",
           "block_attestations_ms.ops", "block_header_ms.ops",
           "block_fallbacks.ops", "idle_in_block.ops",
           "forest_update_roofline", "dirty_leaves_roofline"]
DEVICE_ONLY = {"idle_in_block.ops", "forest_update_roofline",
               "dirty_leaves_roofline"}
NUMBERS = ["registry_root.bytes_differing_from_hashlib",
           "balances_root.bytes_differing_from_hashlib",
           "dirty_slot.state_root.bytes_differing_from_hashlib",
           "block.header_fields_differing_from_reference",
           "block.randao_mix_bytes_differing",
           "block.eth1_votes_differing",
           "block.pending_attestations_differing_from_reference",
           "block.registry_rows_differing_from_reference",
           "block.slashed_balances_differing_from_reference",
           "state_root.bytes_differing_from_hashlib",
           "boundary.balances_differing_from_reference",
           "boundary.effective_balances_differing_from_reference",
           "boundary.other_columns_differing_from_reference",
           "boundary.small_fields_differing_from_reference",
           "boundary.balances_root_after.bytes_differing_from_hashlib",
           "epochs_between_justified_and_previous",
           "block.invalid_blocks_accepted",
           "block.rows_written_by_refused_blocks"]
V = 2 * TINY_V           # sixteen epochs of this mix's exits
V_MESH = V + 2           # no multiple of four: the device columns pad by two rows
MIX = json.loads((REPO / "benchmark/traffic/dirty-slots.json").read_text())


def constants() -> dict:
    C = {}
    for name in ("mainnet", "mainnet.blocks", "mainnet.ops"):
        C.update(json.loads(
            (REPO / "benchmark" / "presets" / f"{name}.json").read_text()))
    return C


# -- the committed entries -------------------------------------------------------

def test_the_configuration_is_mainnet_1m_blocks_with_operations_no_longer_cut():
    cell, was = run.Cell(CELL), run.Cell(SYNC)
    config = cell.config
    assert cell.chips == config["chips"] == 1 and config["validators"] == 1_000_000
    assert config["reduced"] == ["bls_verification", "deposits"]
    assert set(config["reduced_detail"]) == set(config["reduced"])
    assert "registry_operations" in was.config["reduced"]
    assert config["source"] != was.config["source"] and len(config["source"]) <= 200
    for limit in ("MAX_VOLUNTARY_EXITS 16", "MAX_PROPOSER_SLASHINGS 16",
                  "MAX_ATTESTER_SLASHINGS 1", "65,536"):
        assert limit in config["source"]
    same = ("preset", "validators", "chips", "layout", "committees_per_slot",
            "committee_size")
    assert {k: config[k] for k in same} == {k: was.config[k] for k in same}
    for key in ("balances", "identity", "bls_active", "randao_reveal", "eth1_data"):
        assert config["assumed"][key] == was.config["assumed"][key]
    assert "2,049" in config["assumed"]["entry"]
    assert {"finality", "history", "operations"} <= set(config["assumed"])
    assert config["guarantees"][:5] == was.config["guarantees"] \
        and len(config["guarantees"]) == 6
    assert "NotImplementedError" in config["reduced_detail"]["deposits"]
    blocks = config["blocks"]
    assert blocks["attestations_per_block"] \
        == config["committees_per_slot"] * cell.mix["aggregates_per_committee"] == 16
    assert blocks["voluntary_exits_per_block"] == cell.mix["exits_per_block"] == 16
    assert blocks["churn_limit"] == max(4, 1_000_000 // 65_536) == 15


def test_the_mix_is_the_issues():
    mix = run.Cell(CELL).mix
    assert mix["driver"] == "dirty_slots" and mix["warmup_epochs"] == 6
    assert (mix["aggregates_per_committee"], mix["exits_per_block"],
            mix["proposer_slashing_every"], mix["attester_slashing_at"],
            mix["attester_slashing_indices"]) == (1, 16, 8, 32, 4)


def test_the_cell_reports_the_sync_cells_metrics_and_reads_its_own():
    cell, sync, replay = run.Cell(CELL), run.Cell(SYNC), run.Cell(REPLAY)
    assert cell.row["traffic"] == "dirty-slots"
    assert [m["name"] for m in cell.end_to_end] \
        == [m["name"] for m in sync.end_to_end] \
        == ["replay_slots_per_s", "epoch_boundary_s", "slot_root_p95_ms",
            "setup_s"]
    read = [m["name"] for m in cell.per_layer]
    like = [m["name"] for m in replay.per_layer]
    assert len(like) == 27 and read == like + BROUGHT
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for entry in bench["per_layer"]:
        if entry["name"] in BROUGHT:
            assert entry["workloads"] == [CELL]
    # the nine block metrics PR 34 brought stay the sync cell's alone
    assert not set(read) & {"block_ms", "block_header_ms", "block_fallbacks"}


def test_operation_constants_of_the_references_equal_the_programs():
    from consensus_specs_tpu.models import phase0
    spec = phase0.get_spec("mainnet")
    ops = json.loads(
        (REPO / "benchmark" / "presets" / "mainnet.ops.json").read_text())
    assert len(ops) == 7
    for key, value in ops.items():
        if key != "source":
            assert int(getattr(spec, key)) == value, key


def test_the_mature_seed_stands_where_an_exit_is_valid():
    from benchmark import seeded_mature
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.models.phase0.resident import light_state_from_bytes
    spec = phase0.get_spec("mainnet")
    data = seeded_mature.seeded_mature_checkpoint(spec, 256, SEED)
    state = light_state_from_bytes(spec, data)
    epoch = int(spec.PERSISTENT_COMMITTEE_PERIOD) + 1
    assert epoch == 2049 and int(state.slot) == 64 * 2050 - 1
    assert (int(state.previous_justified_epoch), int(state.current_justified_epoch),
            int(state.finalized_epoch)) == (epoch - 2, epoch - 1, epoch - 2)
    assert len(state.eth1_data_votes) == 128 and len(state.historical_roots) == 16
    assert {int(c.end_epoch) for c in state.current_crosslinks} == {epoch - 1}
    assert data == seeded_mature.seeded_mature_checkpoint(spec, 256, SEED)


# -- the cell through `drive` -----------------------------------------------------

def _compared(rows):
    return {r["compared"]: r["got"] for r in rows if "compared" in r}


def test_the_cell_runs_correct_and_reports_its_end_to_end_metrics(drive):
    result, rows = drive(CELL, trace=False, validators=V)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 64 and result["attempted"] % 64 == 0
    assert all(m["value"] > 0 for m in result["metrics"].values())
    compared = _compared(rows)
    assert list(compared)[:len(NUMBERS)] == NUMBERS and not any(compared.values())
    samples = next(r for r in rows if "samples" in r)["samples"]
    assert samples["blocks"] + samples["slots_without_block"] == result["attempted"]
    assert samples["block_fallbacks"] == 0


def test_the_traced_cell_prints_the_operations_layers(monkeypatch, drive):
    # the profiler stops after the first epoch; the window runs on. It has
    # to hold two boundaries for the active set's slope to be read (a line
    # without it is refused), and its first epoch, under the profiler on a
    # busy host, takes many seconds: a long window on a registry of twice
    # the size, which the longer window's exits need
    monkeypatch.setattr(run, "TRACED_SECONDS", 0.0)
    result, rows = drive(CELL, trace=True, seconds=25.0, validators=2 * V)
    assert result["correct"] is True and _failed(rows) == []
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # what only a device plane gives is left out on a host backend
    assert set(BROUGHT) - set(metrics) == DEVICE_ONLY
    assert metrics["block_fallbacks.ops"] == 0 == metrics["compiles_in_window"]
    assert metrics["guard_events"] == 0 == metrics["slot_root_trees_rebuilt"]
    assert metrics["forest_update_registry_leaves"] == 16
    # the window opens six epochs after the first exits: the set shrinks
    # by the churn limit, which at this size is its floor
    assert -4.5 <= metrics["active_validators_per_epoch"] < 0
    parts = sum(metrics[name] for name in (
        "block_header_ms.ops", "block_attestations_ms.ops", "block_exits_ms",
        "block_slashings_ms", "registry_write_ms", "forest_update_ms"))
    assert 0 < parts <= metrics["block_ms.ops"] * 1.5
    # a slot root no longer reads cached forest roots
    assert metrics["slot_root_forests_ms"] > 0


# -- the controls' twins -----------------------------------------------------------

def _control(monkeypatch, drive, fault):
    from benchmark.drivers import dirty_slots
    _before_compare(monkeypatch, dirty_slots, fault)
    result, rows = drive(CELL, trace=False, validators=V)
    assert result["correct"] is False
    return _failed(rows), _compared(rows)


def test_a_dropped_forest_update_makes_correct_false(monkeypatch, drive):
    failed, compared = _control(monkeypatch, drive, controls.forest_update_dropped)
    # the stale paths stand until the boundary rebuilds: the epoch's last
    # root differs too, the boundary (a rebuild from the columns) holds
    assert failed == ["registry_root.bytes_differing_from_hashlib",
                      "balances_root.bytes_differing_from_hashlib",
                      "dirty_slot.state_root.bytes_differing_from_hashlib",
                      "state_root.bytes_differing_from_hashlib"]
    assert compared["block.registry_rows_differing_from_reference"] == 0


def test_an_exit_epoch_one_too_early_makes_correct_false(monkeypatch, drive):
    failed, compared = _control(monkeypatch, drive, controls.exit_epoch_one_too_early)
    assert failed[0] == "block.registry_rows_differing_from_reference"
    # the one exit's exit_epoch and withdrawable_epoch, block after block
    # until the epoch ends (the reference keeps its own columns)
    assert compared["block.registry_rows_differing_from_reference"] % 2 == 0
    assert "block.pending_attestations_differing_from_reference" not in failed
    assert "boundary.balances_differing_from_reference" not in failed


def test_a_slashing_that_skips_the_proposers_reward_makes_correct_false(
        monkeypatch, drive):
    failed, compared = _control(
        monkeypatch, drive, controls.slashing_without_the_proposers_reward)
    assert "block.registry_rows_differing_from_reference" in failed
    assert "block.slashed_balances_differing_from_reference" not in failed
    assert "block.header_fields_differing_from_reference" not in failed


# -- the mix under a mesh ------------------------------------------------------

def test_the_dirty_slots_mix_runs_correct_on_four_devices(
        monkeypatch, drive, with_mesh_cells):
    """The harness's four-chip configuration over this mix: the dirty rows
    go into columns that are sharded and padded, the dirty paths into
    forests whose levels lie on their shards."""
    import jax
    from benchmark.drivers import dirty_slots
    placed = {}
    _before_compare(monkeypatch, dirty_slots, lambda driver: placed.update(
        devices=driver.dep.core.cols.exit_epoch.sharding.device_set,
        rows=driver.dep.core.cols.balance.shape[0]))
    result, rows = drive(MESH_CELLS["dirty-slots"], trace=False,
                         root=with_mesh_cells(), validators=V_MESH)
    assert placed == {"devices": set(jax.devices()[:4]), "rows": V_MESH + 2}
    assert result["correct"] is True and _failed(rows) == []
    assert set(NUMBERS) <= set(_compared(rows))


# -- the plain references against the object model ---------------------------------

def _columns(state) -> dict:
    reg = state.validator_registry
    cols = {f: np.array([getattr(v, f) for v in reg], np.uint64) for f in (
        "activation_eligibility_epoch", "activation_epoch", "exit_epoch",
        "withdrawable_epoch", "effective_balance")}
    cols["slashed"] = np.array([v.slashed for v in reg], bool)
    cols["balance"] = np.array(list(state.balances), np.uint64)
    return cols


@pytest.fixture(scope="module")
def mature_objects():
    """Mainnet preset, 2,048 validators as objects at the mature entry."""
    from benchmark import seeded_mature
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.utils.ssz.impl import deserialize
    bls.bls_active = False
    spec = phase0.get_spec("mainnet")
    spec.clear_caches()
    state = deserialize(
        seeded_mature.seeded_mature_checkpoint(spec, 2048, SEED), spec.BeaconState)
    yield spec, state
    spec.clear_caches()


def test_the_plain_references_leave_what_the_object_model_leaves(mature_objects):
    """Two epochs of this mix's blocks, each held to `spec.process_block`
    on the object state (header, mix, votes, pending attestations, the
    seven columns, latest_slashed_balances), and both boundaries held to
    `spec.process_slots` across them: the second runs on validators the
    first epoch's blocks slashed and on an exit queue 1,000 deep."""
    spec, state = mature_objects[0], deepcopy(mature_objects[1])
    C = constants()
    generator = OpsBlockGenerator(spec, SEED, MIX, 2048)
    ref = _columns(state)
    shuffles = plain_epoch_registry.Shuffles(C, ref)
    spec.process_slots(state, int(state.slot) + 1)      # the entry boundary
    ref.update(_columns(state))
    blocks, exiting, slashed = 0, set(), set()
    while blocks < 2 * 64:
        block = generator.block(state)
        if block is None:       # the slot's proposer is slashed: no block
            assert state.validator_registry[
                spec.get_beacon_proposer_index(state)].slashed
            assert (int(state.slot) + 1) % 64, "an epoch's last slot went without"
            spec.process_slots(state, int(state.slot) + 1)
            continue
        exiting |= {int(e.validator_index) for e in block.body.voluntary_exits}
        slashed |= {int(s.proposer_index) for s in block.body.proposer_slashings}
        for slashing in block.body.attester_slashings:
            slashed |= set(map(int, slashing.attestation_1.custody_bit_0_indices))
        pre = plain_block.read_pre(state)
        pre["latest_slashed_balances"] = [int(x) for x in state.latest_slashed_balances]
        before = {name: len(getattr(state, name)) for name in
                  ("previous_epoch_attestations", "current_epoch_attestations")}
        want = plain_operations.process_block(
            C, pre, ref, plain_block.read_block(block), shuffles)
        spec.process_block(state, block)
        blocks += 1
        assert plain_block.read_value(state.latest_block_header, "BeaconBlockHeader") \
            == want["latest_block_header"]
        assert want["latest_block_header"]["body_root"] \
            == bytes(spec.hash_tree_root(block.body))
        for name, key in (("previous_epoch_attestations", "previous_appended"),
                          ("current_epoch_attestations", "current_appended")):
            assert plain_block.read_pending(getattr(state, name)[before[name]:]) \
                == want[key]
        got = _columns(state)
        assert all((got[f] == ref[f]).all() for f in got)
        assert [int(x) for x in state.latest_slashed_balances] \
            == want["latest_slashed_balances"]
        if (int(state.slot) + 1) % 64 == 0:
            small = plain_ssz.read_state(state)
            after = plain_epoch_registry.boundary(C, small, ref)
            spec.process_slots(state, int(state.slot) + 1)
            got, post = _columns(state), plain_ssz.read_state(state)
            for key, value in after.items():
                if key in got:
                    assert (got[key] == value).all(), key
                    ref[key][:] = value
                else:
                    assert post[key] == value, key
        else:
            spec.process_slots(state, int(state.slot) + 1)
    # two epochs of the mix are this registry's every validator
    assert int(ref["slashed"].sum()) == len(slashed) >= 2 * 8 + 4
    assert int((ref["exit_epoch"] != np.uint64(2**64 - 1)).sum()) \
        == len(exiting | slashed) > 2000
    # the churn limit's floor: four an exit epoch, queued far ahead
    assert int(ref["exit_epoch"][ref["exit_epoch"] != np.uint64(2**64 - 1)].max()) \
        > 2051 + 4 + 500


@pytest.mark.parametrize("spoil,why", [
    ("exit_of_an_exiting_validator", "exiting already"),
    ("exit_dated_in_the_future", "dated in the future"),
    ("proposer_slashing_of_equal_headers", "headers are equal"),
    ("attester_slashing_that_is_no_double_vote_and_no_surround", "double vote")])
def test_the_reference_refuses_what_the_spec_refuses(mature_objects, spoil, why):
    from benchmark import spoiled_operations
    spec, state = mature_objects[0], deepcopy(mature_objects[1])
    C = constants()
    spec.process_slots(state, int(state.slot) + 1)
    generator = OpsBlockGenerator(spec, SEED, MIX, 2048)
    cols = _columns(state)
    before = {f: a.copy() for f, a in cols.items()}
    block = getattr(spoiled_operations, spoil)(spec, generator, state, SEED)
    pre = plain_block.read_pre(state)
    pre["latest_slashed_balances"] = [int(x) for x in state.latest_slashed_balances]
    with pytest.raises(plain_block.Rejected, match=why):
        plain_operations.process_block(
            C, pre, cols, plain_block.read_block(block),
            plain_epoch_registry.Shuffles(C, cols))
    assert all((cols[f] == before[f]).all() for f in cols)     # nothing written
    with pytest.raises(AssertionError):
        spec.process_block(deepcopy(state), block)


def test_plain_operations_refuses_an_exit_of_a_young_validator_and_a_deposit(
        mature_objects):
    spec, state = mature_objects[0], deepcopy(mature_objects[1])
    C = constants()
    spec.process_slots(state, int(state.slot) + 1)
    generator = OpsBlockGenerator(spec, SEED, MIX, 2048)
    block = generator.block(state)
    young = int(block.body.voluntary_exits[3].validator_index)
    state.validator_registry[young].activation_epoch = 100
    cols = _columns(state)
    pre = plain_block.read_pre(state)
    pre["latest_slashed_balances"] = [int(x) for x in state.latest_slashed_balances]
    shuffles = plain_epoch_registry.Shuffles(C, cols)
    with pytest.raises(plain_block.Rejected, match="PERSISTENT_COMMITTEE_PERIOD"):
        plain_operations.process_block(
            C, pre, cols, plain_block.read_block(block), shuffles)
    with pytest.raises(AssertionError):
        spec.process_block(deepcopy(state), block)
    block = generator.block(state)
    block.body.deposits.append(spec.Deposit())
    with pytest.raises(plain_block.Unsupported):
        plain_operations.process_block(
            C, pre, cols, plain_block.read_block(block), shuffles)


def test_plain_epoch_registry_refuses_a_pending_activation(mature_objects):
    spec, state = mature_objects
    C = constants()
    cols = _columns(state)
    cols["activation_epoch"][7] = np.uint64(2**64 - 1)
    with pytest.raises(plain_epoch_registry.Unsupported):
        plain_epoch_registry.boundary(C, plain_ssz.read_state(state), cols)
