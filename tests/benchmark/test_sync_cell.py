"""The cell `mainnet-1m-blocks.sync`, as BENCHMARK.json commits it, on the CPU
at a test's size; the cell `mainnet-300k.restore` beside it; the sync mix on
four virtual devices; the plain block reference against the object model.

At V = 8,192 the mainnet preset has one committee of 128 a slot, so a block
carries 8 aggregates of 16 bits (at 1,000,000 validators 16 x 8 = 128 of
about 122). The controls are control_sync_on_chip.py's, run here at this
size. No timing read here means anything.
"""
from __future__ import annotations

import json
import sys
from copy import deepcopy
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
for p in (REPO, HERE):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmark import plain_block, run  # noqa: E402
from benchmark.block_generator import BlockGenerator  # noqa: E402
import control_sync_on_chip as controls  # noqa: E402
from test_benchmark_harness import (  # noqa: E402,F401  (fixtures)
    MESH_CELLS, MESH_V, SEED, TINY_V, _before_compare, _failed, drive,
    with_mesh_cells)

CELL = "mainnet-1m-blocks.sync"
CONTROL = "mainnet-1m.replay"
BROUGHT = ["block_ms", "block_header_ms", "block_randao_ms", "block_eth1_ms",
           "block_attestations_ms", "block_self_ms", "block_attesting_indices",
           "block_fallbacks", "idle_in_block"]
BLOCK_NUMBERS = ["block.header_fields_differing_from_reference",
                 "block.randao_mix_bytes_differing",
                 "block.eth1_votes_differing",
                 "block.pending_attestations_differing_from_reference",
                 "block.invalid_blocks_accepted"]


# -- the committed entries -------------------------------------------------------

def test_the_configuration_is_mainnet_1m_with_blocks_no_longer_cut():
    cell, control = run.Cell(CELL), run.Cell(CONTROL)
    config, was = cell.config, control.config
    assert cell.chips == config["chips"] == 1 and config["validators"] == 1_000_000
    assert config["reduced"] == ["bls_verification", "registry_operations"]
    assert set(config["reduced_detail"]) == set(config["reduced"])
    assert "block_processing" in was["reduced"]
    assert config["source"] != was["source"] and len(config["source"]) <= 200
    same = ("preset", "validators", "chips", "layout", "committees_per_slot",
            "committee_size")
    assert {k: config[k] for k in same} == {k: was[k] for k in same}
    for key in ("registry", "balances", "identity", "entry", "bls_active"):
        assert config["assumed"][key] == was["assumed"][key]
    assert {"aggregation", "randao_reveal", "eth1_data", "proposer"} \
        <= set(config["assumed"])
    assert config["guarantees"][:4] == was["guarantees"] and len(config["guarantees"]) == 5
    assert "NotImplementedError" in config["reduced_detail"]["registry_operations"]
    blocks = config["blocks"]
    assert blocks["attestations_per_block"] \
        == config["committees_per_slot"] * cell.mix["aggregates_per_committee"] == 128
    assert blocks["pending_attestations_per_epoch"] == 64 * 128


def test_the_cell_reports_the_replay_metrics_and_reads_its_own():
    cell, control = run.Cell(CELL), run.Cell(CONTROL)
    assert cell.row["traffic"] == "sync" and cell.mix["driver"] == "sync"
    assert [m["name"] for m in cell.end_to_end] \
        == [m["name"] for m in control.end_to_end] \
        == ["replay_slots_per_s", "epoch_boundary_s", "slot_root_p95_ms",
            "setup_s"]
    read = [m["name"] for m in cell.per_layer]
    assert read == [m["name"] for m in control.per_layer] + BROUGHT
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for entry in bench["per_layer"]:
        if entry["name"] in BROUGHT:
            assert entry["workloads"] == [CELL]
            assert entry["layer"] == "block processing"
            assert entry["moves"] == "replay_slots_per_s"


def test_the_queued_restore_cell_is_the_restore_mix_on_the_smaller_registry():
    cell, like = run.Cell("mainnet-300k.restore"), run.Cell("mainnet-1m.restore")
    assert cell.mix == like.mix and cell.chips == 1
    assert cell.config == run.Cell("mainnet-300k.replay").config
    assert [m["name"] for m in cell.end_to_end] == ["restore_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] \
        == [m["name"] for m in like.per_layer] and len(cell.per_layer) == 13


def test_block_constants_of_the_reference_equal_the_programs():
    from consensus_specs_tpu.models import phase0
    spec = phase0.get_spec("mainnet")
    constants = json.loads(
        (REPO / "benchmark" / "presets" / "mainnet.blocks.json").read_text())
    assert len(constants) == 9
    for key, value in constants.items():
        if key != "source":
            assert int(getattr(spec, key)) == value, key


# -- the cell through `drive` -----------------------------------------------------

def _compared(rows):
    return {r["compared"]: r["got"] for r in rows if "compared" in r}


def test_the_cell_runs_correct_and_reports_its_end_to_end_metrics(drive):
    result, rows = drive(CELL, trace=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 64 and result["attempted"] % 64 == 0
    assert all(m["value"] > 0 for m in result["metrics"].values())
    compared = _compared(rows)
    assert set(BLOCK_NUMBERS) <= set(compared) and not any(compared.values())
    assert {"state_root.bytes_differing_from_hashlib",
            "boundary.balances_differing_from_reference",
            "epochs_between_justified_and_previous"} <= set(compared)
    samples = next(r for r in rows if "samples" in r)
    assert samples["samples"]["blocks"] == result["attempted"]
    assert samples["samples"]["block_fallbacks"] == 0
    assert len(samples["notes"]["epoch_block_median_ms"]) \
        == result["attempted"] // 64


def test_the_traced_cell_prints_the_blocks_layer(monkeypatch, drive):
    # the profiler stops after the first epoch; the window runs on
    monkeypatch.setattr(run, "TRACED_SECONDS", 0.0)
    result, rows = drive(CELL, trace=True)
    assert result["correct"] is True and _failed(rows) == []
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # what only a device plane gives is left out on a host backend
    assert set(BROUGHT) - set(metrics) == {"idle_in_block"}
    assert metrics["block_fallbacks"] == 0 == metrics["compiles_in_window"]
    assert metrics["guard_events"] == 0 == metrics["slot_root_trees_rebuilt"]
    # one committee a slot attests in full: V / 64 indices a block
    assert metrics["block_attesting_indices"] == TINY_V // 64
    parts = sum(metrics[f"block_{part}_ms"]
                for part in ("header", "randao", "eth1", "attestations"))
    assert 0 < parts <= metrics["block_ms"] * 1.5
    assert metrics["block_self_ms"] >= 0 and metrics["stage_distill_ms"] > 0


# -- the controls' twins -----------------------------------------------------------

def test_an_attestation_dropped_after_its_checks_makes_correct_false(
        monkeypatch, drive):
    from benchmark.drivers import sync
    _before_compare(monkeypatch, sync,
                    controls.attestation_dropped_after_its_checks)
    result, rows = drive(CELL, trace=False)
    assert result["correct"] is False
    assert _failed(rows) == [
        "block.pending_attestations_differing_from_reference",
        "state_root.bytes_differing_from_hashlib",
        "boundary.balances_differing_from_reference"]
    compared = _compared(rows)
    assert compared["block.pending_attestations_differing_from_reference"] == 1
    assert compared["boundary.balances_differing_from_reference"] > TINY_V // 2


def test_a_header_with_a_wrong_body_root_makes_correct_false(monkeypatch, drive):
    from benchmark.drivers import sync
    _before_compare(monkeypatch, sync, controls.header_with_a_wrong_body_root)
    result, rows = drive(CELL, trace=False)
    assert result["correct"] is False
    assert _failed(rows) == ["block.header_fields_differing_from_reference",
                             "state_root.bytes_differing_from_hashlib"]
    assert _compared(rows)["block.header_fields_differing_from_reference"] == 1


def test_a_core_that_skips_the_bitfield_check_makes_correct_false(
        monkeypatch, drive):
    from benchmark.drivers import sync
    from consensus_specs_tpu.models import phase0
    spec = phase0.get_spec("mainnet")
    monkeypatch.setattr(spec, "verify_bitfield", spec.verify_bitfield)  # put back
    _before_compare(monkeypatch, sync, controls.bitfield_check_skipped)
    result, rows = drive(CELL, trace=False)
    assert result["correct"] is False
    assert _failed(rows) == ["block.invalid_blocks_accepted"]
    assert _compared(rows)["block.invalid_blocks_accepted"] == 1


# -- the sync mix under a mesh ------------------------------------------------------

def test_the_sync_mix_runs_correct_on_four_devices(
        monkeypatch, drive, with_mesh_cells):
    """The harness's four-chip configuration over the sync mix: the view
    reads the host mirrors of a core whose columns are sharded and padded;
    at V = 8,194 some committees have 129 members, so their bitfields end
    in padding bits."""
    import jax
    from benchmark.drivers import sync
    placed = {}
    _before_compare(monkeypatch, sync, lambda driver: placed.update(
        devices=driver.dep.core.cols.balance.sharding.device_set,
        rows=driver.dep.core.cols.balance.shape[0]))
    result, rows = drive(MESH_CELLS["sync"], trace=False,
                         root=with_mesh_cells(), validators=MESH_V)
    assert placed == {"devices": set(jax.devices()[:4]), "rows": MESH_V + 2}
    assert result["correct"] is True and _failed(rows) == []
    assert set(BLOCK_NUMBERS) <= set(_compared(rows))


# -- the plain reference against the object model ---------------------------------

def test_plain_block_leaves_what_the_object_model_leaves():
    """Mainnet preset, 2,048 validators as objects: blocks across an epoch's
    end, each held to `spec.process_block` on the object state."""
    import numpy as np
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.testing import factories
    bls.bls_active = False
    spec = phase0.get_spec("mainnet")
    spec.clear_caches()
    C = {**json.loads((REPO / "benchmark/presets/mainnet.json").read_text()),
         **json.loads((REPO / "benchmark/presets/mainnet.blocks.json").read_text())}
    state = factories.seed_genesis_state(spec, 2048)
    factories.advance_slots(spec, state, 60)
    generator = BlockGenerator(spec, SEED, 8)
    shuffles = plain_block.Shuffles(C, 2048)
    cols = {"effective_balance": np.array(
                [v.effective_balance for v in state.validator_registry], np.uint64),
            "slashed": np.zeros(2048, bool)}
    for _ in range(7):
        spec.process_slots(state, int(state.slot) + 1)
        block = generator.block(state)
        pre = plain_block.read_pre(state)
        before = {name: len(getattr(state, name)) for name in
                  ("previous_epoch_attestations", "current_epoch_attestations")}
        want = plain_block.process_block(
            C, pre, cols, plain_block.read_block(block), shuffles)
        spec.process_block(state, block)
        assert plain_block.read_value(state.latest_block_header, "BeaconBlockHeader") \
            == want["latest_block_header"]
        assert want["latest_block_header"]["body_root"] \
            == bytes(spec.hash_tree_root(block.body))
        epoch = int(state.slot) // 64
        assert bytes(state.latest_randao_mixes[epoch]) == want["randao_mix"]
        assert plain_block.read_value(state.eth1_data_votes, ("list", "Eth1Data")) \
            == want["eth1_data_votes"]
        for name, key in (("previous_epoch_attestations", "previous_appended"),
                          ("current_epoch_attestations", "current_appended")):
            assert plain_block.read_pending(getattr(state, name)[before[name]:]) \
                == want[key]
        assert len(want["previous_appended"]) + len(want["current_appended"]) == 8
    assert int(state.slot) == 67
    # and it refuses what the spec refuses, saying which check
    spec.process_slots(state, int(state.slot) + 1)
    pre = plain_block.read_pre(state)
    for spoil, why in ((_late, "inclusion window"), (_source, "FFG source"),
                       (_padding, "committee"), (_parent, "parent root")):
        block = generator.block(state)
        spoil(block)
        with pytest.raises(plain_block.Rejected, match=why):
            plain_block.process_block(
                C, pre, cols, plain_block.read_block(block), shuffles)
        with pytest.raises(AssertionError):
            spec.process_block(deepcopy(state), block)
    block = generator.block(state)
    block.body.voluntary_exits.append(spec.VoluntaryExit())
    with pytest.raises(plain_block.Unsupported):
        plain_block.process_block(
            C, pre, cols, plain_block.read_block(block), shuffles)
    spec.clear_caches()


def _late(block):
    block.body.attestations[0].data.crosslink.shard += 1    # another slot's


def _source(block):
    block.body.attestations[0].data.source_epoch += 1


def _padding(block):
    block.body.attestations[0].aggregation_bitfield += b"\x01"


def _parent(block):
    block.parent_root = b"\x07" * 32
