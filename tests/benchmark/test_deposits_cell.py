"""The cell `mainnet-1m-deposits.deposit-queue`, as BENCHMARK.json commits
it, on the CPU at a test's size; its mix on four virtual devices; the seeded
deposit queue; the plain references of the deposits and of the boundary
with an activation queue against the object model.

At V = 8,192 the mainnet preset has one committee of 128 a slot, so a block
carries one aggregate of 128 bits and 16 deposits, the core has 12,288 free
rows behind the registry (the configuration's 48,576 at a test's size), and
the churn limit is its floor of 4 (15 at 1,000,000 validators). The
controls are control_deposits_on_chip.py's, run here at this size. No
timing read here means anything.
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

from benchmark import (plain_block, plain_deposits,  # noqa: E402
                       plain_epoch_activations, plain_epoch_registry, plain_ssz,
                       run, seeded_deposit_queue, spoiled_deposits)
from benchmark.deposit_generator import DepositBlockGenerator  # noqa: E402
import control_deposits_on_chip as controls  # noqa: E402
from test_benchmark_harness import (  # noqa: E402,F401  (fixtures)
    MESH_CELLS, SEED, TINY_V, _before_compare, _failed, drive,
    with_mesh_cells)

CELL = "mainnet-1m-deposits.deposit-queue"
OPS = "mainnet-1m-ops.dirty-slots"
BROUGHT = ["block_ms.deposits", "block_deposits_ms",
           "block_attestations_ms.deposits", "block_header_ms.deposits",
           "block_fallbacks.deposits", "idle_in_block.deposits",
           "registry_write_ms.deposits", "forest_update_ms.deposits",
           "forest_update_appended_leaves", "deposit_proof_pairs_hashed",
           "registry_rows_per_epoch", "active_validators_per_epoch.deposits",
           "pending_activations_per_epoch", "append_rows_roofline",
           "masked_leaves_roofline", "pending_activations_roofline"]
DEVICE_ONLY = {"idle_in_block.deposits", "append_rows_roofline",
               "masked_leaves_roofline", "pending_activations_roofline"}
NUMBERS = ["registry_root.bytes_differing_from_hashlib",
           "balances_root.bytes_differing_from_hashlib",
           "dirty_slot.state_root.bytes_differing_from_hashlib",
           "block.header_fields_differing_from_reference",
           "block.randao_mix_bytes_differing",
           "block.eth1_votes_differing",
           "block.pending_attestations_differing_from_reference",
           "block.registry_length_differing_from_reference",
           "block.deposit_index_differing_from_reference",
           "block.registry_rows_differing_from_reference",
           "block.appended_identity_bytes_differing_from_reference",
           "block.rows_beyond_the_length_not_inert",
           "state_root.bytes_differing_from_hashlib",
           "boundary.balances_differing_from_reference",
           "boundary.effective_balances_differing_from_reference",
           "boundary.other_columns_differing_from_reference",
           "boundary.small_fields_differing_from_reference",
           "boundary.balances_root_after.bytes_differing_from_hashlib",
           "epochs_between_justified_and_previous",
           "block.invalid_blocks_accepted",
           "block.written_by_refused_blocks"]
V = TINY_V
V_MESH = V + 2           # no multiple of four: the device columns pad
MIX = json.loads((REPO / "benchmark/traffic/deposit-queue.json").read_text())


def constants() -> dict:
    C = {}
    for name in ("mainnet", "mainnet.blocks", "mainnet.ops", "mainnet.deposits"):
        C.update(json.loads(
            (REPO / "benchmark" / "presets" / f"{name}.json").read_text()))
    return C


# -- the committed entries -------------------------------------------------------

def test_the_configuration_is_mainnet_1m_ops_with_deposits_no_longer_cut():
    cell, was = run.Cell(CELL), run.Cell(OPS)
    config = cell.config
    assert cell.chips == config["chips"] == 1 and config["validators"] == 1_000_000
    assert config["reduced"] == ["bls_verification"]
    assert set(config["reduced_detail"]) == set(config["reduced"])
    assert "deposits" in was.config["reduced"]
    assert config["source"] != was.config["source"] and len(config["source"]) <= 200
    for part in ("MAX_DEPOSITS 16", "DEPOSIT_CONTRACT_TREE_DEPTH 32", "65,536",
                 "process_deposit", "1,000,000 validators"):
        assert part in config["source"]
    assert config["registry_capacity"] == 2 ** 20 > config["validators"]
    same = ("preset", "validators", "chips", "committees_per_slot", "committee_size")
    assert {k: config[k] for k in same} == {k: was.config[k] for k in same}
    for key in ("balances", "identity", "bls_active", "randao_reveal", "finality"):
        assert config["assumed"][key] == was.config["assumed"][key]
    assert {"deposits_new_and_top_up", "outstanding_deposits",
            "registry_capacity"} <= set(config["assumed"])
    # the accepted guarantees, the first said for a registry with room, and
    # the deposits' own
    assert config["guarantees"][1:6] == was.config["guarantees"][1:] \
        and len(config["guarantees"]) == 7
    for part in ("whole handler", "before the next slot's root", "V validators"):
        assert any(part in g for g in config["guarantees"]), part
    blocks = config["blocks"]
    assert blocks["deposits_per_block"] == cell.mix["deposits_per_block"] == 16
    assert blocks["new_validators_per_block"] + blocks["top_ups_per_block"] == 16
    assert blocks["new_rows_per_epoch"] == 64 * cell.mix["new_validators_per_block"]
    assert blocks["churn_limit"] == max(4, 1_000_000 // 65_536) == 15
    free = config["registry_capacity"] - config["validators"]
    assert cell.mix["outstanding_deposits"] // 16 * 12 == 46_080 < free == 48_576


def test_the_mix_is_the_issues():
    mix = run.Cell(CELL).mix
    assert mix["driver"] == "deposit_queue" and mix["warmup_epochs"] == 6
    assert (mix["aggregates_per_committee"], mix["deposits_per_block"],
            mix["new_validators_per_block"], mix["top_ups_per_block"],
            mix["new_validator_gwei"], mix["top_up_gwei"],
            mix["outstanding_deposits"], mix["min_epochs_of_deposits_left"],
            mix["checked_blocks"]) \
        == (1, 16, 12, 4, 32 * 10 ** 9, 10 ** 9, 61_440, 3, 64)


def test_the_cell_reports_the_dirty_slots_cells_metrics_and_reads_its_own():
    cell, ops = run.Cell(CELL), run.Cell(OPS)
    assert cell.row["traffic"] == "deposit-queue"
    assert [m["name"] for m in cell.end_to_end] \
        == [m["name"] for m in ops.end_to_end] \
        == ["replay_slots_per_s", "epoch_boundary_s", "slot_root_p95_ms",
            "setup_s"]
    read = [m["name"] for m in cell.per_layer]
    shared = [m["name"] for m in ops.per_layer][:27]
    assert read == shared + BROUGHT and "epoch_program_roofline" in shared
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for entry in bench["per_layer"]:
        if entry["name"] in BROUGHT:
            assert entry["workloads"] == [CELL]
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "mainnet-1m-deposits"
    for name in ("append_rows_bytes", "masked_leaves_bytes",
                 "pending_activations_bytes"):
        count = __import__(f"benchmark.costs.{name}", fromlist=["count"]).count
        # a program over the storage's rows is costed at the capacity
        assert count(cell.config) > count(dict(cell.config, registry_capacity=None))


def test_deposit_constants_of_the_references_equal_the_programs():
    from consensus_specs_tpu.models import phase0
    spec = phase0.get_spec("mainnet")
    C = constants()
    for key in ("MAX_DEPOSITS", "DEPOSIT_CONTRACT_TREE_DEPTH",
                "MIN_PER_EPOCH_CHURN_LIMIT", "CHURN_LIMIT_QUOTIENT",
                "ACTIVATION_EXIT_DELAY", "MAX_EFFECTIVE_BALANCE",
                "EFFECTIVE_BALANCE_INCREMENT"):
        assert int(getattr(spec, key)) == C[key], key
    assert seeded_deposit_queue.DEPTH == C["DEPOSIT_CONTRACT_TREE_DEPTH"]


# -- the seeded entry ------------------------------------------------------------

@pytest.fixture(scope="module")
def rush():
    """Mainnet preset, 256 validators as objects at the deposit rush's
    entry, four epochs of full blocks outstanding."""
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.utils.ssz.impl import deserialize
    bls.bls_active = False
    spec = phase0.get_spec("mainnet")
    spec.clear_caches()
    mix = dict(MIX, outstanding_deposits=4 * 64 * 16)
    data, queue = seeded_deposit_queue.seeded_deposit_queue_checkpoint(
        spec, 256, SEED, mix)
    yield spec, deserialize(data, spec.BeaconState), queue, data, mix
    spec.clear_caches()


def test_the_seeded_entry_owes_the_deposits_and_proves_each(rush):
    spec, state, queue, data, mix = rush
    assert int(state.slot) == 64 * 2050 - 1 and int(state.deposit_index) == 256
    eth1 = state.latest_eth1_data
    assert int(eth1.deposit_count) == 256 + len(queue) == 256 + 4096
    assert bytes(eth1.deposit_root) == queue.root
    assert len(state.eth1_data_votes) == 128
    assert all(v == eth1 for v in state.eth1_data_votes)
    again = seeded_deposit_queue.seeded_deposit_queue_checkpoint(spec, 256, SEED, mix)
    assert again[0] == data and again[1].root == queue.root
    keys = {bytes(v.pubkey) for v in state.validator_registry}
    for at in (256, 256 + 11, 256 + 12, 256 + 15, 256 + 4095):
        deposit, = queue.deposits(spec, at, 1)
        leaf = bytes(spec.hash_tree_root(deposit.data))
        assert leaf == seeded_deposit_queue.deposit_data_root(
            bytes(deposit.data.pubkey), bytes(deposit.data.withdrawal_credentials),
            int(deposit.data.amount), bytes(deposit.data.signature))
        assert spec.verify_merkle_branch(leaf, deposit.proof, 32, at, queue.root)
        assert not spec.verify_merkle_branch(leaf, deposit.proof, 32, at + 1, queue.root)
        top_up = (at - 256) % 16 >= 12
        assert (bytes(deposit.data.pubkey) in keys) == top_up
        assert int(deposit.data.amount) == (10 ** 9 if top_up else 32 * 10 ** 9)


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
    row = next(r for r in rows if "samples" in r)
    assert row["samples"]["blocks"] == result["attempted"]
    assert row["samples"]["block_fallbacks"] == 0
    assert row["notes"]["window_ended_by"] == "seconds"
    # every boundary of the window is in the notes, epoch_boundary_s their median
    assert len(row["notes"]["boundary_ms"]) == row["samples"]["boundaries"]
    # seven epochs of warm-up and the window's, twelve rows a block
    assert row["notes"]["registry_rows_at_close"] \
        == V + 12 * (7 * 64 + result["attempted"])


def test_the_traced_cell_prints_the_deposits_layers(monkeypatch, drive):
    # the profiler stops after the first epoch; the window runs on. It has
    # to hold two boundaries for the slopes to be read (a line without
    # them is refused), and on a busy host an epoch takes any number of
    # seconds: the window is told that its deposits run short after three
    # epochs, which is its other way to end
    from benchmark.drivers import deposit_queue
    real = deposit_queue.Driver._deposits_left_for
    monkeypatch.setattr(
        deposit_queue.Driver, "_deposits_left_for",
        lambda self, epochs: len(self.boundary_s) < 3 and real(self, epochs))
    monkeypatch.setattr(run, "TRACED_SECONDS", 0.0)
    result, rows = drive(CELL, trace=True, seconds=3600.0, validators=V)
    assert result["correct"] is True and _failed(rows) == []
    assert result["attempted"] == 3 * 64
    assert next(r for r in rows if "samples" in r)["notes"]["window_ended_by"] \
        == "deposits"
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # what only a device plane gives is left out on a host backend
    assert set(BROUGHT) - set(metrics) == DEVICE_ONLY
    # the number that says the capacity works
    assert metrics["compiles_in_window"] == 0 == metrics["block_fallbacks.deposits"]
    assert metrics["guard_events"] == 0 == metrics["slot_root_trees_rebuilt"]
    assert metrics["forest_update_appended_leaves"] == 12
    assert metrics["deposit_proof_pairs_hashed"] == 16 * 32
    assert metrics["registry_rows_per_epoch"] == pytest.approx(768)
    # the churn limit's floor lets four through at a boundary that
    # activates; a row keeps its place in the queue until finality passes it
    assert 0 <= metrics["active_validators_per_epoch.deposits"] <= 4
    assert 764 <= metrics["pending_activations_per_epoch"] <= 768
    parts = sum(metrics[name] for name in (
        "block_header_ms.deposits", "block_attestations_ms.deposits",
        "block_deposits_ms", "registry_write_ms.deposits",
        "forest_update_ms.deposits"))
    assert 0 < parts <= metrics["block_ms.deposits"] * 1.5
    # a slot root no longer reads cached forest roots
    assert metrics["slot_root_forests_ms"] > 0


# -- the controls' twins -----------------------------------------------------------

def _control(monkeypatch, drive, fault):
    from benchmark.drivers import deposit_queue
    _before_compare(monkeypatch, deposit_queue, fault)
    try:
        result, rows = drive(CELL, trace=False, validators=V)
    finally:
        import jax
        from consensus_specs_tpu.models.phase0 import resident
        resident._masked_leaves = jax.jit(resident._masked_leaves_traced)
    assert result["correct"] is False
    return _failed(rows), _compared(rows)


def test_a_core_that_skips_the_mask_makes_correct_false(monkeypatch, drive):
    failed, compared = _control(monkeypatch, drive, controls.mask_skipped)
    assert failed == ["registry_root.bytes_differing_from_hashlib",
                      "dirty_slot.state_root.bytes_differing_from_hashlib",
                      "state_root.bytes_differing_from_hashlib"]
    assert compared["block.registry_rows_differing_from_reference"] == 0
    assert compared["block.rows_beyond_the_length_not_inert"] == 0


def test_a_stale_pubkey_index_makes_correct_false(monkeypatch, drive):
    failed, compared = _control(monkeypatch, drive, controls.stale_pubkey_index)
    # a row too many from the first checked block on, block after block;
    # the forests follow the device's columns, so the roots after the first
    # block hold, and the epoch's last root, which the reference's own
    # registry gives, does not
    assert failed[:2] == ["block.registry_length_differing_from_reference",
                          "block.registry_rows_differing_from_reference"]
    assert compared["block.registry_length_differing_from_reference"] == 64
    assert "state_root.bytes_differing_from_hashlib" in failed
    assert "registry_root.bytes_differing_from_hashlib" not in failed
    assert "block.deposit_index_differing_from_reference" not in failed
    assert "block.header_fields_differing_from_reference" not in failed


def test_a_root_that_lags_a_blocks_appends_makes_correct_false(monkeypatch, drive):
    failed, compared = _control(monkeypatch, drive, controls.root_lags_appends)
    assert failed == ["registry_root.bytes_differing_from_hashlib",
                      "balances_root.bytes_differing_from_hashlib",
                      "dirty_slot.state_root.bytes_differing_from_hashlib",
                      "state_root.bytes_differing_from_hashlib"]
    assert compared["block.registry_rows_differing_from_reference"] == 0


# -- the mix under a mesh ------------------------------------------------------

def test_the_deposit_queue_mix_runs_correct_on_four_devices(
        monkeypatch, drive, with_mesh_cells):
    """The harness's four-chip configuration over this mix, given a
    capacity: the appended rows go into columns that are sharded and
    padded, the new leaves into forests whose levels lie on their shards."""
    import jax
    from benchmark.drivers import deposit_queue
    root = with_mesh_cells()
    path = root / "benchmark" / "configs" / "mainnet-tiny-mesh4.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    registry_capacity=2 ** 20)))
    placed = {}
    _before_compare(monkeypatch, deposit_queue, lambda driver: placed.update(
        devices=driver.dep.core.cols.exit_epoch.sharding.device_set,
        rows=driver.dep.core.cols.balance.shape[0],
        capacity=driver.dep.core._capacity))
    result, rows = drive(MESH_CELLS["deposit-queue"], trace=False, root=root,
                         validators=V_MESH)
    capacity = V_MESH + deposit_queue.TEST_FREE_ROWS
    assert placed == {"devices": set(jax.devices()[:4]), "capacity": capacity,
                      "rows": capacity + 2}
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


def _registry(state) -> plain_deposits.Registry:
    reg = state.validator_registry
    return plain_deposits.Registry(
        _columns(state),
        np.frombuffer(b"".join(bytes(v.pubkey) for v in reg), np.uint8).reshape(-1, 48),
        np.frombuffer(b"".join(bytes(v.withdrawal_credentials) for v in reg),
                      np.uint8).reshape(-1, 32))


def test_the_plain_references_leave_what_the_object_model_leaves(rush):
    """Three epochs of this mix's blocks at V = 256, each held to
    `spec.process_block` on the object state (header, mix, votes, pending
    attestations, the registry's length, keys, credentials and seven
    columns, deposit_index), and the boundaries held to
    `spec.process_slots` across them: the second makes the first epoch's
    768 rows eligible, the third activates the first four of a queue of
    1,536 and writes them into the active-index root."""
    spec, state, queue = rush[0], deepcopy(rush[1]), rush[2]
    C = constants()
    generator = DepositBlockGenerator(spec, SEED, MIX, queue)
    spec.process_slots(state, int(state.slot) + 1)      # the entry boundary
    ref = _registry(state)
    shuffles = plain_epoch_registry.Shuffles(C, ref.cols)
    far = np.uint64(2 ** 64 - 1)
    for blocks in range(1, 3 * 64 + 1):
        block = generator.block(state)
        pre = plain_block.read_pre(state)
        before = {name: len(getattr(state, name)) for name in
                  ("previous_epoch_attestations", "current_epoch_attestations")}
        want = plain_deposits.process_block(
            C, pre, ref, plain_block.read_block(block), shuffles)
        spec.process_block(state, block)
        assert plain_block.read_value(state.latest_block_header, "BeaconBlockHeader") \
            == want["latest_block_header"]
        assert want["latest_block_header"]["body_root"] \
            == bytes(spec.hash_tree_root(block.body))
        for name, key in (("previous_epoch_attestations", "previous_appended"),
                          ("current_epoch_attestations", "current_appended")):
            assert plain_block.read_pending(getattr(state, name)[before[name]:]) \
                == want[key]
        assert int(state.deposit_index) == want["deposit_index"] == 256 + 16 * blocks
        assert len(want["new_rows"]) == 12 and len(want["topped_up"]) == 4
        got = _columns(state)
        assert len(ref) == len(state.validator_registry) == 256 + 12 * blocks
        assert all((got[f] == ref.cols[f]).all() for f in got)
        assert ref.pubkeys == [bytes(v.pubkey) for v in state.validator_registry]
        assert ref.credentials == [bytes(v.withdrawal_credentials)
                                   for v in state.validator_registry]
        if (int(state.slot) + 1) % 64 == 0:
            small = plain_ssz.read_state(state)
            after = plain_epoch_activations.boundary(C, small, ref.cols)
            spec.process_slots(state, int(state.slot) + 1)
            got, post = _columns(state), plain_ssz.read_state(state)
            for key, value in after.items():
                if key in got:
                    assert (got[key] == value).all(), key
                    ref.cols[key] = np.array(value)
                else:
                    assert post[key] == value, key
        else:
            spec.process_slots(state, int(state.slot) + 1)
    cols = ref.cols
    eligible = cols["activation_eligibility_epoch"][256:] != far
    assert int(eligible.sum()) == 3 * 768
    # two boundaries found a queue: the churn limit's floor each
    assert int((cols["activation_epoch"][256:] != far).sum()) == 2 * 4
    assert (cols["activation_epoch"][256:264] != far).all()


@pytest.mark.parametrize("spoil,why", [
    ("deposit_with_one_proof_node_flipped", "does not prove"),
    ("deposit_proved_for_the_neighbouring_index", "does not prove"),
    ("one_deposit_fewer_than_outstanding", "the chain owes"),
    ("one_deposit_more_than_the_maximum", "the chain owes")])
def test_the_reference_refuses_what_the_spec_refuses(rush, spoil, why):
    spec, state, queue = rush[0], deepcopy(rush[1]), rush[2]
    C = constants()
    spec.process_slots(state, int(state.slot) + 1)
    generator = DepositBlockGenerator(spec, SEED, MIX, queue)
    ref = _registry(state)
    before = {f: a.copy() for f, a in ref.cols.items()}
    block = getattr(spoiled_deposits, spoil)(spec, generator, state, SEED)
    with pytest.raises(plain_block.Rejected, match=why):
        plain_deposits.process_block(
            C, plain_block.read_pre(state), ref, plain_block.read_block(block),
            plain_epoch_registry.Shuffles(C, ref.cols))
    assert len(ref) == 256 == len(ref.rows)                # nothing written
    assert all((ref.cols[f] == before[f]).all() for f in before)
    with pytest.raises(AssertionError):
        spec.process_block(deepcopy(state), block)


def test_plain_deposits_refuses_an_exit_and_tops_up_a_key_of_its_own_block(rush):
    spec, state, queue = rush[0], deepcopy(rush[1]), rush[2]
    C = constants()
    spec.process_slots(state, int(state.slot) + 1)
    generator = DepositBlockGenerator(spec, SEED, MIX, queue)
    ref = _registry(state)
    shuffles = plain_epoch_registry.Shuffles(C, ref.cols)
    block = generator.block(state)
    block.body.voluntary_exits.append(spec.VoluntaryExit())
    with pytest.raises(plain_block.Unsupported):
        plain_deposits.process_block(
            C, plain_block.read_pre(state), ref, plain_block.read_block(block),
            shuffles)
    # the second deposit under the first one's new key: a top-up of a row
    # one operation old, in the reference as in the object model
    plain = plain_block.read_block(generator.block(state))
    deposits = plain["body"]["deposits"]
    deposits[1]["data"]["pubkey"] = deposits[0]["data"]["pubkey"]
    with pytest.raises(plain_block.Rejected, match="does not prove"):
        plain_deposits.process_block(C, plain_block.read_pre(state), ref, plain,
                                     shuffles)
    pre = plain_block.read_pre(state)
    body = dict(plain["body"], deposits=deposits[:1] * 2)
    fake = lambda *a: pre["latest_eth1_data"]["deposit_root"]  # noqa: E731
    real, plain_deposits.branch_root = plain_deposits.branch_root, fake
    try:
        pre["latest_eth1_data"] = dict(pre["latest_eth1_data"],
                                       deposit_count=pre["deposit_index"] + 2)
        left = plain_deposits.process_deposits(C, pre, ref, body)
    finally:
        plain_deposits.branch_root = real
    assert left["new_rows"] == [256] and left["topped_up"] == [256]
    assert int(ref.cols["balance"][256]) == 64 * 10 ** 9
    assert int(ref.cols["effective_balance"][256]) == 32 * 10 ** 9
