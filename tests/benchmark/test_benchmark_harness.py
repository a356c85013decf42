"""The benchmark's harness, on the CPU at small sizes.

What a CPU run can hold: that every cell of BENCHMARK.json resolves to its
files, that both drivers run end to end at V = 8,192 (the device shuffler's
floor) and print the contract's last line, that the measuring path refuses
a first device that is not a TPU, that the reduction from a profiler trace
to busy/idle share, per-module time and gaps by annotation is right on a
hand-built trace, that the plain references (hashlib SSZ, numpy epoch,
swap-or-not shuffle) agree with the package where the package is sound, and
that `correct` comes out false for the controls (one Gwei on the device, one
attestation dropped before the boundary) and for a timed path broken
underneath, and that a four-chip configuration, its cells and a per-layer
metric added to a copy of the benchmark (new files, new entries, and the
cells' names appended to the `workloads` lists of the metrics they report
and read: no other edit) run on four of this process's virtual devices at a
size the mesh has to pad, sound and under control 1. What a line must hold
comes from the cell (`_check_line`), so a PR that adds a cell or a metric
edits nothing here. No timing read here means anything; no test describes a
TPU topology.
"""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import plain_epoch, plain_ssz, reduce, reference, run  # noqa: E402
from benchmark.costs import epoch_program_bytes  # noqa: E402
from benchmark.sources import Seen  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
TINY_V = 8192
SEED = 2**31 + 4242          # the driver's seeds are larger than 32 signed bits
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
V5E = json.loads((REPO / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]


# -- BENCHMARK.json and the files it names -----------------------------------

def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25 and "workloads" not in setup[0]
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in BENCH[key]]
    assert all(NAME.match(n) for n in names), names
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_the_deployment(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    body = json.loads((REPO / config["file"]).read_text())
    assert body["source"] == config["source"] and len(config["source"]) <= 200
    assert body["reduced"] == config["reduced"]
    assert (REPO / "benchmark" / "presets" / f"{body['preset']}.json").is_file()
    assert body["guarantees"] and body["assumed"]
    cells = [w for w in BENCH["workloads"] if w["config"] == config["name"]]
    # the file places the core; its cells ask the driver for as many chips
    assert cells and {w["chips"] for w in cells} == {body["chips"]} <= {1, 4}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_config_mix_driver_and_metric_files(name):
    cell = run.Cell(name)
    assert cell.name == f"{cell.row['config']}.{cell.row['traffic']}"
    assert cell.chips == cell.config["chips"] and len(cell.row["why"]) <= 200
    assert cell.config["validators"] >= 300_000
    assert callable(cell.driver())
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for metric in cell.per_layer:
        assert metric["moves"] in reported
        kind = metric["reader"]["kind"]
        assert (REPO / "benchmark" / "sources" / f"{kind}.py").is_file()


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_entry_is_its_file(entry):
    body = json.loads((REPO / "benchmark" / "layer_metrics"
                       / f"{entry['name']}.json").read_text())
    assert set(entry) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # the file holds the reader; which cells read it is BENCHMARK.json's to
    # say, where the driver looks, and is written nowhere else
    assert "workloads" not in body and body["reader"]["kind"] and body["what"]
    assert {k: body[k] for k in entry if k != "workloads"} \
        == {k: v for k, v in entry.items() if k != "workloads"}
    assert entry["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", entry["unit"])


def test_peaks_are_sourced_and_an_unknown_device_is_an_error():
    assert V5E["hbm_bytes_per_s"] == 819e9 and V5E["bf16_flops_per_s"] == 197e12
    assert V5E["int8_ops_per_s"] == 393e12 and V5E["hbm_bytes"] == 16e9
    assert "Google Cloud" in V5E["source"]
    assert run.peaks_for("TPU v5 lite") == V5E
    with pytest.raises(SystemExit, match="no published peaks"):
        run.peaks_for("cpu")


def test_epoch_program_bytes_follow_the_column_shapes():
    # 2 x (six uint64 + one bool) + five bools + one uint64 + two int32
    count = epoch_program_bytes.count
    assert count({"validators": 0}) == 2 * 1024 * 8 + 2 * 8192 * 8
    per_validator = (count({"validators": 1_000_000})
                     - count({"validators": 0})) / 1_000_000
    assert per_validator == 2 * 49 + 21


# -- the measuring path refuses anything but a TPU ---------------------------

ARGV = ["--workload", CELLS[0], "--seed", str(SEED), "--seconds", "1",
        "--trace", "0"]


def test_non_tpu_first_device_is_refused(capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(ARGV)
    assert exc.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="no workload"):
        run.Cell("mainnet-1m.nothing")


def test_bare_directory_exits_nonzero_and_prints_no_result(tmp_path):
    """BENCHMARK.json and the files under `paths`, and nothing else."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], *ARGV], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


# -- both drivers end to end, the chip look skipped --------------------------

@pytest.fixture
def drive(monkeypatch, capsys):
    """`run_cell` at V = 8,192 on this backend: the look for the chip is the
    caller's, the oracle has a test of its own, and the peaks are the v5e's."""
    import jax
    monkeypatch.setattr(reference, "oracle_small", lambda: [])
    monkeypatch.setattr(run, "peaks_for", lambda kind: V5E)

    def _drive(name, *, trace, seconds=0.5, root=REPO, validators=TINY_V):
        cell = run.Cell(name, root)
        result = run.run_cell(
            cell, SEED, seconds, trace,
            run.describe_device(jax.devices()[:cell.chips]),
            validators=validators)
        said = capsys.readouterr()
        rows = [json.loads(line) for line in said.out.splitlines()]
        # each number compared stands beside its limit on standard error too
        assert [l.split()[1].rstrip(":") for l in said.err.splitlines()
                if l.startswith("compared ")] \
            == [r["compared"] for r in rows if "compared" in r] != []
        _check_line(result, cell, trace)
        return result, rows
    return _drive


def _before_compare(monkeypatch, driver_module, fault):
    """Run `fault(driver)` once the window has closed, before the comparison:
    the harness has no hook for a fault, the tests bring their own."""
    real = driver_module.Driver.compare

    def compare(self):
        fault(self)
        return real(self)
    monkeypatch.setattr(driver_module.Driver, "compare", compare)


def one_gwei_on_the_device(driver):
    """One Gwei onto one seeded balance, on the device, behind the forests'
    back: a root that does not follow the columns breaks guarantee 1."""
    import numpy as np
    cols = driver.dep.core.cols
    driver.dep.core.cols = cols._replace(
        balance=cols.balance.at[driver.seed % TINY_V].add(np.uint64(1)))


def drop_one_attestation_at_the_boundary(driver):
    """The next boundary runs without one pending attestation: not the
    whole process_epoch over every attestation, which breaks guarantee 2."""
    core = driver.dep.core
    real = core.process_epoch_resident

    def dropped(state):
        atts = state.previous_epoch_attestations
        atts.pop(driver.seed % len(atts))
        return real(state)
    core.process_epoch_resident = dropped


def _check_line(result, cell, trace):
    """The contract's keys, and the cell's own metrics: its end-to-end ones
    untraced; traced on this host backend its per-layer ones but those the
    device's trace gives, whose readers find no device plane."""
    assert set(result) - {"breakdown"} == {"correct", "attempted", "failed",
                                           "metrics", "device"}
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["device"]["count"] == cell.chips
    metrics = [m for m in cell.per_layer if m["source"] != "device_trace"] \
        if trace else cell.end_to_end
    assert set(result["metrics"]) == {m["name"] for m in metrics}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    json.dumps(result)


def test_main_prints_the_contract_line_last(monkeypatch, capsys):
    """`main` as the driver calls it, with only the look for the chip, the
    size, the oracle and the peaks replaced: the last line of stdout is the
    result."""
    import jax
    monkeypatch.setattr(run, "find_chips",
                        lambda want: run.describe_device(jax.devices()[:1]))
    monkeypatch.setattr(reference, "oracle_small", lambda: [])
    monkeypatch.setattr(run, "peaks_for", lambda kind: V5E)
    real = run.run_cell
    monkeypatch.setattr(run, "run_cell",
                        lambda *a: real(*a, validators=TINY_V))
    assert run.main(ARGV) == 0
    lines = capsys.readouterr().out.splitlines()
    result = json.loads(lines[-1])
    _check_line(result, run.Cell(CELLS[0]), trace=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 64 and result["attempted"] % 64 == 0
    assert all(m["value"] > 0 for m in result["metrics"].values())
    compared = [json.loads(l) for l in lines[:-1] if '"compared"' in l]
    assert all(c["ok"] and c["limit"] == 0 for c in compared)
    assert {c["compared"] for c in compared} >= {
        "state_root.bytes_differing_from_hashlib",
        "boundary.balances_differing_from_reference",
        "boundary.effective_balances_differing_from_reference",
        "boundary.small_fields_differing_from_reference"}
    samples = next(json.loads(l) for l in lines if '"samples"' in l)
    # the rate is over the whole window, the generator's time included
    rate = result["metrics"]["replay_slots_per_s"]["value"]
    assert rate == pytest.approx(result["attempted"] / samples["window_s"])
    assert len(samples["notes"]["epoch_slot_median_ms"]) \
        == result["attempted"] // 64


@pytest.mark.skipif("mainnet-1m.restore" not in CELLS, reason="cell not proved")
def test_restore_driver_traced_run_reports_its_layers(monkeypatch, drive):
    # the profiler stops after the first cycle; the window runs on
    monkeypatch.setattr(run, "TRACED_SECONDS", 0.0)
    result, rows = drive("mainnet-1m.restore", trace=True)
    assert result["correct"] is True and result["attempted"] >= 1
    assert any(r.get("compared")
               == "resumed_state_root.bytes_differing_from_hashlib"
               and r["ok"] for r in rows)


def _failed(rows):
    return [r["compared"] for r in rows if "compared" in r and not r["ok"]]


def test_one_gwei_on_the_device_makes_correct_false(monkeypatch, drive):
    """Control 1: the forests' roots, and so the state root that every slot
    records, no longer follow the columns."""
    from benchmark.drivers import replay
    _before_compare(monkeypatch, replay, one_gwei_on_the_device)
    result, rows = drive(CELLS[0], trace=True)
    assert result["correct"] is False
    assert _failed(rows) == ["balances_root.bytes_differing_from_hashlib",
                             "state_root.bytes_differing_from_hashlib"]
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert result["metrics"]["guard_events"]["value"] == 0


def test_one_attestation_dropped_at_the_boundary_makes_correct_false(
        monkeypatch, drive):
    """Control 2: the roots still follow the columns, the justification
    still holds; only the plain epoch sees the missing committee."""
    from benchmark.drivers import replay
    _before_compare(monkeypatch, replay, drop_one_attestation_at_the_boundary)
    result, rows = drive(CELLS[0], trace=False)
    assert result["correct"] is False
    assert _failed(rows) == ["boundary.balances_differing_from_reference"]
    got = next(r["got"] for r in rows if r.get("compared")
               == "boundary.balances_differing_from_reference")
    assert got > TINY_V // 2        # the share of every reward moves


def test_a_boundary_that_returns_its_state_unchanged_makes_correct_false(
        monkeypatch, drive):
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    monkeypatch.setattr(ResidentCore, "process_epoch_resident",
                        lambda self, state: None)
    result, rows = drive(CELLS[0], trace=False)
    assert result["correct"] is False
    assert {"epochs_between_justified_and_previous",
            "boundary.balances_differing_from_reference",
            "boundary.small_fields_differing_from_reference"} <= set(_failed(rows))


def test_a_slot_root_served_from_a_cache_makes_correct_false(
        monkeypatch, drive):
    """The shortcut a later PR might take: the slot's root is not computed
    from the state as it stands but kept from the slot before."""
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    real, kept = ResidentCore._state_root, {}

    def cached(self, state):
        if state is self.state and int(state.slot) % 64 == 63 and kept:
            return kept["root"]
        kept["root"] = real(self, state)
        return kept["root"]
    monkeypatch.setattr(ResidentCore, "_state_root", cached)
    result, rows = drive(CELLS[0], trace=False)
    assert result["correct"] is False
    assert _failed(rows) == ["state_root.bytes_differing_from_hashlib"]


@pytest.mark.skipif("mainnet-1m.restore" not in CELLS, reason="cell not proved")
def test_a_checkpoint_altered_where_it_is_written_fails_its_cycles(
        monkeypatch, drive):
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    real = ResidentCore.checkpoint_bytes

    def altered(self):
        data = bytearray(real(self))
        data[len(data) // 2] ^= 1       # one bit, in the registry's payload
        return bytes(data)
    monkeypatch.setattr(ResidentCore, "checkpoint_bytes", altered)
    result, rows = drive("mainnet-1m.restore", trace=False)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1
    assert "resumed_state_root.bytes_differing_from_hashlib" in _failed(rows)


@pytest.mark.skipif("mainnet-1m.restore" not in CELLS, reason="cell not proved")
def test_one_gwei_on_the_live_core_fails_the_restore_comparison(
        monkeypatch, drive):
    from benchmark.drivers import restore
    _before_compare(monkeypatch, restore, one_gwei_on_the_device)
    result, rows = drive("mainnet-1m.restore", trace=False)
    assert result["correct"] is False
    assert _failed(rows) == ["restore.check_cycle_roots_differing_from_live"]


# -- a configuration, its cells and a metric added to a copy of the benchmark ---

DATA_DIRS = ("configs", "traffic", "layer_metrics", "presets")
MESH_CONFIG = "mainnet-tiny-mesh4"
# one cell the benchmark has on each of its mixes: the added configuration's
# cell on that mix reports and reads what this one does
LIKE = {w["traffic"]: w["name"] for w in reversed(BENCH["workloads"])}
MESH_CELLS = {mix: f"{MESH_CONFIG}.{mix}" for mix in LIKE}
MESH_CELL = MESH_CELLS["replay"]
MESH_V = TINY_V + 2     # no multiple of four: the device columns pad by two rows
MESH_METRIC = {
    "name": "relayout_events.mesh4", "unit": "count", "better": "lower",
    "source": "program_counter", "layer": "guard rails",
    "moves": "replay_slots_per_s", "workloads": [MESH_CELL]}


def _tree(root):
    return {p.relative_to(root): p.read_bytes()
            for p in root.rglob("*") if p.is_file()}


def _but_for_cells_appended(was: dict, now: dict) -> dict:
    """`now` cut back to the entries `was` has, each metric's `workloads`
    to as many cells as `was` lists there."""
    out = dict(now)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        out[key] = [
            dict(new, workloads=new["workloads"][:len(old["workloads"])])
            if "workloads" in old else new
            for old, new in zip(was[key], now[key])]
    return out


@pytest.fixture
def with_mesh_cells(tmp_path):
    """A copy of BENCHMARK.json and the benchmark's data, with what a
    `model_config` PR adds: a four-chip configuration in a file of its own,
    its cell on every mix that is there, and one per-layer metric that only
    its replay cell reads. No copied file is touched, and no entry of
    BENCHMARK.json that was there, but for this: the `workloads` list of a
    metric is where the driver reads which cells report it, so each new
    cell's name is appended to the lists that name the cell it is like.
    Gives `make(cell_chips=4)` -> the copy's root."""
    def make(cell_chips=4):
        root = tmp_path / f"cells_ask_for_{cell_chips}"
        for d in DATA_DIRS:
            shutil.copytree(REPO / "benchmark" / d, root / "benchmark" / d)
        copied = _tree(root)
        was = json.loads((REPO / "BENCHMARK.json").read_text())
        bench = json.loads((REPO / "BENCHMARK.json").read_text())
        one_chip = run.Cell(LIKE["replay"]).config
        row = {"name": MESH_CONFIG, "source": one_chip["source"],
               "file": f"benchmark/configs/{MESH_CONFIG}.json",
               "reduced": one_chip["reduced"] + ["validators"],
               "why": "the harness's own proof: the mainnet preset with the "
                      "validator axis over four devices, at a test's size"}
        (root / row["file"]).write_text(json.dumps(dict(
            one_chip, name=MESH_CONFIG, validators=MESH_V, chips=4,
            reduced=row["reduced"],
            layout="four chips, the validator axis sharded over them")))
        (root / "benchmark" / "layer_metrics"
         / f"{MESH_METRIC['name']}.json").write_text(json.dumps(dict(
             {k: v for k, v in MESH_METRIC.items() if k != "workloads"},
             reader={"kind": "counter_delta",
                     "counters": ["watchdog.relayout_events"]})))
        bench["configs"].append(row)
        for mix, name in MESH_CELLS.items():
            bench["workloads"].append({
                "name": name, "config": MESH_CONFIG, "traffic": mix,
                "chips": cell_chips, "why": f"the {mix} mix on the sharded core"})
            for m in bench["end_to_end"] + bench["per_layer"]:
                if LIKE[mix] in m.get("workloads", []):
                    m["workloads"].append(name)
        bench["per_layer"].append(MESH_METRIC)
        (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
        assert {k: v for k, v in _tree(root).items() if k in copied} == copied
        assert _but_for_cells_appended(was, bench) == was
        return root
    return make


def test_cells_added_to_a_copy_leave_the_others_as_they_resolve(
        with_mesh_cells):
    root = with_mesh_cells()
    for name in CELLS:
        here, there = run.Cell(name), run.Cell(name, root)
        assert there.per_layer == here.per_layer
        assert [m["name"] for m in there.end_to_end] \
            == [m["name"] for m in here.end_to_end]
        assert (there.config, there.mix, there.chips) \
            == (here.config, here.mix, here.chips)
    for mix, name in MESH_CELLS.items():
        added, like = run.Cell(name, root), run.Cell(LIKE[mix])
        assert added.chips == added.config["chips"] == 4
        assert [m["name"] for m in added.end_to_end] \
            == [m["name"] for m in like.end_to_end]
        assert [m["name"] for m in added.per_layer] \
            == [m["name"] for m in like.per_layer] \
            + [MESH_METRIC["name"]] * (name == MESH_CELL)


def test_a_new_cell_reports_only_what_lists_it(with_mesh_cells):
    """Without its name in an end-to-end metric's `workloads` a new cell
    reports `setup_s` alone, and a per-layer metric that lists it is then
    refused by name: the lists are not inherited from the mix."""
    root = with_mesh_cells()
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"]:
        if MESH_CELL in m.get("workloads", []):
            m["workloads"].remove(MESH_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(SystemExit, match=f"{MESH_CELL!r}, which does not report"):
        run.Cell(MESH_CELL, root)
    for m in bench["per_layer"]:
        if MESH_CELL in m["workloads"]:
            m["workloads"].remove(MESH_CELL)
    bench["per_layer"] = [m for m in bench["per_layer"] if m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    bare = run.Cell(MESH_CELL, root)
    assert [m["name"] for m in bare.end_to_end] == ["setup_s"]
    assert bare.per_layer == []


def test_a_cell_whose_chips_differ_from_its_configurations_is_refused(
        with_mesh_cells):
    with pytest.raises(SystemExit, match=(
            f"{MESH_CELL!r} asks for 1 chip.*{MESH_CONFIG}.json lays the "
            f"deployment out on 4")):
        run.Cell(MESH_CELL, with_mesh_cells(cell_chips=1))


def test_the_mesh_cell_runs_correct_on_four_devices(
        monkeypatch, drive, with_mesh_cells):
    """The configuration's file places the core: its columns live on the
    first four devices, padded there to a multiple of four, and every
    comparison holds on the logical rows."""
    from benchmark.drivers import replay
    placed = {}
    _before_compare(monkeypatch, replay, lambda driver: placed.update(
        devices=driver.dep.core.cols.balance.sharding.device_set,
        rows=driver.dep.core.cols.balance.shape[0],
        fetched=len(driver.dep.fetch_columns()["balance"])))
    result, rows = drive(MESH_CELL, trace=False, root=with_mesh_cells(),
                         validators=MESH_V)
    import jax
    assert placed["devices"] == set(jax.devices()[:4])
    assert placed["fetched"] == MESH_V < placed["rows"] == MESH_V + 2
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 64 and _failed(rows) == []
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_one_gwei_on_the_mesh_cells_device_makes_correct_false(
        monkeypatch, drive, with_mesh_cells):
    """Control 1 on the sharded core, traced: the line holds every metric of
    the replay cells that a host backend can read, and the one the cell
    brought, read through its own file."""
    from benchmark.drivers import replay
    _before_compare(monkeypatch, replay, one_gwei_on_the_device)
    result, rows = drive(MESH_CELL, trace=True, root=with_mesh_cells(),
                         validators=MESH_V)
    assert result["correct"] is False
    assert _failed(rows) == ["balances_root.bytes_differing_from_hashlib",
                             "state_root.bytes_differing_from_hashlib"]
    assert result["metrics"][MESH_METRIC["name"]] == {"value": 0.0,
                                                      "unit": "count"}
    assert len(result["metrics"]) > 1


@pytest.mark.skipif("restore" not in MESH_CELLS, reason="no restore mix")
def test_the_mesh_restore_cell_resumes_onto_the_four_devices(
        monkeypatch, drive, with_mesh_cells):
    """The restore driver resumes every cycle's core onto the deployment's
    mesh: padded columns on the four devices, roots equal to the live
    core's and to hashlib's over the logical rows."""
    from consensus_specs_tpu.models.phase0.resident import ResidentCore
    real, resumed = ResidentCore.from_checkpoint.__func__, []

    def recording(cls, spec, data, mesh="env"):
        core = real(cls, spec, data, mesh=mesh)
        resumed.append((mesh, core.cols.balance.sharding.device_set,
                        core.cols.balance.shape[0]))
        return core
    monkeypatch.setattr(ResidentCore, "from_checkpoint", classmethod(recording))
    result, rows = drive(MESH_CELLS["restore"], trace=False,
                         root=with_mesh_cells(), validators=MESH_V)
    import jax
    assert len(resumed) >= 1 + 1 + result["attempted"] + 1  # live, warm-up, window, check
    assert all(mesh is not None and mesh != "env"
               and devices == set(jax.devices()[:4]) and rows_ == MESH_V + 2
               for mesh, devices, rows_ in resumed)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1 and _failed(rows) == []


def test_oracle_small_compares_with_the_object_model():
    compared = reference.oracle_small(validators=32)
    assert len(compared) == 5 and all(c.ok and c.limit == 0 for c in compared)


def test_hashlib_reference_sees_one_gwei():
    import numpy as np
    rng = np.random.default_rng(3)
    n = 5
    cols = {f: rng.integers(0, 1 << 40, n).astype(np.uint64) for f in (
        "activation_eligibility_epoch", "activation_epoch", "exit_epoch",
        "withdrawable_epoch", "effective_balance", "balance")}
    cols["slashed"] = np.zeros(n, bool)
    pk = rng.integers(0, 256, (n, 48), dtype=np.uint8)
    wc = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    reg, bal = reference.host_registry_balances_roots(cols, pk, wc)
    assert bal == reference.host_balances_root(cols["balance"])
    cols["balance"][2] += np.uint64(1)
    reg2, bal2 = reference.host_registry_balances_roots(cols, pk, wc)
    assert reg2 == reg and bal2 != bal and len(bal) == 32


# -- the plain references against the package, where the package is sound -----

def test_plain_ssz_state_root_equals_the_packages_on_an_object_state():
    """A minimal-preset state after blocks with attestations: both
    attestation lists non-empty, crosslinks and roots filled."""
    from consensus_specs_tpu.crypto import bls
    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.testing import factories
    from consensus_specs_tpu.utils.ssz.impl import hash_tree_root
    bls.bls_active = False
    spec = phase0.get_spec("minimal")
    spec.clear_caches()
    state = factories.seed_genesis_state(spec, 64)
    factories.advance_slots(spec, state, 3)
    for _ in range(spec.SLOTS_PER_EPOCH + 2):
        att = factories.new_attestation(spec, state)
        block = factories.empty_block_next(spec, state)
        block.slot = state.slot + spec.MIN_ATTESTATION_INCLUSION_DELAY
        block.body.attestations.append(att)
        spec.state_transition(state, block)
    types = dict(zip(spec.BeaconState.get_field_names(),
                     spec.BeaconState.get_field_types()))
    big = [hash_tree_root(getattr(state, f), types[f])
           for f in ("validator_registry", "balances")]
    plain = plain_ssz.read_state(state)
    assert plain["previous_epoch_attestations"] and plain["current_epoch_attestations"]
    assert plain_ssz.state_root(plain, *big) == hash_tree_root(state)
    plain["current_epoch_attestations"][0]["aggregation_bitfield"] += b"\x00"
    assert plain_ssz.state_root(plain, *big) != hash_tree_root(state)


@pytest.mark.parametrize("n", [1000, 8192, 20001])
def test_plain_shuffle_equals_the_specs_one_point_shuffle(n):
    from consensus_specs_tpu.models import phase0
    spec = phase0.get_spec("mainnet")
    seed = bytes(range(32))
    perm = plain_epoch.shuffle_permutation(n, seed, 90)
    assert sorted(perm.tolist()) == list(range(n))
    for i in range(0, n, 211):
        assert perm[i] == spec.get_shuffled_index(i, n, seed)


def test_preset_constants_of_the_references_equal_the_programs():
    from consensus_specs_tpu.models import phase0
    spec = phase0.get_spec("mainnet")
    constants = json.loads(
        (REPO / "benchmark" / "presets" / "mainnet.json").read_text())
    assert len(constants) > 20
    for key, value in constants.items():
        if key != "source":
            assert int(getattr(spec, key)) == value, key


def test_plain_epoch_refuses_a_registry_it_does_not_cover():
    import numpy as np
    constants = json.loads(
        (REPO / "benchmark" / "presets" / "mainnet.json").read_text())
    far = np.uint64(constants["FAR_FUTURE_EPOCH"])
    cols = {"activation_eligibility_epoch": np.zeros(4, np.uint64),
            "activation_epoch": np.zeros(4, np.uint64),
            "exit_epoch": np.full(4, far), "withdrawable_epoch": np.full(4, far),
            "slashed": np.array([False, True, False, False]),
            "effective_balance": np.full(4, 32 * 10**9, np.uint64),
            "balance": np.full(4, 32 * 10**9, np.uint64)}
    with pytest.raises(plain_epoch.Unsupported):
        plain_epoch.boundary(constants, {"slot": 64 * 3 + 63}, cols)
    with pytest.raises(plain_epoch.Unsupported):
        plain_epoch.boundary(constants, {"slot": 64 * 3 + 5}, cols)


# -- the reduction, on a hand-built trace -------------------------------------

MS = 1e6    # nanoseconds


def _varint(n):
    out = bytearray()
    while True:
        byte, n = n & 0x7F, n >> 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(num, n):
    return _varint(num << 3) + _varint(n)


def _blob(num, b):
    return _varint(num << 3 | 2) + _varint(len(b)) + b


def _xspace(planes) -> bytes:
    """A serialized XSpace (tsl/profiler/protobuf/xplane.proto) by hand:
    planes -> lines -> events, names through each plane's event metadata."""
    out = b""
    for pid, (pname, lines) in enumerate(planes):
        ids = {n: i + 1 for i, n in enumerate(
            sorted({e[0] for _, evs in lines for e in evs}))}
        plane = _int(1, pid) + _blob(2, pname.encode())
        for lid, (lname, evs) in enumerate(lines):
            line = _int(1, lid) + _blob(2, lname.encode()) + _int(3, 0)
            for name, start_ns, dur_ns in evs:
                line += _blob(4, _int(1, ids[name])
                              + _int(2, int(start_ns * 1000))
                              + _int(3, int(dur_ns * 1000)))
            plane += _blob(3, line)
        for n, i in ids.items():
            plane += _blob(4, _int(1, i) + _blob(2, _int(1, i)
                                                  + _blob(2, n.encode())))
        out += _blob(1, plane)
    return out


TRACE = [
    ("/host:CPU", [("python", [
        ("bench.window", 0, 100 * MS), ("bench.generator", 0, 10 * MS),
        ("bench.slot", 10 * MS, 30 * MS), ("bench.boundary", 40 * MS, 55 * MS),
        ("not.ours", 5 * MS, 1 * MS)])]),
    ("/device:TPU:0", [
        ("XLA Ops", [("fusion.1", 20 * MS, 10 * MS), ("sort.2", 25 * MS, 10 * MS),
                     ("fusion.1", 50 * MS, 20 * MS), ("fusion.1", 80 * MS, 10 * MS),
                     ("late", 99 * MS, 5 * MS)]),
        ("XLA Modules", [("jit__epoch_transition_traced(7)", 50 * MS, 20 * MS),
                         ("jit__epoch_transition_traced(7)", 80 * MS, 10 * MS),
                         ("jit_other(1)", 20 * MS, 15 * MS)])]),
]


@pytest.fixture(scope="module")
def planes(tmp_path_factory):
    """Through the file: find_xplane and load read what the profiler
    would have written."""
    root = tmp_path_factory.mktemp("trace")
    where = root / "plugins" / "profile" / "2026_09_27"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(_xspace(TRACE))
    return reduce.load(reduce.find_xplane(str(root)))


def test_reduce_busy_and_idle_share(planes):
    # ops cover [20,35] + [50,70] + [80,90] + [99,100] of the 100 ms window
    busy = reduce.device_busy(planes)
    assert busy["chips"] == 1
    assert busy["busy_s"] == pytest.approx(0.046)
    assert busy["window_s"] == pytest.approx(0.100)
    assert reduce.idle_share(planes) == pytest.approx(54.0)


def test_reduce_per_module_time(planes):
    got = reduce.module_seconds(planes, "jit__epoch_transition_traced")
    assert sorted(got) == pytest.approx([0.010, 0.020])
    assert reduce.module_seconds(planes, "jit_nothing") == []


def test_reduce_top_device_ops(planes):
    top = dict(reduce.top_device_ops(planes))
    assert top["jit_other/fusion.1"] == pytest.approx(0.010)
    assert top["jit__epoch_transition_traced/fusion.1"] == pytest.approx(0.030)
    assert top["jit_other/sort.2"] == pytest.approx(0.010)
    assert len(top) == 3            # `late` runs past the window's end
    assert reduce.short_name(
        "%fusion.5 = (u32[8]{0}, u32[8]{0}) fusion(u32[8]{0} %p), kind=kCustom"
    ) == "fusion.5"


def test_reduce_attributes_idle_gaps_to_the_innermost_annotation(planes):
    gaps = dict(reduce.idle_by_annotation(planes))
    assert gaps["bench.generator"] == pytest.approx(0.010)
    assert gaps["bench.slot"] == pytest.approx(0.015)       # [10,20] + [35,40]
    assert gaps["bench.boundary"] == pytest.approx(0.025)   # [40,50] [70,80] [90,95]
    assert gaps["unannotated"] == pytest.approx(0.004)      # [95,99]
    assert sum(gaps.values()) == pytest.approx(0.054)
    assert "not.ours" not in gaps


def test_reduce_without_a_device_plane_reads_nothing():
    host_only = [reduce.Plane("/host:CPU", [reduce.Line("python", [
        reduce.Event("bench.window", 0.0, 1e9)])])]
    assert reduce.device_busy(host_only) is None
    assert reduce.idle_share(host_only) is None
    assert reduce.top_device_ops(host_only) == []


def test_nested_annotations_flatten_to_the_innermost():
    E = reduce.Event
    segs = reduce.innermost_segments(
        [E("outer", 0, 10), E("inner", 2, 3), E("next", 12, 2)])
    assert segs == [(0, 2, "outer"), (2, 5, "inner"), (5, 10, "outer"),
                    (12, 14, "next")]


# -- the readers --------------------------------------------------------------

def _seen(planes=None, **kw):
    base = dict(spans=[], counters={}, values={}, planes=planes,
                config={"validators": 1_000_000}, mix={}, peaks=V5E)
    return Seen(**dict(base, **kw))


READERS = [
    ({"kind": "span_median", "span": "resident.stage", "scale": 1000.0},
     dict(spans=[{"name": "resident.stage", "dur": d} for d in (0.1, 0.3, 0.2)]
          + [{"name": "resident.device", "dur": 9.0}]), 200.0),
    ({"kind": "span_median", "span": "resident.stage"}, {}, None),
    ({"kind": "counter_delta", "counters": ["a", "b", "c"]},
     dict(counters={"a": 2, "b": 1, "z": 5}), 3.0),
    ({"kind": "counter_delta", "counters": ["a"]}, {}, None),
    ({"kind": "harness_value", "key": "k"}, dict(values={"k": 7}), 7.0),
    ({"kind": "harness_value", "key": "k"}, {}, None),
    ({"kind": "trace_idle_share"}, {}, None),
    ({"kind": "roofline_bytes", "bytes": "epoch_program_bytes",
      "peak": "hbm_bytes_per_s", "module_prefix": "jit__epoch"}, {}, None),
]


@pytest.mark.parametrize("reader,seen,want", READERS,
                         ids=[f"{r['kind']}-{i}" for i, (r, _, _) in
                              enumerate(READERS)])
def test_reader(reader, seen, want):
    got = run.read_metric({"reader": reader}, _seen(**seen))
    assert got == (None if want is None else pytest.approx(want))


def test_trace_readers_on_the_hand_built_trace(planes):
    seen = _seen(planes=planes)
    assert run.read_metric({"reader": {"kind": "trace_idle_share"}},
                           seen) == pytest.approx(54.0)
    share = run.read_metric({"reader": {
        "kind": "roofline_bytes", "bytes": "epoch_program_bytes",
        "peak": "hbm_bytes_per_s",
        "module_prefix": "jit__epoch_transition_traced"}}, seen)
    # bytes / peak / the median execution (15 ms)
    least_s = epoch_program_bytes.count({"validators": 1_000_000}) / 819e9
    assert share == pytest.approx(100 * least_s / 0.015)
    assert 0 < share < 100
