"""chip_smoke.py's phases, in-process at tiny sizes on the CPU.

The script's contract is about the chip (it refuses any other first
device); what CAN be held here is that every phase's control flow and
checks run end to end on the rehearsal path, that a non-TPU device is
refused without the rehearsal switch, that the rehearsal switch never
prints the verdict line, and that a failed check is fatal. The 4-chip
phase runs on four of conftest's eight virtual CPU devices.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import jax

import chip_smoke

REPO = Path(__file__).resolve().parents[1]
TINY_V = 8192       # >= the device shuffler's floor: the chip's path


@pytest.fixture
def run():
    # the phases print the process-wide resilience and watchdog counters as
    # they stand; a test file that ran earlier in this worker (the health
    # endpoint's test degrades the ladder once) must not show up in them
    from consensus_specs_tpu import telemetry
    telemetry.reset()
    return chip_smoke.Run(rehearsal=True, seed=7)


def _lines(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()]


def test_phase_oracle_small_matches_object_model(run, capsys):
    row = chip_smoke.phase_oracle_small(run, validators=32)
    assert row["identical"] and row["boundaries"] >= 2
    assert row["fallback_blocks"] == 1
    assert _lines(capsys)[-1]["rehearsal"] is True


def test_phase_resident_tiny(run, capsys):
    row = chip_smoke.phase_resident_1m(run, validators=TINY_V)
    assert row["boundaries"] == 2 and row["slots"] == 65
    checks = row["checks"]
    assert checks["compiles_after_first_boundary"] == 0
    assert checks["ladder_rung"] == "full"
    assert not any(checks["watchdog"].values())
    assert not any(checks["resilience"].values())
    assert _lines(capsys)[-1]["phase"] == "resident_1m"


def test_phase_resident_failed_check_is_fatal(run, monkeypatch):
    """A wrong root is an exception out of the phase — nothing catches
    it, so the process exits non-zero with no verdict line."""
    monkeypatch.setattr(chip_smoke, "host_registry_balances_roots",
                        lambda *a: (b"\x00" * 32, b"\x00" * 32))
    with pytest.raises(AssertionError, match="registry root"):
        chip_smoke.phase_resident_1m(run, validators=TINY_V)


def test_phase_mesh_on_four_virtual_devices(run):
    if len(jax.devices()) < 4:
        pytest.skip(f"needs 4 devices, have {len(jax.devices())}")
    row = chip_smoke.phase_mesh(run, chips=4, validators=TINY_V)
    assert row["identical"]
    assert all(len(ids) == 4 for ids in row["placement"].values())


def _stub_phases(monkeypatch):
    for name in ("phase_oracle_small", "phase_resident_1m",
                 "phase_pair_hash_pallas", "phase_mesh", "phase_bls_block"):
        monkeypatch.setattr(chip_smoke, name, lambda *a, **k: {})


def test_non_tpu_device_is_refused_without_rehearsal(monkeypatch, capsys):
    _stub_phases(monkeypatch)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("argv", [[], ["--bls"], ["--chips", "4"]])
def test_rehearsal_never_prints_the_verdict_line(monkeypatch, capsys, argv):
    _stub_phases(monkeypatch)
    assert chip_smoke.main(["--rehearse", *argv]) == 0
    lines = _lines(capsys)
    assert lines and all(l["rehearsal"] is True for l in lines)
    assert not any("ok" in l for l in lines)


def test_script_exits_nonzero_where_jax_finds_no_tpu():
    """The driver's first run of the script: a sandbox without a chip."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


# -- the compile-cache helper (placed from outside) --------------------------

def _cache_dir_in_config():
    from consensus_specs_tpu.utils import compile_cache
    return getattr(jax.config, compile_cache.CONFIG_KEY)


@pytest.fixture
def cache_config():
    from consensus_specs_tpu.utils import compile_cache
    before = (_cache_dir_in_config(),
              jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update(compile_cache.CONFIG_KEY, before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before[1])


def test_cache_dir_from_the_environment_is_left_alone(monkeypatch, tmp_path,
                                                      cache_config):
    from consensus_specs_tpu.utils import compile_cache
    jax.config.update(compile_cache.CONFIG_KEY, "/sentinel")
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert _cache_dir_in_config() == "/sentinel"


def test_cache_dir_defaults_to_the_checkout(monkeypatch, cache_config):
    from consensus_specs_tpu.utils import compile_cache
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = str(REPO / ".cache" / "xla")
    assert compile_cache.configure() == want
    assert _cache_dir_in_config() == want
    assert os.path.isdir(want)
