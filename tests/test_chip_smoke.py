"""chip_smoke.py on the CPU.

The script's contract is about the chip (it refuses any other first
device); what CAN be held here is that the pair-hash phase's control flow
and checks run end to end on the rehearsal path, that a non-TPU device is
refused without the rehearsal switch, and that the rehearsal switch never
prints the verdict line. The served path's phases went to the benchmark
(tests/benchmark/ drives them with controls that must fail).
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


@pytest.fixture
def run():
    return chip_smoke.Run(rehearsal=True, seed=7)


def _lines(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()]


def test_phase_pair_hash_pallas_rehearsal(run, capsys):
    """The interpreter stands in for Mosaic here; the phase's own checks
    (XLA kernel, hashlib, the two forest backends) run as on the chip."""
    row = chip_smoke.phase_pair_hash_pallas(run, lanes=1 << 9,
                                            leaves=1 << 6)
    assert row["identical"] and row["kernel"].startswith("interpreter")
    assert _lines(capsys)[-1]["phase"] == "pair_hash_pallas"


def _stub_phases(monkeypatch):
    for name in ("phase_pair_hash_pallas", "phase_bls_block"):
        monkeypatch.setattr(chip_smoke, name, lambda *a, **k: {})


def test_non_tpu_device_is_refused_without_rehearsal(monkeypatch, capsys):
    _stub_phases(monkeypatch)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("argv", [[], ["--bls"]])
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
