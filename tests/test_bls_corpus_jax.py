"""Signature-bearing spec scenarios under the JAX BLS backend.

The e2e gate: rows from the scenario corpus that
actually exercise signatures (the @always_bls rejection rows plus the
success rows re-run with BLS ON) execute under BOTH crypto backends, and
their generator-mode artifacts — encoded pre/post states and operations —
must match byte-for-byte. This proves the device pairing path is a drop-in
for the bignum oracle inside real process_* handlers, not just in isolated
curve tests.

Backend boundary: consensus_specs_tpu/crypto/bls.py (mirrors
/root/reference test_libs/pyspec/eth2spec/utils/bls.py:24-46 + the
bls_setting test switch at eth2spec/test/context.py:79-90).
"""
import importlib

import pytest

pytestmark = pytest.mark.slow  # pairing compiles dominate suite wall-clock

from consensus_specs_tpu.crypto import bls

# (table module, case name) — kept small: every row here signs and/or
# verifies real signatures, and each runs twice (once per backend)
ROWS = [
    ("attestation", "test_success"),
    ("attestation", "test_invalid_attestation_signature"),
    ("block_header", "test_success_block_header"),
    ("block_header", "test_invalid_sig_block_header"),
    ("proposer_slashing", "test_success"),
    ("proposer_slashing", "test_invalid_sig_1"),
    ("deposit", "test_new_deposit"),
    ("deposit", "test_invalid_sig_new_deposit"),
    ("voluntary_exit", "test_success"),
    ("voluntary_exit", "test_invalid_signature"),
]


def _run_row(module_name: str, case_name: str, backend: str):
    mod = importlib.import_module(
        f"consensus_specs_tpu.testing.cases.{module_name}")
    fn = getattr(mod, case_name)
    old = bls._active_backend_name
    bls.set_backend(backend)
    try:
        return fn(generator_mode=True, phase="phase0", preset="minimal",
                  bls_active=True)
    finally:
        bls.set_backend(old)


@pytest.mark.parametrize("module_name,case_name", ROWS,
                         ids=[f"{m}:{c}" for m, c in ROWS])
def test_jax_backend_matches_python(module_name, case_name):
    via_python = _run_row(module_name, case_name, "python")
    via_jax = _run_row(module_name, case_name, "jax")
    assert via_python == via_jax


def test_backend_sign_agreement():
    """Direct cross-backend signing equality on a spec-shaped message."""
    msg, sk, dom = b"\x42" * 32, 777, 5
    bls.set_backend("python")
    ref = bls.get_backend().sign(msg, sk, dom)
    bls.set_backend("jax")
    try:
        dev = bls.get_backend().sign(msg, sk, dom)
    finally:
        bls.set_backend("python")
    assert ref == dev
