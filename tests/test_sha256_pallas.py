"""Pallas pair-hash kernel == XLA kernel == hashlib. These tests run on the
CPU, where Mosaic cannot lower, so each passes interpret=True itself (the
interpreter's fori-loop body); the Mosaic body is compiled for a v5e in
tests/test_tpu_compile.py and run on the chip by chip_smoke.py."""
import hashlib

import numpy as np
import pytest

from consensus_specs_tpu.ops import sha256 as S
from consensus_specs_tpu.ops.sha256_pallas import sha256_pairs_pallas


@pytest.mark.parametrize("n", [1, 5, 128, 300])
def test_pallas_pairs_match_xla(n):
    """Ragged sizes cross the lane-padding boundaries (128, 512)."""
    rng = np.random.default_rng(n)
    words = rng.integers(0, 2 ** 32, (n, 16), dtype=np.uint32)
    got = np.asarray(sha256_pairs_pallas(words, interpret=True))
    want = np.asarray(S.sha256_pairs(words))
    assert (got == want).all()


def test_pallas_pairs_multi_tile_grid():
    """n=300 at block_lanes=128 runs a 3-step grid: a broken BlockSpec
    index map (e.g. every step reading tile 0) cannot pass this."""
    rng = np.random.default_rng(99)
    words = rng.integers(0, 2 ** 32, (300, 16), dtype=np.uint32)
    got = np.asarray(sha256_pairs_pallas(words, block_lanes=128, interpret=True))
    want = np.asarray(S.sha256_pairs(words))
    assert (got == want).all()


def test_pallas_pairs_match_hashlib():
    msgs = [bytes(range(64)), b"\x00" * 64, b"\xff" * 64]
    words = np.stack([
        S.bytes_to_words(np.frombuffer(m, dtype=np.uint8)) for m in msgs])
    got = np.asarray(sha256_pairs_pallas(words, interpret=True))
    for k, m in enumerate(msgs):
        assert S.words_to_bytes(got[k]).tobytes() == hashlib.sha256(m).digest()


def test_pallas_default_is_mosaic_and_fails_off_tpu():
    """No device-dependent interpret default: off-TPU the plain call
    raises instead of quietly running the interpreter."""
    words = np.zeros((4, 16), dtype=np.uint32)
    with pytest.raises(Exception):
        np.asarray(sha256_pairs_pallas(words))
