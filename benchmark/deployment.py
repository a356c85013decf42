"""One deployment on the device, as both drivers need it: the seeded state
entered through the production resume path, the generator, and the
full-size comparisons against the plain references (hashlib merkleization
of the fetched columns, plain_ssz's state root, plain_epoch's boundary)."""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from benchmark import plain_epoch, plain_ssz, reference
from benchmark.reference import Compared

HERE = Path(__file__).resolve().parent
OTHER_COLUMNS = ("activation_eligibility_epoch", "activation_epoch",
                 "exit_epoch", "withdrawable_epoch", "slashed")


class Deployment:
    """The mainnet-preset spec, the seeded checkpoint and the live core.

    `validators` is the configuration's own count; tests pass a smaller one
    through the driver's test-only argument (never a command-line option)."""

    def __init__(self, config: dict, seed: int, validators: int | None = None):
        from consensus_specs_tpu import telemetry
        from consensus_specs_tpu.crypto import bls
        from consensus_specs_tpu.models import phase0
        from consensus_specs_tpu.models.phase0.resident import ResidentCore
        from consensus_specs_tpu.ops.shuffle import install_device_shuffler

        self.config = config
        self.validators = int(validators or config["validators"])
        with open(HERE / "presets" / f"{config['preset']}.json") as f:
            self.constants = json.load(f)   # what the plain references read
        telemetry.set_enabled(True)
        bls.bls_active = bool(config["assumed"]["bls_active"])
        install_device_shuffler()
        self.spec = spec = phase0.get_spec(config["preset"])
        spec.clear_caches()
        self.spe = int(spec.SLOTS_PER_EPOCH)
        self.timings = {}
        t0 = time.perf_counter()
        data = reference.seeded_checkpoint(spec, self.validators, seed)
        self.timings["state_build_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.mesh = serving_mesh(int(config["chips"]))
        self.core = ResidentCore.from_checkpoint(spec, data, mesh=self.mesh)
        self.timings["enter_s"] = time.perf_counter() - t0
        self.state = self.core.state
        self._lay = None

    # -- the generator: what the slot's block would have left ---------------

    def append_attestations(self) -> int:
        """Full-participation PendingAttestations for the committees of the
        slot before the state's, into current_epoch_attestations."""
        from consensus_specs_tpu.models.phase0.epoch_soa import _epoch_layout
        spec, state = self.spec, self.state
        epoch = spec.get_current_epoch(state)
        if self._lay is None or self._lay.epoch != epoch:
            self._lay = _epoch_layout(spec, state, self.core.mirrors, epoch)
        if int(state.slot) <= epoch * self.spe:
            return 0            # the epoch's first slot: nothing to include yet
        return reference.append_slot_attestations(
            spec, state, self._lay, int(state.slot) - 1, epoch,
            (state.current_justified_epoch, state.current_justified_root),
            state.current_epoch_attestations)

    # -- full size, after the window ---------------------------------------

    def fetch_columns(self) -> dict:
        """The validator columns, off the device, as numpy."""
        return self.core._materialize_np_cols()

    def hashlib_roots(self, cols: dict) -> tuple:
        """(registry root, balances root) of the fetched columns, by hashlib."""
        return reference.host_registry_balances_roots(
            cols, self.core._pk_np, self.core._wc_np)

    def compare_forest_roots(self, want: tuple, got=None) -> list:
        """Registry and balances roots (the live core's device forests', or
        `got`) against the hashlib roots `want`."""
        got = got or self.core._registry_balances_roots()
        return [
            Compared("registry_root.bytes_differing_from_hashlib",
                     _bytes_differing(bytes(got[0]), want[0]), 0),
            Compared("balances_root.bytes_differing_from_hashlib",
                     _bytes_differing(bytes(got[1]), want[1]), 0),
        ]

    def plain_state_root(self, want: tuple) -> tuple:
        """(the state's small fields as plain values, the state root that
        hashlib gives them with the hashlib registry and balances roots)."""
        pre = plain_ssz.read_state(self.state)
        return pre, plain_ssz.state_root(pre, *want)

    def compare_boundary(self, pre: dict, before: dict) -> list:
        """The boundary the core has just run, against plain_epoch's on the
        columns fetched before it and the small fields read before it."""
        after = self.fetch_columns()
        try:
            want = plain_epoch.boundary(self.constants, pre, before)
        except plain_epoch.Unsupported:
            return [Compared("boundary.registry_not_covered_by_reference", 1, 0)]
        post = plain_ssz.read_state(self.state)
        small = [k for k in want if k not in ("balance", "effective_balance")]
        fields = sum(
            (sum(a != b for a, b in zip(post[k], want[k]))
             + abs(len(post[k]) - len(want[k])))
            if isinstance(want[k], list) else int(post[k] != want[k])
            for k in small)
        forest = self.core._registry_balances_roots()[1]
        return [
            Compared("boundary.balances_differing_from_reference",
                     int(np.count_nonzero(after["balance"] != want["balance"])), 0),
            Compared("boundary.effective_balances_differing_from_reference",
                     int(np.count_nonzero(after["effective_balance"]
                                          != want["effective_balance"])), 0),
            Compared("boundary.other_columns_entries_changed",
                     sum(int(np.count_nonzero(after[f] != before[f]))
                         for f in OTHER_COLUMNS), 0),
            Compared("boundary.small_fields_differing_from_reference",
                     int(fields), 0),
            Compared("boundary.balances_root_after.bytes_differing_from_hashlib",
                     _bytes_differing(
                         bytes(forest),
                         reference.host_balances_root(after["balance"])), 0),
        ]

    def compare_justification(self) -> Compared:
        """Under full participation the epoch just ended is justified."""
        current = int(self.spec.get_current_epoch(self.state))
        justified = int(self.state.current_justified_epoch)
        return Compared("epochs_between_justified_and_previous",
                        abs(current - 1 - justified), 0)

    def close(self) -> None:
        self.core._uninstall()
        self.spec.clear_caches()


def serving_mesh(chips: int):
    """Where the configuration's file places the core: on one chip with no
    mesh, on more with the validator axis over the first `chips` devices."""
    from consensus_specs_tpu.parallel.sharding import ServingMesh
    if chips == 1:
        return None
    if chips < 1 or chips & (chips - 1):
        raise SystemExit(
            f"benchmark: a serving mesh takes a power of two of chips, the "
            f"configuration states {chips}")
    return ServingMesh.create(chips)


def _bytes_differing(got: bytes, want: bytes) -> int:
    return abs(len(got) - len(want)) + sum(a != b for a, b in zip(got, want))


def guard_counters() -> dict:
    return reference.counters(
        reference.RESILIENCE_COUNTERS + reference.WATCHDOG_COUNTERS)
