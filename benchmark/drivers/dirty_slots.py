"""Closed-loop sync of whole epochs of blocks that change the registry, on
the seeded state of a mature chain.

The sync mix's slot, timed exactly as there (`bench.slot` / `bench.boundary`
round `core.process_slots(state, slot + 1)`, the generator's block under
`bench.generator` inside the rate, `core.process_block(state, block)` under
`bench.block`), with `ops_generator.OpsBlockGenerator`'s block: one full
aggregate a committee, 16 voluntary exits, now and then a proposer or an
attester slashing. Every block dirties registry leaves (and a slashing's
block balance chunks), so every slot root takes both forests' roots after a
path update, where the other mixes read roots cached since the boundary;
and from five epochs after the first exit the active set shrinks by the
churn limit every epoch, so the shuffle, the committees and the boundary
run on a count that moves.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from benchmark import (plain_block, plain_epoch_registry, plain_operations,
                       plain_ssz, reference, seeded_mature, spoiled_blocks,
                       spoiled_operations)
from benchmark.deployment import Deployment, _bytes_differing, serving_mesh
from benchmark.drivers import sync
from benchmark.ops_generator import OpsBlockGenerator
from benchmark.reference import Compared

HERE = Path(__file__).resolve().parent
COLUMNS = ("activation_eligibility_epoch", "activation_epoch", "exit_epoch",
           "withdrawable_epoch", "slashed", "effective_balance", "balance")


class MatureDeployment(Deployment):
    """`Deployment` on `seeded_mature.seeded_mature_checkpoint`'s state,
    with the boundary held to `plain_epoch_registry`. `Deployment.__init__`
    calls `reference.seeded_checkpoint` and may not be edited, so its
    steps are repeated here around the other seed."""

    def __init__(self, config: dict, seed: int, validators: int | None = None):
        from consensus_specs_tpu import telemetry
        from consensus_specs_tpu.crypto import bls
        from consensus_specs_tpu.models import phase0
        from consensus_specs_tpu.models.phase0.resident import ResidentCore
        from consensus_specs_tpu.ops.shuffle import install_device_shuffler

        self.config = config
        self.validators = int(validators or config["validators"])
        self.constants = {}
        for name in ("", ".blocks", ".ops"):    # what the plain references read
            with open(HERE.parent / "presets" / f"{config['preset']}{name}.json") as f:
                self.constants.update(json.load(f))
        telemetry.set_enabled(True)
        bls.bls_active = bool(config["assumed"]["bls_active"])
        install_device_shuffler()
        self.spec = spec = phase0.get_spec(config["preset"])
        spec.clear_caches()
        self.spe = int(spec.SLOTS_PER_EPOCH)
        self.timings = {}
        t0 = time.perf_counter()
        data = seeded_mature.seeded_mature_checkpoint(spec, self.validators, seed)
        self.timings["state_build_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.mesh = serving_mesh(int(config["chips"]))
        self.core = ResidentCore.from_checkpoint(spec, data, mesh=self.mesh)
        self.timings["enter_s"] = time.perf_counter() - t0
        self.state = self.core.state
        self._lay = None

    def compare_boundary(self, pre: dict, before: dict) -> list:
        """The boundary the core has just run, against
        plain_epoch_registry's on the reference's own columns `before` and
        the small fields `pre` as the reference says the blocks left them:
        all seven columns, every small field it writes, the balances root."""
        after = self.fetch_columns()
        want = plain_epoch_registry.boundary(self.constants, pre, before)
        post = plain_ssz.read_state(self.state)
        small = [k for k in want if k not in COLUMNS]
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
            Compared("boundary.other_columns_differing_from_reference",
                     sum(int(np.count_nonzero(after[f] != want[f]))
                         for f in COLUMNS[:5]), 0),
            Compared("boundary.small_fields_differing_from_reference",
                     int(fields), 0),
            Compared("boundary.balances_root_after.bytes_differing_from_hashlib",
                     _bytes_differing(
                         bytes(forest),
                         reference.host_balances_root(after["balance"])), 0),
        ]


class Driver(sync.Driver):
    """The sync driver's slot, warm-up, window and end-to-end metrics on the
    mature deployment, with the generator of registry-changing blocks; a
    slot whose proposer is slashed goes without a block."""

    def __init__(self, config: dict, mix: dict, seed: int,
                 validators: int | None = None):
        from consensus_specs_tpu.models.phase0 import helpers
        if not hasattr(helpers.ObjectRegistry, "initiate_exit"):
            # before the state is built and anything compiles: a program
            # whose registry view only reads refuses this mix's first block
            # by name (NotImplementedError), and says so at once
            raise SystemExit(
                "benchmark: this program's registry view writes no exit and "
                "no slashing (helpers.ObjectRegistry has no initiate_exit): "
                "a checkpoint-resumed core refuses such blocks, so the "
                "dirty-slots mix cannot run on it")
        # what drivers.Base, replay.Driver and sync.Driver set, around a
        # deployment of the mature seed in `Deployment`'s place
        self.mix = mix
        self.seed = seed
        self.dep = MatureDeployment(config, seed, validators)
        self.set_up = self.dep.timings
        self.window_s = 0.0
        self.values: dict = {}
        self.notes: dict = {}
        self.attempted = self.failed = 0
        self.on_epoch = lambda: None
        self.slot_s: list = []
        self.boundary_s: list = []
        self.generator_s = 0.0
        self.block_s: list = []
        self.generator = OpsBlockGenerator(
            self.dep.spec, seed, mix, self.dep.validators)

    def _slot(self, record: bool) -> None:
        boundary, dt = self._advance()
        block, t_gen = self._generate()
        t_block = self._apply(block) if block is not None else None
        if record:
            self.generator_s += t_gen
            if t_block is not None:
                self.block_s.append(t_block)
            (self.boundary_s if boundary else self.slot_s).append(dt)

    def window(self, seconds: float) -> None:
        skipped0 = self.generator.skipped
        super().window(seconds)
        self.values["slots_without_block"] = self.generator.skipped - skipped0

    # -- correct -----------------------------------------------------------------

    def compare(self) -> list:
        """One boundary, then one more epoch through the window's own calls,
        on the timed core. The reference keeps its OWN columns from that
        boundary on (`plain_operations` writes them block by block,
        `plain_epoch_registry` at the epoch's end). Guarantees 5 and 6:
        what every block leaves (header, RANDAO mix, votes,
        PendingAttestations, as the sync cell holds them; all seven device
        columns and `latest_slashed_balances` against the reference's).
        Guarantee 1 on the dirty path: after the first block, which carries
        exits and a proposer slashing, both forests' roots and the next
        slot's state root against hashlib over the fetched columns, a
        from-scratch build at full size; and again the root the epoch's
        last `process_slots` records, after 64 blocks of path updates.
        Guarantee 2: the boundary against plain_epoch_registry. Last, on a
        state that is then thrown away: spoiled blocks, each refused by
        core and reference with the device's columns and the mirrors as
        they stood."""
        dep = self.dep
        C, state = dep.constants, dep.state
        self._advance()                 # the boundary that opens the checked epoch
        # the reference's own from here on (copies: it writes them)
        ref = {f: np.array(a) for f, a in dep.fetch_columns().items()}
        shuffles = plain_epoch_registry.Shuffles(C, ref)
        pending = {name: plain_block.read_pending(getattr(state, name))
                   for name in ("previous_epoch_attestations",
                                "current_epoch_attestations")}
        differing = dict.fromkeys(
            ("header_fields", "randao_mix_bytes", "eth1_votes",
             "pending_attestations", "registry_rows", "slashed_balances"), 0)
        compared, want, dirty_root = [], None, None
        while True:
            block, _ = self._generate()
            left = int(state.slot) % dep.spe
            if block is None:
                want = None         # the slot goes without a block
            else:
                pre = plain_block.read_pre(state)
                pre["latest_slashed_balances"] = [
                    int(x) for x in state.latest_slashed_balances]
                want = plain_operations.process_block(
                    C, pre, ref, plain_block.read_block(block), shuffles)
                lengths = {name: len(getattr(state, name)) for name in pending}
                self._apply(block)
                appended = {name: plain_block.read_pending(
                    getattr(state, name)[lengths[name]:]) for name in pending}
                for key, n in self._differing(want, appended).items():
                    differing[key] += n
                got = dep.fetch_columns()
                differing["registry_rows"] += sum(
                    int(np.count_nonzero(got[f] != ref[f])) for f in COLUMNS)
                differing["slashed_balances"] += sum(
                    int(a) != b for a, b in zip(state.latest_slashed_balances,
                                                want["latest_slashed_balances"]))
                pending["previous_epoch_attestations"] += want["previous_appended"]
                pending["current_epoch_attestations"] += want["current_appended"]
                if dirty_root is None and want["rows"]:
                    # the forests have taken this block's dirty paths
                    big_roots = dep.hashlib_roots(got)
                    compared += dep.compare_forest_roots(big_roots)
                    dirty_root = self._reference_root(want, pending, big_roots)
            if left == dep.spe - 1:
                break
            slot = int(state.slot)
            self._advance()
            if isinstance(dirty_root, bytes):
                compared.append(self._root_compared(
                    "dirty_slot.state_root.bytes_differing_from_hashlib",
                    slot, dirty_root))
                dirty_root = True
        compared += [
            Compared("block.header_fields_differing_from_reference",
                     differing["header_fields"], 0),
            Compared("block.randao_mix_bytes_differing",
                     differing["randao_mix_bytes"], 0),
            Compared("block.eth1_votes_differing", differing["eth1_votes"], 0),
            Compared("block.pending_attestations_differing_from_reference",
                     differing["pending_attestations"], 0),
            Compared("block.registry_rows_differing_from_reference",
                     differing["registry_rows"], 0),
            Compared("block.slashed_balances_differing_from_reference",
                     differing["slashed_balances"], 0)]
        # the epoch's last root, after a path update a block: the small
        # fields as the reference says the blocks left them, hashlib over
        # the reference's own columns
        pre = self._reference_fields(want, pending)
        want_root = plain_ssz.state_root(pre, *dep.hashlib_roots(ref))
        self._advance()                 # records the root, then the boundary
        compared.append(self._root_compared(
            "state_root.bytes_differing_from_hashlib", pre["slot"], want_root))
        compared += dep.compare_boundary(pre, ref)
        compared.append(dep.compare_justification())
        compared += self._refused_blocks(C)
        return compared

    def _reference_fields(self, want: dict, pending: dict) -> dict:
        """The state's small fields with what the reference says the last
        block left in place of what the core wrote."""
        pre = plain_ssz.read_state(self.dep.state)
        pre.update(pending)
        if want is None:
            return pre
        epoch = pre["slot"] // self.dep.spe
        pre.update(latest_block_header=want["latest_block_header"],
                   eth1_data_votes=want["eth1_data_votes"],
                   latest_eth1_data=want["latest_eth1_data"],
                   latest_slashed_balances=want["latest_slashed_balances"])
        mixes = pre["latest_randao_mixes"]
        mixes[epoch % len(mixes)] = want["randao_mix"]
        return pre

    def _reference_root(self, want: dict, pending: dict, big_roots: tuple) -> bytes:
        return plain_ssz.state_root(
            self._reference_fields(want, {k: list(v) for k, v in pending.items()}),
            *big_roots)

    def _root_compared(self, name: str, slot: int, want_root: bytes) -> Compared:
        roots = self.dep.state.latest_state_roots
        got_root = bytes(roots[slot % len(roots)])
        return Compared(name, sum(a != b for a, b in zip(got_root, want_root)), 0)

    def _refused_blocks(self, C: dict) -> list:
        """The block of the state's slot spoiled eight ways (the sync
        mix's four of an attestation, four of an operation), each given to
        the core and to the reference on the state put back as it was: how
        many were taken by either, and how many entries of the device's
        columns and of the mirrors the refusals changed. The state is not
        used again."""
        dep = self.dep
        state, spec = dep.state, dep.spec
        while spec.registry_view(state).slashed(
                spec.get_beacon_proposer_index(state)):
            self._advance()     # a slot that goes without a block: the next
        before = dep.fetch_columns()
        shuffles = plain_epoch_registry.Shuffles(C, before)
        pre = plain_block.read_pre(state)
        pre["latest_slashed_balances"] = [
            int(x) for x in state.latest_slashed_balances]
        accepted = 0
        for spoil in spoiled_blocks.SPOILS + spoiled_operations.SPOILS:
            block = spoil(spec, self.generator, state, self.seed)
            kept = spoiled_operations.keep(spec, state)
            try:
                dep.core.process_block(state, block)
                accepted += 1
            except (AssertionError, IndexError):
                pass
            spoiled_operations.put_back(state, *kept)
            try:
                plain_operations.process_block(
                    C, pre, {k: a.copy() for k, a in before.items()},
                    plain_block.read_block(block), shuffles)
                accepted += 1
            except plain_block.Rejected:
                pass
        after = dep.fetch_columns()
        written = sum(int(np.count_nonzero(after[f] != before[f]))
                      for f in COLUMNS)
        written += sum(int(np.count_nonzero(dep.core.mirrors[f] != before[f]))
                       for f in dep.core.mirrors)
        return [Compared("block.invalid_blocks_accepted", accepted, 0),
                Compared("block.rows_written_by_refused_blocks", written, 0)]
