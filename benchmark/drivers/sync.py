"""Closed-loop sync of whole epochs of attestation-full blocks.

Per slot the system takes `core.process_slots(state, slot + 1)` (the slot's
root; at an epoch's end the boundary), the generator builds the block of
the new slot (`block_generator.py`: every committee of the slot four
before, as partial aggregates, up to MAX_ATTESTATIONS), and the system takes
`core.process_block(state, block)`; the two calls in turn are
`core.state_transition`. An epoch is one boundary, 63 slot roots and 64
blocks. A syncing node feels the slots per second, each with its block; a
node following the head feels the slot root over a state a block has just
changed, and the boundary over the 8,192 pending attestations blocks leave.
"""
from __future__ import annotations

import json
import statistics
import time
from pathlib import Path

import jax

from benchmark import plain_block, plain_ssz, spoiled_blocks
from benchmark.block_generator import BlockGenerator
from benchmark.drivers import replay
from benchmark.reference import Compared, counters

HERE = Path(__file__).resolve().parent
FALLBACKS = "resident.block.fallbacks"


class Driver(replay.Driver):
    """The replay driver's slot (`_advance`: the root or the boundary, timed
    as there), warm-up, window and end-to-end metrics, with a block a slot
    where the replay mix appends what a block would have left."""

    def __init__(self, *args, **kw):
        from consensus_specs_tpu.models.phase0.resident import ResidentCore
        if not hasattr(ResidentCore, "process_block"):
            # before the state is built and anything compiles: a program
            # older than the block path cannot run this mix, and says so at once
            raise SystemExit(
                "benchmark: this program's ResidentCore has no process_block: "
                "a checkpoint-resumed core takes no block, so the sync mix "
                "cannot run on it")
        super().__init__(*args, **kw)
        self.block_s: list = []
        self.generator = BlockGenerator(
            self.dep.spec, self.seed, self.mix["aggregates_per_committee"])

    # -- one slot: its root (or the boundary), its block ---------------------

    def _generate(self) -> tuple:
        with jax.profiler.TraceAnnotation("bench.generator"):
            t0 = time.perf_counter()
            block = self.generator.block(self.dep.state)
            return block, time.perf_counter() - t0

    def _apply(self, block) -> float:
        """The system's second part of a slot: its block."""
        with jax.profiler.TraceAnnotation("bench.block"):
            t0 = time.perf_counter()
            self.dep.core.process_block(self.dep.state, block)
            return time.perf_counter() - t0

    def _slot(self, record: bool) -> None:
        boundary, dt = self._advance()
        block, t_gen = self._generate()
        t_block = self._apply(block)
        if record:
            self.generator_s += t_gen
            self.block_s.append(t_block)
            (self.boundary_s if boundary else self.slot_s).append(dt)

    def _epoch(self, record: bool) -> None:
        """A boundary with its block, then 63 slots with theirs: from the
        last slot of an epoch to the last slot of the next. The warm-up is
        the entry boundary with its epoch of blocks, then whole epochs, so
        the window opens with both pending lists full and every boundary
        over a whole epoch of the blocks' attestations."""
        while True:
            self._slot(record)
            if (int(self.dep.state.slot) + 1) % self.dep.spe == 0:
                return

    def window(self, seconds: float) -> None:
        fallbacks0 = counters([FALLBACKS])[FALLBACKS]
        super().window(seconds)
        self.values["blocks"] = len(self.block_s)
        # the program's counter over the window: Seen.counters carries the
        # guard counters alone, so the driver reads this one for the harness
        self.values["block_fallbacks"] = counters([FALLBACKS])[FALLBACKS] - fallbacks0
        self.notes["epoch_block_median_ms"] = [
            round(1e3 * statistics.median(self.block_s[i:i + self.dep.spe]), 3)
            for i in range(0, len(self.block_s), self.dep.spe)]

    # -- correct: one more epoch, every block held to the plain reference -----

    def compare(self) -> list:
        """One boundary, then one more epoch through the window's own calls,
        on the timed core. Guarantee 5: what every checked block leaves
        (header, RANDAO mix, votes, PendingAttestations) against
        plain_block's on the fields read before it. Guarantee 1: the root
        that the epoch's last `process_slots` records, against hashlib over
        the fetched columns and the small fields as the reference says the
        blocks left them. Guarantee 2: the columns and small fields after
        that boundary against plain_epoch's on the pending attestations the
        reference says the blocks left. Last, on a state that is then
        thrown away: spoiled blocks, each of which must be refused."""
        dep = self.dep
        with open(HERE.parent / "presets" / f"{dep.config['preset']}.blocks.json") as f:
            C = dict(dep.constants, **json.load(f))
        self._advance()                 # the boundary that opens the checked epoch
        before = dep.fetch_columns()
        big_roots = dep.hashlib_roots(before)
        compared = dep.compare_forest_roots(big_roots)
        shuffles = plain_block.Shuffles(C, dep.validators)
        pending = {name: plain_block.read_pending(getattr(dep.state, name))
                   for name in ("previous_epoch_attestations",
                                "current_epoch_attestations")}
        checked = int(self.mix["checked_blocks"])
        differing = dict.fromkeys(("header_fields", "randao_mix_bytes",
                                   "eth1_votes", "pending_attestations"), 0)
        while True:
            block, _ = self._generate()
            left = int(dep.state.slot) % dep.spe
            want = None
            if dep.spe - left <= checked:
                pre = plain_block.read_pre(dep.state)
                want = plain_block.process_block(
                    C, pre, before, plain_block.read_block(block), shuffles)
            lengths = {name: len(getattr(dep.state, name)) for name in pending}
            self._apply(block)
            appended = {name: plain_block.read_pending(
                getattr(dep.state, name)[lengths[name]:]) for name in pending}
            if want is not None:
                for key, n in self._differing(want, appended).items():
                    differing[key] += n
                appended = {"previous_epoch_attestations": want["previous_appended"],
                            "current_epoch_attestations": want["current_appended"]}
            for name in pending:
                pending[name] += appended[name]
            if left == dep.spe - 1:
                break
            self._advance()
        compared += [
            Compared("block.header_fields_differing_from_reference",
                     differing["header_fields"], 0),
            Compared("block.randao_mix_bytes_differing",
                     differing["randao_mix_bytes"], 0),
            Compared("block.eth1_votes_differing", differing["eth1_votes"], 0),
            Compared("block.pending_attestations_differing_from_reference",
                     differing["pending_attestations"], 0)]
        # the small fields as the reference says the last block left them
        pre = plain_ssz.read_state(dep.state)
        epoch = pre["slot"] // dep.spe
        pre.update(pending, latest_block_header=want["latest_block_header"],
                   eth1_data_votes=want["eth1_data_votes"],
                   latest_eth1_data=want["latest_eth1_data"])
        mixes = pre["latest_randao_mixes"]
        mixes[epoch % len(mixes)] = want["randao_mix"]
        want_root = plain_ssz.state_root(pre, *big_roots)
        self._advance()                 # records the root, then the boundary
        roots = dep.state.latest_state_roots
        got_root = bytes(roots[pre["slot"] % len(roots)])
        compared.append(Compared(
            "state_root.bytes_differing_from_hashlib",
            sum(a != b for a, b in zip(got_root, want_root)), 0))
        compared += dep.compare_boundary(pre, before)
        compared.append(dep.compare_justification())
        compared.append(Compared("block.invalid_blocks_accepted",
                                 self._invalid_blocks_accepted(C, shuffles), 0))
        return compared

    def _differing(self, want: dict, appended: dict) -> dict:
        """What the block just applied left, against the reference's `want`."""
        state = self.dep.state
        header = plain_block.read_value(state.latest_block_header,
                                        "BeaconBlockHeader")
        epoch = int(state.slot) // self.dep.spe
        mixes = state.latest_randao_mixes
        votes = plain_block.read_value(state.eth1_data_votes, ("list", "Eth1Data"))
        eth1 = plain_block.read_value(state.latest_eth1_data, "Eth1Data")
        got = (appended["previous_epoch_attestations"]
               + appended["current_epoch_attestations"])
        left = want["previous_appended"] + want["current_appended"]
        return {
            "header_fields": sum(header[f] != want["latest_block_header"][f]
                                 for f in header),
            "randao_mix_bytes": sum(a != b for a, b in zip(
                bytes(mixes[epoch % len(mixes)]), want["randao_mix"])),
            "eth1_votes": _entries_differing(votes, want["eth1_data_votes"])
            + int(eth1 != want["latest_eth1_data"]),
            "pending_attestations": _entries_differing(got, left)}

    def _invalid_blocks_accepted(self, C: dict, shuffles) -> int:
        """The block of the state's slot spoiled four ways, each given to
        the core and to the reference on the state put back as it was: how
        many were taken by either. The state is not used again."""
        dep = self.dep
        state = dep.state
        before = dep.fetch_columns()
        pre = plain_block.read_pre(state)
        accepted = 0
        for spoil in spoiled_blocks.SPOILS:
            block = spoil(dep.spec, self.generator, state, self.seed)
            kept = spoiled_blocks.keep(dep.spec, state)
            try:
                dep.core.process_block(state, block)
                accepted += 1
            except (AssertionError, IndexError):
                pass
            spoiled_blocks.put_back(state, *kept)
            try:
                plain_block.process_block(
                    C, pre, before, plain_block.read_block(block), shuffles)
                accepted += 1
            except plain_block.Rejected:
                pass
        return accepted


def _entries_differing(got: list, want: list) -> int:
    return abs(len(got) - len(want)) + sum(a != b for a, b in zip(got, want))
