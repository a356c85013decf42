"""Closed-loop sync of whole epochs of blocks full of deposits, on the seeded
state of a mature chain during a deposit rush.

The sync mix's slot, timed exactly as there (`bench.slot` / `bench.boundary`
round `core.process_slots(state, slot + 1)`, the generator's block under
`bench.generator` inside the rate, `core.process_block(state, block)` under
`bench.block`), with `deposit_generator.DepositBlockGenerator`'s block: one
full aggregate a committee and the MAX_DEPOSITS deposits the chain owes,
each proved against the state's `deposit_root`. Every block appends rows to
the registry (into the free rows of the core's capacity: no shape moves)
and new leaves to both forests, so every slot root takes both forests'
roots after a path update; every boundary makes an epoch's new rows
eligible and cuts the activation queue at the churn limit.

The window ends at an epoch's end, after `--seconds` or, earlier, when fewer
than `min_epochs_of_deposits_left` epochs of deposits would be outstanding
(the checks after the window need two).
"""
from __future__ import annotations

import json
import statistics
import time
from pathlib import Path

import jax
import numpy as np

from benchmark import (plain_block, plain_deposits, plain_epoch_activations,
                       plain_epoch_registry, plain_ssz, reference,
                       seeded_deposit_queue, spoiled_blocks, spoiled_deposits)
from benchmark.deployment import _bytes_differing, serving_mesh
from benchmark.deposit_generator import DepositBlockGenerator
from benchmark.drivers import dirty_slots
from benchmark.reference import Compared, counters

HERE = Path(__file__).resolve().parent
COLUMNS = plain_deposits.COLUMNS
FALLBACKS = "resident.block.fallbacks"
# the free rows a test-sized core is given (the tests' size hook; the
# configuration's file states the capacity of the real one)
TEST_FREE_ROWS = 16 * 768


def _tail_traced(cols, pk, wc, start, rows: int):
    """The `rows` rows from `start` on of every column and of both identity
    matrices: one shape whatever `start` is."""
    cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, rows)  # noqa: E731
    return jax.tree_util.tree_map(cut, (cols, pk, wc))


_tail = jax.jit(_tail_traced, static_argnames=("rows",))


class DepositDeployment(dirty_slots.MatureDeployment):
    """`Deployment` on `seeded_deposit_queue`'s entry, resumed with the
    configuration's `registry_capacity`, the boundary held to
    `plain_epoch_activations`. `Deployment.__init__` and
    `MatureDeployment.__init__` call their own seeds and may not be edited,
    so their steps are repeated here around this one."""

    def __init__(self, config: dict, mix: dict, seed: int,
                 validators: int | None = None):
        from consensus_specs_tpu import telemetry
        from consensus_specs_tpu.crypto import bls
        from consensus_specs_tpu.models import phase0
        from consensus_specs_tpu.models.phase0.resident import ResidentCore
        from consensus_specs_tpu.ops.shuffle import install_device_shuffler

        self.config = config
        self.validators = int(validators or config["validators"])
        capacity = config.get("registry_capacity")
        if capacity and validators:
            capacity = self.validators + TEST_FREE_ROWS
        self.constants = {}
        for name in ("", ".blocks", ".ops", ".deposits"):
            with open(HERE.parent / "presets" / f"{config['preset']}{name}.json") as f:
                self.constants.update(json.load(f))   # what the plain references read
        telemetry.set_enabled(True)
        bls.bls_active = bool(config["assumed"]["bls_active"])
        install_device_shuffler()
        self.spec = spec = phase0.get_spec(config["preset"])
        spec.clear_caches()
        self.spe = int(spec.SLOTS_PER_EPOCH)
        self.timings = {}
        t0 = time.perf_counter()
        data, self.queue = seeded_deposit_queue.seeded_deposit_queue_checkpoint(
            spec, self.validators, seed, mix)
        self.timings["state_build_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.mesh = serving_mesh(int(config["chips"]))
        self.core = ResidentCore.from_checkpoint(spec, data, mesh=self.mesh,
                                                 capacity=capacity)
        self.timings["enter_s"] = time.perf_counter() - t0
        self.state = self.core.state
        self._lay = None

    # -- full size, after the window -------------------------------------------

    def fetch_tail(self, start: int) -> tuple:
        """(the seven columns, the pubkeys, the credentials) of the device's
        rows from `start` to the end of its storage, as numpy."""
        core = self.core
        rows = int(core.cols.balance.shape[0]) - start
        cols, pk, wc = jax.device_get(_tail(
            core.cols, core.pk_dev, core.wc_dev, np.int32(start), rows=rows))
        return {f: np.asarray(getattr(cols, f)) for f in COLUMNS}, \
            np.asarray(pk), np.asarray(wc)

    def fetch_identity(self) -> tuple:
        """(pubkeys, credentials) of the logical rows, off the device."""
        v = self.core._v
        return (np.asarray(jax.device_get(self.core.pk_dev))[:v],
                np.asarray(jax.device_get(self.core.wc_dev))[:v])

    def hashlib_roots_of(self, cols: dict, pubkeys, credentials) -> tuple:
        """(registry root, balances root) by hashlib, a from-scratch build
        over columns and identity rows at their own (logical) length."""
        n = int(cols["balance"].shape[0])
        pk = np.frombuffer(b"".join(pubkeys), np.uint8).reshape(n, 48) \
            if isinstance(pubkeys, list) else pubkeys
        wc = np.frombuffer(b"".join(credentials), np.uint8).reshape(n, 32) \
            if isinstance(credentials, list) else credentials
        return reference.host_registry_balances_roots(cols, pk, wc)

    def compare_boundary(self, pre: dict, before: dict) -> list:
        """The boundary the core has just run, against
        plain_epoch_activations' on the reference's own columns `before`
        and the small fields `pre` as the reference says the blocks left
        them: all seven columns, every small field it writes (the
        active-index root of the epoch the new activations reach among
        them), the balances root."""
        after = self.fetch_columns()
        want = plain_epoch_activations.boundary(self.constants, pre, before)
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
                     _rows_differing(after["balance"], want["balance"]), 0),
            Compared("boundary.effective_balances_differing_from_reference",
                     _rows_differing(after["effective_balance"],
                                     want["effective_balance"]), 0),
            Compared("boundary.other_columns_differing_from_reference",
                     sum(_rows_differing(after[f], want[f])
                         for f in COLUMNS[:5]), 0),
            Compared("boundary.small_fields_differing_from_reference",
                     int(fields), 0),
            Compared("boundary.balances_root_after.bytes_differing_from_hashlib",
                     _bytes_differing(
                         bytes(forest),
                         reference.host_balances_root(after["balance"])), 0),
        ]


class Driver(dirty_slots.Driver):
    """The sync driver's slot, warm-up and end-to-end metrics on the deposit
    rush's deployment, with the generator of deposit-full blocks."""

    def __init__(self, config: dict, mix: dict, seed: int,
                 validators: int | None = None):
        from consensus_specs_tpu.models.phase0 import helpers
        if not hasattr(helpers.ObjectRegistry, "index_of_pubkey"):
            # before the state is built and anything compiles: a program
            # whose registry view looks no pubkey up and appends no row
            # refuses this mix's first block by name (NotImplementedError),
            # and says so at once
            raise SystemExit(
                "benchmark: this program's registry view serves no deposit "
                "(helpers.ObjectRegistry has no index_of_pubkey): a "
                "checkpoint-resumed core refuses such blocks, so the "
                "deposit-queue mix cannot run on it")
        # what drivers.Base, replay.Driver and sync.Driver set, around a
        # deployment of the deposit rush's seed in `Deployment`'s place
        self.mix = mix
        self.seed = seed
        self.dep = DepositDeployment(config, mix, seed, validators)
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
        self.generator = DepositBlockGenerator(
            self.dep.spec, seed, mix, self.dep.queue)

    def _deposits_left_for(self, epochs: int) -> bool:
        owed = self.generator.outstanding(self.dep.state)
        return owed >= epochs * self.dep.spe * int(self.mix["deposits_per_block"])

    def window(self, seconds: float) -> None:
        """`replay.Driver.window` and `sync.Driver.window`, with the second
        way for the window to end: the deposits running short."""
        fallbacks0 = counters([FALLBACKS])[FALLBACKS]
        keep = 1 + int(self.mix["min_epochs_of_deposits_left"])
        t_open, paused = time.perf_counter(), 0.0
        ended_by = "deposits"
        while self._deposits_left_for(keep):
            if time.perf_counter() - t_open - paused >= seconds:
                ended_by = "seconds"
                break
            self._epoch(record=True)
            paused += self.pause()
        self.window_s = time.perf_counter() - t_open - paused
        self.attempted = len(self.slot_s) + len(self.boundary_s)
        self.values["generator_share"] = 100.0 * self.generator_s / self.window_s
        self.values["slots"] = self.attempted
        self.values["boundaries"] = len(self.boundary_s)
        self.values["blocks"] = len(self.block_s)
        self.values["block_fallbacks"] = counters([FALLBACKS])[FALLBACKS] - fallbacks0
        per_epoch = self.dep.spe - 1
        self.notes["window_ended_by"] = ended_by
        self.notes["registry_rows_at_close"] = self.dep.core._v
        self.notes["epoch_slot_median_ms"] = [
            round(1e3 * statistics.median(self.slot_s[i:i + per_epoch]), 3)
            for i in range(0, len(self.slot_s), per_epoch)]
        self.notes["epoch_block_median_ms"] = [
            round(1e3 * statistics.median(self.block_s[i:i + self.dep.spe]), 3)
            for i in range(0, len(self.block_s), self.dep.spe)]
        # every boundary of the window, in order: what epoch_boundary_s is
        # the median of (the first admission check read its runs too far
        # apart; the next reader has the distribution and not the median)
        self.notes["boundary_ms"] = [round(1e3 * s, 2) for s in self.boundary_s]

    # -- correct -----------------------------------------------------------------

    def compare(self) -> list:
        """One boundary, then one more epoch through the window's own calls,
        on the timed core. The reference keeps its OWN registry from that
        boundary on (`plain_deposits` writes it block by block,
        `plain_epoch_activations` at the epoch's end). After every block:
        what it leaves in the small fields (header, RANDAO mix, votes,
        PendingAttestations, as the sync cell holds them; `deposit_index`),
        the registry's length, all seven device columns over the logical
        rows, the appended rows' pubkeys and credentials as fetched from
        the device, and that every row from the length to the end of the
        storage is inert. After the first block both forests' roots and
        the next slot's state root against hashlib over the fetched
        columns at the logical length, a from-scratch build at full size;
        and again the root the epoch's last `process_slots` records, after
        64 blocks of appended leaves. The boundary against
        plain_epoch_activations. Last, on a state that is then thrown
        away: spoiled blocks, each refused by core and reference with the
        length, the device's columns, the mirrors, the host's identity
        copies, the pubkey index and both forests as they stood."""
        dep = self.dep
        C, state, core = dep.constants, dep.state, dep.core
        self._advance()                 # the boundary that opens the checked epoch
        # the reference's own from here on
        v0 = core._v
        registry = plain_deposits.Registry(dep.fetch_columns(),
                                           *dep.fetch_identity())
        shuffles = plain_epoch_registry.Shuffles(C, registry.cols)
        pending = {name: plain_block.read_pending(getattr(state, name))
                   for name in ("previous_epoch_attestations",
                                "current_epoch_attestations")}
        differing = dict.fromkeys(
            ("header_fields", "randao_mix_bytes", "eth1_votes",
             "pending_attestations", "registry_length", "deposit_index",
             "registry_rows", "appended_identity_bytes", "rows_not_inert"), 0)
        compared, dirty_root = [], None
        while True:
            block, _ = self._generate()
            left = int(state.slot) % dep.spe
            pre = plain_block.read_pre(state)
            want = plain_deposits.process_block(
                C, pre, registry, plain_block.read_block(block), shuffles)
            lengths = {name: len(getattr(state, name)) for name in pending}
            self._apply(block)
            appended = {name: plain_block.read_pending(
                getattr(state, name)[lengths[name]:]) for name in pending}
            for key, n in self._differing(want, appended).items():
                differing[key] += n
            got = dep.fetch_columns()
            v = len(registry)
            differing["registry_length"] += int(
                not v == core._v == int(got["balance"].shape[0])
                == len(dep.spec.registry_view(state)))
            differing["deposit_index"] += int(
                int(state.deposit_index) != want["deposit_index"])
            differing["registry_rows"] += sum(
                _rows_differing(got[f], registry.cols[f]) for f in COLUMNS)
            tail, pk, wc = dep.fetch_tail(v0)
            grown = v - v0
            differing["appended_identity_bytes"] += _bytes_differing(
                pk[:grown].tobytes(), b"".join(registry.pubkeys[v0:])) \
                + _bytes_differing(wc[:grown].tobytes(),
                                   b"".join(registry.credentials[v0:]))
            differing["rows_not_inert"] += _rows_not_inert(
                C, {f: a[grown:] for f, a in tail.items()}, pk[grown:], wc[grown:])
            pending["previous_epoch_attestations"] += want["previous_appended"]
            pending["current_epoch_attestations"] += want["current_appended"]
            if dirty_root is None:
                # the forests have taken this block's new leaves
                big_roots = dep.hashlib_roots_of(got, *dep.fetch_identity())
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
            Compared("block.registry_length_differing_from_reference",
                     differing["registry_length"], 0),
            Compared("block.deposit_index_differing_from_reference",
                     differing["deposit_index"], 0),
            Compared("block.registry_rows_differing_from_reference",
                     differing["registry_rows"], 0),
            Compared("block.appended_identity_bytes_differing_from_reference",
                     differing["appended_identity_bytes"], 0),
            Compared("block.rows_beyond_the_length_not_inert",
                     differing["rows_not_inert"], 0)]
        # the epoch's last root, after a block of new leaves a slot: the
        # small fields as the reference says the blocks left them, hashlib
        # over the reference's own columns and identity rows
        pre = self._reference_fields(want, pending)
        want_root = plain_ssz.state_root(pre, *dep.hashlib_roots_of(
            registry.cols, registry.pubkeys, registry.credentials))
        self._advance()                 # records the root, then the boundary
        compared.append(self._root_compared(
            "state_root.bytes_differing_from_hashlib", pre["slot"], want_root))
        compared += dep.compare_boundary(pre, registry.cols)
        compared.append(dep.compare_justification())
        compared += self._refused_blocks(C)
        return compared

    def _reference_fields(self, want: dict, pending: dict) -> dict:
        """The state's small fields with what the reference says the last
        block left in place of what the core wrote."""
        pre = plain_ssz.read_state(self.dep.state)
        pre.update(pending)
        epoch = pre["slot"] // self.dep.spe
        pre.update(latest_block_header=want["latest_block_header"],
                   eth1_data_votes=want["eth1_data_votes"],
                   latest_eth1_data=want["latest_eth1_data"],
                   deposit_index=want["deposit_index"])
        mixes = pre["latest_randao_mixes"]
        mixes[epoch % len(mixes)] = want["randao_mix"]
        return pre

    def _served(self) -> tuple:
        """What a refused block must leave as it stood, off the device and
        off the host's part of the core."""
        dep = self.dep
        core = dep.core
        tail, pk, wc = dep.fetch_tail(0)
        return (core._v, tail, pk, wc,
                {f: a.copy() for f, a in core.mirrors.items()},
                core._pk_np.copy(), core._wc_np.copy(),
                dict(core._pubkey_index),
                tuple(bytes(r) for r in core._forest_roots()))

    def _refused_blocks(self, C: dict) -> list:
        """The block of the state's slot spoiled eight ways (the sync mix's
        four of an attestation, four of its deposits), each given to the
        core and to the reference on the state put back as it was: how many
        were taken by either, and how much of the served state the
        refusals changed (the length, entries of the device's columns and
        identity matrices over all the storage, of the mirrors and the
        host's identity copies, the pubkey index, the forests' roots as
        fetched anew). The state is not used again."""
        dep = self.dep
        state, spec = dep.state, dep.spec
        registry = plain_deposits.Registry(dep.fetch_columns(),
                                           *dep.fetch_identity())
        shuffles = plain_epoch_registry.Shuffles(C, registry.cols)
        pre = plain_block.read_pre(state)
        was = self._served()
        accepted = 0
        for spoil in spoiled_blocks.SPOILS + spoiled_deposits.SPOILS:
            block = spoil(spec, self.generator, state, self.seed)
            kept = spoiled_deposits.keep(spec, state)
            try:
                dep.core.process_block(state, block)
                accepted += 1
            except (AssertionError, IndexError):
                pass
            spoiled_deposits.put_back(state, *kept)
            try:
                plain_deposits.process_block(
                    C, pre, registry, plain_block.read_block(block), shuffles)
                accepted += 1
            except plain_block.Rejected:
                pass
        now = self._served()
        written = int(was[0] != now[0]) + int(was[7] != now[7]) \
            + sum(a != b for a, b in zip(was[8], now[8]))
        for a, b in ((was[1], now[1]), (was[4], now[4])):
            written += sum(int(np.count_nonzero(a[f] != b[f])) for f in a)
        for i in (2, 3, 5, 6):
            written += int(np.count_nonzero(was[i] != now[i]))
        return [Compared("block.invalid_blocks_accepted", accepted, 0),
                Compared("block.written_by_refused_blocks", written, 0)]


def _rows_differing(got: np.ndarray, want: np.ndarray) -> int:
    n = min(len(got), len(want))
    return abs(len(got) - len(want)) + int(np.count_nonzero(got[:n] != want[:n]))


def _rows_not_inert(C: dict, cols: dict, pk: np.ndarray, wc: np.ndarray) -> int:
    """Rows that are not what an unused row of the storage is: never
    eligible, active, exiting or withdrawable, not slashed, no balance, no
    key and no credentials."""
    far = np.uint64(C["FAR_FUTURE_EPOCH"])
    bad = np.zeros(len(pk), bool)
    for f in COLUMNS[:4]:
        bad |= cols[f] != far
    for f in COLUMNS[4:]:
        bad |= cols[f] != 0
    return int(np.count_nonzero(bad | pk.any(axis=1) | wc.any(axis=1)))
