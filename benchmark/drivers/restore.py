"""Closed loop of restart cycles on a live core one boundary past entry.

Each cycle writes the whole SSZ checkpoint off the device and resumes a
second core from it up to its registry, balances and full state roots: the
time to recover a node. Forest build and SSZ decode do nearly all the work;
the epoch program does none.
"""
from __future__ import annotations

import statistics
import time

import jax

from benchmark.drivers import Base
from benchmark.reference import Compared


class Driver(Base):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.cycle_s: list = []
        self.write_s: list = []
        self.enter_s: list = []
        self.mismatched_cycles = 0
        self.resumed_roots = None       # the last cycle's, for compare()

    def _cycle(self, record: bool) -> bool:
        from consensus_specs_tpu.models.phase0.resident import ResidentCore
        dep = self.dep
        live = (*dep.core._registry_balances_roots(),
                dep.core._state_root(dep.state))
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.restore.write"):
            data = dep.core.checkpoint_bytes()
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.restore.enter"):
            resumed = ResidentCore.from_checkpoint(
                dep.spec, data, mesh=dep.mesh)
            try:
                roots = (*resumed._registry_balances_roots(),
                         resumed._state_root(resumed.state))
            finally:
                resumed._uninstall()
        t2 = time.perf_counter()
        del resumed
        self.resumed_roots = roots
        same = all(bytes(a) == bytes(b) for a, b in zip(roots, live))
        if record:
            self.cycle_s.append(t2 - t0)
            self.write_s.append(t1 - t0)
            self.enter_s.append(t2 - t1)
            self.mismatched_cycles += not same
        # the live core moves on one slot, so that no two checkpoints match
        dep.core.process_slots(dep.state, int(dep.state.slot) + 1)
        return same

    def warm_up(self) -> None:
        """The entry boundary, so that the live core is one boundary past
        entry, then whole cycles: every program of the resume path loads."""
        dep = self.dep
        dep.core.process_slots(dep.state, int(dep.state.slot) + 1)
        for _ in range(int(self.mix["warmup_cycles"])):
            self._cycle(record=False)

    def window(self, seconds: float) -> None:
        t_open, paused = time.perf_counter(), 0.0
        while time.perf_counter() - t_open - paused < seconds:
            self._cycle(record=True)
            paused += self.pause()
        self.window_s = time.perf_counter() - t_open - paused
        self.attempted = len(self.cycle_s)
        self.failed = self.mismatched_cycles
        self.values["restore_enter_ms"] = 1e3 * statistics.median(self.enter_s)
        self.values["checkpoint_write_ms"] = 1e3 * statistics.median(self.write_s)
        self.values["cycles"] = self.attempted

    def end_to_end(self) -> dict:
        return {"restore_s": statistics.median(self.cycle_s)}

    def compare(self) -> list:
        """One more cycle through the window's own call: the roots that the
        resumed core gives against hashlib over the live core's fetched
        columns and its state's small fields; then every cycle's roots
        against the live core's."""
        dep = self.dep
        big_roots = dep.hashlib_roots(dep.fetch_columns())
        _, want_root = dep.plain_state_root(big_roots)
        same = self._cycle(record=False)
        return dep.compare_forest_roots(big_roots, self.resumed_roots) + [
            Compared("restore.check_cycle_roots_differing_from_live",
                     int(not same), 0),
            Compared("resumed_state_root.bytes_differing_from_hashlib",
                     sum(a != b for a, b in zip(
                         bytes(self.resumed_roots[2]), want_root)), 0),
            Compared("restore.cycles_with_roots_differing_from_live",
                     self.mismatched_cycles, 0)]
