"""Closed-loop replay of whole epochs under full participation.

Per slot the generator appends the previous slot's committees' attestations
and the system takes `core.process_slots(state, slot + 1)`: 63 slot roots
and one epoch boundary per epoch. A syncing node feels the slots per second;
a node following the head feels the tail of the slot root and the boundary.
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
        self.slot_s: list = []          # non-boundary slots, in the window
        self.boundary_s: list = []
        self.generator_s = 0.0

    def _generate(self) -> float:
        with jax.profiler.TraceAnnotation("bench.generator"):
            t0 = time.perf_counter()
            self.dep.append_attestations()
            return time.perf_counter() - t0

    def _advance(self) -> tuple:
        """The system's part of a slot: (is it a boundary, its seconds)."""
        dep = self.dep
        slot = int(dep.state.slot)
        boundary = (slot + 1) % dep.spe == 0
        with jax.profiler.TraceAnnotation(
                "bench.boundary" if boundary else "bench.slot"):
            t0 = time.perf_counter()
            # the root is bytes on the host when this returns, and the
            # boundary's refresh has fetched its roots: nothing is in flight
            dep.core.process_slots(dep.state, slot + 1)
            return boundary, time.perf_counter() - t0

    def _slot(self, record: bool) -> None:
        t_gen = self._generate()
        boundary, dt = self._advance()
        if record:
            self.generator_s += t_gen
            (self.boundary_s if boundary else self.slot_s).append(dt)

    def _epoch(self, record: bool) -> None:
        while True:
            self._slot(record)
            if int(self.dep.state.slot) % self.dep.spe == 0:
                return

    def warm_up(self) -> None:
        """The entry boundary (every program of the cell's shape compiles
        or loads here), then whole epochs with attestations so that the
        window opens in the steady state: both attestation lists full."""
        for _ in range(1 + int(self.mix["warmup_epochs"])):
            self._epoch(record=False)

    def window(self, seconds: float) -> None:
        t_open, paused = time.perf_counter(), 0.0
        while time.perf_counter() - t_open - paused < seconds:
            self._epoch(record=True)
            paused += self.pause()
        self.window_s = time.perf_counter() - t_open - paused
        self.attempted = len(self.slot_s) + len(self.boundary_s)
        self.values["generator_share"] = 100.0 * self.generator_s / self.window_s
        self.values["slots"] = self.attempted
        self.values["boundaries"] = len(self.boundary_s)
        per_epoch = self.dep.spe - 1
        self.notes["epoch_slot_median_ms"] = [
            round(1e3 * statistics.median(self.slot_s[i:i + per_epoch]), 3)
            for i in range(0, len(self.slot_s), per_epoch)]

    def end_to_end(self) -> dict:
        return {
            # over all the window: the generator's time and whatever the
            # system leaves to be paid between its calls count against it
            "replay_slots_per_s": self.attempted / self.window_s,
            "epoch_boundary_s": statistics.median(self.boundary_s),
            "slot_root_p95_ms": 1e3 * _percentile(self.slot_s, 95),
        }

    def compare(self) -> list:
        """One more epoch through the window's own calls, on the timed core.
        Guarantee 1: the root that the boundary slot's `process_slots`
        records, with both attestation lists full, against hashlib over the
        fetched columns and the state's small fields. Guarantee 2: the
        columns and small fields after that boundary against plain_epoch's
        on the columns and fields read before it."""
        dep = self.dep
        before = dep.fetch_columns()
        big_roots = dep.hashlib_roots(before)
        compared = dep.compare_forest_roots(big_roots)
        while (int(dep.state.slot) + 1) % dep.spe:
            self._slot(record=False)
        self._generate()
        pre, want_root = dep.plain_state_root(big_roots)
        self._advance()                 # records the root, then the boundary
        roots = dep.state.latest_state_roots
        got_root = bytes(roots[pre["slot"] % len(roots)])
        compared.append(Compared(
            "state_root.bytes_differing_from_hashlib",
            sum(a != b for a, b in zip(got_root, want_root)), 0))
        compared += dep.compare_boundary(pre, before)
        compared.append(dep.compare_justification())
        return compared


def _percentile(xs, q: float) -> float:
    """Nearest-rank percentile: a sample that was measured, not a blend."""
    ordered = sorted(xs)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]
