"""Traffic drivers, found by the `driver` named in a mix's file.

`benchmark/drivers/<driver>.py` holds a class `Driver(Base)` that adds
`warm_up()`, `window(seconds)`, `end_to_end()` -> {name: value} and
`compare()` -> [Compared], fills `values` (what the harness_value reader
reads), `notes` (printed, never a metric), `attempted` and `failed`, and
calls `pause()` between epochs or cycles, outside every timing.
"""
from __future__ import annotations

import time

from benchmark.deployment import Deployment


class Base:
    """What every driver of a resident deployment shares."""

    def __init__(self, config: dict, mix: dict, seed: int,
                 validators: int | None = None):
        self.mix = mix
        self.seed = seed
        self.dep = Deployment(config, seed, validators)   # tests pass validators
        self.set_up = self.dep.timings
        self.window_s = 0.0
        self.values: dict = {}
        self.notes: dict = {}
        self.attempted = self.failed = 0
        self.on_epoch = lambda: None

    def pause(self) -> float:
        """The harness's turn (it drains spans and may stop the profiler,
        which takes seconds); returns how long it took, and the window's
        clock leaves that out."""
        t0 = time.perf_counter()
        self.on_epoch()
        return time.perf_counter() - t0

    def compare(self) -> list:
        """Full size, after the window and outside set-up: the numbers that
        decide `correct`, each beside its limit."""
        raise NotImplementedError

    def close(self) -> None:
        if self.dep is not None:
            self.dep.close()
            self.dep = None     # the columns and forests leave the device
