"""Readers of per-layer metrics, found by the `kind` of a metric's reader.

`read(reader: dict, seen: Seen) -> float | None`: None where the run holds
nothing to read, and the harness then leaves the metric out of the line.
"""
from __future__ import annotations

from typing import NamedTuple


class Seen(NamedTuple):
    """What one run observed inside its window."""
    spans: list             # telemetry span records closed in the window
    counters: dict          # name -> delta of an always-on counter
    values: dict            # the harness's and the driver's own readings
    planes: list | None     # the reduced profiler trace (--trace 1)
    config: dict            # the cell's configuration file
    mix: dict               # the cell's traffic file
    peaks: dict             # this device_kind's row of peaks.json
