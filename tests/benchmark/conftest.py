"""Two test files trace one cell (`test_distill_parts.py` and
`test_forests_ahead.py` both drive `mainnet-300k.replay` with `--trace 1`),
and under xdist they run in two processes at once: `run.TracedPart` clears
and reads `.cache/benchmark/trace/<cell>`, so one run's start removed the
other's trace ("no .xplane.pb under ..."). Every worker gets a trace
directory of its own; the harness and the test files are as they were."""
import os
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


@pytest.fixture(autouse=True)
def _a_trace_directory_of_this_worker(monkeypatch):
    from benchmark import run
    worker = os.environ.get("PYTEST_XDIST_WORKER", "main")
    real = run.TracedPart.__init__

    def init(self, trace_dir):
        real(self, trace_dir.with_name(f"{trace_dir.name}.{worker}"))
    monkeypatch.setattr(run.TracedPart, "__init__", init)
