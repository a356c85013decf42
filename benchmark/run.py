#!/usr/bin/env python3
"""The benchmark's entry: one cell, one process, one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is `<config>.<mix>` of BENCHMARK.json. Everything that belongs to one
configuration, one mix, one driver, one per-layer metric or one kind of
reader is a file of its own, found by name, so each is added by new files
and new entries; no file that is there is edited, and of BENCHMARK.json's
entries only the `workloads` lists of metrics grow:

    configuration  configs/<config>.json (sizes, `chips`, `preset`, guarantees) + an entry in `configs`;
                   a new preset brings presets/<preset>.json, the constants the plain references read
    cell           an entry in `workloads` naming a configuration and a mix, with the file's `chips`,
                   AND its name appended to the `workloads` list of every end-to-end metric it reports
                   and every per-layer metric it reads: a cell inherits nothing from its mix
    mix            traffic/<mix>.json, naming its `driver`; a new driver is drivers/<driver>.py
    per-layer      layer_metrics/<metric>.json (the reader, `what`) + an entry in `per_layer` equal to it
    metric         key for key, whose `workloads` (in the entry alone) lists the cells that read it
    reader kind    sources/<kind>.py (`read(reader, seen)`; a roofline's bytes in costs/<program>.py),
                   with a synthetic record in tests/benchmark/reader_records/<kind>.py

Which cells report a metric is written once, in BENCHMARK.json, where the
driver reads it: an end-to-end metric with no list is every cell's, a
per-layer entry with no list is refused, and so is one that lists a cell
which does not report what it `moves`.

Set-up (backend, state from the seed, residency, warm-up) is `setup_s`; the
window measures for --seconds; the comparisons that decide `correct` (at
the cell's full size on the timed core: the state root against hashlib,
one boundary against the plain numpy epoch; then the object-model oracle at
V = 256 on this backend) run after the window and are in neither. The first device
must be a TPU. The last line of stdout is the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # process start, as near as Python gets

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402
from pathlib import Path            # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
# The profiler records the first part of a traced run's window and no more:
# a restore window of 51 s holds 2.3 million device operations, and reading
# them back took 155 s of a run that has to end within 360.
TRACED_SECONDS = 20.0
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


# ---------------------------------------------------------------------------
# The cell, from data
# ---------------------------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of BENCHMARK.json's `workloads`, resolved to its files
    under `root` (the checkout; the tests resolve a copy with a cell added)."""

    def __init__(self, name: str, root: Path = ROOT):
        bench = load_json(root / "BENCHMARK.json")
        data = root / HERE.relative_to(ROOT)
        rows = [w for w in bench["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit(
                f"benchmark: no workload {name!r} in BENCHMARK.json "
                f"(has: {', '.join(w['name'] for w in bench['workloads'])})")
        self.row = rows[0]
        self.name = name
        self.chips = int(self.row["chips"])
        config_row = next(c for c in bench["configs"]
                          if c["name"] == self.row["config"])
        self.config = load_json(root / config_row["file"])
        if int(self.config["chips"]) != self.chips:
            raise SystemExit(
                f"benchmark: workload {name!r} asks for {self.chips} chip(s), "
                f"{config_row['file']} lays the deployment out on "
                f"{self.config['chips']}: the file places the core")
        self.mix = load_json(data / "traffic" / f"{self.row['traffic']}.json")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = []
        for m in bench["per_layer"]:
            if "workloads" not in m:
                raise SystemExit(
                    f"benchmark: per-layer metric {m['name']!r} lists no "
                    f"`workloads`: an entry names the cells that read it")
            if name not in m["workloads"]:
                continue
            if m["moves"] not in reported:
                raise SystemExit(
                    f"benchmark: per-layer metric {m['name']!r} lists "
                    f"{name!r}, which does not report {m['moves']!r}")
            self.per_layer.append(
                load_json(data / "layer_metrics" / f"{m['name']}.json"))

    def driver(self):
        return importlib.import_module(
            f"benchmark.drivers.{self.mix['driver']}").Driver


def read_metric(metric: dict, seen):
    reader = metric["reader"]
    return importlib.import_module(
        f"benchmark.sources.{reader['kind']}").read(reader, seen)


# ---------------------------------------------------------------------------
# The device
# ---------------------------------------------------------------------------

def find_chips(want: int) -> dict:
    """The measuring path's look for the chip: anything but a TPU first
    device, or fewer chips than the cell asks for, ends the process."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"benchmark: the first device is {d0.platform!r}, not a TPU - "
            f"nothing is measured on a host backend")
    if len(devices) < want:
        raise SystemExit(
            f"benchmark: the cell asks for {want} chip(s), jax reports "
            f"{len(devices)}")
    return describe_device(devices)


def describe_device(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest chip; 0 where the backend reports none."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def peaks_for(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise SystemExit(
            f"benchmark: no published peaks for device_kind {device_kind!r} "
            f"in peaks.json (has: {', '.join(table)}); add a sourced row")
    return table[device_kind]


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def configure_compile_cache() -> str:
    """The program's own placement of the persistent cache (the directory
    the environment names, else `<checkout>/.cache/xla`), with every program
    kept, the short compiles too: a warm run then builds nothing (JAX's
    one-second floor left 77 executables to rebuild in every run)."""
    import jax
    from consensus_specs_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


class SpanDrain:
    """The telemetry ring holds 4,096 spans and a window closes more: each
    call keeps the spans that closed since the call before."""

    def __init__(self):
        self.spans: list = []
        self._closed = 0.0
        self()
        self.spans.clear()      # what closed before the window is not its

    def __call__(self) -> None:
        from consensus_specs_tpu import telemetry
        new = [s for s in telemetry.ring()
               if s["ts"] + s["dur"] > self._closed]
        self.spans.extend(new)
        self._closed = max((s["ts"] + s["dur"] for s in new),
                           default=self._closed)


class TracedPart:
    """The profiler over the first TRACED_SECONDS of a traced run's window,
    under the `bench.window` annotation the reduction takes its window from."""

    def __init__(self, trace_dir: Path):
        self.dir = trace_dir
        self.on = False

    def start(self) -> None:
        import jax
        from benchmark import reduce
        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # annotations only: the host
        options.host_tracer_level = 2       # is the system under test
        jax.profiler.start_trace(str(self.dir), profiler_options=options)
        self.annotation = jax.profiler.TraceAnnotation(reduce.WINDOW_ANNOTATION)
        self.annotation.__enter__()
        self.t_open = time.perf_counter()
        self.on = True

    def stop_if_due(self) -> None:
        if self.on and time.perf_counter() - self.t_open >= TRACED_SECONDS:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.on:
            self.annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False

    def planes(self) -> list:
        from benchmark import reduce
        self.stop()
        planes = reduce.load(reduce.find_xplane(str(self.dir)))
        shutil.rmtree(self.dir, ignore_errors=True)
        return planes


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, device: dict,
             *, validators: int | None = None) -> dict:
    """Everything after the look for the chip. `validators` is the tests'
    size hook; the command line does not reach it."""
    import jax
    from benchmark import reduce, reference
    from benchmark.deployment import guard_counters
    from benchmark.sources import Seen

    def say(**row) -> None:
        print(json.dumps(row), flush=True)

    cache_dir = configure_compile_cache()
    listener = reference.CompileListener()
    marks = {"imports_and_backend_s": time.perf_counter() - T_START}
    peaks = peaks_for(device["kind"])
    say(cell=cell.name, seed=seed, seconds=seconds, trace=int(trace),
        device=device, jax=jax.__version__, compile_cache_dir=cache_dir)

    # -- set-up --------------------------------------------------------------
    t0 = time.perf_counter()
    driver = cell.driver()(cell.config, cell.mix, seed, validators=validators)
    marks["driver_init_s"] = time.perf_counter() - t0
    try:
        t0 = time.perf_counter()
        driver.warm_up()
        marks["warm_up_s"] = time.perf_counter() - t0
        marks["compile_s_in_set_up"] = listener.compile_seconds
        marks["compiles_in_set_up"] = listener.compiles
        drain = SpanDrain()
        profile = TracedPart(ROOT / ".cache" / "benchmark" / "trace" / cell.name)

        def on_epoch() -> None:
            drain()
            profile.stop_if_due()

        guards0 = guard_counters()
        compiles0 = listener.compiles
        setup_s = time.perf_counter() - T_START

        # -- the window ------------------------------------------------------
        if trace:
            driver.on_epoch = on_epoch
            profile.start()
        driver.window(seconds)

        compiles_in_window = listener.compiles - compiles0
        guards = {k: v - guards0[k] for k, v in guard_counters().items()}
        planes = None
        if trace:
            drain()
            planes = profile.planes()
        memory_peak = memory_peak_bytes(jax.devices()[:cell.chips])

        # -- correct: outside the window and outside set-up ------------------
        t0 = time.perf_counter()
        compared = driver.compare()
        compare_s = time.perf_counter() - t0
    finally:
        driver.close()
    t0 = time.perf_counter()
    # the big core is closed: the small one has the device
    compared += reference.oracle_small()
    oracle_s = time.perf_counter() - t0

    guard_events = sum(abs(v) for v in guards.values())
    attempted = int(driver.attempted)
    failed = attempted if guard_events else int(driver.failed)
    for c in compared:      # on standard error too: the driver keeps its end
        say(compared=c.name, got=c.got, limit=c.limit, ok=c.ok)
        print(f"compared {c.name}: got {c.got}, limit {c.limit}, "
              f"{'ok' if c.ok else 'NOT OK'}", file=sys.stderr, flush=True)
    correct = all(c.ok for c in compared) and failed == 0 and attempted > 0

    values = dict(driver.values, compiles_in_window=compiles_in_window)
    say(samples={k: v for k, v in values.items() if isinstance(v, int)},
        window_s=driver.window_s, setup_s=setup_s, compare_s=compare_s,
        oracle_s=oracle_s, compiles=listener.compiles,
        compile_seconds=listener.compile_seconds,
        cache_hits=listener.cache_hits, slowest_compiles_s=listener.largest,
        guard_events=guards, notes=driver.notes,
        set_up=dict(driver.set_up, **marks))

    device_out = dict(device, memory_peak_bytes=memory_peak)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": {}, "device": device_out}
    if trace:
        config = dict(cell.config,
                      validators=int(validators or cell.config["validators"]))
        seen = Seen(spans=drain.spans, counters=guards, values=values,
                    planes=planes, config=config, mix=cell.mix, peaks=peaks)
        for metric in cell.per_layer:
            value = read_metric(metric, seen)
            if value is not None:
                result["metrics"][metric["name"]] = {
                    "value": value, "unit": metric["unit"]}
        busy = reduce.device_busy(planes)
        if busy is not None:
            device_out.update(busy_s=busy["busy_s"], window_s=busy["window_s"])
            result["breakdown"] = {
                "device_ops": reduce.top_device_ops(planes),
                "idle_gaps": reduce.idle_by_annotation(planes)}
    else:
        measured = dict(driver.end_to_end(), setup_s=setup_s)
        for metric in cell.end_to_end:
            result["metrics"][metric["name"]] = {
                "value": measured[metric["name"]], "unit": metric["unit"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    device = find_chips(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
