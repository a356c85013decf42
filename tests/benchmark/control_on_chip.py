#!/usr/bin/env python3
"""The controls, on the chip at the cell's own size, several seeds in one
process (set-up is long): a short window and the sound comparison, then each
control of the cell's driver and the comparison again.

    python3 tests/benchmark/control_on_chip.py <cell> <seconds> <seed>... [--sound <seed>...]

Seeds after `--sound` run the sound comparison only. The benchmark's own
runs never run this and pytest does not collect it; the controls themselves
are test_benchmark_harness.py's (the CPU-sized twins run them there):

  replay   one Gwei on the device behind the forests (guarantee 1);
           one attestation dropped at the boundary (guarantee 2)
  restore  one Gwei on the live core (a resumed core's roots equal the
           live core's); one bit of the checkpoint altered where it is
           written (a checkpoint is the whole state)

One JSON line per seed: the numbers the sound run compares, and for each
control the numbers that failed. Exit code 0 only if every sound comparison
held and every control failed.
"""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import run  # noqa: E402


def one_gwei(driver) -> None:
    import numpy as np
    cols = driver.dep.core.cols
    index = driver.seed % driver.dep.validators
    driver.dep.core.cols = cols._replace(
        balance=cols.balance.at[index].add(np.uint64(1)))


def dropped_attestation(driver) -> None:
    core = driver.dep.core
    real = core.process_epoch_resident

    def dropped(state):
        core.process_epoch_resident = real      # this boundary only
        atts = state.previous_epoch_attestations
        atts.pop(driver.seed % len(atts))
        return real(state)
    core.process_epoch_resident = dropped


def altered_checkpoint(driver) -> None:
    core = driver.dep.core
    real = core.checkpoint_bytes

    def altered():
        core.checkpoint_bytes = real            # this cycle only
        data = bytearray(real())
        data[len(data) // 2] ^= 1
        return bytes(data)
    core.checkpoint_bytes = altered


CONTROLS = {"replay": [one_gwei, dropped_attestation],
            "restore": [altered_checkpoint, one_gwei]}     # the Gwei stays


def main(argv) -> int:
    cell = run.Cell(argv[0])
    seconds = float(argv[1])
    seeds = argv[2:]
    sound_only = set()
    if "--sound" in seeds:
        at = seeds.index("--sound")
        sound_only = set(map(int, seeds[at + 1:]))
        seeds = seeds[:at] + seeds[at + 1:]
    device = run.find_chips(cell.chips)
    run.configure_compile_cache()
    ok = True
    for seed in map(int, seeds):
        driver = cell.driver()(cell.config, cell.mix, seed)
        row = {"cell": cell.name, "seed": seed, "device": device}
        try:
            driver.warm_up()
            driver.window(seconds)
            sound = driver.compare()
            row.update(attempted=driver.attempted, failed=driver.failed,
                       sound={c.name: c.got for c in sound})
            ok &= all(c.ok for c in sound) and driver.failed == 0
            for control in ([] if seed in sound_only
                            else CONTROLS[cell.mix["driver"]]):
                control(driver)
                failed = {c.name: c.got for c in driver.compare() if not c.ok}
                row[control.__name__] = failed
                ok &= bool(failed)
        finally:
            driver.close()
        print(json.dumps(row), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
