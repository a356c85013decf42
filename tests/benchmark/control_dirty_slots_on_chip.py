#!/usr/bin/env python3
"""The dirty-slots mix's controls, on the chip at the cell's own size, several
seeds in one process (set-up is long): a short window and the sound
comparison, then each control and the comparison again, in
control_sync_on_chip.py's manner.

    python3 tests/benchmark/control_dirty_slots_on_chip.py <cell> <seconds> <seed>...

The benchmark's own runs never run this and pytest does not collect it; the
controls' CPU-sized twins are test_dirty_slots_cell.py's, which imports them
from here. Each breaks guarantee 6 in one place:

  a dropped forest update          the first checked block's dirty leaves and
      chunks never reach the forests (its rows are in the columns): fails
      `registry_root`, `balances_root` and `dirty_slot.state_root` after that
      block and the epoch's last `state_root` (the stale paths stand until
      the boundary rebuilds); the rows and the boundary hold
  an exit epoch one too early      the exit queue answers one epoch short,
      once: that validator's `exit_epoch` and `withdrawable_epoch` differ
      from the reference's, the kept queue then counts one exit fewer at its
      head than the reference's scans, so later exits cross to the next
      epoch one exit apart from the reference's: rows differ in every block
      from there on (`block.registry_rows_differing_from_reference`, counted
      block after block), in the epoch's last `state_root` and in
      `boundary.other_columns_differing_from_reference`; the forests follow
      the device's columns, so the roots after the first block hold
  a slashing that skips the proposer's reward   no balance is ever increased
      by a block: the proposers of the slashing blocks lack their rewards
      in `block.registry_rows_differing_from_reference`, the epoch's last
      `state_root` and `boundary.balances_differing_from_reference`;
      `latest_slashed_balances` and the headers hold

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


def forest_update_dropped(driver) -> None:
    core = driver.dep.core
    real = core._update_forest_paths

    def dropped(rows, chunks):
        core._update_forest_paths = real
    core._update_forest_paths = dropped


def exit_epoch_one_too_early(driver) -> None:
    core = driver.dep.core
    real = core._exit_queue_head

    def stale(floor_epoch):
        core._exit_queue_head = real
        return (real(floor_epoch)[0] - 1, 0)
    core._exit_queue_head = stale


def slashing_without_the_proposers_reward(driver) -> None:
    core = driver.dep.core
    real = core._move_balance

    def no_reward(index, up, down):
        if not up:
            real(index, up, down)
    core._move_balance = no_reward


def realign(driver) -> None:
    """After a comparison the state stands at an epoch's first slot without
    its block; the next comparison starts, as the window ends, at an epoch's
    last slot with its block applied (control_sync_on_chip.realign, for a
    mix in which a slot may go without a block)."""
    block = driver._generate()[0]
    if block is not None:
        driver._apply(block)
    while (int(driver.dep.state.slot) + 1) % driver.dep.spe:
        driver._slot(record=False)


CONTROLS = [forest_update_dropped, exit_epoch_one_too_early,
            slashing_without_the_proposers_reward]


def undo(driver) -> None:
    """Take the controls' wrappers off the core (instance attributes over
    the class's methods)."""
    for name in ("_update_forest_paths", "_exit_queue_head", "_move_balance"):
        vars(driver.dep.core).pop(name, None)


def main(argv) -> int:
    cell = run.Cell(argv[0])
    seconds = float(argv[1])
    device = run.find_chips(cell.chips)
    run.configure_compile_cache()
    ok = True
    for seed in map(int, argv[2:]):
        driver = cell.driver()(cell.config, cell.mix, seed)
        row = {"cell": cell.name, "seed": seed, "device": device}
        try:
            driver.warm_up()
            driver.window(seconds)
            sound = driver.compare()
            row.update(attempted=driver.attempted, failed=driver.failed,
                       sound={c.name: c.got for c in sound})
            ok &= all(c.ok for c in sound) and driver.failed == 0
            for control in CONTROLS:
                # a comparison ends on a state its spoiled blocks used up:
                # each control runs on a driver of its own seed's state
                # brought to an epoch's end again
                realign(driver)
                control(driver)
                failed = {c.name: c.got for c in driver.compare() if not c.ok}
                undo(driver)
                row[control.__name__] = failed
                ok &= bool(failed)
        finally:
            driver.close()
        print(json.dumps(row), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
