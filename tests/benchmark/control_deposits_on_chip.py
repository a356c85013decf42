#!/usr/bin/env python3
"""The deposit-queue mix's controls, on the chip at the cell's own size,
several seeds in one process (set-up is long): a short window and the sound
comparison, then each control and the comparison again, in
control_dirty_slots_on_chip.py's manner.

    python3 tests/benchmark/control_deposits_on_chip.py <cell> <seconds> <seed>...

The benchmark's own runs never run this and pytest does not collect it; the
controls' CPU-sized twins are test_deposits_cell.py's, which imports them
from here. Each breaks the deposits' guarantee in one place:

  a core that skips the mask       the registry forest's leaf program roots
      the inert rows beyond the registry's length like validators (a hash,
      where the SSZ list pads with zero chunks): every rebuild from the
      boundary on gives another registry root, so `registry_root` after the
      first checked block, `dirty_slot.state_root` and the epoch's last
      `state_root` fail; the columns, the balances root and the boundary's
      columns hold
  a stale pubkey index             the index has lost the key of the
      validator the first checked block's first top-up names: the deposit
      appends a thirteenth row where the reference tops one up, so the
      registry's length and its rows differ from the reference's from that
      block on, and so do the epoch's last `state_root` and the boundary;
      the forests follow the device's columns, so the roots after the
      first block hold
  a root that lags a block's appends   the first checked block's new leaves
      and chunks never reach the forests (its rows are in the columns):
      fails `registry_root`, `balances_root` and `dirty_slot.state_root`
      after that block; later blocks' paths re-hash over the stale leaves,
      so the epoch's last `state_root` fails too; the rows hold

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


def mask_skipped(driver) -> None:
    from consensus_specs_tpu.models.phase0 import resident
    from consensus_specs_tpu.utils.ssz import bulk
    resident._masked_leaves = \
        lambda *cols_and_count: bulk.registry_leaf_words_device(*cols_and_count[:-1])


def stale_pubkey_index(driver) -> None:
    dep = driver.dep
    new = int(driver.mix["new_validators_per_block"])
    at = int(dep.state.deposit_index) - dep.queue.first + new
    dep.core._pubkey_lookup().pop(dep.queue.pubkeys[at].tobytes())


def root_lags_appends(driver) -> None:
    core = driver.dep.core
    real = core._update_forest_paths

    def dropped(rows, chunks):
        core._update_forest_paths = real
    core._update_forest_paths = dropped


# in this order: the first two heal at the next boundary's rebuild, the last
# leaves a row more than the chain's (the reference starts every comparison
# from the device's own registry, so the next one would still be sound)
CONTROLS = [root_lags_appends, mask_skipped, stale_pubkey_index]


def undo(driver) -> None:
    """Take the controls off the program: the module's leaf program, the
    core's wrapper (an instance attribute over the class's method), the
    index (built anew at the next deposit)."""
    import jax
    from consensus_specs_tpu.models.phase0 import resident
    resident._masked_leaves = jax.jit(resident._masked_leaves_traced)
    vars(driver.dep.core).pop("_update_forest_paths", None)
    driver.dep.core._pubkey_index = None


def realign(driver) -> None:
    """After a comparison the state stands at an epoch's first slot without
    its block; the next comparison starts, as the window ends, at an epoch's
    last slot with its block applied (control_dirty_slots_on_chip.realign)."""
    driver._apply(driver._generate()[0])
    while (int(driver.dep.state.slot) + 1) % driver.dep.spe:
        driver._slot(record=False)


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
                # each control runs on the seed's state brought to an
                # epoch's end again
                realign(driver)
                control(driver)
                failed = {c.name: c.got for c in driver.compare() if not c.ok}
                undo(driver)
                row[control.__name__] = failed
                ok &= bool(failed)
        finally:
            undo(driver)
            driver.close()
        print(json.dumps(row), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
