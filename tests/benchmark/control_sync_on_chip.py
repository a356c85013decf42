#!/usr/bin/env python3
"""The sync mix's controls, on the chip at the cell's own size, several seeds
in one process (set-up is long): a short window and the sound comparison,
then each control and the comparison again, in control_on_chip.py's manner.

    python3 tests/benchmark/control_sync_on_chip.py <cell> <seconds> <seed>...

The benchmark's own runs never run this and pytest does not collect it; the
controls' CPU-sized twins are test_sync_cell.py's, which imports them from
here. Each breaks guarantee 5 (or what rests on it) in one place:

  an attestation dropped after its checks   the first checked block's last
      PendingAttestation leaves the state once the block has returned: fails
      `block.pending_attestations_differing_from_reference`, the state root
      (the list is part of the state) and the boundary's balances (the
      reference's boundary runs on what the reference says the blocks left)
  a header with a wrong body root           the last checked block's header is
      written with one byte of its body root flipped: fails
      `block.header_fields_differing_from_reference` and `state_root`
  a core that skips the bitfield check      `verify_bitfield` answers True: the
      spoiled block with a bit past its committee's end is taken, and
      `block.invalid_blocks_accepted` reads 1

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


def _once(driver, when, then) -> None:
    """Wrap the core's `process_block`: after the first block whose slot's
    place in its epoch is `when`, run `then(state)` and step aside."""
    core = driver.dep.core
    real = core.process_block

    def process_block(state, block):
        real(state, block)
        if int(state.slot) % driver.dep.spe == when % driver.dep.spe:
            core.process_block = real
            then(state)
    core.process_block = process_block


def attestation_dropped_after_its_checks(driver) -> None:
    # the epoch's first block includes the previous epoch's last committees:
    # its attestations are the ones the coming boundary rewards
    _once(driver, 0, lambda state: state.previous_epoch_attestations.pop())


def header_with_a_wrong_body_root(driver) -> None:
    def flip(state):
        root = bytearray(bytes(state.latest_block_header.body_root))
        root[driver.seed % 32] ^= 1
        state.latest_block_header.body_root = bytes(root)
    _once(driver, -1, flip)


def bitfield_check_skipped(driver) -> None:
    driver.dep.spec.verify_bitfield = lambda bitfield, size: True


CONTROLS = [attestation_dropped_after_its_checks, header_with_a_wrong_body_root,
            bitfield_check_skipped]


def realign(driver) -> None:
    """After a comparison the state stands at an epoch's first slot without
    its block; the next comparison starts, as the window ends, at an epoch's
    last slot with its block applied."""
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
        spec = driver.dep.spec
        verify_bitfield = spec.verify_bitfield
        row = {"cell": cell.name, "seed": seed, "device": device}
        try:
            driver.warm_up()
            driver.window(seconds)
            sound = driver.compare()
            row.update(attempted=driver.attempted, failed=driver.failed,
                       sound={c.name: c.got for c in sound})
            ok &= all(c.ok for c in sound) and driver.failed == 0
            for control in CONTROLS:
                realign(driver)
                control(driver)
                failed = {c.name: c.got for c in driver.compare() if not c.ok}
                row[control.__name__] = failed
                ok &= bool(failed)
        finally:
            spec.verify_bitfield = verify_bitfield
            driver.close()
        print(json.dumps(row), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
