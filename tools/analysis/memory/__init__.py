"""Memory tier: a peak-buffer-liveness abstract interpreter over the
REAL jaxprs that machine-checks HBM/VMEM byte budgets.

The trace tier counts ops, the range tier bounds values, the lifetime
tier proves ownership; this tier bounds BYTES. ROADMAP items 3 and 4
both block on memory facts nobody proved before it: the Pallas kernels
need a machine-checked VMEM block budget (the range hulls give widths,
nothing bounds bytes-on-chip), and the 10M-validator epoch needs a
per-shard HBM capacity argument that is not hand arithmetic.

Kernel modules export `MEM_CONTRACTS` lists (the TRACE_CONTRACTS /
RANGE_CONTRACTS idiom — plain data, the engine imports the kernel
modules, never the reverse). Each contract names a traceable program at
its CEILING shape (V = 10^7 validators, the 2^20-leaf forest, the
G = 128 x P = 3 grouped pairing, the firehose ring plus two in-flight
batches — ShapeDtypeStructs, so nothing allocates) and the liveness
interpreter (memory/liveness.py) walks the jaxpr in program order: a
buffer is live from its defining eqn to its last use, a DONATED input
aliases its congruent output and is counted once, and scan/while/cond
sub-jaxprs contribute their body's transient peak atop the carried
live set. The modeled peak is cross-checked against what XLA itself
allocates (`compiled.memory_analysis()` — argument/output/alias/temp
bytes) wherever the backend reports it, the per-shard footprint of the
sharded epoch is proven == single/N + the declared replicated cap on
the 8-device virtual mesh, a scaling exponent fitted from 2-3 probe
shapes asserts the declared order (epoch O(V), forest update
O(dirty * log V) bytes), and Pallas BlockSpec footprints are bounded
against the 16 MiB/core VMEM budget.

  CSA1601  declared-budget violation   (modeled peak over the declared
                                        HBM budget, the per-shard bound
                                        single/N + replicated cap fails,
                                        or the model diverges from
                                        compiled.memory_analysis()
                                        beyond the documented tolerance)
  CSA1602  memory-baseline regression  (modeled bytes grew vs the
                                        committed memory_baseline.json,
                                        or a contract with no snapshot —
                                        the bytes ratchet, like the
                                        trace tier's lane ratchet)
  CSA1603  superlinear scaling         (the exponent fitted from the
                                        contract's probe shapes exceeds
                                        the declared order)
  CSA1604  Pallas VMEM overflow        (BlockSpec blocks x dtype x
                                        pipeline buffering exceed the
                                        16 MiB/core VMEM budget)
  CSA1605  host round-trip             (notice: a callback between
                                        device eqns widens every
                                        spanning buffer's live range to
                                        host latency)

Entry points:

  python -m tools.analysis --memory [--memory-baseline b.json]
                                    [--update-memory-baseline]
                                    [--json out/memory.json]
  make memory

This module registers the rule catalog only (stdlib, importable by the
no-jax lint lane for `--list-rules`); liveness.py and engine.py are
loaded lazily by the CLI's --memory path and by tests.
"""
from ..core import register_rule

register_rule(
    "CSA1601",
    "memory budget violation: modeled peak bytes escape the declared "
    "budget, the per-shard bound, or the compiled cross-check",
    "error",
    "the liveness model derived a peak the contract's declared budget "
    "(or the single/N + replicated-cap shard bound, or the compiled "
    "memory_analysis within the documented tolerance) cannot cover — "
    "shrink the kernel's live set or raise the budget in the same "
    "reviewable diff",
)
register_rule(
    "CSA1602",
    "memory-baseline regression: modeled bytes grew vs the committed "
    "snapshot",
    "error",
    "modeled peak/temp bytes only grow by a reviewed edit: run "
    "`python -m tools.analysis --memory --update-memory-baseline` and "
    "commit tools/analysis/memory_baseline.json in the diff that "
    "explains the new bytes",
)
register_rule(
    "CSA1603",
    "superlinear memory scaling vs the contract's declared order",
    "error",
    "the exponent fitted from the contract's probe shapes exceeds the "
    "declared order (epoch O(V), forest update O(dirty*log V)) — a "
    "full-width rebuild or quadratic temp crept onto the scaled path",
)
register_rule(
    "CSA1604",
    "Pallas VMEM overflow: BlockSpec blocks x dtype x buffering exceed "
    "the per-core budget",
    "error",
    "the kernel's block shapes, times the pipeline's buffering factor, "
    "do not fit the 16 MiB/core VMEM — shrink the block_lanes tile or "
    "the declared buffering",
)
register_rule(
    "CSA1605",
    "host round-trip between device eqns widens live buffer ranges",
    "notice",
    "a callback primitive executes while device buffers are live: every "
    "spanning buffer stays resident across host latency — hoist the "
    "callback out of the program or move it before the buffers' "
    "defining eqns",
)

MEMORY_RULE_IDS = ("CSA1601", "CSA1602", "CSA1603", "CSA1604", "CSA1605")
