"""Trace-tier contract analyzer: op-budget ratchets and lowered-program
hygiene over the REAL jaxprs/StableHLO of the hot kernels.

PRs 4/5/6 bought their wins as op-count invariants (256->72 dependent
adds, 54->12 REDC lanes, zero re-layout on chained steps). The AST tier
(tools/analysis/passes/) cannot see those: they are properties of the
*traced programs*, not the source. This tier traces and lowers the
actual jitted programs and checks them against declarative **kernel
contracts** exported by the modules that own the kernels
(`TRACE_CONTRACTS` lists in consensus_specs_tpu/ops/*.py,
parallel/sharding.py, models/phase0/epoch_soa.py,
utils/ssz/incremental.py), ratcheting measured values against the
committed `tools/analysis/trace_baseline.json`:

  CSA11xx  jaxpr op-budget ratchet   (REDC lanes, dependent jac_add
                                      chains, pair-hash lanes, graph size)
  CSA12xx  lowered-program hygiene   (f64 ops, host callbacks,
                                      device_put inside jit, dropped
                                      donation)
  CSA13xx  collective/layout drift   (collective inventory, chained
                                      out_shardings != next in_shardings)

The ratchet: tightening a budget requires touching the contract (next
to the kernel), loosening one requires touching the baseline — both
reviewable diffs.  Entry points:

  python -m tools.analysis --trace [--trace-baseline b.json]
                                   [--update-trace-baseline]
                                   [--json out/contracts.json]
  make contracts

This module registers the rule catalog only (stdlib, importable by the
no-jax lint lane for `--list-rules`); tracer.py and engine.py import
jax and are loaded lazily by the CLI's --trace path and by tests.
"""
from ..core import register_rule

# -- CSA11xx: jaxpr op-budget ratchet ---------------------------------------

register_rule(
    "CSA1101",
    "traced op count violates the kernel contract's declared budget",
    "error",
    "the budget lives next to the kernel (TRACE_CONTRACTS); fix the "
    "kernel regression, or change the contract in the same diff that "
    "justifies the new cost",
)
register_rule(
    "CSA1102",
    "traced op count regressed vs the committed trace baseline",
    "error",
    "the committed snapshot (tools/analysis/trace_baseline.json) only "
    "loosens by a reviewed edit: update the entry (or run "
    "--update-trace-baseline) in the same diff that explains the cost",
)
register_rule(
    "CSA1103",
    "traced op count improved below the committed trace baseline",
    "notice",
    "tighten the ratchet: refresh the baseline entry "
    "(--update-trace-baseline) so the win cannot silently regress",
)
register_rule(
    "CSA1104",
    "kernel contract metric has no committed trace-baseline entry",
    "error",
    "run `python -m tools.analysis --trace --update-trace-baseline` and "
    "commit the snapshot: a new contract without a baseline has no "
    "ratchet",
)

# -- CSA12xx: lowered-program hygiene ---------------------------------------

register_rule(
    "CSA1201",
    "f64 ops in the lowered program of an f64-forbidding contract",
    "error",
    "a silent float64 upcast doubles lane width and is rejected (or "
    "software-emulated) on TPU; trace the upcast to a weak-typed float "
    "literal or a missing dtype= and pin it",
)
register_rule(
    "CSA1202",
    "host callback staged inside a hot jitted program",
    "error",
    "pure_callback/io_callback/debug round-trips the host every call — "
    "hoist the host work out of the traced program",
)
register_rule(
    "CSA1203",
    "device_put with an explicit placement staged inside a hot jitted "
    "program",
    "error",
    "a targeted device_put under jit records a mid-program transfer/"
    "re-placement in the compiled artifact; place inputs before the "
    "call (the resident/ServingMesh pattern) instead",
)
register_rule(
    "CSA1204",
    "declared donation dropped in lowering",
    "error",
    "the contract declares donate_argnums but the lowered program "
    "carries fewer tf.aliasing_output annotations than the contract's "
    "donate_min — the buffer reuse the epoch boundary depends on is "
    "silently gone",
)

# -- CSA13xx: collective/layout inventory drift -----------------------------

register_rule(
    "CSA1301",
    "collective inventory drift vs the kernel contract",
    "error",
    "the compiled program's collective kinds differ from the contract's "
    "declared inventory — a new all-to-all/all-gather on the serving "
    "path is cross-device traffic the mesh design did not budget",
)
register_rule(
    "CSA1302",
    "chained program's lowered out-shardings disagree with its "
    "in-shardings",
    "error",
    "the pjit staging contract (SNIPPETS.md [1][2], runtime twin: "
    "telemetry/watchdog.layout_check): a chained step whose lowered "
    "result sharding differs from the matching operand sharding "
    "re-lays data out on every call",
)

TRACE_RULE_IDS = tuple(
    f"CSA{n}" for n in (1101, 1102, 1103, 1104,
                        1201, 1202, 1203, 1204, 1301, 1302))
