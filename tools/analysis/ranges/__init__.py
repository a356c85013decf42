"""Value-range tier: an interval abstract interpreter over the REAL
jaxprs that machine-checks the limb-overflow and wrap-semantics budgets.

The trace tier (tools/analysis/trace/) counts ops; this tier bounds
VALUES. The double-width lazy-Montgomery fast path (ops/fq.py, PR 5,
Aranha et al. EUROCRYPT 2011) is only correct while wide accumulation
columns stay inside `|col| < 2^35` and narrow limbs inside the
`[-1, 2^29]` budget — claims that used to live as docstring prose and a
syntactic notice (CSA901) that pattern-matches source, not values. Here
they are theorems: kernel modules export `RANGE_CONTRACTS` lists (the
TRACE_CONTRACTS idiom) declaring per-argument input intervals, and the
interpreter (ranges/interp.py) propagates per-element magnitude
intervals through the traced program — positionally along the trailing
(limb/column) axis, so structural facts like "schoolbook column 27 is
identically zero" survive — and proves the declared output bounds plus
the absence of undeclared integer wraparound.

`fori_loop`/`scan` are handled by exact abstract unrolling when the
trip count is small and statically evident, else inductively: the
contract supplies the loop invariant, the interpreter checks the body
maps invariant -> invariant, and otherwise widens the carries to the
dtype range and flags. Intentional modular arithmetic (SHA-256's
mod-2^32 words, the justification bitfield's shifted uint64) is
DECLARED (`wrap_ok`, or an inline `# csa: ignore[CSA1401]` at the
wrapping site), never inferred.

  CSA1401  proved-overflow violation   (a wrap the input bounds cannot
                                        exclude, a declared output bound
                                        the interpreter cannot prove, or
                                        a loop invariant the body escapes)
  CSA1402  unprovable-op notice        (an op the interpreter cannot
                                        model — result widened to the
                                        dtype range; the proof degrades,
                                        visibly)
  CSA1403  missing loop invariant      (a loop beyond the unroll window
                                        with no declared invariant)
  CSA1404  stale range contract        (proven intervals regressed vs the
                                        committed ranges_baseline.json,
                                        or a contract with no snapshot)

Entry points:

  python -m tools.analysis --ranges [--ranges-baseline b.json]
                                    [--update-ranges-baseline]
                                    [--json out/ranges.json]
  make ranges

This module registers the rule catalog only (stdlib, importable by the
no-jax lint lane for `--list-rules`); interval.py, interp.py and
engine.py are loaded lazily by the CLI's --ranges path and by tests.
"""
from ..core import register_rule

register_rule(
    "CSA1401",
    "proved overflow: a traced op can wrap, or a declared range bound "
    "fails",
    "error",
    "the interpreter derived an interval that escapes the dtype (or the "
    "contract's declared output/invariant bound) from the declared input "
    "ranges — tighten the kernel, widen the contract in the same "
    "reviewable diff, or declare the wrap intentional (wrap_ok / inline "
    "suppression at the wrapping site)",
)
register_rule(
    "CSA1402",
    "unprovable op: the interval interpreter widened a value to the "
    "dtype range",
    "notice",
    "an unmodeled primitive or a possible division-by-zero degraded the "
    "proof at this op; the widened value is tracked (not flagged again "
    "downstream) — extend ranges/interp.py or refine the input ranges",
)
register_rule(
    "CSA1403",
    "loop beyond the unroll window with no declared range invariant",
    "error",
    "declare the carry invariant in the contract (`invariants`, checked "
    "inductively: body must map invariant -> invariant) — without one "
    "the carries widen to the dtype range and the proof is vacuous",
)
register_rule(
    "CSA1404",
    "range-contract snapshot drift vs the committed ranges baseline",
    "error",
    "proven intervals only loosen by a reviewed edit: run "
    "`python -m tools.analysis --ranges --update-ranges-baseline` and "
    "commit tools/analysis/ranges_baseline.json in the diff that "
    "explains the new bound",
)

RANGE_RULE_IDS = ("CSA1401", "CSA1402", "CSA1403", "CSA1404")
