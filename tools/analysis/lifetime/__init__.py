"""Buffer-lifetime tier: an interprocedural abstract interpreter of
device-buffer OWNERSHIP over the call-graph IR (tools/analysis/
callgraph.py), cross-checked against the real lowering facts the trace
tier extracts (`tf.aliasing_output` donation survival,
tools/analysis/trace/tracer.donated_count).

Three hazards in this repo's history were the same bug class — host
code touching a device buffer whose ownership had been given away: the
PR 3 donated-epoch callers that reused `cols` after the donating call,
the XLA:CPU deserialized-donated-executable aliasing violation (worked
around with the pinned undonated twin), and the PR 15 verdict ring
whose donated `dynamic_update_slice` must never leave a stale host
reference outstanding. The trace tier counts ops, the range tier
bounds values; this tier proves LIFETIME.

Each array-typed value carries an abstract ownership state:

  LIVE            the host handle is valid
  DONATED         passed through a donated argument position of an
                  unconditionally-donating jit — dead on every backend
  MAYBE-DONATED   same, but the donation is platform-conditional (the
                  utils/donation.platform_donated_jit idiom) — dead on
                  accelerators, alive on XLA:CPU; both worlds model as
                  "must not be read again"

states flow through calls (interprocedural summaries over module-level
defs and uniquely-named methods), returns, attribute stores/loads
(`self._ring`), tuple/pytree destructuring, and loops to fixpoint.
Donation facts come from `donate_argnums`/`donate_argnames` at jit
sites (decorator / wrapper-assign / partial forms, resolved through
the same machinery as CSA5xx), and the trace tier's donate_min
contracts distinguish "declared but dead after lowering" (inert — no
findings) from "really consumed".

  CSA1501  use-after-donate          (a read or dispatch of a value in
                                      DONATED / MAYBE-DONATED state)
  CSA1502  donated-value escape      (a donated value stored to an
                                      attribute or returned while the
                                      stale host alias remains)
  CSA1503  double-in-flight donation (one buffer passed to two async
                                      dispatches before any
                                      materialization point — the
                                      firehose overlap shape)
  CSA1504  missing CPU-undonated twin (a donate_argnums jit with no
                                      platform guard — the PR 3 caveat
                                      codified; platform_donated_jit is
                                      the blessed pattern)
  CSA1505  redundant defensive copy  (notice: a .copy()/copy=True
                                      re-upload feeding a callable the
                                      prover shows never donates)

Entry points:

  python -m tools.analysis --lifetime [--lifetime-baseline b.json]
                                      [--update-lifetime-baseline]
                                      [--no-lower] [--json out]
  make lifetime

This module registers the rule catalog only (stdlib, importable by the
no-jax lint lane for `--list-rules`); engine.py is loaded lazily by
the CLI's --lifetime path and by tests.
The lowering cross-check is the only part that imports jax, and it
degrades to a notice when jax is absent or `--no-lower` is passed.
"""
from ..core import register_rule

register_rule(
    "CSA1501",
    "use-after-donate: a value is read after being passed through a "
    "donated jit argument",
    "error",
    "donation kills the host handle at dispatch — rebind the name to "
    "the call's output (the `cols = out[0]` chaining idiom), read host "
    "copies BEFORE the donating call, or route through the undonated "
    "twin (utils/donation.platform_donated_jit `.undonated`)",
)
register_rule(
    "CSA1502",
    "donated-value escape: a donated buffer is stored to an attribute "
    "or returned while the stale host alias remains",
    "error",
    "an escaping stale handle outlives the function and fails at an "
    "arbitrarily distant use — rebind the attribute to the donating "
    "call's output in the same statement (the `self._ring = "
    "dispatch(..., ring, ...)` idiom) or drop the escape",
)
register_rule(
    "CSA1503",
    "double-in-flight donation: one buffer reaches two dispatches with "
    "no materialization point between",
    "error",
    "the second dispatch consumes a buffer the first may still own "
    "(the firehose overlap shape) — materialize between launches "
    "(block_until_ready / np.asarray) or give each launch its own "
    "buffer (the double-buffer rotation)",
)
register_rule(
    "CSA1504",
    "donating jit with no platform guard (missing CPU-undonated twin)",
    "warning",
    "XLA:CPU executables deserialized from the persistent compilation "
    "cache have violated donated input/output aliasing (PR 3) — "
    "construct the program through utils/donation.platform_donated_jit "
    "(the blessed guard) or gate donation on jax.default_backend()",
)
register_rule(
    "CSA1505",
    "redundant defensive copy feeding a donation-free program",
    "notice",
    "the copied buffer feeds a callable the prover shows never donates "
    "its inputs — the defensive copy is pure overhead; drop it (or "
    "suppress with the reason the copy exists)",
)

LIFETIME_RULE_IDS = ("CSA1501", "CSA1502", "CSA1503", "CSA1504",
                     "CSA1505")
