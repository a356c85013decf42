"""Output-integrity tripwires: spot-check dispatch outputs against the
hulls the value-range tier already committed (ISSUE 13 tentpole (c)).

The value-range tier (tools/analysis/ranges/, `make ranges`) PROVES at
trace time that every epoch output stays inside its declared hull —
balances below 2^45, effective balances at MAX_EFFECTIVE_BALANCE, no
NaN anywhere on the integer path. A poisoned device buffer (bad HBM, a
cosmic-ray flip, an injected `poison` fault) violates exactly those
proofs at RUN time, which makes the committed hulls the natural
tripwire: one tiny jitted reduction per guarded output answers "is this
buffer inside the ranges the prover guaranteed?" — and a `False` turns
into `CorruptOutput`, re-dispatch, and (if it persists) a degradation
rung, instead of a corrupt state root propagating silently.

The checks are deliberately cheap (a fused min/max/isnan reduction per
leaf, one bool down): they run per guarded dispatch at the epoch
boundary, not per lane. They are pure consumers — no re-layout of the
chained columns (the trace contract `resilience.integrity.epoch_tripwire`
pins zero device_put and no collectives beyond the reduction's
all-reduce).
"""
from __future__ import annotations

import functools
import os
from typing import Callable, Dict

import numpy as np


def tripwires_enabled() -> bool:
    """CSTPU_TRIPWIRES switch, default ON: the resident epoch boundary
    arms `epoch_output_check` on its guarded dispatch (the boundary
    syncs its outputs immediately anyway, so the one fused reduction is
    noise next to the epoch program: it runs inside the benchmark's
    `epoch_boundary_s`)."""
    raw = os.environ.get("CSTPU_TRIPWIRES", "").strip().lower()
    if not raw:
        return True
    return raw not in ("0", "off", "false", "no")


def _hulls_from_spec(spec_tuple) -> Dict[str, tuple]:
    return {f: (int(spec["lo"]), int(spec["hi"]))
            for f, spec in spec_tuple._asdict().items()
            if isinstance(spec, dict)}


@functools.lru_cache(maxsize=None)
def declared_epoch_hulls() -> Dict[str, tuple]:
    """The committed per-column hulls, read from the SAME declaration the
    range prover checks (`epoch_soa._epoch_ranges_build`'s input specs):
    outputs chain into the next boundary's inputs, so every output column
    must re-enter the declared input hull or the prover's premise — and
    the chain — is broken."""
    from ..models.phase0.epoch_soa import _epoch_ranges_build

    return _hulls_from_spec(_epoch_ranges_build()["ranges"][0])


@functools.lru_cache(maxsize=None)
def declared_epoch_scalar_hulls() -> Dict[str, tuple]:
    """Same source, the EpochScalars leaves: slot/epoch ceilings, the
    shard index bound, the slashed-balance table's 2^59 — everything the
    prover declared finite. (The justification bitfield legitimately
    spans all of uint64, so a range tripwire cannot see a flip there —
    the inherent limit of hull checks: in-hull corruption is invisible.)
    """
    from ..models.phase0.epoch_soa import _epoch_ranges_build

    return _hulls_from_spec(_epoch_ranges_build()["ranges"][1])


def _check_traced(hull_items, cols):
    """all(leaf in hull) AND no NaN on any float leaf — one fused
    program, one bool out."""
    import jax.numpy as jnp

    ok = jnp.bool_(True)
    for f, (lo, hi) in hull_items:
        leaf = getattr(cols, f)
        if np.dtype(leaf.dtype).kind == "b":
            continue                      # bool is its own hull
        if np.dtype(leaf.dtype).kind == "f":
            ok &= ~jnp.any(jnp.isnan(leaf))
            ok &= jnp.all((leaf >= lo) & (leaf <= hi))
        else:
            # int hulls compare in the leaf's own dtype (hi fits: every
            # declared hull is < 2^64) — no upcast, the trace contract
            # forbids f64/widening creep in this program
            ok &= jnp.all(leaf <= np.asarray(hi, dtype=leaf.dtype))
            if lo > 0:
                ok &= jnp.all(leaf >= np.asarray(lo, dtype=leaf.dtype))
    return ok


_tripwire_jits: Dict[tuple, Callable] = {}


def _finite_items(hulls: Dict[str, tuple]) -> tuple:
    # full-uint64 hulls (FAR_FUTURE_EPOCH sentinels, the justification
    # bitfield) are vacuous at runtime and free to skip — the poison
    # surface the tripwire can see is the finitely-bounded leaves
    return tuple(sorted(
        (f, hull) for f, hull in hulls.items()
        if hull[1] < (1 << 64) - 1))


def _check_epoch_traced(col_items, scal_items, cols, scal):
    ok = _check_traced(col_items, cols)
    if scal is not None:
        ok &= _check_traced(scal_items, scal)
    return ok


def epoch_output_check(out) -> bool:
    """Tripwire for the epoch program's output tuple `(cols, scal,
    report)`: every validator column AND every EpochScalars leaf with a
    declared finite hull stays inside it. Returns True when the buffer
    is clean. Compiled once per shape set (the jit key carries the
    shapes, so chained steady-state boundaries hit the cache).

    Coverage is exactly the prover's finite declarations — a flipped
    bool or a corruption that stays in-hull is invisible to a range
    check by construction; those are the differential oracles' and the
    chain's own validation's to catch."""
    import jax

    cols, scal = out[0], (out[1] if len(out) > 1 else None)
    items = _finite_items(declared_epoch_hulls())
    scal_items = _finite_items(declared_epoch_scalar_hulls()) \
        if scal is not None else ()
    key = (items, scal_items,
           tuple((f, str(getattr(cols, f).dtype), getattr(cols, f).shape)
                 for f, _ in items),
           tuple((f, str(getattr(scal, f).dtype), getattr(scal, f).shape)
                 for f, _ in scal_items))
    fn = _tripwire_jits.get(key)
    if fn is None:
        fn = jax.jit(functools.partial(_check_epoch_traced, items,
                                       scal_items))
        _tripwire_jits[key] = fn
    return bool(fn(cols, scal))


def finite_check(tree) -> bool:
    """Generic NaN/Inf tripwire for float-bearing outputs (the pairing
    path's fq limbs are int64, so this mostly guards future float
    kernels): True when every float leaf is finite."""
    import jax
    import jax.numpy as jnp

    for leaf in jax.tree_util.tree_leaves(tree):
        if np.dtype(getattr(leaf, "dtype", np.int32)).kind != "f":
            continue
        if not bool(jnp.all(jnp.isfinite(leaf))):
            return False
    return True


# ---------------------------------------------------------------------------
# Trace-tier contract: the tripwire itself must stay cheap and inert —
# no device_put (it must READ the chained columns where they live, never
# move them), no callbacks, no f64, and its only cross-device traffic is
# the reduction's own all-reduce. Checked statically on the lowered
# program by `make contracts` next to the serving-path contracts it
# guards.
# ---------------------------------------------------------------------------

_CONTRACT_MESH_DEVICES = 8


def _tripwire_contract_build():
    import jax.numpy as jnp
    from ..models.phase0 import get_spec
    from ..models.phase0.epoch_soa import (EpochConfig, EpochScalars,
                                           ValidatorColumns)
    from ..parallel.sharding import ServingMesh

    serving = ServingMesh.create(_CONTRACT_MESH_DEVICES)
    V = 64 * serving.size
    cfg = EpochConfig.from_spec(get_spec("minimal"))
    items = _finite_items(declared_epoch_hulls())
    scal_items = _finite_items(declared_epoch_scalar_hulls())
    cols = ValidatorColumns(
        *(jnp.zeros(V, dtype=bool) if f == "slashed"
          else jnp.zeros(V, dtype=jnp.uint64)
          for f in ValidatorColumns._fields))
    scal = EpochScalars(
        *([jnp.zeros((), jnp.uint64)] * 6),
        latest_slashed_balances=jnp.zeros(
            cfg.LATEST_SLASHED_EXIT_LENGTH, jnp.uint64))
    cols_sh = ValidatorColumns(
        *([serving.shard_v] * len(ValidatorColumns._fields)))
    scal_sh = EpochScalars(*([serving.replicated] * len(EpochScalars._fields)))
    return dict(
        fn=functools.partial(_check_epoch_traced, items, scal_items),
        args=(cols, scal),
        jit_kwargs=dict(in_shardings=(cols_sh, scal_sh),
                        out_shardings=serving.replicated))


TRACE_CONTRACTS = [
    dict(
        name="resilience.integrity.epoch_tripwire",
        build=_tripwire_contract_build,
        requires_devices=_CONTRACT_MESH_DEVICES,
        # the only cross-device traffic the hull check may emit is the
        # reduction of its per-shard partial verdicts
        collectives=("all-reduce",),
        budgets={"collective_ops": 4},
        forbid=("f64", "callback", "device_put"),
    ),
]
