"""Peak-buffer-liveness abstract interpreter over jaxprs.

The model (documented, and cross-checked against
`compiled.memory_analysis()` by the engine wherever the backend
reports it):

  * program inputs are CALLER-OWNED: a non-donated invar is resident
    for the whole call (XLA cannot free the caller's buffer), so it
    contributes its bytes from eqn 0 to the end;
  * a DONATED invar whose shape/dtype matches an output is ALIASED to
    that output (greedy congruent matching, the same pairing XLA's
    donation performs): the pair shares ONE buffer, live for the whole
    program, and the output's defining eqn adds no bytes. A donated
    invar nothing matches is freed after its last use;
  * an intermediate value is live from its defining eqn to its last
    use; a program output stays live to the end;
  * jaxpr constants are baked into the executable and counted resident
    for the whole program, each distinct VALUE once: tracing hoists a
    constant once per array object that carried it (the pairing trace
    holds 2,436 [28] int64 constvars of 14 values), and how many
    objects that is moves with jax (0.9.0 hoists 1,904 B more of them
    there than the version the ratchet was first written under), while
    the executable holds a value once;
  * an eqn with sub-jaxprs (scan / while / cond / pjit / custom_*)
    contributes its body's TRANSIENT peak (body peak beyond the body's
    own inputs and outputs, which the outer walk already tracks as the
    eqn's operands and results) atop the live set carried across the
    eqn;
  * the modeled peak is the max, over eqns, of live bytes at that eqn
    plus the eqn's transient contribution;
  * every traced value is a buffer: the model knows no fusion. Where a
    kernel leans on one (a compare-select-reduce over `[V, K]` that the
    compiler runs inside the reduction's loop), it wraps that expression
    in a named jit and its contract DECLARES the name (`fused_calls` in
    the build spec): such a call contributes its operands and results
    and no transient. Declared, never inferred, and backed by a compile
    for the chip that holds the compiler to it (tests/test_tpu_compile.py).
    The declaration is about the chip's compiler; the engine's
    cross-check compiles with another, so there the walk is given, per
    name, the transient that compiler holds inside the call.

Per-shard footprints reuse the same walk with a different byte
function: a leaf whose element count reaches the contract's sharding
threshold divides by the mesh size (the repo's placement policy — [V]
columns shard over "v", scalars and SHARD_COUNT-sized tables
replicate; see parallel/sharding.py), everything else replicates.

CSA1605 events: a callback primitive staged BETWEEN device eqns, while
buffers defined earlier and used later are live, widens every spanning
buffer's live range by a host round-trip. The walk records
(primitive, spanning bytes) for each such eqn.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

# primitives that bounce through the host mid-program (the trace tier
# forbids them on committed kernels; here they are a liveness event)
_HOST_PRIMS = ("pure_callback", "io_callback", "debug_callback",
               "host_callback")


def aval_bytes(aval) -> int:
    """Bytes of one buffer with the given abstract value. Non-array
    avals (tokens, abstract refs without a shape) cost nothing."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(dtype.itemsize)


def sharded_bytes_fn(devices: int, min_elems: int) -> Callable:
    """Byte function for the per-shard walk: a leaf with >= min_elems
    elements shards over `devices` (ceil division — XLA pads the last
    shard), smaller leaves replicate on every device."""
    def fn(aval) -> int:
        full = aval_bytes(aval)
        shape = getattr(aval, "shape", None)
        if not shape:
            return full
        elems = 1
        for d in shape:
            elems *= int(d)
        if elems >= min_elems:
            return -(-full // devices)
        return full
    return fn


@dataclass
class HostEvent:
    primitive: str
    eqn_index: int
    spanning_bytes: int


@dataclass
class Liveness:
    peak_bytes: int = 0
    arg_bytes: int = 0
    out_bytes: int = 0
    alias_bytes: int = 0      # donated-input bytes aliased onto outputs
    const_bytes: int = 0
    temp_bytes: int = 0       # peak beyond args + outs - alias
    n_eqns: int = 0
    host_events: List[HostEvent] = field(default_factory=list)
    # (eqn_index, primitive, live bytes at that eqn) of the peak eqn
    peak_site: Optional[Tuple[int, str, int]] = None


def _is_literal(atom) -> bool:
    return hasattr(atom, "val")


def _sub_jaxprs(eqn):
    """Every sub-jaxpr closed over by an eqn's params (pjit/scan keep a
    ClosedJaxpr under "jaxpr", custom_* under "call_jaxpr"/"fun_jaxpr",
    cond a tuple under "branches", while_loop cond/body pairs)."""
    subs = []
    for val in eqn.params.values():
        for item in (val if isinstance(val, (tuple, list)) else (val,)):
            if hasattr(item, "jaxpr") and hasattr(item, "consts"):
                subs.append(item)          # ClosedJaxpr
            elif hasattr(item, "eqns") and hasattr(item, "invars"):
                subs.append(item)          # raw Jaxpr (rare)
    return subs


def _const_bytes(closed, bytes_fn: Callable) -> List[int]:
    """Bytes charged to each constvar: a value's first constvar carries
    it, its repeats nothing. A raw Jaxpr has no values to compare and is
    charged per constvar."""
    jaxpr = getattr(closed, "jaxpr", closed)
    consts = getattr(closed, "consts", None)
    if consts is None:
        return [bytes_fn(v.aval) for v in jaxpr.constvars]
    import numpy as np
    seen, charged = set(), []
    for v, value in zip(jaxpr.constvars, consts):
        key = (tuple(v.aval.shape), str(v.aval.dtype),
               np.asarray(value).tobytes())
        charged.append(0 if key in seen else bytes_fn(v.aval))
        seen.add(key)
    return charged


def _match_donations(invars, outvars, donated: set,
                     bytes_fn: Callable) -> Tuple[set, set, int]:
    """Greedy congruent pairing of donated invars with outputs — the
    matching XLA's donation performs. Returns (aliased invar ids,
    aliased outvar ids, aliased bytes under bytes_fn)."""
    aliased_in, aliased_out = set(), set()
    alias_bytes = 0
    taken = set()
    for i in sorted(donated):
        if i >= len(invars):
            continue
        iv = invars[i]
        sig = (tuple(iv.aval.shape), str(iv.aval.dtype))
        for ov in outvars:
            if _is_literal(ov) or id(ov) in taken or id(ov) in aliased_out:
                continue
            aval = getattr(ov, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            if (tuple(aval.shape), str(aval.dtype)) == sig:
                aliased_in.add(id(iv))
                aliased_out.add(id(ov))
                alias_bytes += bytes_fn(iv.aval)
                break
    return aliased_in, aliased_out, alias_bytes


def analyze(closed, donated: Optional[set] = None,
            bytes_fn: Callable = aval_bytes,
            fused_calls: Optional[Dict[str, int]] = None) -> Liveness:
    """Walk a ClosedJaxpr and return the modeled peak liveness.

    `donated` holds FLAT invar indices (the engine expands jit-level
    donate_argnums over each argument's leaves); `fused_calls` maps the
    name of each nested jit the contract declares fused to the transient
    bytes it stands for (0: the declaration as the chip's compiler keeps
    it)."""
    jaxpr = getattr(closed, "jaxpr", closed)
    donated = donated or set()
    fused_calls = fused_calls or {}
    res = Liveness(n_eqns=len(jaxpr.eqns))

    invars = list(jaxpr.invars)
    outvars = [v for v in jaxpr.outvars if not _is_literal(v)]
    outvar_ids = {id(v) for v in outvars}
    res.arg_bytes = sum(bytes_fn(v.aval) for v in invars)
    res.out_bytes = sum(bytes_fn(v.aval) for v in jaxpr.outvars
                        if getattr(v, "aval", None) is not None)
    const_bytes = _const_bytes(closed, bytes_fn)
    res.const_bytes = sum(const_bytes)

    aliased_in, aliased_out, res.alias_bytes = _match_donations(
        invars, jaxpr.outvars, donated, bytes_fn)

    # last program-order use of every var (program outputs: the end)
    last_use: Dict[int, int] = {}
    end = len(jaxpr.eqns)
    for i, eqn in enumerate(jaxpr.eqns):
        for atom in eqn.invars:
            if not _is_literal(atom):
                last_use[id(atom)] = i
    for v in outvars:
        last_use[id(v)] = end

    # resident for the whole program: non-donated inputs (caller-owned),
    # donated-and-aliased inputs (the shared in/out buffer), constants
    live: Dict[int, int] = {}
    never_free = set()
    for i, v in enumerate(invars):
        live[id(v)] = bytes_fn(v.aval)
        if i not in donated or id(v) in aliased_in:
            never_free.add(id(v))
    for v, b in zip(jaxpr.constvars, const_bytes):
        live[id(v)] = b
        never_free.add(id(v))

    live_total = sum(live.values())
    peak = live_total
    res.peak_site = (-1, "<args>", peak)

    for i, eqn in enumerate(jaxpr.eqns):
        # transient contribution of sub-jaxpr bodies beyond their own
        # I/O (already tracked as this eqn's operands and results)
        extra = fused_calls.get(eqn.params.get("name"))
        if extra is None:
            extra = 0
            for sub in _sub_jaxprs(eqn):
                inner = analyze(sub, bytes_fn=bytes_fn,
                                fused_calls=fused_calls)
                extra = max(extra, inner.temp_bytes)
        prim = getattr(eqn.primitive, "name", str(eqn.primitive))
        if any(h in prim for h in _HOST_PRIMS):
            spanning = sum(b for vid, b in live.items()
                           if last_use.get(vid, -1) > i)
            if spanning:
                res.host_events.append(HostEvent(prim, i, spanning))
        for ov in eqn.outvars:
            if type(ov).__name__ == "DropVar":
                continue
            if id(ov) in aliased_out:
                continue          # donation: the input's buffer is reused
            if id(ov) in last_use and id(ov) not in live:
                b = bytes_fn(ov.aval)     # dead results allocate nothing
                live[id(ov)] = b
                live_total += b
        here = live_total + extra
        if here > peak:
            peak = here
            res.peak_site = (i, prim, here)
        for atom in eqn.invars:
            vid = id(atom) if not _is_literal(atom) else None
            if (vid is not None and vid not in never_free
                    and vid not in outvar_ids
                    and last_use.get(vid) == i):
                b = live.pop(vid, None)
                if b is not None:
                    live_total -= b

    res.peak_bytes = peak
    res.temp_bytes = max(
        0, peak - (res.arg_bytes + res.out_bytes - res.alias_bytes
                   + res.const_bytes))
    return res


def traffic_bounds(closed, bytes_fn: Callable = aval_bytes
                   ) -> Tuple[int, int]:
    """(lo, hi) HBM-traffic bounds from the same cost model the
    contracts use: `lo` assumes perfect fusion (each program input read
    once, each output written once); `hi` assumes NO fusion (every eqn
    streams its operands in and its results out). The real machine
    lands between them."""
    jaxpr = getattr(closed, "jaxpr", closed)
    lo = (sum(aval_bytes(v.aval) for v in jaxpr.invars)
          + sum(bytes_fn(getattr(v, "aval", None))
                if hasattr(getattr(v, "aval", None), "shape") else 0
                for v in jaxpr.outvars))
    hi = 0

    def walk(jx):
        nonlocal hi
        for eqn in jx.eqns:
            for atom in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(atom, "aval", None)
                if aval is not None and hasattr(aval, "shape"):
                    hi += bytes_fn(aval)
            for sub in _sub_jaxprs(eqn):
                walk(getattr(sub, "jaxpr", sub))
    walk(jaxpr)
    return lo, max(lo, hi)


def fit_order(ns, ys) -> float:
    """Least-squares slope of log y over log n — the scaling exponent a
    contract's probe shapes exhibit. Degenerate inputs (a constant
    metric, probes of one size) fit 0.0."""
    pts = [(math.log(n), math.log(y)) for n, y in zip(ns, ys)
           if n > 0 and y > 0]
    if len(pts) < 2:
        return 0.0
    mx = sum(x for x, _ in pts) / len(pts)
    my = sum(y for _, y in pts) / len(pts)
    den = sum((x - mx) ** 2 for x, _ in pts)
    if den == 0:
        return 0.0
    return sum((x - mx) * (y - my) for x, y in pts) / den
