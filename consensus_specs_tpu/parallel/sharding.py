"""Validator-axis sharding policy for the SoA epoch state.

Placement contract (SURVEY.md §2c: the registry is the protocol's
embarrassingly-parallel axis):
  - every `[V]` column of ValidatorColumns / EpochInputs shards over the
    mesh's "v" axis;
  - scalars and small tables (EpochScalars, the two shard-balance
    tables, the proposer table and its row count) replicate — they feed
    cross-shard reductions XLA lowers to psum/all-gather collectives
    over ICI.
"""
from __future__ import annotations

import os
from functools import partial
from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.phase0.epoch_soa import (
    REPLICATED_INPUT_FIELDS, EpochInputs, EpochReport, EpochScalars,
    ValidatorColumns, _epoch_transition_traced)
from ..resilience import faults as _faults
from ..resilience.dispatch import RETRIES_DEFAULT, guarded_dispatch
from ..utils.donation import platform_donated_jit
from ..utils.merkle import next_power_of_two


def validator_mesh(devices=None, n: int = None) -> Mesh:
    """A 1-D mesh over the validator axis ("v"). The ambient device list
    routes through the fault harness's device-loss filter
    (resilience/faults.py `mesh=lose:<k>`), so a simulated loss surfaces
    here — at mesh construction — exactly like a real missing chip."""
    if devices is None:
        devices = _faults.filter_devices(jax.devices())
    if n is not None:
        assert len(devices) >= n, f"need {n} devices, have {len(devices)}"
        devices = devices[:n]
    return Mesh(np.asarray(devices), axis_names=("v",))


def _epoch_input_shardings(shard_v, replicated) -> EpochInputs:
    """EpochInputs placement convention: every field is a [V]
    participation-fact column EXCEPT epoch_soa.REPLICATED_INPUT_FIELDS
    (the small tables and the row count), which replicate. Single
    definition shared by shard_epoch_state and ServingMesh."""
    return EpochInputs(**{
        f: replicated if f in REPLICATED_INPUT_FIELDS else shard_v
        for f in EpochInputs._fields})


def shard_epoch_state(mesh: Mesh, cols: ValidatorColumns, scal: EpochScalars,
                      inp: EpochInputs):
    """Place one epoch step's inputs per the contract above."""
    shard_v = NamedSharding(mesh, P("v"))
    repl = NamedSharding(mesh, P())
    cols_s = ValidatorColumns(*(jax.device_put(x, shard_v) for x in cols))
    scal_s = EpochScalars(*(jax.device_put(x, repl) for x in scal))
    inp_s = jax.device_put(inp, _epoch_input_shardings(shard_v, repl))
    return cols_s, scal_s, inp_s


def hierarchical_mesh(devices=None, hosts: int = None) -> Mesh:
    """A ("host", "v") mesh for multi-host topologies: the outer axis spans
    processes (DCN), the inner axis the devices within a host (ICI).

    The scaling recipe (jax-ml.github.io/scaling-book): put the heavy
    embarrassingly-parallel axis on the FLATTENED (host, v) product so the
    bulk of every collective runs over ICI — for this framework's three
    parallel axes (validator columns, pairing groups, Merkle leaves) the
    per-device partial reductions (balance sums, group verdicts, subtree
    roots) combine within a host first and only one scalar/root per host
    crosses DCN. XLA inserts exactly that hierarchy from the mesh order;
    this is the counterpart of the reference ecosystem's NCCL/MPI backend,
    expressed as device placement instead of explicit sends.

    `hosts` overrides process grouping (virtual CPU meshes are all one
    process — tests shape 8 devices as 2x4)."""
    if devices is None:
        devices = jax.devices()
    if hosts is None:
        pids = sorted({d.process_index for d in devices})
        hosts = len(pids)
        devices = sorted(devices, key=lambda d: (d.process_index, d.id))
    assert len(devices) % hosts == 0, "devices must tile hosts evenly"
    arr = np.asarray(devices).reshape(hosts, len(devices) // hosts)
    return Mesh(arr, axis_names=("host", "v"))


def shard_hierarchical(mesh: Mesh, tree):
    """Shard every leaf's leading axis over the flattened ("host", "v")
    product of a hierarchical_mesh; 0-d leaves replicate."""
    shard = NamedSharding(mesh, P(("host", "v")))
    repl = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(
            x, shard if getattr(x, "ndim", 0) >= 1 else repl),
        tree)


def pow2_pad_rows(n: int, mesh_size: int) -> int:
    """The next power of two >= max(n, 1) — because the serving mesh size
    is itself a power of two, the result is a multiple of it whenever it
    is at least the mesh size. This is the row count the sharded forests
    materialize per level: the capacity a sharded tree is laid out with
    rounds to a multiple of the mesh size."""
    assert mesh_size & (mesh_size - 1) == 0, \
        f"mesh size must be a power of two, got {mesh_size}"
    return next_power_of_two(max(n, 1))


def pad_leading_pow2(x, mesh: Mesh):
    """Zero-pad an array's leading axis to pow2_pad_rows so it becomes
    shardable over the mesh — the helper `shard_leading_axis` names when
    it rejects a non-divisible axis. Callers that need non-zero padding
    semantics (inert validator rows) pad themselves before sharding."""
    import jax.numpy as jnp
    n = x.shape[0]
    m = pow2_pad_rows(n, mesh.devices.size)
    if m == n:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((m - n,) + tuple(x.shape[1:]), dtype=x.dtype)])


def shard_leading_axis(mesh: Mesh, tree):
    """Shard every leaf's LEADING axis over the mesh's "v" axis.

    The placement for the two other first-class parallel axes (SURVEY.md
    §2c): the attestation/group axis of the grouped pairing check (each
    group's pair product is independent — no cross-device traffic until
    the final verdict gather) and the leaf axis of the bulk Merkleizer
    (the reduction tree halves locally until the level fits one device,
    then XLA inserts the cross-device combines). 0-d leaves replicate.

    Leading axes must divide the mesh size: this jax pins shard sizes at
    placement time, so a non-divisible axis would make pjit pad (or
    reject) unpredictably per jax version. Pad explicitly first —
    `pad_leading_pow2(x, mesh)` gives the pow2 row count every sharded
    consumer here (forests, serving columns) already uses."""
    size = int(mesh.devices.size)
    for leaf in jax.tree_util.tree_leaves(tree):
        n = getattr(leaf, "shape", (0,))[0] if getattr(leaf, "ndim", 0) else None
        if n is not None and n % size:
            if size & (size - 1) == 0:
                hint = next_power_of_two(max(n, 1))
                while hint % size:        # pow2 size: terminates at >= size
                    hint *= 2
                how = f"e.g. pad_leading_pow2 to {hint} rows"
            else:                         # non-pow2 mesh: next multiple
                how = f"e.g. zero-pad to {-(-n // size) * size} rows"
            raise ValueError(
                f"shard_leading_axis: leading axis of {n} rows does not "
                f"divide the {size}-device mesh — pad first ({how}) "
                f"instead of letting pjit pad unpredictably")
    shard = NamedSharding(mesh, P("v"))
    repl = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(
            x, shard if getattr(x, "ndim", 0) >= 1 else repl),
        tree)


class ServingMesh:
    """Placement layer for the resident serving loop (ROADMAP item 1).

    Owns the validator-axis mesh and produces MATCHED in/out shardings for
    every jitted program ResidentCore dispatches, so chained per-slot and
    per-epoch steps pass device arrays straight through without re-layout —
    the staged-pjit contract of SNIPPETS.md [1][2]: a producer's
    out_shardings must be the next call's in_shardings. Placement policy:

      - every `[Vp]` validator column (and the `[Vp]` participation facts)
        shards over "v"; Vp is the logical validator count padded up to a
        multiple of the mesh size with INERT rows (never-activated,
        zero-balance validators the epoch program's masks exclude — jax
        pins shard sizes at placement, so the axis must divide the mesh);
      - scalars, the shard-balance tables, and the epoch report replicate;
      - forest levels shard while their row count divides the mesh and
        replicate above that (the tiny cap tree).
    """

    def __init__(self, mesh: Mesh):
        assert mesh.axis_names == ("v",), mesh.axis_names
        size = int(mesh.devices.size)
        assert size & (size - 1) == 0, \
            f"serving mesh size must be a power of two, got {size}"
        self.mesh = mesh
        self.shard_v = NamedSharding(mesh, P("v"))
        self.replicated = NamedSharding(mesh, P())
        self._jits: Dict = {}

    @property
    def size(self) -> int:
        return int(self.mesh.devices.size)

    @classmethod
    def create(cls, n: int = None) -> "ServingMesh":
        return cls(validator_mesh(n=n))

    @classmethod
    def available(cls, max_n: int = None) -> Optional["ServingMesh"]:
        """The largest power-of-two serving mesh the SURVIVING devices
        support (the ambient list filtered through the fault harness's
        device-loss hook) — the restore-after-hardware-loss entry: a
        checkpoint written under 8 devices restores onto whatever is
        left. None when fewer than 2 devices remain."""
        devices = list(_faults.filter_devices(jax.devices()))
        limit = len(devices) if max_n is None else min(len(devices), max_n)
        n = 1
        while n * 2 <= limit:
            n *= 2
        if n <= 1:
            return None
        # devices already filtered: pass them through so validator_mesh
        # does not consume a second device-loss fault occurrence
        return cls(validator_mesh(devices=devices, n=n))

    @classmethod
    def from_env(cls) -> Optional["ServingMesh"]:
        """CSTPU_SERVING_MESH knob: unset/""/"0"/"off" -> single-device
        (None); "all" -> the largest power-of-two device count available;
        an integer -> exactly that many devices (must be a power of two —
        an explicit ask is honored or refused, never silently rounded).
        A 1-device request also resolves to None (nothing to shard)."""
        spec = os.environ.get("CSTPU_SERVING_MESH", "").strip().lower()
        if spec in ("", "0", "off", "none"):
            return None
        if spec == "all":
            return cls.available()
        else:
            try:
                n = int(spec)
            except ValueError:
                raise ValueError(
                    f"CSTPU_SERVING_MESH={spec!r}: expected an integer "
                    f"device count, 'all', or '0'/'off'") from None
            if n > 1 and n & (n - 1):
                raise ValueError(
                    f"CSTPU_SERVING_MESH={n}: the serving mesh size must "
                    f"be a power of two (forest levels halve per tree "
                    f"level); use {1 << (n.bit_length() - 1)} or 'all'")
        if n <= 1:
            return None
        return cls.create(n)

    # -- padding ------------------------------------------------------------

    def pad_rows(self, n: int) -> int:
        """Smallest multiple of the mesh size >= n (the padded column
        length Vp for a logical registry of n validators)."""
        return -(-n // self.size) * self.size

    def row_sharding(self, rows: int) -> NamedSharding:
        """Forest-level placement: shard a level over "v" while its row
        count divides the mesh, replicate the (tiny) cap levels above."""
        return self.shard_v if rows and rows % self.size == 0 \
            else self.replicated

    # -- epoch program ------------------------------------------------------

    def epoch_shardings(self):
        """(cols, scal, inp) placement pytrees — the epoch program's
        in_shardings AND (for cols/scal) its out_shardings."""
        return (
            ValidatorColumns(*([self.shard_v] * len(ValidatorColumns._fields))),
            EpochScalars(*([self.replicated] * len(EpochScalars._fields))),
            _epoch_input_shardings(self.shard_v, self.replicated),
        )

    def place_epoch_inputs(self, scal, inp):
        """(scal, inp) from the HOST to where the epoch program takes them
        (`epoch_shardings`): each `[Vp]` fact's rows go to their own
        shard's device, the scalars, the small tables and the row count
        to every device. `inp` is already padded to a mesh multiple
        (epoch_soa.pad_epoch_inputs on the host arrays). Facts uploaded
        to one device first would be re-laid-out chip to chip by the
        program's in_shardings, inside the dispatch."""
        _, scal_sh, inp_sh = self.epoch_shardings()
        return jax.device_put((scal, inp), (scal_sh, inp_sh))

    def epoch_transition(self, cfg, cols, scal, inp, check=None):
        """The fused epoch program with matched in/out shardings: sharded
        `[Vp]` columns in, sharded `[Vp]` columns out, so consecutive
        boundaries chain with zero re-layout. Donation is per shard on
        accelerator backends (each device's column shard is rewritten in
        place); XLA:CPU stays undonated for the same persistent-cache
        aliasing reason as epoch_soa.epoch_transition_device.

        Dispatch goes through the resilience guard: with nothing armed
        it degenerates to the watchdog-wrapped call; under a deadline
        budget / fault schedule it gains retry + the typed classification, and
        `check` (resilience/integrity.py) tripwires the output before it
        can chain (the caller decides how to degrade — ResidentCore
        walks the ladder)."""
        key = ("epoch", cfg)
        pd = self._jits.get(key)
        if pd is None:
            cols_sh, scal_sh, inp_sh = self.epoch_shardings()
            report_sh = EpochReport(
                *([self.replicated] * len(EpochReport._fields)))
            # under the function's own name: a bare functools.partial
            # compiles as the XLA module `jit__unknown`, which is what
            # every other partial's program is called too (the output
            # tripwire's among them), and a device trace is read by
            # module name
            program = partial(_epoch_transition_traced, cfg)
            program.__name__ = _epoch_transition_traced.__name__
            pd = platform_donated_jit(
                program,
                in_shardings=(cols_sh, scal_sh, inp_sh),
                out_shardings=(cols_sh, scal_sh, report_sh),
                donate_argnums=(0,))
            self._jits[key] = pd
        donate = pd.donate_now()
        fn = pd.resolve()
        # retrace watchdog: the key pins the full static context (mesh
        # size, padded V, config), so any compile-cache miss after the
        # first compile is a genuine retrace of the steady-state program.
        # Donated programs must NOT retry: a failure observed after the
        # dispatch consumed the per-shard column buffers would re-call fn
        # on deleted arrays — the typed error surfaces on the FIRST
        # attempt instead, and the caller recovers at a coarser grain
        # (ResidentCore's ladder / checkpoint restore).
        wkey = ("mesh.epoch", self.size, int(cols.balance.shape[0]),
                cfg, donate)
        return guarded_dispatch(wkey, fn, cols, scal, inp, check=check,
                                retries=0 if donate else RETRIES_DEFAULT)

    # -- forest level-0 builders --------------------------------------------

    def registry_forest_leaves(self, pubkeys, withdrawal_credentials,
                               activation_eligibility_epoch, activation_epoch,
                               exit_epoch, withdrawable_epoch, slashed,
                               effective_balance, v_count: int,
                               capacity: int = None):
        """[P2, 8] sharded level-0 rows of the registry forest from padded
        `[Vp]` device columns: validator hash_tree_root words for rows
        below the LOGICAL count, zero rows (the SSZ virtual padding)
        beyond — P2 = pow2_pad_rows(capacity), the rows of storage the
        registry has (its length when none is given), a multiple of the
        mesh size whenever it reaches it. v_count rides as a traced scalar
        so a deposit that grows the registry inside the capacity re-uses
        the compiled program."""
        import jax.numpy as jnp
        from ..utils.ssz.bulk import _registry_leaf_words

        vp = int(pubkeys.shape[0])
        p2 = pow2_pad_rows(capacity or v_count, self.size)
        key = ("regleaves", vp, p2)
        fn = self._jits.get(key)
        if fn is None:
            def traced(pk, wc, a, b, c, d, s, eb, n_valid):
                leaves = _registry_leaf_words(pk, wc, a, b, c, d, s, eb)
                mask = jnp.arange(vp, dtype=jnp.int32)[:, None] < n_valid
                leaves = jnp.where(mask, leaves, jnp.uint32(0))
                if p2 > vp:
                    leaves = jnp.concatenate(
                        [leaves, jnp.zeros((p2 - vp, 8), dtype=jnp.uint32)])
                return leaves[:p2]
            fn = jax.jit(
                traced,
                in_shardings=tuple([self.shard_v] * 8) + (self.replicated,),
                out_shardings=self.row_sharding(p2))
            self._jits[key] = fn
        return guarded_dispatch(
            ("mesh.regleaves", self.size, vp, p2), fn,
            pubkeys, withdrawal_credentials,
            activation_eligibility_epoch, activation_epoch,
            exit_epoch, withdrawable_epoch, slashed,
            effective_balance, np.int32(v_count))

    def balances_forest_chunks(self, balances, v_count: int):
        """[P2c, 8] sharded level-0 rows of the balances forest from the
        padded `[Vp]` balance column, `v_count` the registry's rows of
        storage (its capacity). Inert padding rows hold balance 0, which
        IS the SSZ pack's virtual zero padding, so no masking is needed —
        only the pow2 row padding."""
        import jax.numpy as jnp
        from ..utils.ssz.bulk import _balances_chunk_words

        vp = int(balances.shape[0])
        c = max(1, -(-v_count // 4))
        p2 = pow2_pad_rows(c, self.size)
        key = ("balchunks", vp, p2)
        fn = self._jits.get(key)
        if fn is None:
            def traced(bal):
                chunks = _balances_chunk_words(bal)
                if p2 > chunks.shape[0]:
                    chunks = jnp.concatenate(
                        [chunks,
                         jnp.zeros((p2 - chunks.shape[0], 8),
                                   dtype=jnp.uint32)])
                return chunks[:p2]
            fn = jax.jit(traced, in_shardings=(self.shard_v,),
                         out_shardings=self.row_sharding(p2))
            self._jits[key] = fn
        return guarded_dispatch(("mesh.balchunks", self.size, vp, p2),
                                fn, balances)

    def forest_build_shardings(self, capacity: int):
        """(in_shardings, out_shardings) of the forest-build program at a
        pow2 capacity — one definition shared by forest_build_jit and the
        trace-tier contract, so the contract checks the REAL placement."""
        from ..utils.merkle import tree_depth
        assert capacity & (capacity - 1) == 0, capacity
        return ((self.row_sharding(capacity),),
                tuple(self.row_sharding(capacity >> d)
                      for d in range(tree_depth(capacity) + 1)))

    def forest_build_jit(self, capacity: int):
        """One traced program building EVERY level of a pow2 `capacity`-leaf
        forest, each level placed per row_sharding — per-shard subtree
        levels stay on their shard, the cap levels replicate (the join of
        the per-shard roots happens once, inside this program)."""
        from ..utils.ssz.incremental import _build_levels

        key = ("build", capacity)
        fn = self._jits.get(key)
        if fn is None:
            in_sh, out_sh = self.forest_build_shardings(capacity)
            fn = jax.jit(_build_levels,
                         in_shardings=in_sh, out_shardings=out_sh)
            self._jits[key] = fn
        wkey = ("mesh.forest_build", self.size, capacity)
        return lambda leaves, _fn=fn: guarded_dispatch(wkey, _fn, leaves)


def trees_bitwise_equal(a, b) -> bool:
    """Leafwise dtype/shape/value equality of two pytrees (host compare)."""
    leaves_a = jax.tree_util.tree_leaves(a)
    leaves_b = jax.tree_util.tree_leaves(b)
    if len(leaves_a) != len(leaves_b):
        return False
    for x, y in zip(leaves_a, leaves_b):
        xn, yn = np.asarray(x), np.asarray(y)
        if xn.dtype != yn.dtype or xn.shape != yn.shape or not (xn == yn).all():
            return False
    return True


# ---------------------------------------------------------------------------
# Trace-tier kernel contracts (tools/analysis/trace/, `make contracts`)
# ---------------------------------------------------------------------------
# The ServingMesh dispatch contracts, checked STATICALLY on the lowered
# programs (the compile-time counterpart of telemetry/watchdog.py's
# re-layout check): the sharded epoch program's lowered out-shardings
# must equal its in-shardings position-for-position across the chained
# (cols, scal) prefix — so consecutive slot/epoch boundaries pass device
# arrays straight through — and its compiled collective inventory is
# pinned, so a jax/XLA/kernel change that starts re-sharding mid-program
# (a new all-to-all on the serving path) fails before any bench run.
# Runs on the 8-device virtual CPU mesh; skips (with a notice) when the
# process has fewer devices.

_CONTRACT_MESH_DEVICES = 8


def _mesh_epoch_chain_build():
    from ..models.phase0 import get_spec
    from ..models.phase0.epoch_soa import (
        EpochConfig, synthetic_epoch_state)
    import numpy as _np

    serving = ServingMesh.create(_CONTRACT_MESH_DEVICES)
    cfg = EpochConfig.from_spec(get_spec("minimal"))
    cols, scal, inp = synthetic_epoch_state(
        cfg, 64 * serving.size, _np.random.default_rng(1))
    cols_sh, scal_sh, inp_sh = serving.epoch_shardings()
    report_sh = EpochReport(*([serving.replicated] * len(EpochReport._fields)))
    return dict(
        fn=partial(_epoch_transition_traced, cfg),
        args=(cols, scal, inp),
        jit_kwargs=dict(in_shardings=(cols_sh, scal_sh, inp_sh),
                        out_shardings=(cols_sh, scal_sh, report_sh)))


def _forest_build_build():
    import jax.numpy as jnp
    from ..utils.ssz.incremental import _build_levels

    serving = ServingMesh.create(_CONTRACT_MESH_DEVICES)
    capacity = 64
    in_sh, out_sh = serving.forest_build_shardings(capacity)
    return dict(
        fn=_build_levels,
        args=(jnp.zeros((capacity, 8), jnp.uint32),),
        jit_kwargs=dict(in_shardings=in_sh, out_shardings=out_sh))


# ---------------------------------------------------------------------------
# Memory contract (tools/analysis/memory/, `make memory`)
# ---------------------------------------------------------------------------
# The per-shard HBM capacity argument of the sharded epoch at the 10M
# ceiling, PROVEN rather than hand arithmetic: rerun the liveness walk
# with the mesh placement policy as the byte function — a leaf with
# >= 2^20 elements shards over the 8 virtual devices ([V] columns and
# every [V]-sized intermediate; epoch_shardings places them on "v"),
# anything smaller replicates (scalars, the LATEST_SLASHED_EXIT_LENGTH
# table, the SHARD_COUNT aggregates; `replicated` placement) — and
# check shard_peak <= ceil(single_peak / 8) + the declared replicated
# cap. The cap bounds the replicated remainder (small tables + scalar
# reductions live at the peak eqn): 1 MiB of slack vs the ~200 MB
# per-shard column footprint, so a [V] buffer silently dropping out of
# the sharded set (a placement regression re-materializing a full
# column per device) overshoots it by orders of magnitude.

def _mesh_epoch_mem_build():
    from ..models.phase0.epoch_soa import _epoch_mem_build
    return _epoch_mem_build()


MEM_CONTRACTS = [
    dict(
        name="parallel.sharding.epoch_shard_hbm",
        build=_mesh_epoch_mem_build,
        sharded=dict(devices=_CONTRACT_MESH_DEVICES,
                     min_elems=1 << 20,
                     replicated_cap_bytes=1 << 20),
    ),
]


TRACE_CONTRACTS = [
    dict(
        name="parallel.sharding.mesh_epoch_chain",
        build=_mesh_epoch_chain_build,
        requires_devices=_CONTRACT_MESH_DEVICES,
        # the chained prefix: every ValidatorColumns and EpochScalars
        # leaf (outputs 0..13) must come back under the SAME sharding
        # annotation its matching input carries (out == next in)
        chained_prefix=(len(ValidatorColumns._fields)
                        + len(EpochScalars._fields)),
        # the epoch program's budgeted cross-device traffic: balance-sum
        # / justification reductions (all-reduce) plus the activation-
        # queue sort's gathers — anything beyond this inventory is a new
        # reshard on the serving path
        collectives=("all-gather", "all-reduce"),
        budgets={"collective_ops": 20, "f64_ops": 2},
        exact=("f64_ops",),
        forbid=("callback", "device_put"),
    ),
    dict(
        name="parallel.sharding.forest_build",
        build=_forest_build_build,
        requires_devices=_CONTRACT_MESH_DEVICES,
        # per-shard subtrees build shard-locally; the only traffic is the
        # gather joining shard roots into the replicated cap levels
        collectives=("all-gather",),
        budgets={"collective_ops": 8},
        forbid=("f64", "callback", "device_put"),
    ),
]
