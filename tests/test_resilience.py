"""Resilience subsystem tests (ISSUE 13): fault-schedule grammar,
guarded dispatch (fake-clock retry/backoff, deadline, classification,
tripwires), the degradation ladder over the committed oracle knobs, and
the CSTPU_FAULTS-off no-op bound.

No test here sleeps for real: the clock and sleeper of guarded_dispatch
are injectable, so the retry/backoff assertions run in microseconds.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from consensus_specs_tpu import resilience, telemetry
from consensus_specs_tpu.resilience import dispatch as rdispatch
from consensus_specs_tpu.resilience import faults, integrity
from consensus_specs_tpu.resilience.errors import (
    DeadlineExceeded, FatalDispatchError, InjectedFault,
    TransientDispatchError)
from consensus_specs_tpu.telemetry import watchdog as wd


@pytest.fixture(autouse=True)
def _clean():
    """Every test starts fault-free, full-speed, with zeroed metrics."""
    faults.set_schedule(None)
    resilience.ladder().reset()
    telemetry.reset()
    wd.reset()
    yield
    faults.set_schedule(None)
    resilience.ladder().reset()
    telemetry.reset()
    wd.reset()


def _ctr(name):
    return telemetry.counter(name, always=True).value


# ---------------------------------------------------------------------------
# Schedule grammar
# ---------------------------------------------------------------------------

def test_schedule_grammar_round_trip():
    s = faults.parse_schedule(
        "seed=42;dispatch:*epoch*@2=raise;dispatch:*@5-7=hang:150;"
        "ckpt.write@1=truncate:33;ckpt.read@2=bitflip:4;mesh@1=lose:2")
    assert s.seed == 42 and len(s.entries) == 5
    e = s.entries[1]
    assert (e.site, e.lo, e.hi, e.action, e.param) == \
        ("dispatch", 5, 7, "hang", "150")


@pytest.mark.parametrize("bad", [
    "dispatch@0=raise",              # occurrences count from 1
    "dispatch@3-2=raise",            # inverted range
    "nosite@1=raise",                # unknown site
    "ckpt.write@1=poison",           # action/site mismatch
    "mesh:glob@1=lose:1",            # only dispatch takes a glob
    "dispatch@x=raise",              # non-integer occurrence
    "dispatch=raise",                # missing @occurrence
    "dispatch@1",                    # missing =action
])
def test_schedule_grammar_rejects(bad):
    with pytest.raises(ValueError, match="CSTPU_FAULTS|occurrence|site"):
        faults.parse_schedule(bad)


def test_env_rearm_resets_occurrence_counters(monkeypatch):
    """Disarm + re-arm of the IDENTICAL env text must parse fresh: spent
    occurrence counters from the first arming cannot make the second
    drill silently fault-free."""
    monkeypatch.setenv("CSTPU_FAULTS", "dispatch@1=raise")
    faults.set_schedule(None)
    assert faults.on_dispatch("k").action == "raise"    # occurrence spent
    assert faults.on_dispatch("k") is None
    monkeypatch.delenv("CSTPU_FAULTS")
    assert not faults.active()                          # disarm drops cache
    monkeypatch.setenv("CSTPU_FAULTS", "dispatch@1=raise")
    assert faults.on_dispatch("k").action == "raise"    # fresh counters


def test_occurrence_counting_and_glob():
    faults.set_schedule("dispatch:*epoch*@2=raise")
    assert faults.on_dispatch(("mesh.other",)) is None      # glob miss
    assert faults.on_dispatch(("mesh.epoch", 8)) is None    # occurrence 1
    fault = faults.on_dispatch(("mesh.epoch", 8))           # occurrence 2
    assert fault is not None and fault.action == "raise"
    assert faults.on_dispatch(("mesh.epoch", 8)) is None    # spent


def test_faults_inactive_when_unset(monkeypatch):
    monkeypatch.delenv("CSTPU_FAULTS", raising=False)
    faults.set_schedule(None)
    assert not faults.active()
    assert faults.on_dispatch("k") is None
    assert faults.filter_devices([1, 2, 3]) == [1, 2, 3]
    data, crash = faults.on_checkpoint_write(b"x")
    assert data == b"x" and not crash


def test_faults_env_driven(monkeypatch):
    monkeypatch.setenv("CSTPU_FAULTS", "dispatch@1=raise")
    faults.set_schedule(None)
    assert faults.active()
    assert faults.on_dispatch("anything").action == "raise"


# ---------------------------------------------------------------------------
# Guarded dispatch: retry / backoff / deadline / classification (fake clock)
# ---------------------------------------------------------------------------

def test_transient_retries_with_backoff_fake_clock():
    sleeps = []
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("UNAVAILABLE: device link flaked")
        return 7

    out = rdispatch.guarded_dispatch(
        ("t", 1), flaky, retries=3, backoff_ms=25.0, sleep=sleeps.append)
    assert out == 7 and len(calls) == 3
    # exponential: 25 ms, then 50 ms — and NO real time passed
    assert sleeps == [0.025, 0.05]
    assert _ctr("resilience.retries") == 2
    assert _ctr("resilience.transient_errors") == 2


def test_transient_exhaustion_raises_typed():
    def always_down():
        raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    with pytest.raises(TransientDispatchError) as ei:
        rdispatch.guarded_dispatch(("t", 2), always_down, retries=2,
                                   sleep=lambda s: None)
    assert ei.value.attempts == 3


def test_predispatch_transient_retries_despite_retries_zero():
    """A donated call site pins retries=0 for post-consume safety, but a
    failure raised BEFORE the dispatch (injected raise, pre-flight
    error) leaves the argument buffers intact — the guard must honor the
    standard budget for those instead of walking the ladder on a
    one-off blip."""
    faults.set_schedule("dispatch:*donated*@1=raise")
    out = rdispatch.guarded_dispatch(
        ("donated",), lambda: 42, retries=0, sleep=lambda s: None)
    assert out == 42
    assert _ctr("resilience.retries") == 1

    # post-dispatch failures (here: a tripwire rejection) must NOT gain
    # that allowance: retries=0 means the first corrupt output raises
    with pytest.raises(rdispatch.CorruptOutput):
        rdispatch.guarded_dispatch(
            ("donated2",), lambda: 7, retries=0,
            check=lambda o: False, sleep=lambda s: None)


def test_fatal_never_retries():
    calls = []

    def buggy():
        calls.append(1)
        raise TypeError("shapes do not match")

    with pytest.raises(FatalDispatchError):
        rdispatch.guarded_dispatch(("t", 3), buggy, retries=5,
                                   sleep=lambda s: None)
    assert len(calls) == 1
    assert _ctr("resilience.fatal_errors") == 1
    assert _ctr("resilience.retries") == 0


def test_deadline_miss_fake_clock_then_recovery():
    # attempt 1 "takes" 400 ms on the fake clock, attempt 2 is instant
    times = iter([0.0, 0.4, 1.0, 1.001])
    fn = jax.jit(lambda x: x + 1)
    _ = fn(jnp.arange(4))                       # warm compile
    out = rdispatch.guarded_dispatch(
        ("t", 4), fn, jnp.arange(4), deadline_ms=100.0,
        clock=lambda: next(times), sleep=lambda s: None)
    assert np.array_equal(np.asarray(out), [1, 2, 3, 4])
    assert _ctr("resilience.deadline_misses") == 1


def test_deadline_exhaustion_raises_typed():
    t = [0.0]

    def clock():
        t[0] += 10.0
        return t[0]

    fn = jax.jit(lambda x: x + 1)
    with pytest.raises(DeadlineExceeded) as ei:
        rdispatch.guarded_dispatch(("t", 5), fn, jnp.arange(4),
                                   deadline_ms=50.0, retries=1,
                                   clock=clock, sleep=lambda s: None)
    assert ei.value.deadline_ms == 50.0 and ei.value.elapsed_ms > 50.0


def test_deadline_salvage_on_zero_retry_sites():
    """A donated call site (retries=0) gets its valid-but-late output
    BACK instead of an exception: the consumed buffers make re-dispatch
    impossible, so raising would turn lateness into unavailability (and
    on the resident path, a restore loop). The miss is still counted."""
    t = [0.0]

    def clock():
        t[0] += 10.0
        return t[0]

    fn = jax.jit(lambda x: x + 1)
    _ = fn(jnp.arange(4))
    out = rdispatch.guarded_dispatch(
        ("salv",), fn, jnp.arange(4), deadline_ms=50.0, retries=0,
        clock=clock, sleep=lambda s: None)
    assert np.array_equal(np.asarray(out), [1, 2, 3, 4])
    assert _ctr("resilience.deadline_misses") == 1
    assert _ctr("resilience.deadline_salvaged") == 1
    # ...but a late output that ALSO fails its tripwire is never salvaged
    with pytest.raises(rdispatch.DeadlineExceeded):
        rdispatch.guarded_dispatch(
            ("salv2",), fn, jnp.arange(4), deadline_ms=50.0, retries=0,
            check=lambda o: False, clock=clock, sleep=lambda s: None)


def test_injected_hang_burns_the_injected_clock():
    """A `hang` fault wedges the dispatch via the injectable sleeper —
    the deadline sees it, the suite never really sleeps."""
    faults.set_schedule("dispatch:*t6*@1=hang:400")
    t = [0.0]
    slept = []

    def sleep(s):
        slept.append(s)
        t[0] += s

    fn = jax.jit(lambda x: x * 2)
    _ = fn(jnp.arange(3))
    out = rdispatch.guarded_dispatch(
        ("t6",), fn, jnp.arange(3), deadline_ms=100.0,
        clock=lambda: t[0], sleep=sleep)
    assert np.array_equal(np.asarray(out), [0, 2, 4])
    assert 0.4 in slept                      # the injected wedge
    assert _ctr("resilience.deadline_misses") == 1
    assert _ctr("resilience.faults_injected") == 1


def test_poison_tripwire_redispatch():
    faults.set_schedule("dispatch:*t7*@1=poison:0")
    fn = jax.jit(lambda x: x + 1)

    out = rdispatch.guarded_dispatch(
        ("t7",), fn, jnp.arange(8, dtype=jnp.uint32),
        check=lambda o: bool(jnp.all(o < 1000)), sleep=lambda s: None)
    assert np.array_equal(np.asarray(out), np.arange(8, dtype=np.uint32) + 1)
    assert _ctr("resilience.corrupt_outputs") == 1
    assert _ctr("resilience.retries") == 1


def test_injected_fault_classifies_like_real_weather():
    faults.set_schedule("dispatch:*t8*@1=raise;dispatch:*t8f*@1=fatal")
    assert rdispatch.guarded_dispatch(
        ("t8",), lambda: 3, sleep=lambda s: None) == 3
    with pytest.raises(FatalDispatchError):
        rdispatch.guarded_dispatch(("t8f",), lambda: 3,
                                   sleep=lambda s: None)
    with pytest.raises(InjectedFault):
        faults.raise_injected("k", faults.Fault("raise", None, "e"))


# ---------------------------------------------------------------------------
# Degradation ladder
# ---------------------------------------------------------------------------

def test_ladder_walks_the_oracle_knobs():
    from consensus_specs_tpu.ops import fq, scalar_mul, sha256
    lad = rdispatch.DegradationLadder()
    assert lad.rung_name == "full"
    assert lad.degrade("test") == "merkle_xla"
    assert sha256.merkle_pair_backend_name() == "xla"
    assert lad.degrade("test") == "redc_leaf"
    assert fq.fq_redc_backend_name() == "leaf"
    assert lad.degrade("test") == "scalar_double_add"
    assert scalar_mul.scalar_mul_backend_name() == "double_add"
    hits = []
    lad.register_single_device(lambda: hits.append(1))
    assert lad.degrade("test") == "single_device"
    assert hits == [1]
    assert lad.exhausted and lad.degrade("test") is None
    assert _ctr("resilience.degradations") == 4
    assert telemetry.gauge("resilience.rung", always=True).value == 4
    lad.reset()
    assert lad.rung_name == "full"
    assert telemetry.gauge("resilience.rung", always=True).value == 0
    # reset returns the knobs to env control
    assert sha256._pair_backend_override is None
    assert fq.fq_redc_backend_name() in ("coeff", "leaf")
    # ...but the IRREVERSIBLE rung's history survives reset on /healthz:
    # a core that went single-device only re-shards via restore
    snap = resilience.health_snapshot()
    assert snap["counters"]["degradations.single_device"] == 1
    assert snap["status"] == "ok"      # rung gauge reset — counter remains


def test_ladder_counters_survive_telemetry_off():
    telemetry.set_enabled(False)
    try:
        lad = rdispatch.DegradationLadder()
        lad.degrade("weather")
        assert _ctr("resilience.degradations") == 1
        snap = resilience.health_snapshot()
        assert snap["counters"]["degradations"] == 1
        lad.reset()
    finally:
        telemetry.set_enabled(None)


def test_run_with_recovery_degrades_then_succeeds():
    lad = rdispatch.DegradationLadder()
    state = {"fail": True}

    def make():
        def fn():
            if state["fail"]:
                raise RuntimeError("INTERNAL: wedged")
            return 11
        return fn, ()

    # heal the moment the ladder first degrades
    lad.register_single_device(lambda: None)
    orig = lad._apply

    def apply_and_heal(name):
        state["fail"] = False
        return orig(name)

    lad._apply = apply_and_heal
    out = rdispatch.run_with_recovery(
        ("r", 1), make, ladder=lad, retries=1, sleep=lambda s: None)
    assert out == 11 and lad.rung_name == "merkle_xla"
    lad.reset()


def test_run_with_recovery_exhausted_is_fatal():
    lad = rdispatch.DegradationLadder()

    def make():
        def fn():
            raise RuntimeError("UNAVAILABLE: forever")
        return fn, ()

    with pytest.raises(FatalDispatchError):
        rdispatch.run_with_recovery(("r", 2), make, ladder=lad,
                                    retries=0, sleep=lambda s: None)
    assert lad.exhausted
    lad.reset()


# ---------------------------------------------------------------------------
# Integrity tripwires
# ---------------------------------------------------------------------------

def test_epoch_tripwire_hulls_match_range_contracts():
    hulls = integrity.declared_epoch_hulls()
    # spot-pin the committed declarations the tripwire derives from
    assert hulls["balance"] == (0, 1 << 45)
    assert hulls["effective_balance"][1] == 32 * 10 ** 9
    from consensus_specs_tpu.models.phase0.epoch_soa import ValidatorColumns
    assert set(hulls) == set(ValidatorColumns._fields)


def test_epoch_tripwire_trips_on_poison():
    from consensus_specs_tpu.models.phase0.epoch_soa import ValidatorColumns
    V = 16
    u = jnp.zeros(V, jnp.uint64)
    cols = ValidatorColumns(u, u, u, u, jnp.zeros(V, bool), u, u)
    out = (cols, None, None)
    assert integrity.epoch_output_check(out)
    bad = cols._replace(balance=u.at[3].set(jnp.uint64(1) << 60))
    assert not integrity.epoch_output_check((bad, None, None))
    # poison_tree's int corruption is exactly what the hull rejects
    poisoned = faults.poison_tree(
        out, str(list(ValidatorColumns._fields).index("balance")))
    assert not integrity.epoch_output_check(poisoned)


def test_epoch_tripwire_covers_scalar_hulls():
    """The poison surface includes the EpochScalars leaves (flattened
    indices past the 7 columns): every finitely-declared scalar hull is
    checked, so a poisoned slot/epoch/slashed-balance leaf trips the
    wire instead of chaining into justification state."""
    from consensus_specs_tpu.models.phase0.epoch_soa import (EpochScalars,
                                                             ValidatorColumns)
    V = 16
    u = jnp.zeros(V, jnp.uint64)
    cols = ValidatorColumns(u, u, u, u, jnp.zeros(V, bool), u, u)
    scal = EpochScalars(*([jnp.zeros((), jnp.uint64)] * 6),
                        latest_slashed_balances=jnp.zeros(8, jnp.uint64))
    out = (cols, scal, None)
    assert integrity.epoch_output_check(out)
    hulls = integrity.declared_epoch_scalar_hulls()
    assert hulls["slot"][1] < (1 << 64) - 1          # declared finite
    bad = scal._replace(slot=jnp.asarray(1 << 40, jnp.uint64))
    assert not integrity.epoch_output_check((cols, bad, None))
    # poison leaf 7 = the first EpochScalars leaf (slot -> uint64 max)
    assert not integrity.epoch_output_check(faults.poison_tree(out, "7"))
    # the bitfield leaf legitimately spans uint64: excluded from the
    # finite item set — the documented blind spot of a range tripwire
    assert hulls["justification_bitfield"][1] == (1 << 64) - 1
    assert "justification_bitfield" not in dict(
        integrity._finite_items(hulls))


def test_finite_check_and_float_poison():
    tree = {"a": jnp.ones((4,), jnp.float32), "b": jnp.arange(3)}
    assert integrity.finite_check(tree)
    assert not integrity.finite_check(faults.poison_tree(tree, "0"))


def test_tripwires_env_knob(monkeypatch):
    monkeypatch.delenv("CSTPU_TRIPWIRES", raising=False)
    assert integrity.tripwires_enabled()
    monkeypatch.setenv("CSTPU_TRIPWIRES", "0")
    assert not integrity.tripwires_enabled()


# ---------------------------------------------------------------------------
# Steady-state hygiene: zero overhead off, zero watchdog events guarded
# ---------------------------------------------------------------------------

def test_noop_bound_faults_off(monkeypatch):
    """CSTPU_FAULTS unset + no deadline + no check => guarded_dispatch is
    the plain watchdog call: under the same generous <20 us/op bound the
    telemetry no-op test uses (mirrors test_telemetry's)."""
    monkeypatch.delenv("CSTPU_FAULTS", raising=False)
    monkeypatch.delenv("CSTPU_DEADLINE_MS", raising=False)
    faults.set_schedule(None)
    telemetry.set_enabled(False)
    try:
        def fn():
            return None
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            rdispatch.guarded_dispatch(("noop",), fn)
        per_op = (time.perf_counter() - t0) / n
        assert per_op < 20e-6, f"guarded no-op {per_op * 1e6:.2f} us/op"
    finally:
        telemetry.set_enabled(None)


def test_guarded_chain_zero_watchdog_events():
    """Chained guarded dispatches of one jitted program: the retrace
    watchdog under the guard sees one warm-up compile and NOTHING else —
    the runtime half of the guarded_epoch_chain trace contract."""
    telemetry.set_enabled(True)
    try:
        fn = jax.jit(lambda x: x * 2 + 1)
        x = jnp.arange(16)
        for _ in range(6):
            x = rdispatch.guarded_dispatch(("chain",), fn, x)
        stats = wd.stats(("chain",))
        assert stats["calls"] == 6 and stats["events"] == 0
        assert telemetry.counter("watchdog.retrace_events").value == 0
    finally:
        telemetry.set_enabled(None)


def test_trace_contract_registry_shape():
    """The committed resilience contracts: the guarded chain pins the
    SAME chained prefix as the serving-mesh contract (a ValidatorColumns
    or EpochScalars field addition must update both), and the tripwire
    contract stays collective-lean."""
    from consensus_specs_tpu.models.phase0.epoch_soa import (
        EpochScalars, ValidatorColumns)
    from consensus_specs_tpu.parallel import sharding

    [c_chain] = rdispatch.TRACE_CONTRACTS
    assert c_chain["chained_prefix"] == \
        len(ValidatorColumns._fields) + len(EpochScalars._fields)
    assert c_chain["chained_prefix"] == \
        sharding.TRACE_CONTRACTS[0]["chained_prefix"]
    [c_trip] = integrity.TRACE_CONTRACTS
    assert c_trip["collectives"] == ("all-reduce",)
    assert "device_put" in c_trip["forbid"]


def test_health_snapshot_shape():
    snap = resilience.health_snapshot()
    assert snap["status"] == "ok"
    assert snap["rung"]["name"] == "full"
    assert set(snap["counters"]) >= {"retries", "deadline_misses",
                                     "degradations", "faults_injected",
                                     "corrupt_outputs"}
    assert "last_good_generation" in snap["checkpoint"]
