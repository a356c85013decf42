"""Attestation-firehose streaming verifier (ISSUE 15).

The acceptance contract: verdicts BIT-IDENTICAL to the synchronous
per-block path (`JaxBackend.verify_indexed_batch` /
`_grouped_pairing_dispatch`) for random mixes of valid + invalid +
duplicate aggregates accumulated across slot boundaries; partial
batches flush at the deadline (salvaged, counted) instead of stalling;
and >= 4 steady-state batch launches record ZERO retrace / re-layout
watchdog events.
"""
import numpy as np
import pytest

from consensus_specs_tpu import streaming, telemetry
from consensus_specs_tpu.crypto import bls
from consensus_specs_tpu.crypto import bls12_381 as gt
from consensus_specs_tpu.ops import bls_jax as BJ

P = 3   # spec aggregate-verify pair count of the staged example groups


@pytest.fixture(autouse=True)
def _no_global_verifier():
    prev = streaming.activate(None)
    yield
    streaming.activate(prev)


def _counter(name):
    return telemetry.counter(name, always=True).value


_STAGED = {}


def _staged_groups(n=2):
    """n distinct spec-shaped (P=3) verifying groups, staged once per
    session (host signing is the slow part, device work is shared)."""
    if n not in _STAGED:
        _STAGED[n] = BJ.stage_example_groups(n, n_distinct=n)
    return _STAGED[n]


def _group_pairs(g1, g2, i):
    return [(g1[i, p], g2[i, p]) for p in range(P)]


def _mismatched_pairs(g1, g2):
    """A deterministic FALSE group: group 0's G1 points against group
    1's G2 points — a well-formed pairing product that is not one."""
    return [(g1[0, p], g2[1, p]) for p in range(P)]


def _verifier(**kw):
    kw.setdefault("register", False)
    return streaming.StreamingVerifier(**kw)


def _fake_clock(step_s):
    t = [0.0]

    def clock():
        t[0] += step_s
        return t[0]

    return clock


# ---------------------------------------------------------------------------
# Differential: streamed verdicts == synchronous dispatch
# ---------------------------------------------------------------------------

def test_staged_stream_matches_sync_dispatch():
    """Valid + invalid + duplicate staged groups through the queue ==
    the synchronous _grouped_pairing_dispatch verdict map."""
    g1, g2 = _staged_groups()
    groups = [("ok0", _group_pairs(g1, g2, 0)),
              ("ok1", _group_pairs(g1, g2, 1)),
              ("bad", _mismatched_pairs(g1, g2)),
              ("ok0b", _group_pairs(g1, g2, 0))]   # same content, new key
    v = _verifier(target_groups=2)
    for key, pairs in groups:
        v.submit_staged(key, pairs)
    v.pump()
    got = dict(v.flush())
    sync = BJ._grouped_pairing_dispatch(groups)
    assert got == sync
    assert sync["bad"] is False and sync["ok0"] is True
    # duplicate KEY submission is dropped, not re-verified
    before = _counter("firehose.duplicates")
    v.submit_staged("ok0", _group_pairs(g1, g2, 0))
    assert _counter("firehose.duplicates") == before + 1
    assert v.queue.depth == 0


def test_item_stream_matches_verify_indexed_batch():
    """Random mix of valid / wrong-signer / malformed / empty items in
    the verify_indexed shape: streamed verdicts == the synchronous
    verify_indexed_batch, item by item."""
    py = gt.PythonBackend()
    dom = 1
    rng = np.random.RandomState(7)

    def item(msg, keys, sig_keys=None, custody=False):
        sig_keys = keys if sig_keys is None else sig_keys
        sig = py.aggregate_signatures([py.sign(msg, k, dom)
                                       for k in sig_keys])
        sets = [[gt.privtopub(k) for k in keys], []]
        mhs = [msg, bytes(32)]
        if custody:
            sets = sets[::-1]
            mhs = mhs[::-1]
        return (sets, mhs, sig, dom)

    msgs = [bytes([m]) * 32 for m in range(3)]
    items = [
        item(msgs[0], [11, 12]),                      # valid
        item(msgs[1], [13]),                          # valid
        item(msgs[0], [11, 12], sig_keys=[13, 14]),   # wrong signers
        item(msgs[2], [15, 16]),                      # valid
        ([[b"\x00" * 47]], [msgs[0]], b"\x11" * 96, dom),   # malformed pk
        ([[], []], [msgs[0], msgs[1]],
         gt.compress_g2(None), dom),                  # empty product
        item(msgs[1], [13]),                          # duplicate of #1
    ]
    order = rng.permutation(len(items))
    items = [items[i] for i in order]

    backend = BJ.JaxBackend()
    expect = backend.verify_indexed_batch(items)

    v = _verifier(backend=backend, target_groups=2)
    got = v.verdicts_for(items)
    assert got == expect
    assert got.count(False) >= 2 and got.count(True) >= 3
    # the duplicate collapsed onto one digest
    assert _counter("firehose.duplicates") >= 1


def test_grouped_dispatch_multi_bucket_verdict_map():
    """Overlap-fix regression: _grouped_pairing_dispatch now launches
    every bucket's program before materializing any verdict — the
    verdict map over MIXED pair counts (two buckets in one call) must
    be identical to per-group pairing_product_is_one."""
    g1, g2 = _staged_groups()
    groups = [
        ("p3_ok", _group_pairs(g1, g2, 0)),
        ("p3_bad", _mismatched_pairs(g1, g2)),
        ("p2_ok", _group_pairs(g1, g2, 1)[:2] + []),
    ]
    # a 2-pair group is NOT a verifying triple: compute its true verdict
    # from the single-group device oracle, like each 3-pair group's
    import jax.numpy as jnp
    expect = {}
    for key, pairs in groups:
        ok = np.asarray(BJ.pairing_product_is_one(
            jnp.asarray(np.stack([a for a, _ in pairs])),
            jnp.asarray(np.stack([b for _, b in pairs]))))
        expect[key] = bool(ok[0])
    launches0 = _counter("bls.grouped.launches")
    got = BJ._grouped_pairing_dispatch(groups)
    assert got == expect
    assert _counter("bls.grouped.launches") == launches0 + 2  # two buckets


# ---------------------------------------------------------------------------
# Cross-slot accumulation + deadline flush
# ---------------------------------------------------------------------------

def test_cross_slot_accumulation_single_launch():
    """Groups accumulate across slot ticks until the target occupancy;
    one launch carries work from BOTH slots."""
    g1, g2 = _staged_groups()
    v = _verifier(target_groups=4)
    launches0 = v.pipeline.launches
    for k in range(2):                       # slot N: 2 aggregates
        v.submit_staged(("s1", k), _group_pairs(g1, g2, k % 2))
    v.pump()
    assert v.pipeline.launches == launches0 and v.queue.depth == 2
    for k in range(2):                       # slot N+1: 2 more
        v.submit_staged(("s2", k), _group_pairs(g1, g2, k % 2))
    v.pump()                                 # bucket hits target: launch
    assert v.pipeline.launches == launches0 + 1
    assert v.pipeline.occupancies[-1] == 4 and v.queue.depth == 0
    got = v.flush()
    assert len(got) == 4 and all(got.values())
    assert telemetry.gauge("firehose.queue_depth", always=True).value == 0


def test_deadline_flush_partial_batch_salvaged():
    """A partial batch (occupancy < target) flushes AT the deadline; a
    budget blown by the materialization is salvaged — verdicts land,
    the miss is counted on /healthz — instead of stalling fork choice."""
    g1, g2 = _staged_groups()
    # fake clock: every read advances 100 ms, so any armed window "takes"
    # >= 100 ms against a 5 ms budget — a guaranteed, sleep-free miss
    v = _verifier(target_groups=8, clock=_fake_clock(0.1),
                  sleep=lambda s: None)
    v.submit_staged("late", _group_pairs(g1, g2, 0))
    misses0 = _counter("firehose.deadline_miss")
    salvaged0 = _counter("resilience.deadline_salvaged")
    partial0 = _counter("firehose.partial_flushes")
    got = v.flush(deadline_ms=5.0)
    assert got == {"late": True}             # late but landed
    assert v.verdict("late") is True
    assert _counter("firehose.deadline_miss") == misses0 + 1
    assert _counter("resilience.deadline_salvaged") == salvaged0 + 1
    assert _counter("firehose.partial_flushes") == partial0 + 1
    assert v.pipeline.occupancies[-1] == 1   # the partial batch


def test_flush_within_budget_counts_no_miss():
    g1, g2 = _staged_groups()
    v = _verifier(target_groups=2)
    v.submit_staged("a", _group_pairs(g1, g2, 0))
    v.submit_staged("b", _group_pairs(g1, g2, 1))
    misses0 = _counter("firehose.deadline_miss")
    got = v.flush(deadline_ms=120_000.0)     # generous real-clock budget
    assert got == {"a": True, "b": True}
    assert _counter("firehose.deadline_miss") == misses0


# ---------------------------------------------------------------------------
# Steady state: zero retrace / zero re-layout
# ---------------------------------------------------------------------------

def test_steady_state_zero_watchdog_events():
    """>= 4 steady-state batch launches at one shape: the pairing
    programs, the ring scatter, and the chained ring placement must
    record ZERO watchdog events (first compiles are warm-up, never
    events)."""
    g1, g2 = _staged_groups()
    v = _verifier(target_groups=2)
    retrace0 = _counter("watchdog.retrace_events")
    relayout0 = _counter("watchdog.relayout_events")
    for wave in range(5):
        for k in range(2):
            v.submit_staged((wave, k), _group_pairs(g1, g2, k))
        v.pump()
        if wave % 2:
            got = v.flush()
            assert all(got.values())
    v.flush()
    assert v.pipeline.launches >= 5
    assert _counter("watchdog.retrace_events") == retrace0
    assert _counter("watchdog.relayout_events") == relayout0


def test_ring_wrap_drains_early():
    """A flush window larger than the verdict ring drains early
    (counted) and still returns every verdict."""
    g1, g2 = _staged_groups()
    v = _verifier(target_groups=2, ring_capacity=4)
    wraps0 = _counter("firehose.ring_wraps")
    for k in range(6):                       # 3 batches of G=2 vs R=4
        v.submit_staged(("w", k), _group_pairs(g1, g2, k % 2))
    v.pump()
    got = v.flush()
    assert len(got) == 6 and all(got.values())
    assert _counter("firehose.ring_wraps") == wraps0 + 1


# ---------------------------------------------------------------------------
# Gossip ingest -> block path consumes queued verdicts
# ---------------------------------------------------------------------------

def test_gossip_preverification_feeds_block_path():
    """Attestations arriving over gossip are pre-verified by the
    firehose; when a block including them executes, the batched
    attestation family serves every signature verdict from the queue's
    cache (zero new pairing launches) and the post-state is
    bit-identical to the synchronous path."""
    from copy import deepcopy

    from consensus_specs_tpu.models import phase0
    from consensus_specs_tpu.networking.gossip import (GossipRouter,
                                                       TOPIC_BEACON_ATTESTATION)
    from consensus_specs_tpu.testing.states import (
        build_config3_state_and_block)
    from consensus_specs_tpu.utils.ssz.impl import hash_tree_root, serialize

    spec = phase0.get_spec("minimal")
    old_active = bls.bls_active
    bls.bls_active = True
    bls.set_backend("python")   # stage signatures with the bignum oracle
    try:
        state, block = build_config3_state_and_block(
            spec, 8 * spec.SLOTS_PER_EPOCH, 3, n_keys=8)
        bls.set_backend("jax")

        # synchronous reference run
        ref = deepcopy(state)
        spec.state_transition(ref, deepcopy(block))

        # gossip ingest on the pre-state via the router decode path
        v = _verifier(target_groups=2)
        router = GossipRouter()
        router.subscribe("verifier", TOPIC_BEACON_ATTESTATION,
                         lambda _topic, payload:
                         v.ingest_gossip(spec, state, payload))
        for att in block.body.attestations:
            reached = router.publish(
                "peer", TOPIC_BEACON_ATTESTATION,
                serialize(att, spec.Attestation))
            assert reached == 1
            # a duplicate gossip publish dedups in the router seen-cache
            assert router.publish("peer2", TOPIC_BEACON_ATTESTATION,
                                  serialize(att, spec.Attestation)) == 0
        v.pump()
        v.flush()

        # block path: every sink verdict must come from the cache
        hits0 = _counter("firehose.cache_hits")
        launches0 = v.pipeline.launches
        spec._streaming_verifier = v
        try:
            spec.state_transition(state, block)
        finally:
            spec._streaming_verifier = None
        assert hash_tree_root(state) == hash_tree_root(ref)
        assert _counter("firehose.cache_hits") - hits0 == 3
        assert v.pipeline.launches == launches0   # no new device batches
    finally:
        bls.bls_active = old_active
        bls.set_backend("python")
        spec._streaming_verifier = None


def test_gossip_undecodable_payload_is_counted_not_fatal():
    from consensus_specs_tpu.models import phase0
    spec = phase0.get_spec("minimal")
    from consensus_specs_tpu.testing import factories as f
    state = f.seed_genesis_state(spec, spec.SLOTS_PER_EPOCH * 8)
    v = _verifier(target_groups=2)
    bad0 = _counter("firehose.undecodable")
    assert v.ingest_gossip(spec, state, b"\x00\x01garbage") is None
    assert _counter("firehose.undecodable") == bad0 + 1
    assert v.queue.depth == 0 and not v._pending


# ---------------------------------------------------------------------------
# Health surface
# ---------------------------------------------------------------------------

def test_firehose_health_reflects_backlog_and_flush_age():
    g1, g2 = _staged_groups()
    v = streaming.StreamingVerifier(target_groups=8, register=True)
    try:
        assert streaming.active() is v
        v.submit_staged("h0", _group_pairs(g1, g2, 0))
        health = streaming.firehose_health()
        assert health["backlog"] == 1
        assert health["last_flush_age_s"] is None   # never flushed
        assert health["counters"]["ingested"] >= 1
        v.flush()
        health = streaming.firehose_health()
        assert health["backlog"] == 0
        assert health["last_flush_age_s"] is not None
        assert health["last_flush_age_s"] < 60.0
    finally:
        streaming.activate(None)


def test_verdict_retention_is_bounded():
    """A sustained firehose must not grow host state per aggregate:
    resolved digests (and their dedup entries) evict FIFO past the
    retention bound; an evicted digest can re-verify."""
    v = _verifier(target_groups=2, retain=4096)
    assert v.retain == 4096
    for i in range(v.retain + 10):
        v._seen.add(i)
        v._remember(i, True)
    assert len(v._verdicts) == v.retain
    assert len(v._seen) == v.retain
    assert v.verdict(0) is None          # evicted (oldest)
    assert v.verdict(v.retain + 9) is True


def test_ring_capacity_misconfig_raises_clearly():
    """ring_capacity smaller than the padded target batch must fail at
    construction, not as a trace-time XLA shape error."""
    with pytest.raises(AssertionError):
        _verifier(target_groups=128, ring_capacity=64)


def test_health_without_active_verifier_is_zeroed():
    health = streaming.firehose_health()
    assert health["backlog"] == 0
    assert health["in_flight_batches"] == 0
    assert health["target_groups"] is None
    assert set(health["counters"]) >= {"ingested", "deadline_miss",
                                       "cache_hits"}
