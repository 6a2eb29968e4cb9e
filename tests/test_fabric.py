"""Sharded execution fabric: consistent-hash ring properties, the
serializable envelope codec, routing/locality, failover (zero job loss),
rebalancing, telemetry aggregation, and the async AIDE driver on shards."""

import pickle

import numpy as np
import pytest

from repro.core import GENERIC, LazyOp, PipelineBatch
from repro.core.dag import toposort
from repro.core.runtime import ExecutionError
from repro.service import merge_tenant_snapshots
from repro.service.fabric import (CodecError, ConsistentHashRing,
                                  JobEnvelope, NoShardsError, ResultEnvelope,
                                  ShardedStratum, decode_job, decode_result,
                                  encode_job, encode_result, routing_key_for)
import repro.tabular as T


def _pipeline(n_rows=2000, cols=(10, 11, 12), kind="mae", data_seed=0):
    x = T.read("uk_housing", n_rows, seed=data_seed)
    xs = T.scale(T.impute(T.project(x, list(cols))))
    y = T.project(x, [0])
    return T.metric(T.project(xs, [0]), y, kind=kind)


def _batch(name="p", **kw):
    return PipelineBatch([_pipeline(**kw)], [name])


def _boom(*_a, **_k):
    raise ValueError("poisoned op")


def _fabric(n_shards=2, **kw):
    kw.setdefault("memory_budget_bytes", 1 << 30)
    kw.setdefault("n_executors", 1)
    kw.setdefault("coalesce_window_s", 0.0)
    return ShardedStratum(n_shards=n_shards, **kw)


# ---------------------------------------------------------------------------
# consistent-hash ring
# ---------------------------------------------------------------------------

KEYS = [f"key-{i}" for i in range(2000)]


def test_ring_routing_is_deterministic_across_instances():
    a = ConsistentHashRing(["s0", "s1", "s2"], vnodes=32)
    b = ConsistentHashRing(["s0", "s1", "s2"], vnodes=32)
    assert [a.route(k) for k in KEYS] == [b.route(k) for k in KEYS]
    # every shard owns a nontrivial share (vnodes spread the arcs)
    counts = {n: 0 for n in a.nodes()}
    for k in KEYS:
        counts[a.route(k)] += 1
    assert min(counts.values()) > len(KEYS) * 0.1


def test_ring_add_moves_at_most_bounded_fraction_and_only_to_new_node():
    ring = ConsistentHashRing([f"s{i}" for i in range(4)], vnodes=64)
    before = {k: ring.route(k) for k in KEYS}
    ring.add("s4")
    moved = {k for k in KEYS if ring.route(k) != before[k]}
    # expected K/N = K/5; generous 2x slack for hash variance
    assert len(moved) <= 2 * len(KEYS) / 5
    assert moved, "a new shard must take over some keys"
    # consistent hashing's defining property: keys only move TO the joiner
    assert all(ring.route(k) == "s4" for k in moved)


def test_ring_remove_remaps_only_the_removed_nodes_keys():
    ring = ConsistentHashRing([f"s{i}" for i in range(4)], vnodes=64)
    before = {k: ring.route(k) for k in KEYS}
    ring.remove("s2")
    for k in KEYS:
        if before[k] == "s2":
            assert ring.route(k) != "s2"
        else:
            assert ring.route(k) == before[k]


def test_ring_successors_distinct_and_respect_exclusion():
    ring = ConsistentHashRing([f"s{i}" for i in range(5)], vnodes=16)
    succ = list(ring.successors("some-key"))
    assert sorted(succ) == sorted(ring.nodes())     # all, each once
    assert succ[0] == ring.route("some-key")
    excl = list(ring.successors("some-key", exclude={"s1", "s3"}))
    assert "s1" not in excl and "s3" not in excl and len(excl) == 3


def test_ring_membership_errors():
    ring = ConsistentHashRing(["s0"])
    with pytest.raises(ValueError):
        ring.add("s0")
    with pytest.raises(KeyError):
        ring.remove("nope")
    ring.remove("s0")
    with pytest.raises(LookupError):
        ring.route("k")


# ---------------------------------------------------------------------------
# envelope codec — the serializable submission boundary
# ---------------------------------------------------------------------------

def test_job_envelope_round_trip_preserves_signatures_with_fresh_uids():
    batch = _batch()
    env = JobEnvelope(envelope_id="e-1", tenant="t", priority=0,
                      routing_key=routing_key_for(batch), batch=batch)
    out = decode_job(encode_job(env))
    assert (out.envelope_id, out.tenant, out.priority, out.routing_key) \
        == ("e-1", "t", 0, env.routing_key)
    assert out.batch.names == batch.names
    # content signatures survive bit-exactly (CSE/cache keys intact) ...
    assert [r.signature for r in out.batch.sinks] \
        == [r.signature for r in batch.sinks]
    # ... but every op is re-identified: no uid crosses the boundary, so
    # envelopes from different origin processes can't collide on a shard
    old_uids = {op.uid for op in toposort(batch.sinks)}
    new_uids = {op.uid for op in toposort(out.batch.sinks)}
    assert old_uids.isdisjoint(new_uids)


def test_codec_rejects_corruption_and_wrong_kind():
    data = encode_job(JobEnvelope("e", "t", 1, "rk", _batch()))
    flipped = data[:30] + bytes([data[30] ^ 0xFF]) + data[31:]
    with pytest.raises(CodecError):
        decode_job(flipped)
    with pytest.raises(CodecError):
        decode_result(data)          # job frame fed to the result decoder
    with pytest.raises(CodecError):
        decode_job(b"not a frame at all")


def test_result_envelope_round_trip_hosts_arrays_and_carries_errors():
    import jax.numpy as jnp
    ok = ResultEnvelope(envelope_id="e", tenant="t", shard_id="s", ok=True,
                        results={"p": jnp.arange(4.0)})
    out = decode_result(encode_result(ok))
    assert isinstance(out.results["p"], np.ndarray)
    np.testing.assert_allclose(out.results["p"], [0.0, 1.0, 2.0, 3.0])

    op = LazyOp("boom", GENERIC, spec={"fn": _boom})
    err = ExecutionError(op, ValueError("poisoned op"))
    bad = decode_result(encode_result(ResultEnvelope(
        envelope_id="e", tenant="t", shard_id="s", ok=False, error=err)))
    assert isinstance(bad.error, ExecutionError)
    assert isinstance(bad.error.cause, ValueError)
    assert bad.error.op.op_name == "boom"


def test_execution_error_pickles_directly():
    op = LazyOp("boom", GENERIC, spec={"fn": _boom})
    e = pickle.loads(pickle.dumps(ExecutionError(op, ValueError("x"))))
    assert isinstance(e.cause, ValueError) and e.op.op_name == "boom"


def test_routing_key_groups_by_source_not_by_sink():
    a = _batch(kind="mae")
    b = _batch(kind="rmse")            # same dataset, different pipeline
    c = _batch(data_seed=3)            # different dataset read
    assert routing_key_for(a) == routing_key_for(b)
    assert routing_key_for(a) != routing_key_for(c)
    # "batch" policy keys on the full sink set instead
    assert routing_key_for(a, "batch") != routing_key_for(b, "batch")
    with pytest.raises(ValueError):
        routing_key_for(a, "bogus")


# ---------------------------------------------------------------------------
# fabric end-to-end
# ---------------------------------------------------------------------------

def test_fabric_executes_and_all_traffic_crosses_the_codec():
    fab = _fabric(n_shards=3)
    try:
        from repro.core import Stratum
        ref, _ = Stratum(memory_budget_bytes=1 << 30).run_batch(_batch())
        ref_val = float(np.asarray(ref["p"]))

        futs = [fab.session(f"t{i}").submit(_batch()) for i in range(3)]
        for f in futs:
            results, report = f.result(timeout=120)
            assert float(np.asarray(results["p"])) \
                == pytest.approx(ref_val, rel=1e-6)
            assert report.shard_id.startswith("shard-")
            # the wire gave us host arrays, not device buffers
            assert isinstance(results["p"], np.ndarray)
        # every submission and every reply crossed the byte codec
        transports = fab.router._transports.values()
        assert sum(t.jobs_received for t in transports) == 3
        assert sum(t.results_sent for t in transports) == 3
        assert all(t.bytes_in > 0 or t.jobs_received == 0
                   for t in transports)
    finally:
        fab.stop()


def test_identical_sources_land_on_one_shard_and_share_work():
    fab = _fabric(n_shards=4, coalesce_window_s=0.05, autostart=False)
    try:
        f1 = fab.session("a").submit(_batch())
        f2 = fab.session("b").submit(_batch(kind="rmse"))
        fab.start()
        f1.result(timeout=120), f2.result(timeout=120)
        g = fab.telemetry.global_snapshot()
        routed = [s["envelopes_routed"] for s in g["per_shard"].values()]
        assert sorted(routed) == [0, 0, 0, 2]      # co-located by source
        assert g["ops_deduped_cross_agent"] > 0    # per-shard CSE survived
        # locality is measured over repeat keys only: the second
        # occurrence landed where the first did, and a stable ring is 1.0
        assert g["signature_locality_hit_rate"] == pytest.approx(1.0)
    finally:
        fab.stop()


def test_affinity_overrides_content_routing():
    fab = _fabric(n_shards=4, autostart=False)
    try:
        # different datasets would normally spread; affinity pins them
        f1 = fab.session("a").submit(_batch(data_seed=1), affinity="pin-me")
        f2 = fab.session("a").submit(_batch(data_seed=2), affinity="pin-me")
        fab.start()
        f1.result(timeout=120), f2.result(timeout=120)
        routed = [s["envelopes_routed"] for s in
                  fab.telemetry.per_shard().values()]
        assert sorted(routed) == [0, 0, 0, 2]
    finally:
        fab.stop()


def test_admission_backpressure_raises_synchronously_from_submit():
    from repro.service import AdmissionError
    fab = _fabric(n_shards=1, autostart=False, max_queued_total=2)
    try:
        ses = fab.session("t")
        ses.submit(_batch())
        ses.submit(_batch(kind="rmse"))
        with pytest.raises(AdmissionError):    # Session.submit contract
            ses.submit(_batch(data_seed=9))
        assert fab.router.pending_count() == 2   # no leaked pending entry
    finally:
        fab.start()
        fab.stop()


def test_unencodable_batch_fails_future_without_leaking_pending():
    fab = _fabric(n_shards=1)
    try:
        bad = LazyOp("boom", GENERIC,
                     spec={"fn": lambda: None},     # lambdas don't pickle
                     inputs=(_pipeline(n_rows=500),)).out()
        fut = fab.session("t").submit(PipelineBatch([bad], ["bad"]))
        with pytest.raises(Exception):
            fut.result(timeout=10)
        assert fab.router.pending_count() == 0
    finally:
        fab.stop()


def test_execution_error_crosses_the_boundary_with_cause():
    fab = _fabric(n_shards=2)
    try:
        sink = LazyOp("boom", GENERIC, spec={"fn": _boom},
                      inputs=(_pipeline(n_rows=500),)).out()
        fut = fab.session("t").submit(PipelineBatch([sink], ["bad"]))
        with pytest.raises(ExecutionError) as ei:
            fut.result(timeout=120)
        assert isinstance(ei.value.cause, ValueError)
    finally:
        fab.stop()


# ---------------------------------------------------------------------------
# failover + rebalancing
# ---------------------------------------------------------------------------

def _key_for_shard(fab, shard_id: str, tag="k") -> str:
    """An affinity key that routes to ``shard_id`` on the current ring."""
    for i in range(10_000):
        key = f"{tag}-{i}"
        if fab.router._ring.route(key) == shard_id:
            return key
    raise AssertionError("no key found")  # pragma: no cover


def test_failover_requeues_all_inflight_zero_loss():
    fab = _fabric(n_shards=2, autostart=False)
    try:
        shard_ids = fab.shard_ids()
        victim, survivor = shard_ids[0], shard_ids[1]
        # queue jobs on BOTH shards (none running yet: autostart=False)
        n_victim, n_survivor = 3, 2
        futs = []
        for i in range(n_victim):
            futs.append(fab.session("t").submit(
                _batch(name="p", cols=(10 + i, 11, 12)),
                affinity=_key_for_shard(fab, victim, f"v{i}")))
        for i in range(n_survivor):
            futs.append(fab.session("t").submit(
                _batch(name="p", cols=(10, 11 + i, 13)),
                affinity=_key_for_shard(fab, survivor, f"s{i}")))
        assert fab.router.pending_count(victim) == n_victim
        requeued = fab.fail_shard(victim)
        assert requeued == n_victim
        fab.start()
        # ZERO jobs lost: every future resolves with a real result
        for f in futs:
            results, report = f.result(timeout=180)
            assert "p" in results
            assert report.shard_id == survivor
        g = fab.telemetry.global_snapshot()
        assert g["failover_requeues"] == n_victim
        assert g["shards_failed"] == 1
        assert fab.shard_ids() == [survivor]
        # the dead shard's history is retired, not erased: fabric-wide
        # counters stay monotone and include its routed envelopes
        assert g["per_shard"][victim]["retired"] is True
        assert g["envelopes_routed"] == n_victim + n_survivor + n_victim
        assert g["n_shards"] == 1
    finally:
        fab.stop()


def test_dead_transport_detected_on_send_and_fails_over():
    fab = _fabric(n_shards=2)
    try:
        victim = fab.shard_ids()[0]
        key = _key_for_shard(fab, victim)
        fab.router._transports[victim].kill()   # crash without notice
        fut = fab.session("t").submit(_batch(), affinity=key)
        results, report = fut.result(timeout=120)
        assert "p" in results and report.shard_id != victim
        assert fab.router.shards_failed == 1
    finally:
        fab.stop()


def test_router_fail_shard_alone_silences_transport():
    # the crash model lives in the ROUTER: failing a shard through the
    # public router API (not the fabric wrapper) must silence its
    # transport so a still-running host can't answer for requeued work
    fab = _fabric(n_shards=2, autostart=False)
    try:
        victim = fab.shard_ids()[0]
        fut = fab.session("t").submit(
            _batch(), affinity=_key_for_shard(fab, victim))
        transport = fab.router._transports[victim]
        assert fab.router.fail_shard(victim) == 1
        assert transport._dead            # silenced by the router itself
        fab.start()
        results, report = fut.result(timeout=120)
        assert "p" in results and report.shard_id != victim
    finally:
        fab.stop()


def test_corrupted_reply_frame_is_counted_not_raised():
    fab = _fabric(n_shards=1)
    try:
        fab.router._on_result(b"garbage frame")      # must not raise
        assert fab.router.reply_codec_errors == 1
        g = fab.telemetry.global_snapshot()
        assert g["reply_codec_errors"] == 1
        # the fabric still serves normally afterwards
        r, _ = fab.session("t").submit(_batch()).result(timeout=120)
        assert "p" in r
    finally:
        fab.stop()


def test_all_shards_dead_raises_no_shards():
    fab = _fabric(n_shards=1, autostart=False)
    try:
        victim = fab.shard_ids()[0]
        fut = fab.session("t").submit(_batch())
        fab.fail_shard(victim)
        with pytest.raises(NoShardsError):
            fut.result(timeout=10)
    finally:
        fab.stop()


def test_drain_shard_reroutes_new_work_and_keeps_results():
    fab = _fabric(n_shards=2)
    try:
        first = fab.session("t").submit(_batch())
        first.result(timeout=120)
        victim = fab.shard_ids()[0]
        fab.drain_shard(victim, timeout=30)
        assert victim not in fab.shard_ids()
        # fabric still serves everything after the drain
        r, rep = fab.session("t").submit(_batch(kind="rmse")).result(
            timeout=120)
        assert "p" in r and rep.shard_id == fab.shard_ids()[0]
        g = fab.telemetry.global_snapshot()
        assert g["shards_drained"] == 1
        # drained shard's tenant history survives in the merged view
        assert fab.telemetry.snapshot()["t"]["jobs_completed"] == 2
    finally:
        fab.stop()


def test_add_shard_extends_ring_and_serves():
    fab = _fabric(n_shards=1)
    try:
        new = fab.add_shard()
        assert len(fab.shard_ids()) == 2
        key = _key_for_shard(fab, new)
        r, rep = fab.session("t").submit(_batch(), affinity=key).result(
            timeout=120)
        assert "p" in r and rep.shard_id == new
    finally:
        fab.stop()


# ---------------------------------------------------------------------------
# telemetry aggregation + drivers
# ---------------------------------------------------------------------------

def test_merge_tenant_snapshots_sums_and_maxes():
    a = {"t": {"jobs_completed": 1, "queue_wait_s": 0.5,
               "queue_wait_max_s": 0.5, "per_backend": {"jax": 2}}}
    b = {"t": {"jobs_completed": 2, "queue_wait_s": 0.25,
               "queue_wait_max_s": 0.75, "per_backend": {"jax": 1,
                                                         "python": 4}},
         "u": {"jobs_completed": 1, "queue_wait_s": 0.0,
               "queue_wait_max_s": 0.0, "per_backend": {}}}
    m = merge_tenant_snapshots([a, b])
    assert m["t"]["jobs_completed"] == 3
    assert m["t"]["queue_wait_s"] == pytest.approx(0.75)
    assert m["t"]["queue_wait_max_s"] == pytest.approx(0.75)
    assert m["t"]["per_backend"] == {"jax": 3, "python": 4}
    assert m["u"]["jobs_completed"] == 1


def test_session_telemetry_merges_across_shards():
    fab = _fabric(n_shards=3)
    try:
        ses = fab.session("t")
        ses.submit(_batch()).result(timeout=120)
        ses.submit(_batch(data_seed=5)).result(timeout=120)
        snap = ses.telemetry
        assert snap["jobs_completed"] == 2
        assert snap["jobs_submitted"] == 2
    finally:
        fab.stop()


def test_async_aide_search_on_fabric_with_shard_affinity():
    from repro.agents import AIDEAgent, AsyncAIDESearch
    fab = _fabric(n_shards=3, coalesce_window_s=0.02)
    try:
        agent = AIDEAgent(n_rows=2000, cv_k=2, seed=0)
        search = AsyncAIDESearch(fab.session("aide"), agent,
                                 batch_size=2, max_inflight=2,
                                 shard_affinity=True)
        best = search.run(n_rounds=2)
        assert best is not None and best.score is not None
        assert len(agent.nodes) == 4
        # affinity pinned the whole search to exactly one shard
        routed = [s["envelopes_routed"] for s in
                  fab.telemetry.per_shard().values()]
        assert sorted(routed) == [0, 0, 2]
        assert fab.telemetry.snapshot()["aide"]["jobs_completed"] == 2
    finally:
        fab.stop()


# ---------------------------------------------------------------------------
# shard-aware cancellation (CancelEnvelope through the codec)
# ---------------------------------------------------------------------------

def test_cancel_envelope_codec_round_trip():
    from repro.service.fabric import (CancelEnvelope, decode_cancel,
                                      encode_cancel)
    env = CancelEnvelope(envelope_id="c-7", tenant="t", attempt=2)
    out = decode_cancel(encode_cancel(env))
    assert (out.envelope_id, out.tenant, out.attempt) == ("c-7", "t", 2)
    with pytest.raises(CodecError):           # wrong kind
        decode_cancel(encode_job(JobEnvelope(
            envelope_id="x", tenant="t", priority=1, routing_key="k",
            batch=_batch())))


def test_fabric_cancel_removes_queued_work_on_owning_shard():
    from concurrent.futures import CancelledError
    fab = _fabric(n_shards=2, autostart=False)
    try:
        ses = fab.session("t")
        futs = [ses.submit(_batch(name=f"p{i}", data_seed=i),
                           affinity="pin") for i in range(3)]
        shard_depths = {sid: row["queue_depth"] for sid, row in
                        fab.telemetry.per_shard().items()}
        owner = max(shard_depths, key=shard_depths.get)
        assert shard_depths[owner] == 3       # all pinned to one shard
        assert futs[1].cancel() is True       # still queued: removed
        assert futs[1].cancelled()
        with pytest.raises(CancelledError):
            futs[1].result(timeout=5)
        # the job is gone from the OWNING SHARD's queue, not just local
        assert fab.telemetry.per_shard()[owner]["queue_depth"] == 2
        assert fab.router.pending_count() == 2   # no leaked pending entry
        g = fab.telemetry.global_snapshot()
        assert g["cancels_sent"] == 1 and g["cancels_confirmed"] == 1
        assert fab.telemetry.snapshot()["t"]["jobs_cancelled"] == 1
        fab.start()
        for f in (futs[0], futs[2]):          # survivors run to completion
            res, _ = f.result(timeout=120)
            assert all(np.isfinite(float(np.asarray(v)))
                       for v in res.values())
    finally:
        fab.start()
        fab.stop()


def test_fabric_cancel_after_completion_returns_false():
    fab = _fabric(n_shards=1)
    try:
        fut = fab.session("t").submit(_batch())
        fut.result(timeout=120)
        assert fut.cancel() is False          # nothing queued to remove
        assert not fut.cancelled()
        assert fab.router.cancel("no-such-envelope") is False
    finally:
        fab.stop()


# ---------------------------------------------------------------------------
# SubmitOptions on the wire: deadline/tags through the envelope codec
# ---------------------------------------------------------------------------

def test_job_envelope_carries_deadline_and_tags_through_the_codec():
    batch = _batch()
    env = JobEnvelope(envelope_id="e-1", tenant="t", priority=1,
                      routing_key=routing_key_for(batch), batch=batch,
                      deadline_s=1.5, tags=("probe", "r3"))
    out = decode_job(encode_job(env))
    assert out.deadline_s == 1.5
    assert out.tags == ("probe", "r3")
    # deadline_t is client-local state and must NOT cross the wire
    assert out.deadline_t is None
    # deadline-free envelopes stay deadline-free
    bare = decode_job(encode_job(JobEnvelope(
        envelope_id="e-2", tenant="t", priority=1,
        routing_key=env.routing_key, batch=batch)))
    assert bare.deadline_s is None and bare.tags == ()


def test_deadline_envelope_corruption_still_raises_codec_error():
    env = JobEnvelope("e", "t", 1, "rk", _batch(), deadline_s=2.0,
                      tags=("x",))
    data = encode_job(env)
    flipped = data[:40] + bytes([data[40] ^ 0xFF]) + data[41:]
    with pytest.raises(CodecError):
        decode_job(flipped)


def test_stale_attempt_reply_dropped_for_deadline_job():
    """A failover bumps the attempt; a stale reply from the dead shard
    must not resolve a deadline-carrying future."""
    from repro.service.fabric.envelope import FabricJobReport
    fab = _fabric(n_shards=1, autostart=False)
    try:
        fut = fab.session("t").submit(_batch(), deadline_s=300.0,
                                      tags=("slo",))
        (eid, pending), = fab.router._pending.items()
        assert pending.envelope.deadline_s is not None
        pending.envelope.attempt += 1            # as a failover would
        stale = encode_result(ResultEnvelope(
            envelope_id=eid, tenant="t", shard_id="s", ok=True,
            results={"p": np.zeros(1)},
            report=FabricJobReport(tenant="t", envelope_id=eid,
                                   shard_id="s"),
            attempt=0))                          # pre-failover attempt
        fab.router._on_result(stale)
        assert not fut.done()                    # stale reply dropped
        assert fab.router.pending_count() == 1   # still owed an answer
    finally:
        fab.stop()


def test_fabric_future_resolves_deadline_exceeded_like_an_error():
    """An expired deadline sheds ON THE SHARD; the DeadlineExceeded
    travels back through the result codec and resolves the future."""
    from repro.service import DeadlineExceeded
    fab = _fabric(n_shards=2)
    try:
        ses = fab.session("t")
        with pytest.raises(DeadlineExceeded):
            ses.submit(_batch(), deadline_s=1e-9).result(timeout=60)
        # ... exactly like a normal error: done, not cancelled, and the
        # exception is also readable without raising
        fut = ses.submit(_batch(), deadline_s=1e-9)
        assert isinstance(fut.exception(timeout=60), DeadlineExceeded)
        assert fut.done() and not fut.cancelled()
        # attainment aggregates fabric-wide from the shard ledgers
        d = fab.telemetry.global_snapshot()["deadline"]
        assert d["jobs"] == 2 and d["shed"] == 2 and d["met"] == 0
    finally:
        fab.stop()


def test_remaining_deadline_shrinks_at_reencode_on_failover():
    """Failover re-encodes the envelope; the deadline budget that crossed
    the wire must be the REMAINING budget, not the original SLO."""
    import time as _time
    fab = _fabric(n_shards=2, autostart=False)
    try:
        victim = fab.shard_ids()[0]
        fut = fab.session("t").submit(
            _batch(), deadline_s=300.0,
            affinity=_key_for_shard(fab, victim))
        (eid, pending), = fab.router._pending.items()
        sent_first = pending.envelope.deadline_s
        _time.sleep(0.05)
        assert fab.fail_shard(victim) == 1       # re-routes + re-encodes
        sent_second = fab.router._pending[eid].envelope.deadline_s
        assert sent_second < sent_first <= 300.0
        fab.start()
        results, report = fut.result(timeout=180)
        assert "p" in results and report.deadline_met is True
    finally:
        fab.stop()


def test_fabric_shard_i_dispatches_to_device_i():
    """On a host with several devices (four virtual CPU devices here, four
    chips on a v5e host) shard i's compiled segments run on device i."""
    import json
    import os
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
    import json, jax
    import repro.tabular as T
    from repro.core import PipelineBatch
    from repro.core.backends import JaxSegmentBackend
    from repro.service.fabric import ConsistentHashRing, StratumFabric
    # record, for every compiled segment, the runtime's device and the
    # devices its outputs landed on
    placed = []
    commit = JaxSegmentBackend._commit
    def recording_commit(self, rt, compute, outs, selection, report):
        placed.append([str(rt.device), sorted({
            str(d) for out in outs for v in out if hasattr(v, "devices")
            for d in v.devices()})])
        return commit(self, rt, compute, outs, selection, report)
    JaxSegmentBackend._commit = recording_commit
    fab = StratumFabric(n_shards=4, n_executors=1)
    try:
        ring = ConsistentHashRing(fab.shard_ids(), vnodes=64)
        keys, k = {}, 0
        while len(keys) < 4:
            keys.setdefault(ring.route(f"k{k}"), f"k{k}")
            k += 1
        out = {}
        for sid, key in sorted(keys.items()):
            x = T.read("uk_housing", 500, seed=0)
            sink = T.scale(T.impute(T.project(x, [10, 11, 12])))
            res, rep = fab.session("t").submit(
                PipelineBatch([sink], ["p"]), affinity=key).result(120)
            out[sid] = [rep.shard_id, rep.per_backend.get("jax-seg", 0),
                        str(fab._shards_snapshot()[sid].device)]
        print(json.dumps({"devices": [str(d) for d in jax.devices()],
                          "out": out, "placed": placed}))
    finally:
        fab.stop()
    """)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(got["devices"]) == 4
    for sid, (ran_on, n_seg, device) in got["out"].items():
        assert ran_on == sid and n_seg > 0
        assert device == got["devices"][int(sid.split("-")[1])]
    # every compiled segment's outputs sit on its shard's device, and the
    # four shards used all four devices
    assert all(outs == [device] for device, outs in got["placed"])
    assert {device for device, _ in got["placed"]} == set(got["devices"])
