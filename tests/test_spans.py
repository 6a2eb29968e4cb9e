"""Spans (``repro.core.spans``): one tree per super-batch, mirrored into a
profiler capture on its clock, compile and tier-crossing counters, the
fabric merge and the JSONL log."""

import glob
import json
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PipelineBatch
from repro.core import spans
from repro.core.runtime import crossing_bytes
from repro.service import StratumService
from repro.service.observability import replay
from repro.service.observability.events import record_span, span_record
from repro.service.telemetry import merge_compile_snapshots
from repro.tabular import gbt
import repro.tabular as T

#: spans that hold others and are never mirrored into a capture
PARENTS = ("stratum.dispatch", "stratum.execute", "stratum.segment")


def _pipeline(kind="mae", n_rows=3000):
    x = T.read("uk_housing", n_rows, seed=0)
    xs = T.scale(T.impute(T.project(x, [10, 11, 12])))
    y = T.project(x, [0])
    return T.metric(T.project(xs, [0]), y, kind=kind)


def _run_three(**kw):
    svc = StratumService(memory_budget_bytes=1 << 30,
                         coalesce_window_s=0.2, **kw)
    try:
        futs = [svc.session(f"t{i}").submit(
                    PipelineBatch([_pipeline(kind)], [f"p{i}"]))
                for i, kind in enumerate(("mae", "rmse", "mae"))]
        reports = [f.result(timeout=120)[1] for f in futs]
    finally:
        svc.stop()
    return svc, reports


def _by_id(run_spans):
    return {s[0]: s for s in run_spans}


# ---------------------------------------------------------------------------
# one tree per super-batch
# ---------------------------------------------------------------------------

def test_super_batch_spans_form_one_tree_under_dispatch():
    _svc, reports = _run_three()
    runs = {id(r.run): r.run for r in reports}
    for run in runs.values():
        ids = _by_id(run.spans)
        (root,) = [s for s in run.spans if s[1] is None]
        assert root[2] == "stratum.dispatch"
        # every span's parent is a span of the same run: one tree
        assert all(s[1] in ids for s in run.spans if s is not root)
        job_ids = [r.job_id for r in reports if r.run is run]
        assert sorted(root[5]["jobs"]) == sorted(job_ids)
        assert root[5]["n_jobs"] == len(job_ids)
        for name in ("stratum.admit", "stratum.queue"):
            assert sorted(s[5]["job"] for s in run.spans
                          if s[2] == name) == sorted(job_ids)
        names = {s[2] for s in run.spans}
        assert {"stratum.coalesce", "stratum.compile_batch",
                "stratum.execute", "stratum.segment",
                "stratum.commit"} <= names
        assert [s for s in run.spans if s[2] == "stratum.compile_batch"][0][
            5]["ops_submitted"] > 0
        # every job of the super-batch shares the one list
        assert all(r.run.spans is run.spans for r in reports if r.run is run)


def test_jax_segment_spans_name_their_program():
    _svc, reports = _run_three()
    run_spans = reports[0].run.spans
    seg_runs = [s for s in run_spans if s[2] == "stratum.segment.run"]
    assert seg_runs
    ids = _by_id(run_spans)
    for s in seg_runs:
        program = s[5]["program"]
        assert program.startswith("seg_") and len(program) == 20
        parent = ids[s[1]]
        assert parent[2] == "stratum.segment"
        assert parent[5] == {"kind": "jax", "program": program}


def test_self_time_is_duration_less_union_of_children():
    run_spans = [(1, None, "p", 0, 100, {}),
                 (2, 1, "a", 10, 30, {}),
                 (3, 1, "b", 20, 40, {}),          # overlaps a
                 (4, 1, "c", 90, 120, {}),         # runs past the parent
                 (5, 2, "d", 12, 14, {})]          # a grandchild
    selfs = spans.self_times(run_spans)
    assert selfs[1] == 100 - (30 + 10)
    assert selfs[2] == 20 - 2
    assert selfs == {1: 60, 2: 18, 3: 20, 4: 30, 5: 2}
    by_name = spans.self_seconds_by_name(run_spans)
    assert by_name["p"] == (pytest.approx(60e-9), 1)


def test_self_times_of_a_real_run_match_the_definition():
    _svc, reports = _run_three()
    run_spans = reports[0].run.spans
    selfs = spans.self_times(run_spans)
    for s in run_spans:
        kids = [c for c in run_spans if c[1] == s[0]]
        covered = set()
        for c in kids:
            lo, hi = max(c[3], s[3]), min(c[4], s[4])
            if hi > lo:
                covered.add((lo, hi))
        union = spans._union_ns(covered)
        assert selfs[s[0]] == (s[4] - s[3]) - union
        assert selfs[s[0]] >= 0


def test_pool_threads_take_their_parent_explicitly():
    seen = []

    def work():
        with spans.span("leaf"):
            seen.append(spans.current().id)

    def handed(parent):
        with spans.attach(parent):
            work()

    sink: list = []
    with spans.collect(sink), spans.scope("parent") as parent:
        with ThreadPoolExecutor(1) as pool:
            pool.submit(handed, spans.current()).result()
            pool.submit(work).result()       # no parent handed over
    leaves = [s for s in sink if s[2] == "leaf"]
    assert len(leaves) == 1 and leaves[0][1] == parent.id


# ---------------------------------------------------------------------------
# the profiler mirror
# ---------------------------------------------------------------------------

def _xplane_events(out_dir):
    (path,) = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    start, events = None, []
    for plane in data.planes:
        for name, value in plane.stats:
            if name == "profile_start_time":
                start = int(value)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events.extend((e.name, int(e.start_ns)) for e in line.events)
    assert start is not None
    return start, events


def test_leaf_spans_appear_in_the_profiler_capture_on_their_clock(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    rng = np.random.default_rng(0)
    X, y = rng.normal(size=(400, 5)), rng.normal(size=400)
    gbt_spans: list = []
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _svc, reports = _run_three()
        with spans.collect(gbt_spans):
            model = gbt.fit_jax(X, y, n_trees=3, depth=2)
            gbt.predict_jax(model, X)
    finally:
        jax.profiler.stop_trace()
    start, events = _xplane_events(str(tmp_path))
    starts: dict = {}
    for name, t in events:
        starts.setdefault(name, []).append(start + t)
    run_spans = list(reports[0].run.spans) + gbt_spans
    leaves = 0
    for s in run_spans:
        if s[2] in PARENTS + ("stratum.queue", "stratum.compile"):
            continue
        mirror = ("stratum.op." + s[5]["op"] if s[2] == "stratum.op"
                  else s[2])
        near = [t for t in starts.get(mirror, ()) if abs(t - s[3]) < 1e6]
        assert near, (mirror, s)
        leaves += 1
    assert leaves > 10
    assert {"stratum.gbt.bin", "stratum.gbt.put", "stratum.gbt.fit",
            "stratum.gbt.predict"} <= {s[2] for s in gbt_spans}
    # no span that holds others is mirrored, nor a recorded interval
    for name in PARENTS + ("stratum.queue", "stratum.compile"):
        assert name not in starts


def test_an_op_whose_impl_opens_spans_is_not_mirrored():
    from repro.core.selection import impls_for
    for op_name in ("gbt_fit", "gbt_predict"):
        jax_impls = [i for i in impls_for(op_name) if i.backend == "jax"]
        assert jax_impls and all(getattr(i.fn, "opens_spans", False)
                                 for i in jax_impls)
        assert not any(getattr(i.fn, "opens_spans", False)
                       for i in impls_for(op_name) if i.backend == "python")


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

def test_a_fresh_jit_is_counted_and_recorded_under_the_current_op():
    before = spans.compile_totals()
    sink: list = []

    def fresh(v):
        return jnp.sin(v) * 3.0 + 1.0

    with spans.collect(sink), spans.span("stratum.op", op="probe",
                                         tier="jax") as op:
        jax.jit(fresh)(jnp.ones(173)).block_until_ready()
    after = spans.compile_totals()
    assert after["n"] >= before["n"] + 1
    assert after["s"] > before["s"]
    compiles = [s for s in sink if s[2] == "stratum.compile"]
    assert compiles
    ids = _by_id(sink)
    backend = [s for s in compiles if s[5]["event"]
               == "backend_compile_duration"]
    assert any("fresh" in s[5]["fun_name"] for s in backend)
    for s in compiles:
        # directly under the op, or under the compile that encloses it
        top = s
        while top[1] != op.id:
            top = ids[top[1]]
            assert top[2] == "stratum.compile"
    # nested compile events count once: the counter's seconds equal the
    # compile spans' self seconds
    self_s = spans.self_seconds_by_name(sink)["stratum.compile"][0]
    assert after["s"] - before["s"] == pytest.approx(self_s, abs=1e-5)


def test_the_service_snapshot_carries_the_compile_counter():
    svc, _reports = _run_three()
    g = svc.telemetry.global_snapshot()
    assert g["compile"]["pid"] == os.getpid()
    assert g["compile"]["n"] >= 0 and g["compile"]["s"] >= 0.0


@pytest.mark.parametrize("tier,values,expected", [
    ("jax", [np.zeros(10, np.float64), jnp.zeros(4)], (80, 0)),
    ("pallas", [np.zeros((3, 2), np.int32)], (24, 0)),
    ("python", [jnp.zeros(5, jnp.float32), np.zeros(9)], (0, 20)),
    ("jax-vmap", [np.zeros(10)], (0, 0)),
    ("ref", [jnp.zeros(5)], (0, 0)),
])
def test_tier_crossing_rule_counts_each_direction(tier, values, expected):
    assert crossing_bytes(tier, values) == expected


def test_run_report_counts_the_bytes_its_ops_crossed():
    _svc, reports = _run_three()
    run = reports[0].run
    # the read is a numpy table handed to the jax segment, and the python
    # tier's metric reads device arrays
    assert run.h2d_bytes > 0
    assert isinstance(run.d2h_bytes, int) and run.d2h_bytes >= 0


def test_fabric_merge_counts_each_process_once():
    rows = [{"n": 3, "s": 1.5, "pid": 10},
            {"n": 4, "s": 2.0, "pid": 10},   # same process, later reading
            {"n": 7, "s": 0.5, "pid": 11}]
    assert merge_compile_snapshots(rows) == {"n": 11, "s": 2.5}


def test_fabric_global_snapshot_sums_the_compile_counter():
    from repro.service.fabric.telemetry import FabricTelemetry

    class _Router:
        envelopes_routed: dict = {}
        failover_requeues = shards_failed = shards_added = 0
        shards_drained = reply_codec_errors = 0
        cancels_sent = cancels_confirmed = 0

        def locality_hit_rate(self):
            return 1.0

        def pending_count(self, _sid):
            return 0

    class _Tele:
        def __init__(self, compile_block):
            self.block = compile_block

        def global_snapshot(self):
            return {"super_batches": 0, "jobs_coalesced": 0,
                    "ops_deduped_cross_agent": 0, "preemptions": 0,
                    "compile": dict(self.block)}

        def snapshot(self):
            return {}

    class _Shard:
        def __init__(self, block):
            self.telemetry = _Tele(block)

        def queue_depth(self):
            return 0

        def inflight(self):
            return 0

    shards = {"a": _Shard({"n": 2, "s": 1.0, "pid": 1}),
              "b": _Shard({"n": 5, "s": 4.0, "pid": 2}),
              "c": _Shard({"n": 5, "s": 4.0, "pid": 2})}
    tele = FabricTelemetry(_Router(), lambda: dict(shards))
    g = tele.global_snapshot()
    assert g["compile"] == {"n": 7, "s": 5.0}
    assert g["per_shard"]["a"]["compile"]["n"] == 2


# ---------------------------------------------------------------------------
# the event log
# ---------------------------------------------------------------------------

def test_span_record_round_trips():
    s = (7, 3, "stratum.op", 10, 25, {"op": "scale", "tier": "jax"})
    rec = json.loads(json.dumps(span_record(["j1", "j2"], s)))
    assert rec["jobs"] == ["j1", "j2"]
    assert record_span(rec) == s


def test_spans_round_trip_through_the_jsonl_log_and_replay(tmp_path):
    _svc, reports = _run_three(trace_dir=str(tmp_path))
    records = replay.load_records(str(tmp_path))
    logged = replay.spans_of(records)
    (file_spans,) = logged.values()
    for run in {id(r.run): r.run for r in reports}.values():
        assert all(s in file_spans for s in run.spans)
    # hop lines still reassemble into timelines, span lines aside
    assert set(replay.reassemble(replay.load_events(str(tmp_path)))) == {
        f"j{r.job_id}" for r in reports}
    key = f"j{reports[0].job_id}"
    one = replay.span_self_times(records, job=key)
    assert one["stratum.dispatch"][1] >= 1
    whole = replay.span_self_times(records)
    assert whole["stratum.admit"][1] == 3
    text = replay.format_span_times(whole)
    assert "stratum.compile_batch" in text


def test_replay_cli_prints_self_time_per_span(tmp_path, capsys):
    _svc, reports = _run_three(trace_dir=str(tmp_path))
    assert replay.main([str(tmp_path), "--spans"]) == 0
    out = capsys.readouterr().out
    assert "stratum.dispatch" in out and "self s" in out
    assert replay.main([str(tmp_path), "--spans", "--job",
                        f"j{reports[1].job_id}"]) == 0
    assert "stratum.commit" in capsys.readouterr().out
