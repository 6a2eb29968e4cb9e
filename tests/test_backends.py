"""Compiled plan-segment backends: structural signatures, the plan cache,
whole-segment jit execution, segment-boundary preemption salvage, and the
tenant-aware cache probe in vmap variant batching."""

import numpy as np
import pytest

import repro.tabular as T
from repro.core import (PipelineBatch, PlanCache, Stratum,
                        structural_signature)
from repro.core.cache import IntermediateCache
from repro.core.runtime import ExecutionPreempted, Runtime
from repro.core.scheduler import partition_segments


def _variant_sink(alpha, cols=(10, 11, 12, 13), n_rows=2000):
    """A jax-heavy pipeline; alpha is a tunable constant."""
    x = T.read("uk_housing", n_rows, seed=0)
    y = T.project(x, [0])
    Xv = T.scale(T.impute(T.project(x, list(cols))))
    w = T.ridge_fit(Xv, y, alpha=alpha)
    return T.metric(y, T.predict(w, Xv), kind="rmse")


def _compiled_sessions(**kw):
    on = Stratum(memory_budget_bytes=1 << 30, **kw)
    off = Stratum(memory_budget_bytes=1 << 30, compiled_segments=False,
                  **kw)
    return on, off


# ---------------------------------------------------------------------------
# structural signatures
# ---------------------------------------------------------------------------

def test_structural_signature_shared_across_constants():
    """Pipelines differing only in tunable constants share one structural
    signature; differing in topology (or non-tunable spec) don't."""
    a = _variant_sink(alpha=0.1)
    b = _variant_sink(alpha=42.0)
    c = _variant_sink(alpha=0.1, cols=(10, 11))          # topology change
    assert structural_signature([a]) == structural_signature([b])
    assert structural_signature([a]) != structural_signature([c])
    # content signatures still differ (they hash the constants)
    assert a.op.signature != b.op.signature


def test_structural_signature_nontunable_spec_is_structural():
    x = T.read("uk_housing", 1000, seed=0)
    y = T.project(x, [0])
    Xv = T.impute(T.project(x, [10, 11]))
    m1 = T.metric(y, T.project(Xv, [0]), kind="rmse")
    m2 = T.metric(y, T.project(Xv, [0]), kind="mae")     # kind: not tunable
    assert structural_signature([m1]) != structural_signature([m2])


def test_structural_signature_seed_value_excluded():
    """Seed values are payload (runtime-side), presence is structural."""
    w1 = T.ridge_fit(T.project(T.read("uk_housing", 1000, seed=0), [1, 2]),
                     T.project(T.read("uk_housing", 1000, seed=0), [0]),
                     alpha=1.0, seed=3)
    w2 = T.ridge_fit(T.project(T.read("uk_housing", 1000, seed=0), [1, 2]),
                     T.project(T.read("uk_housing", 1000, seed=0), [0]),
                     alpha=1.0, seed=9)
    assert w1.op.structural_signature == w2.op.structural_signature
    # seed *absence* is structural (it flips cacheability semantics)
    from repro.core import ESTIMATOR, LazyOp
    w3 = LazyOp("ridge_fit", ESTIMATOR, spec={"alpha": 1.0},
                inputs=tuple(w1.op.inputs), seed=None).out()
    assert w1.op.structural_signature != w3.op.structural_signature


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

def test_plan_cache_lru_eviction_and_telemetry():
    pc = PlanCache(capacity=2)
    pc.put("a", 1)
    pc.put("b", 2)
    assert pc.get("a") == 1                  # refresh a: b is now LRU
    pc.put("c", 3)                           # evicts b
    assert "b" not in pc and "a" in pc and "c" in pc
    assert pc.get("b") is None
    snap = pc.snapshot()
    assert snap["entries"] == 2
    assert snap["evictions"] == 1
    assert snap["compiles"] == 3
    assert snap["hits"] == 1 and snap["misses"] == 1
    assert snap["hit_rate"] == 0.5
    # re-put of a live key is not a new compile
    pc.put("a", 10)
    assert pc.snapshot()["compiles"] == 3
    with pytest.raises(ValueError):
        PlanCache(capacity=0)


def test_plan_cache_reused_across_hyperparameter_variants():
    """The same structure with different constants compiles once; later
    variants are pure plan-cache hits (no retraces)."""
    # no intermediate cache: isolate compiled-plan reuse from value reuse
    s = Stratum(memory_budget_bytes=1 << 30,
                enable=("logical", "lowering", "selection", "parallel"))
    scores = []
    for alpha in (0.1, 1.0, 10.0):
        r, rep = s.run(_variant_sink(alpha))
        scores.append(float(np.asarray(r)))
    snap = s.plan_cache.snapshot()
    assert snap["compiles"] > 0
    assert snap["hits"] >= snap["compiles"]  # variants 2..3 all hit
    first_compiles = snap["compiles"]
    s.run(_variant_sink(123.0))
    assert s.plan_cache.snapshot()["compiles"] == first_compiles
    assert len(set(scores)) == 3             # different alphas, real work


# ---------------------------------------------------------------------------
# compiled execution equivalence
# ---------------------------------------------------------------------------

def test_compiled_segments_match_per_op_dispatch():
    on, off = _compiled_sessions()
    sink = _variant_sink(alpha=2.0)
    r_on, rep_on = on.run(sink)
    r_off, rep_off = off.run(sink)
    assert rep_on.run.per_backend.get("jax-seg", 0) > 0
    assert "jax-seg" not in rep_off.run.per_backend
    np.testing.assert_allclose(float(np.asarray(r_on)),
                               float(np.asarray(r_off)), rtol=1e-6)


def test_plan_has_backend_homogeneous_segments():
    s = Stratum(memory_budget_bytes=1 << 30)
    sinks, sel, plan, *_ = s.compile_batch(
        PipelineBatch([_variant_sink(1.0)], ["p"]))
    kinds = [seg.kind for seg in plan.segments]
    assert "jax" in kinds and "python" in kinds
    # segments tile the wave list exactly, in order
    assert sum(len(seg.waves) for seg in plan.segments) == len(plan.waves)
    # maximality: no two adjacent segments share a kind
    assert all(a != b for a, b in zip(kinds, kinds[1:]))
    # every op of a jax segment selected a traceable jax impl
    for seg in plan.segments:
        if seg.kind != "jax":
            continue
        for wave in seg.waves:
            for op in wave.ops:
                impl = sel[op.signature]
                assert impl.backend == "jax" and impl.traceable


def test_one_op_jax_runs_demoted_to_python():
    """A single traceable op gains nothing from whole-segment tracing, so
    1-op jax runs stay per-op; ≥2 contiguous traceable ops segment."""
    from repro.core.scheduler import Wave
    from repro.core.selection import impls_for
    impl = next(i for i in impls_for("project") if i.backend == "jax")
    x = T.read("uk_housing", 500, seed=0)
    a, b = T.project(x, [1, 2]).op, T.project(x, [3, 4]).op
    sel = {a.signature: impl, b.signature: impl}
    assert [s.kind for s in
            partition_segments([Wave(ops=[a])], sel)] == ["python"]
    assert [s.kind for s in
            partition_segments([Wave(ops=[a]), Wave(ops=[b])], sel)] \
        == ["jax"]


def test_uncompilable_segment_falls_back_to_per_op(monkeypatch):
    """An impl wrongly declared traceable must not break execution: the
    segment falls back to per-op dispatch, the plan-cache entry is
    poisoned, and results match the per-op path."""
    from repro.core.selection import impls_for
    impl = next(i for i in impls_for("string_encode") if i.backend == "jax")
    monkeypatch.setattr(impl, "traceable", True)    # lie: it uses np.unique
    x = T.read("uk_housing", 1500, seed=0)
    y = T.project(x, [0])
    enc = T.string_encode(T.project(x, [5]), dim=4, seed=1)
    sink = T.metric(y, T.predict(
        T.ridge_fit(T.scale(T.impute(enc)), y, alpha=1.0),
        T.scale(T.impute(enc))), kind="rmse")
    on, off = _compiled_sessions()
    r_on, rep_on = on.run(sink)
    r_off, _ = off.run(sink)
    np.testing.assert_allclose(float(np.asarray(r_on)),
                               float(np.asarray(r_off)), rtol=1e-6)
    # second run goes straight to the poisoned-entry fallback (no retrace)
    r_on2, _ = on.run(sink)
    np.testing.assert_allclose(float(np.asarray(r_on2)),
                               float(np.asarray(r_off)), rtol=1e-6)


def test_compiled_segment_runtime_failure_is_counted(monkeypatch):
    """A compiled program that raises when called (a compile the device
    refuses, memory exhaustion) re-runs per-op with correct results, and
    the failure is counted in the run report and the plan cache."""
    from repro.core.backends import JaxSegmentBackend

    def refused(*args, **kwargs):
        raise RuntimeError("RESOURCE_EXHAUSTED: forced for the test")

    real = JaxSegmentBackend._build_probed

    def build_refused(self, *args, **kwargs):
        return refused if real(self, *args, **kwargs) is not None else None

    monkeypatch.setattr(JaxSegmentBackend, "_build_probed", build_refused)
    on, off = _compiled_sessions()
    sink = _variant_sink(alpha=2.0)
    r_on, rep_on = on.run(sink)
    r_off, _ = off.run(sink)
    np.testing.assert_allclose(float(np.asarray(r_on)),
                               float(np.asarray(r_off)), rtol=1e-6)
    assert rep_on.run.segment_runtime_failures >= 1
    assert "jax-seg" not in rep_on.run.per_backend
    snap = on.plan_cache.snapshot()
    assert snap["runtime_failures"] == rep_on.run.segment_runtime_failures
    assert snap["warm_failures"] == 0 and snap["uncompilable"] == 0


def test_compiled_segments_report_no_failures_when_healthy():
    on, _ = _compiled_sessions()
    _, rep = on.run(_variant_sink(alpha=2.0))
    assert rep.run.per_backend.get("jax-seg", 0) > 0
    assert rep.run.segment_runtime_failures == 0
    assert on.plan_cache.snapshot()["runtime_failures"] == 0


# ---------------------------------------------------------------------------
# segment-boundary preemption: salvage exactness
# ---------------------------------------------------------------------------

def test_segment_boundary_preemption_salvage_exact():
    """Preempting between segments and resuming with the salvage executes
    every op exactly once across the two dispatches."""
    s = Stratum(memory_budget_bytes=1 << 30,
                enable=("logical", "lowering", "selection", "parallel"))
    sink = _variant_sink(alpha=3.0)
    sinks, sel, plan, cands, *_ = s.compile_batch(
        PipelineBatch([sink], ["p"]))
    n_unique = len({op.signature for w in plan.waves for op in w.ops})

    fired = []

    def preempt_once():
        if not fired:
            fired.append(True)
            return True
        return False

    rt1 = Runtime(parallel=False, preempt_check=preempt_once,
                  backends=s._backends)
    with pytest.raises(ExecutionPreempted) as ei:
        rt1.execute(sinks, plan, sel)
    salvage = ei.value.salvage
    assert salvage                            # something completed pre-yield

    rt2 = Runtime(parallel=False, preloaded=salvage, backends=s._backends)
    results, rep2 = rt2.execute(sinks, plan, sel)
    # exactness: nothing executed twice, nothing skipped
    assert ei.value.waves_done <= len(plan.waves)
    assert rep2.ops_executed + rep2.ops_salvaged == n_unique
    assert rep2.ops_executed < n_unique       # the resume reused salvage
    # and the result is correct
    r_ref, _ = Stratum(memory_budget_bytes=1 << 30).run(sink)
    np.testing.assert_allclose(float(np.asarray(results[0])),
                               float(np.asarray(r_ref)), rtol=1e-6)


# ---------------------------------------------------------------------------
# vmap variant batching: tenant-aware cache probe (PR satellite)
# ---------------------------------------------------------------------------

def test_batch_variants_cache_hits_attribute_cross_tenant():
    """vmap-grouped ops served from the shared cache must go through the
    tenant-aware get: cross-tenant hits are attributed, and the fetched
    value is the one used (no membership-probe/eviction race window)."""
    x = T.read("uk_housing", 1500, seed=0)
    y = T.project(x, [0])
    Xv = T.scale(T.impute(T.project(x, [10, 11, 12])))
    fits = [T.ridge_fit(Xv, y, alpha=a) for a in (0.5, 5.0)]
    batch = PipelineBatch(fits, ["w0", "w1"])

    cache = IntermediateCache(budget_bytes=64 << 20)
    # per-op path so _batch_variants is exercised
    s = Stratum(memory_budget_bytes=1 << 30, cache=cache,
                compiled_segments=False)
    sinks, sel, plan, cands, *_ = s.compile_batch(batch)
    fit_sigs = [op.signature for w in plan.waves for op in w.ops
                if op.op_name == "ridge_fit"]
    assert len(fit_sigs) == 2

    # tenant A materializes everything (including the fits)
    rt_a = Runtime(cache=cache, cache_candidates=set(
        cands | set(fit_sigs)), parallel=False, compiled_segments=False,
        sig_tenant={sig: "A" for w in plan.waves for op in w.ops
                    for sig in [op.signature]})
    rt_a.execute(sinks, plan, sel)
    assert all(sig in cache for sig in fit_sigs)

    before = cache.stats.cross_tenant_hits
    # tenant B re-runs the same structure: the vmap group probe must be a
    # tenant-aware get and count both fits as cross-tenant hits
    rt_b = Runtime(cache=cache, cache_candidates=cands, parallel=False,
                   compiled_segments=False,
                   sig_tenant={sig: "B" for w in plan.waves for op in w.ops
                               for sig in [op.signature]})
    _, rep_b = rt_b.execute(sinks, plan, sel)
    assert all(rep_b.sig_source[sig] == "cache" for sig in fit_sigs)
    assert cache.stats.cross_tenant_hits >= before + 2
    assert rep_b.per_backend.get("jax-vmap", 0) == 0   # nothing re-fit


# ---------------------------------------------------------------------------
# service + fabric telemetry surface
# ---------------------------------------------------------------------------

def test_plan_cache_hit_rate_in_service_and_fabric_snapshots():
    from repro.service import StratumService
    from repro.service.fabric import ShardedStratum

    svc = StratumService(memory_budget_bytes=1 << 30, n_executors=1,
                         autostart=True)
    try:
        ses = svc.session("t")
        for alpha in (0.2, 2.0):
            ses.submit(PipelineBatch([_variant_sink(alpha)], ["p"])
                       ).result(timeout=120)
        g = svc.telemetry.global_snapshot()
        assert "plan_cache" in g
        assert g["plan_cache"]["hits"] + g["plan_cache"]["misses"] > 0
        assert "hit_rate" in g["plan_cache"]
    finally:
        svc.stop()

    fab = ShardedStratum(n_shards=2, memory_budget_bytes=1 << 30,
                         n_executors=1)
    try:
        ses = fab.session("t")
        for alpha in (0.2, 2.0):
            ses.submit(PipelineBatch([_variant_sink(alpha)], ["p"])
                       ).result(timeout=120)
        g = fab.telemetry.global_snapshot()
        assert "plan_cache_hit_rate" in g
        assert g["plan_cache_hits"] + g["plan_cache_misses"] > 0
        assert any("plan_cache" in row for row in g["per_shard"].values())
    finally:
        fab.stop()


# ---------------------------------------------------------------------------
# custom register_backend kinds get their own segments (ROADMAP item)
# ---------------------------------------------------------------------------

class _ToyBackend:
    """Minimal custom ExecutionBackend: executes per-op through the
    runtime helpers and stamps its own name into sig_source."""

    name = "toy"

    def __init__(self, plan_cache=None):
        self.plan_cache = plan_cache
        self.segments_executed = 0

    def execute_segment(self, rt, segment, selection, report):
        self.segments_executed += 1
        report.waves += len(segment.waves)
        for wave in segment.waves:
            for op in wave.ops:
                rt._run_op(op, selection, report)
            rt._free_wave(wave)


def test_partition_emits_segments_for_registered_custom_kind(monkeypatch):
    from repro.core.backends.base import _FACTORIES
    from repro.core.scheduler import Wave
    from repro.core.selection import PhysicalImpl
    monkeypatch.setitem(_FACTORIES, "toy", _ToyBackend)

    def _ident(op, inputs):
        return (inputs[0],)

    toy_impl = PhysicalImpl(op_name="noop", backend="toy", fn=_ident)
    x = T.read("uk_housing", 500, seed=0)
    a, b = T.project(x, [1, 2]).op, T.project(x, [3, 4]).op
    sel = {a.signature: toy_impl, b.signature: toy_impl}
    segs = partition_segments([Wave(ops=[a]), Wave(ops=[b])], sel)
    assert [s.kind for s in segs] == ["toy"]
    # unregistered custom backends still flatten onto the python path
    monkeypatch.delitem(_FACTORIES, "toy")
    segs = partition_segments([Wave(ops=[a]), Wave(ops=[b])], sel)
    assert [s.kind for s in segs] == ["python"]


def test_custom_backend_executes_its_segments_end_to_end(monkeypatch):
    """register_backend("toy") + a selection picking backend="toy" runs
    the toy backend for whole segments through the ordinary Runtime."""
    from repro.core import GENERIC, LazyOp
    from repro.core.backends.base import _FACTORIES, make_backends
    from repro.core.scheduler import SchedulerConfig, plan as make_plan
    from repro.core.selection import BACKENDS, BackendProfile, PhysicalImpl
    monkeypatch.setitem(_FACTORIES, "toy", _ToyBackend)
    monkeypatch.setitem(BACKENDS, "toy",
                        BackendProfile("toy", 1e9, 1e9, 1e-6, 1.0))

    def _add_one(op, inputs):
        return (np.asarray(inputs[0]) + 1.0,)

    a = LazyOp("toy_add", GENERIC, spec={"fn": lambda v: v + 1.0},
               inputs=(LazyOp("const0", GENERIC,
                              spec={"fn": lambda: np.zeros(4)}).out(),))
    sink = LazyOp("toy_add2", GENERIC, spec={"fn": lambda v: v + 1.0},
                  inputs=(a.out(),)).out()
    toy = PhysicalImpl(op_name="toy_add", backend="toy", fn=_add_one)
    sel = {a.signature: toy, sink.op.signature: toy}
    p = make_plan([sink], sel, SchedulerConfig())
    assert "toy" in {seg.kind for seg in p.segments}
    backends = make_backends(None, compiled=True)
    assert "toy" in backends            # registry factory picked up
    rt = Runtime(backends=backends)
    results, report = rt.execute([sink], p, sel)
    np.testing.assert_allclose(np.asarray(results[0]), np.full(4, 2.0))
    assert backends["toy"].segments_executed >= 1
    assert report.per_backend.get("toy", 0) == 2


# ---------------------------------------------------------------------------
# segment est_time budget bounds compiled-segment preempt latency
# ---------------------------------------------------------------------------

def test_segment_time_budget_splits_jax_segments():
    budget = 1e-9           # below any wave's est_time → one wave each
    s_nb = Stratum(memory_budget_bytes=1 << 30)
    s_b = Stratum(memory_budget_bytes=1 << 30,
                  segment_time_budget_s=budget)
    batch = PipelineBatch([_variant_sink(1.0)], ["p"])
    _, _, plan_nb, *_ = s_nb.compile_batch(batch)
    _, _, plan_b, *_ = s_b.compile_batch(batch)
    n_jax_nb = sum(1 for seg in plan_nb.segments if seg.kind == "jax")
    n_jax_b = sum(1 for seg in plan_b.segments if seg.kind == "jax")
    assert n_jax_b > n_jax_nb          # the cap split the big segment
    for seg in plan_b.segments:
        if seg.kind == "jax":
            assert len(seg.waves) == 1
    # splitting changes dispatch granularity, never results
    r_b, _ = s_b.run_batch(batch)
    r_nb, _ = s_nb.run_batch(batch)
    np.testing.assert_allclose(float(np.asarray(r_b["p"])),
                               float(np.asarray(r_nb["p"])), rtol=1e-6)


def test_segment_pieces_respect_the_budget():
    from repro.core.scheduler import partition_segments as ps
    s = Stratum(memory_budget_bytes=1 << 30)
    sinks, sel, plan, *_ = s.compile_batch(
        PipelineBatch([_variant_sink(1.0)], ["p"]))
    base = [seg for seg in ps(plan.waves, sel) if seg.kind == "jax"]
    assert base, "workload must produce a jax segment"
    times = [w.est_time for seg in base for w in seg.waves]
    budget = max(times) * 1.5          # forces a split mid-segment
    for seg in ps(plan.waves, sel, time_budget_s=budget):
        if seg.kind != "jax" or len(seg.waves) == 1:
            continue                   # single waves may overshoot alone
        assert sum(w.est_time for w in seg.waves) <= budget


def test_budget_bounds_preempt_latency_at_segment_boundaries():
    """With the cap, a preempt check fires BETWEEN pieces of what would
    have been one monolithic compiled segment: the yield arrives with
    partial salvage instead of after the whole segment."""
    s = Stratum(memory_budget_bytes=1 << 30, segment_time_budget_s=1e-9)
    batch = PipelineBatch([_variant_sink(1.0)], ["p"])
    sinks, sel, plan, candidates, *_ = s.compile_batch(batch)
    n_ops = sum(len(w.ops) for w in plan.waves)
    fired = {"n": 0}

    def preempt_after_first_progress():
        fired["n"] += 1
        return fired["n"] > 2          # let the first segments run

    rt = Runtime(preempt_check=preempt_after_first_progress,
                 backends=s._backends)
    with pytest.raises(ExecutionPreempted) as exc:
        rt.execute(sinks, plan, sel)
    salvage = exc.value.salvage
    assert 0 < len(salvage) < n_ops    # a bounded slice ran, not the lot
    # the salvage resumes losslessly (preemption semantics preserved)
    rt2 = Runtime(preloaded=salvage, backends=s._backends)
    results, report = rt2.execute(sinks, plan, sel)
    ref, _ = Stratum(memory_budget_bytes=1 << 30).run_batch(batch)
    np.testing.assert_allclose(float(np.asarray(results[0])),
                               float(np.asarray(ref["p"])), rtol=1e-6)
    # every salvaged value is honored; completed-then-freed ops the
    # reverse-topo sweep skips count as salvaged too, hence >=
    assert report.ops_salvaged >= len(salvage)
