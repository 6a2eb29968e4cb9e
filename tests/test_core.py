"""Core stratum invariants: DAG hashing, CSE soundness, rewrites, scheduler,
cache — unit + hypothesis property tests."""

import numpy as np
from _hypothesis_compat import given, settings, st

from repro.core import CONST, LazyOp, Stratum, TRANSFORM, count_ops, toposort
from repro.core.cache import IntermediateCache, mark_cache_candidates
from repro.core.metadata import collect_metadata
from repro.core.rewrites import cse, optimize_logical, project_pushdown
from repro.core.runtime import execute_reference
from repro.core.scheduler import SchedulerConfig, plan as make_plan
from repro.core.selection import SelectionConfig, select
import repro.tabular as T  # registers impls/meta/lowerings


# ---------------------------------------------------------------------------
# signatures / CSE
# ---------------------------------------------------------------------------

def _const(v):
    return LazyOp("const", CONST, spec={"value": np.asarray(v)}).out()


def test_signature_deterministic_across_instances():
    a1 = _const([1.0, 2.0])
    a2 = _const([1.0, 2.0])
    assert a1.op.signature == a2.op.signature
    assert _const([1.0, 3.0]).op.signature != a1.op.signature


def test_signature_includes_seed_and_spec():
    x = _const([1.0])
    f1 = LazyOp("string_encode", TRANSFORM, spec={"dim": 4}, inputs=(x,),
                seed=1)
    f2 = LazyOp("string_encode", TRANSFORM, spec={"dim": 4}, inputs=(x,),
                seed=2)
    f3 = LazyOp("string_encode", TRANSFORM, spec={"dim": 8}, inputs=(x,),
                seed=1)
    assert len({f1.signature, f2.signature, f3.signature}) == 3


def test_unseeded_nondeterministic_never_merged():
    x = _const([1.0])
    n1 = LazyOp("udf", "generic", inputs=(x,), deterministic=False)
    n2 = LazyOp("udf", "generic", inputs=(x,), deterministic=False)
    assert n1.signature != n2.signature
    merged = cse([n1.out(), n2.out()])
    assert merged[0].op is not merged[1].op


def test_cse_merges_identical_subgraphs():
    def pipeline():
        x = T.read("uk_housing", 500, seed=0)
        return T.scale(T.project(x, [10, 11]))
    a, b = pipeline(), pipeline()
    assert a.op is not b.op
    out = cse([a, b])
    assert out[0].op is out[1].op
    assert count_ops(out) < count_ops([a, b])


@given(st.integers(0, 5), st.integers(0, 5))
@settings(max_examples=20, deadline=None)
def test_cse_preserves_results(seed_a, seed_b):
    """Fusing two pipelines never changes their outputs."""
    x = T.read("uk_housing", 200, seed=0)
    pa = T.metric(T.project(x, [0]),
                  T.project(x, [10 + seed_a % 3]), kind="mae")
    pb = T.metric(T.project(x, [0]),
                  T.project(x, [10 + seed_b % 3]), kind="mae")

    def run(sinks):
        vals = {}
        for op in toposort(sinks):
            ins = [vals[r.signature] for r in op.inputs]
            outs = execute_reference(op, ins)
            for i, v in enumerate(outs):
                vals[f"{op.signature}:{i}"] = v
        return [vals[r.signature] for r in sinks]

    plain = run([pa, pb])
    fused = run(cse([pa, pb]))
    np.testing.assert_allclose(plain, fused)


# ---------------------------------------------------------------------------
# rewrites
# ---------------------------------------------------------------------------

def test_projection_pushdown_commutes():
    x = T.read("uk_housing", 300, seed=1)
    clipped = LazyOp("clip_outliers", TRANSFORM, spec={"q": 0.05},
                     inputs=(x,)).out()
    proj = T.project(clipped, [2, 3])
    pushed = project_pushdown([proj])

    def run(sink):
        vals = {}
        for op in toposort([sink]):
            ins = [vals[r.signature] for r in op.inputs]
            for i, v in enumerate(execute_reference(op, ins)):
                vals[f"{op.signature}:{i}"] = v
        return vals[sink.signature]

    np.testing.assert_allclose(run(proj), run(pushed[0]))
    # and the projection actually moved below the transform
    assert pushed[0].op.op_name == "clip_outliers"


def test_constant_folding():
    a = _const(np.ones((4, 4)))
    s = LazyOp("metric", "eval", spec={"kind": "mae"},
               inputs=(a, a)).out()
    collect_metadata([s])
    out, stats = optimize_logical(
        [s], lambda op, ins: execute_reference(op, ins))
    assert stats.constants_folded >= 1
    assert out[0].op.op_class == CONST
    assert float(np.asarray(out[0].op.spec["value"])) == 0.0


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def _random_dag(rng, n_ops: int):
    nodes = [_const(rng.normal(size=(8,)))]
    for i in range(n_ops):
        k = 1 + int(rng.integers(0, min(2, len(nodes))))
        ins = tuple(nodes[int(rng.integers(0, len(nodes)))] for _ in range(k))
        nodes.append(LazyOp("mean_scalars", "eval", inputs=ins).out())
    return nodes[-1]


@given(st.integers(0, 10_000), st.integers(2, 30))
@settings(max_examples=25, deadline=None)
def test_scheduler_schedules_every_op_once(seed, n_ops):
    rng = np.random.default_rng(seed)
    sink = _random_dag(rng, n_ops)
    collect_metadata([sink])
    sel = select([sink], SelectionConfig())
    p = make_plan([sink], sel, SchedulerConfig())
    planned = [op.uid for w in p.waves for op in w.ops]
    assert sorted(planned) == sorted(o.uid for o in toposort([sink]))
    # topological: every input appears in an earlier wave
    seen = set()
    for w in p.waves:
        for op in w.ops:
            for r in op.inputs:
                assert r.op.uid in seen
        seen.update(op.uid for op in w.ops)


def test_scheduler_respects_memory_budget_estimates():
    x = T.read("uk_housing", 5000, seed=0)
    sinks = [T.scale(T.project(x, [10 + i])) for i in range(4)]
    collect_metadata(sinks)
    sel = select(sinks, SelectionConfig())
    tight = make_plan(sinks, sel, SchedulerConfig(
        memory_budget_bytes=1 << 20))
    loose = make_plan(sinks, sel, SchedulerConfig(
        memory_budget_bytes=1 << 34))
    assert len(tight.waves) >= len(loose.waves)


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def test_cache_lru_and_disk_spill(tmp_path):
    c = IntermediateCache(budget_bytes=3000, spill_dir=str(tmp_path))
    big = np.zeros(256)  # 2 KB
    c.put("a", (big,))
    c.put("b", (big,))   # evicts "a" from RAM → disk
    assert c.get("a") is not None          # reload from disk
    assert c.stats.disk_hits >= 1

    # persistence across "restart"
    c2 = IntermediateCache(budget_bytes=3000, spill_dir=str(tmp_path))
    assert c2.get("b") is not None


def test_cache_candidates_exclude_cheap_ops():
    x = T.read("uk_housing", 50_000, seed=0)
    scaled = T.scale(T.project(x, [10, 11, 12]))
    tiny = T.mean_of([T.metric(T.project(x, [0]), T.project(x, [0]))])
    collect_metadata([scaled, tiny])
    cands = mark_cache_candidates([scaled, tiny], min_cost_s=1e-4)
    assert x.op.signature in cands or scaled.op.signature in cands
    assert tiny.op.signature not in cands


def test_runtime_cache_hits_are_exact(tmp_path):
    x = T.read("uk_housing", 2000, seed=3)
    y = T.project(x, [0])
    Xv = T.scale(T.impute(T.project(x, [10, 11, 12, 13])))
    sink = T.cv_score(Xv, y, {"name": "ridge_fit", "alpha": 1.0}, k=2,
                      seed=1)
    s = Stratum(memory_budget_bytes=1 << 30, spill_dir=str(tmp_path))
    r1, rep1 = s.run(sink)
    r2, rep2 = s.run(sink)
    assert rep2.run.ops_from_cache > 0
    np.testing.assert_allclose(np.asarray(r1, dtype=np.float64),
                               np.asarray(r2, dtype=np.float64))


# ---------------------------------------------------------------------------
# persistent compilation cache placement
# ---------------------------------------------------------------------------

def test_compilation_cache_env_setting_is_left_alone(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it and no code path
    of the session or the service places the cache itself."""
    import jax
    from repro.core.api import resolve_compilation_cache
    from repro.service import StratumService
    calls = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert resolve_compilation_cache() is None
    Stratum()
    StratumService(autostart=False)
    assert calls == []


def test_compilation_cache_defaults_to_the_checkout(monkeypatch):
    import jax
    from pathlib import Path
    from repro.core.api import JAX_CACHE_DIR, resolve_compilation_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = Path(__file__).resolve().parents[1]
    assert JAX_CACHE_DIR == str(checkout / ".jax_cache")
    Stratum()
    assert jax.config.jax_compilation_cache_dir == JAX_CACHE_DIR
    assert resolve_compilation_cache() == JAX_CACHE_DIR
