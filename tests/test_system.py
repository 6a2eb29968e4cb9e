"""End-to-end behaviour tests for stratum on the paper's workload (§6):
fusion + CSE + lowering + selection + caching over the two-iteration
agentic search, plus agent–system co-design hooks."""

import numpy as np

from repro.agents import paper_workload_batches
from repro.agents.aide import AIDEAgent, diff_fraction, second_iteration_batch
from repro.core import ALL_FEATURES, PipelineBatch, Stratum, annotate
import repro.tabular as T

N_ROWS = 6000


def _iteration1(enable=ALL_FEATURES, spill_dir=None):
    s = Stratum(memory_budget_bytes=2 << 30, enable=enable,
                spill_dir=spill_dir)
    name, batch, ctx = next(iter(paper_workload_batches(
        n_rows=N_ROWS, cv_k=2)))
    results, report = s.run_batch(batch)
    return s, results, report, ctx


def test_paper_workload_iteration1_all_models_score():
    _, results, report, _ = _iteration1()
    assert len(results) == 8                       # 2 preproc × 4 models
    for name, score in results.items():
        assert np.isfinite(float(np.asarray(score))), name
        assert 0.05 < float(np.asarray(score)) < 5.0, (name, score)
    # fusion+CSE actually deduplicated shared stages
    assert report.rewrites.cse_merged > 20
    assert report.rewrites.reads_shared >= 7       # 8 pipelines share 1 read


def test_iteration2_reuses_iteration1_preprocessing(tmp_path):
    s, results, _, ctx = _iteration1(spill_dir=str(tmp_path))
    best = min(results, key=lambda k: float(np.asarray(results[k])))
    batch2, specs2 = second_iteration_batch(ctx["specs"][best])
    r2, rep2 = s.run_batch(batch2)
    assert rep2.run.ops_from_cache > 0             # cross-iteration reuse
    assert all(np.isfinite(float(np.asarray(v))) for v in r2.values())


def test_ablation_features_produce_identical_scores():
    """Every optimization level computes the same pipeline scores (within
    backend dtype differences) — the paper's semantic-equivalence claim."""
    base = None
    for enable in [(), ("logical",), ("logical", "lowering"),
                   ALL_FEATURES]:
        en = tuple(enable) + (("lowering",) if "lowering" not in enable
                              else ())
        s = Stratum(memory_budget_bytes=2 << 30, enable=en)
        x = T.read("uk_housing", 3000, seed=0)
        y = T.project(x, [0])
        Xv = T.scale(T.impute(T.project(x, [10, 11, 12, 13])))
        sink = T.cv_score(Xv, y, {"name": "ridge_fit", "alpha": 1.0},
                          k=2, seed=5)
        out, _ = s.run(sink)
        val = float(np.asarray(out))
        if base is None:
            base = val
        assert abs(val - base) / base < 5e-3, (en, val, base)


def test_grid_search_shares_folds_across_grid_points():
    x = T.read("uk_housing", 4000, seed=2)
    y = T.project(x, [0])
    Xv = T.scale(T.impute(T.project(x, [10, 11, 12, 13])))
    best_score, best_idx = T.grid_search(
        x=Xv, y=y, estimator_name="ridge_fit",
        grid=[{"alpha": a} for a in (0.1, 1.0, 10.0)], k=3, seed=4)
    s = Stratum(memory_budget_bytes=2 << 30)
    batch = PipelineBatch([best_score, best_idx], ["score", "idx"])
    results, report = s.run_batch(batch)
    # 3 grid points × 3 folds, but only 3 kfold_split ops must execute
    kfolds = [op for w in report.plan.waves for op in w.ops
              if op.op_name == "kfold_split"]
    assert len(kfolds) == 3
    assert 0 <= int(np.asarray(results["idx"])) < 3


def test_fidelity_annotation_selects_approx_impl():
    x = T.read("uk_housing", 2000, seed=0)
    Xv = T.scale(T.impute(T.project(x, [10, 11, 12, 13])))
    red = T.svd_reduce(Xv, k=2, seed=0)
    annotate(red, stage="explore")
    s = Stratum(memory_budget_bytes=2 << 30)
    sinks, sel, plan, _, _, _, _ = s.compile_batch(
        PipelineBatch([red], ["p"]))
    from repro.core.dag import toposort
    svd_ops = [op for op in toposort(sinks) if op.op_name == "svd_reduce"]
    assert svd_ops and sel[svd_ops[0].signature].fidelity == "approx"


def test_agent_diff_statistics_match_paper_characterization():
    """Fig 2a: ~50% of iterations change ≤16% of the pipeline code."""
    agent = AIDEAgent(seed=3)
    specs = agent.propose(4)
    agent.observe(specs, [1.0, 0.9, 1.1, 0.95])
    prev = agent.best().spec
    fracs = []
    for i in range(60):
        new = agent.propose(1)[0]
        fracs.append(diff_fraction(prev, new))
        agent.observe([new], [0.9 + 0.001 * i])
        prev = new
    frac_small = float(np.mean(np.asarray(fracs) <= 0.17))
    assert 0.35 <= frac_small <= 0.9


def test_agent_search_improves_over_drafts():
    agent = AIDEAgent(seed=1, n_rows=3000, cv_k=2)
    s = Stratum(memory_budget_bytes=2 << 30)
    for _ in range(3):
        specs = agent.propose(2)
        batch = PipelineBatch([sp.build() for sp in specs],
                              [f"s{i}" for i in range(len(specs))])
        results, _ = s.run_batch(batch)
        agent.observe(specs, [float(np.asarray(results[f"s{i}"]))
                              for i in range(len(specs))])
    assert agent.best() is not None
    assert np.isfinite(agent.best().score)


def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    """chip_smoke.py exits non-zero and prints no result line when JAX
    finds no TPU, and when it stands alone without the repository."""
    import os
    import shutil
    import subprocess
    import sys
    from pathlib import Path
    script = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(script, alone)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for path in (script, alone):
        proc = subprocess.run([sys.executable, str(path), "--rows", "2000"],
                              env=env, capture_output=True, text=True,
                              timeout=300, cwd=tmp_path)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert "no TPU" in proc.stderr
