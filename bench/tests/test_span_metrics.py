"""The readers of the program's spans and counters, on a synthetic
window: each per-pipeline metric takes its base from the super-batches
of the completed jobs, and a program that records no spans or compile
counter reads nothing."""

from types import SimpleNamespace

import pytest

from conftest import ROOT

import bench.run as R

S = 1_000_000_000      # ns per second


def _run(n_jobs, extra, h2d=0, d2h=0):
    spans = [(1, None, "stratum.dispatch", 0, 10 * S,
              {"jobs": list(range(n_jobs)), "n_jobs": n_jobs,
               "retry": False})]
    spans += [(i + 2, 1, name, 0, int(sec * S), attrs)
              for i, (name, sec, attrs) in enumerate(extra)]
    return SimpleNamespace(spans=spans, h2d_bytes=h2d, d2h_bytes=d2h)


def _ctx(runs, before=None, after=None):
    # every job of a run completes, each with the run's report
    completed = [SimpleNamespace(report=SimpleNamespace(run=run))
                 for run in runs
                 for _ in range(next(s[5]["n_jobs"] for s in run.spans
                                     if s[2] == "stratum.dispatch"))]
    return SimpleNamespace(completed=completed, before=before or {},
                           after=after or {})


def _window():
    a = _run(3, [("stratum.gbt.bin", 1.5, {"n": 10, "F": 2}),
                 ("stratum.gbt.bin", 0.5, {"n": 10, "F": 2}),
                 ("stratum.op", 0.25, {"op": "read", "tier": "python"}),
                 ("stratum.op", 4.0, {"op": "gbt_fit", "tier": "jax"}),
                 ("stratum.compile_batch", 0.3, {"ops_submitted": 9})],
             h2d=2_000_000, d2h=1_000_000)
    b = _run(1, [("stratum.op", 0.75, {"op": "metric", "tier": "python"}),
                 ("stratum.compile_batch", 0.1, {"ops_submitted": 3})],
             h2d=1_000_000)
    return _ctx([a, b], before={"compile": {"n": 4, "s": 2.0, "pid": 1}},
                after={"compile": {"n": 6, "s": 3.25, "pid": 1}})


@pytest.mark.parametrize("name,expected", [
    ("gbt_bin_s_per_pipeline.sweep", 2.0 / 4),
    ("python_op_s_per_pipeline.sweep", 1.0 / 4),
    ("optimize_s_per_pipeline.sweep", 0.4 / 4),
    ("tier_mb_per_pipeline.sweep", 4.0 / 4),
    ("compile_s.sweep", 1.25),
])
def test_reader_on_a_synthetic_window(name, expected):
    assert R.reader(ROOT, name)(_window()) == pytest.approx(expected)


@pytest.mark.parametrize("name", [
    "gbt_bin_s_per_pipeline.sweep", "python_op_s_per_pipeline.sweep",
    "optimize_s_per_pipeline.sweep", "tier_mb_per_pipeline.sweep",
    "compile_s.sweep"])
def test_reader_reads_nothing_from_a_program_without_spans(name):
    # a run report with no spans and a snapshot with no compile counter,
    # as a program from before them reports
    old = SimpleNamespace(report=SimpleNamespace(run=SimpleNamespace(
        per_backend={"python": 1})))
    ctx = SimpleNamespace(completed=[old, old], before={}, after={})
    assert R.reader(ROOT, name)(ctx) is None


def test_a_super_batch_counts_once_whatever_its_jobs():
    # the base is each super-batch's own job count: the same run seen
    # through one job or through all three reads the same
    run = _run(3, [("stratum.compile_batch", 0.6, {})])
    full = _ctx([run])
    one = SimpleNamespace(completed=full.completed[:1], before={}, after={})
    read = R.reader(ROOT, "optimize_s_per_pipeline.sweep")
    assert read(full) == pytest.approx(0.2)
    assert read(one) == pytest.approx(0.2)


def test_the_new_metrics_are_declared_for_the_cell():
    bench, cell, _config, _traffic = R.load_cell(ROOT, "sweep.quarter250k")
    names = {m["name"] for m in R.metrics_for(bench, cell["name"],
                                              "per_layer")}
    assert {"compile_s.sweep", "gbt_bin_s_per_pipeline.sweep",
            "python_op_s_per_pipeline.sweep",
            "optimize_s_per_pipeline.sweep",
            "tier_mb_per_pipeline.sweep"} <= names
