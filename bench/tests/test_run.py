"""A whole run without the chip: the harness's look for a chip is
skipped, the rest runs on the CPU at a small size.  A sound run comes out
correct; with the timed path broken underneath it comes out not correct;
the result line keeps its schema; and ``run.py`` itself refuses the CPU.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import CELL, ROOT, small

import bench.run as R

PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


# the cells' limits hold at their sizes; at 3,000 rows a float32 feature
# that rounds across a bin edge flips a split whose change runs through
# every later tree (2.6e-2 seen), so here the linear families catch the
# faults
SMALL_LIMITS = {"ridge": 1e-3, "enet": 1e-3, "gbt": 1e-1}


def _run(trace=False, seconds=3.0):
    # one agent for each (preprocessing, model) group, so the check has
    # a GBT on the table vectorizer's features to compare
    bench, c, config, traffic = small(rows=3000, agents=8)
    config = dict(config, check_limits=SMALL_LIMITS)
    return R.run_cell(ROOT, bench, c, config, traffic, 2 ** 31 + 99,
                      seconds, trace, DEVICE, PEAK, time.perf_counter())


def _impl(op_name):
    from repro.core.selection import impls_for
    (impl,) = [i for i in impls_for(op_name) if i.backend == "python"]
    return impl


@pytest.fixture
def broken(request):
    """Swap a program operator's python implementation for a faulty one."""
    import repro.tabular  # noqa: F401  (registers the operators)
    op_name, faulty = request.param
    impl = _impl(op_name)
    original = impl.fn
    impl.fn = faulty(original)
    yield
    impl.fn = original


def _altered(original):
    """The job's answer altered where it is produced."""
    def fn(op, ins):
        (score,) = original(op, ins)
        return (score * (1 + 1e-2),)
    return fn


def _half_rows(original):
    """Half of the held-out rows left out, the mean taken over the rest."""
    def fn(op, ins):
        y, yhat = (np.asarray(v).ravel() for v in ins)
        half = len(y) // 2
        return original(op, (y[:half], yhat[:half]))
    return fn


def test_sound_run_is_correct_and_keeps_the_schema():
    out = _run(trace=True)
    assert out["correct"] is True, out["checks"]
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    for name, m in out["metrics"].items():
        assert isinstance(m["value"], (int, float)) and m["unit"], name
    assert set(out["metrics"]) == {
        "ops_deduped_share.sweep", "python_op_share.sweep",
        "plan_cache_misses.sweep", "device_idle_share.sweep"}
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes", "busy_s",
            "window_s"} <= set(dev)
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert set(out["checks"]) == {"ridge", "enet", "gbt", "failed_jobs"}
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]
    json.dumps(out)


@pytest.mark.parametrize("broken", [("mean_scalars", _altered),
                                    ("metric", _half_rows)],
                         indirect=True, ids=["answer_altered", "half_rows"])
def test_broken_timed_path_is_not_correct(broken):
    out = _run()
    assert out["correct"] is False, out["checks"]


def test_run_py_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", CELL, "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
