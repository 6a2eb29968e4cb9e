"""A whole run without the chip: the harness's look for a chip is
skipped, the rest runs on the CPU at a small size.  A sound run comes out
correct; with the timed path broken underneath it comes out not correct;
the result line keeps its schema; ``run.py`` itself refuses the CPU, and
a configuration whose fabric does not fit the cell; and a fabric run on
four virtual CPU devices keeps every job on its shard.
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
    # long enough for a step of the loop to end inside the window on a
    # busy CPU: the per-pipeline readers read the window's completed jobs
    out = _run(trace=True, seconds=20.0)
    assert out["correct"] is True, out["checks"]
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    for name, m in out["metrics"].items():
        assert isinstance(m["value"], (int, float)) and m["unit"], name
    # every per-layer metric of the cell but the GBT roofline, which pairs
    # fit programs in the TPU's trace, and the CPU's trace has none
    bench = R.load_cell(ROOT, CELL)[0]
    assert set(out["metrics"]) == {
        m["name"] for m in R.metrics_for(bench, CELL, "per_layer")} - {
        "gbt_fit_roofline"}
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


def test_run_py_refuses_a_fabric_that_does_not_fit_the_cell(monkeypatch,
                                                            capsys):
    """A fabric of four shards for a one-chip cell is refused with exit
    2, before the look for a chip."""
    bench, c, config, traffic = R.load_cell(ROOT, CELL)
    config = dict(config, client={"target": "fabric", "n_shards": 4})
    monkeypatch.setattr(R, "load_cell",
                        lambda root, w: (bench, c, config, traffic))

    def no_chip(*a):
        raise AssertionError("looked for a chip")
    monkeypatch.setattr(R, "device_info", no_chip)
    code = R.main(["--workload", CELL, "--seed", "1", "--seconds", "1"])
    assert code == 2
    out, err = capsys.readouterr()
    assert out == "" and "4 shards and the cell 1 chips" in err


FABRIC_RUN = """
import json, sys, time
sys.path[:0] = [{root!r}, {tests!r}]
from conftest import ROOT, small
import bench.run as R
from bench.harness import drive

recs = []
submit = drive.submit
def spy(client, rec, build, done_q):
    recs.append(rec)
    submit(client, rec, build, done_q)
drive.submit = spy
windows = []
closed_loop = drive.closed_loop
def keep(*a, **k):
    windows.append(closed_loop(*a, **k))
    return windows[-1]
drive.closed_loop = keep

bench, c, config, traffic = small(rows=3000, agents=8)
c = dict(c, chips=4)
config = dict(config, check_limits={limits!r},
              client={{"target": "fabric", "n_shards": 4}})
out = R.run_cell(ROOT, bench, c, config, traffic, 2 ** 31 + 7, 10.0, False,
                 {{"platform": "cpu", "kind": "cpu", "count": 4}},
                 {peak!r}, time.perf_counter())
(w,) = windows
row = lambda r: [r.job["agent"], r.pin[0], r.report and r.report.shard_id]
print(json.dumps({{"out": out,
                   "warm": [row(r) for r in recs
                            if r.job["agent"].startswith("warm-")],
                   "window": [row(r) for r in w.records],
                   "completed": [r.pin[0] for r in w.completed()]}}))
"""


def test_fabric_run_keeps_every_job_on_its_shard():
    """Four shards on four virtual CPU devices, 8 agents: correct, every
    job ran on the shard it was pinned to, every shard completed window
    jobs, and the warm-up ran on every shard."""
    code = FABRIC_RUN.format(root=ROOT, tests=os.path.dirname(__file__),
                             limits=SMALL_LIMITS, peak=PEAK)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    out = res["out"]
    assert out["correct"] is True, out["checks"]
    assert out["device"]["count"] == 4 and out["failed"] == 0
    shards = {f"shard-{s}" for s in range(4)}
    for agent, pin, ran in res["window"]:
        assert ran == pin, (agent, pin, ran)
        # agent i of 8 is pinned to shard floor(i * 4 / 8)
        assert pin == f"shard-{int(agent.split('-')[1]) * 4 // 8}"
    assert set(res["completed"]) == shards
    assert {pin for _, pin, _ in res["warm"]} == shards
    assert all(ran == pin for _, pin, ran in res["warm"])
    per_shard = [sum(pin == s for _, pin, _ in res["warm"]) for s in shards]
    assert len(set(per_shard)) == 1
    assert "window jobs per shard" in p.stderr
