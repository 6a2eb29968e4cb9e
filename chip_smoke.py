"""Chip smoke: agentic pipeline search on a TPU, through StratumClient.

Generates the UK-housing table from ``--seed`` (default 1,000,000 rows,
about one year of the UK Price Paid records the paper's workload models),
then, on one chip:

1. runs the paper's §6 two-iteration workload (8 pipelines, then the grid
   on the winner) for two tenants through ``connect("service", ...)`` with
   the default config;
2. runs one ``AsyncAIDESearch`` round over the same client;
3. scores the iteration-1 pipelines on the python reference tier (float64
   NumPy, ``run_base``'s isolated per-pipeline execution), in threads,
   once the service has closed: at a million rows each side holds more
   than 10 GB of host memory, so they do not overlap.

It fails (non-zero exit, no result line) unless JAX's first device is a
TPU, every future completes, the backend mix holds compiled segments
(``jax-seg``) and no reference fallback (``ref``), no compiled segment
failed at run time or in a background warm call, no segment was
uncompilable, no round fell back per-op, and every iteration-1 score is
within a relative 1e-2 of the python reference.

``--chips 4`` runs only the multi-chip path: the §6 workload as one job
per pipeline over a 4-shard in-process fabric (shard i dispatches to
device i), against the same jobs on a 1-shard fabric.  Scores must agree
within the same tolerance and every device must show non-zero peak
memory.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

    python chip_smoke.py [--rows N] [--seed S] [--chips 4]
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import threading
import time
import traceback
from collections import Counter
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
TOL = 1e-2             # relative score agreement (float32 tier vs float64)
TENANTS = ("tenant-0", "tenant-1")
REF_THREADS = 6
BUDGET_S = 1100.0      # seconds for the whole run, compilation included


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def scores_of(results: dict) -> dict:
    import numpy as np
    out = {k: float(np.asarray(v)) for k, v in results.items()}
    bad = [k for k, v in out.items() if not math.isfinite(v)]
    check(not bad, f"non-finite scores: {bad}")
    return out


class Deadline:
    """Remaining seconds of the run's budget, for every blocking wait."""

    def __init__(self, seconds: float):
        self.end = time.monotonic() + seconds

    def left(self) -> float:
        left = self.end - time.monotonic()
        check(left > 0, "time budget exhausted")
        return left


def host_max_rss_bytes() -> int:
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def phase(name: str, t0: float, note: str = "") -> None:
    dt = time.perf_counter() - t0
    print(f"phase {name}: {dt:.3f} s{'  ' + note if note else ''}",
          flush=True)


def reference_scores(rows: int, seed: int, deadline: Deadline) -> dict:
    """Score the iteration-1 pipelines on the python tier, each pipeline
    isolated (``benchmarks.baselines.run_base``'s execution), in threads:
    each isolated 1M-row pipeline holds ~2.5 GB of host memory."""
    from benchmarks.baselines import run_pipeline_isolated
    from repro.agents import paper_workload_batches
    _, batch, _ = next(paper_workload_batches(n_rows=rows, cv_k=3,
                                              data_seed=seed))
    pool = ThreadPoolExecutor(max_workers=REF_THREADS,
                              thread_name_prefix="reference")
    try:
        futs = {name: pool.submit(run_pipeline_isolated, sink)
                for name, sink in zip(batch.names, batch.sinks)}
        return scores_of({name: f.result(timeout=deadline.left())[0]
                          for name, f in futs.items()})
    finally:
        pool.shutdown(wait=False, cancel_futures=True)


def iteration1(rows: int, seed: int):
    from repro.agents import paper_workload_batches
    _, batch, ctx = next(paper_workload_batches(n_rows=rows, cv_k=3,
                                                data_seed=seed))
    return batch, ctx["specs"]


# ---------------------------------------------------------------------------
# one chip: the service target
# ---------------------------------------------------------------------------

def service_phases(args, deadline: Deadline) -> dict:
    """The §6 workload and one AIDE round through the service; returns
    the iteration-1 scores by tenant."""
    import jax

    from repro.agents import AIDEAgent, AsyncAIDESearch
    from repro.agents.aide import second_iteration_batch
    from repro.client import StratumConfig, connect

    reports = []
    with connect("service", StratumConfig.make()) as client:
        # -- §6 iteration 1: 2 preprocessings x 4 models, per tenant ------
        t0 = time.perf_counter()
        specs = {}
        futs = {}
        for tenant in TENANTS:
            batch, specs = iteration1(args.rows, args.seed)
            futs[tenant] = client.session(tenant).submit(batch)
        it1 = {}
        for tenant, f in futs.items():
            res, rep = f.result(timeout=deadline.left())
            reports.append(rep)
            it1[tenant] = scores_of(res)
        phase("iteration1", t0, f"({len(TENANTS)} tenants x "
              f"{len(it1[TENANTS[0]])} pipelines)")
        a, b = it1[TENANTS[0]], it1[TENANTS[1]]
        check(max(rel_diff(a[k], b[k]) for k in a) <= TOL,
              "the two tenants' iteration-1 scores disagree")

        # -- §6 iteration 2: grid search on the winner ---------------------
        best = min(a, key=a.get)
        t0 = time.perf_counter()
        futs = {}
        for tenant in TENANTS:
            batch2, _ = second_iteration_batch(specs[best])
            futs[tenant] = client.session(tenant).submit(batch2)
        it2 = {}
        for tenant, f in futs.items():
            res, rep = f.result(timeout=deadline.left())
            reports.append(rep)
            it2[tenant] = scores_of(res)
        phase("iteration2", t0, f"(grid on {best}, "
              f"{len(it2[TENANTS[0]])} points)")

        # -- one AsyncAIDESearch round over the same client ----------------
        t0 = time.perf_counter()
        agent = AIDEAgent(n_rows=args.rows, cv_k=3, seed=args.seed,
                          data_seed=args.seed)
        search = AsyncAIDESearch(client.session("aide-0"), agent,
                                 batch_size=4, max_inflight=1)
        node = search.run(n_rounds=1)
        check(node is not None and len(search.reports) == 1,
              "the AIDE round produced no scored result")
        check(math.isfinite(node.score), "AIDE best score not finite")
        reports.extend(search.reports)
        phase("aide_round", t0, f"(best {node.spec.preproc}+"
              f"{node.spec.model} rmse={node.score:.6f})")

        plan_cache = client.telemetry.global_snapshot()["plan_cache"]
    mix = Counter()
    runtime_failures = fallback_rounds = 0
    for rep in reports:
        mix.update(rep.per_backend)
        runtime_failures += rep.run.segment_runtime_failures
        fallback_rounds += rep.run.plan_cache_fallback_rounds
    print(f"backend mix: {dict(sorted(mix.items()))}")
    print(f"plan cache: {json.dumps(plan_cache, sort_keys=True)}")
    print(f"segment runtime failures: {runtime_failures}  "
          f"fallback rounds: {fallback_rounds}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(f"host max RSS after the service: {host_max_rss_bytes()} bytes",
          flush=True)
    check("jax-seg" in mix, "no compiled segment ran (no jax-seg)")
    check("ref" not in mix, "ops fell back to the reference evaluator")
    check(runtime_failures == 0 and plan_cache["runtime_failures"] == 0,
          "compiled segments failed at run time")
    check(plan_cache["warm_failures"] == 0, "background warm calls failed")
    check(plan_cache["uncompilable"] == 0, "uncompilable segments")
    check(fallback_rounds == 0, "rounds fell back to per-op dispatch")
    return it1


def run_one_chip(args, deadline: Deadline) -> None:
    it1 = service_phases(args, deadline)
    # the closed service's caches and intermediates go before the
    # reference's own million-row copies arrive
    gc.collect()
    t0 = time.perf_counter()
    ref = reference_scores(args.rows, args.seed, deadline)
    phase("reference", t0, f"(python tier, isolated pipelines, "
          f"{REF_THREADS} threads)")
    worst, worst_name = 0.0, ""
    for name in sorted(ref):
        for tenant in TENANTS:
            d = rel_diff(it1[tenant][name], ref[name])
            if d > worst:
                worst, worst_name = d, name
        print(f"  {name}: service={it1[TENANTS[0]][name]!r} "
              f"reference={ref[name]!r} "
              f"rel_diff={rel_diff(it1[TENANTS[0]][name], ref[name]):.3e}")
    print(f"largest relative score difference vs python reference: "
          f"{worst:.3e} ({worst_name})")
    print(f"host max RSS: {host_max_rss_bytes()} bytes")
    check(len(ref) == 8 and worst <= TOL,
          f"iteration-1 scores differ from the python reference by "
          f"{worst:.3e} > {TOL}")


# ---------------------------------------------------------------------------
# four chips: one fabric shard per device
# ---------------------------------------------------------------------------

def _affinity_keys(shard_ids, vnodes: int) -> dict:
    """One routing key per shard, found by probing a replica of the
    fabric's consistent-hash ring."""
    from repro.service.fabric.ring import ConsistentHashRing
    ring = ConsistentHashRing(shard_ids, vnodes=vnodes)
    keys: dict = {}
    k = 0
    while len(keys) < len(shard_ids):
        keys.setdefault(ring.route(f"chip-{k}"), f"chip-{k}")
        k += 1
    return keys


def run_fabric(n_shards: int, args, deadline: Deadline) -> dict:
    """The §6 workload as one job per pipeline, job j pinned to shard
    j mod n_shards.  Returns scores by pipeline name."""
    from repro.agents.aide import second_iteration_batch
    from repro.client import StratumConfig, SubmitOptions, connect
    from repro.core.fusion import PipelineBatch

    cfg = StratumConfig.make(n_shards=n_shards)
    with connect("fabric", cfg) as client:
        shards = client.fabric.shard_ids()
        keys = _affinity_keys(shards, cfg.service.vnodes)
        mix = Counter()

        def run_jobs(batch) -> dict:
            futs = []
            for j, (name, sink) in enumerate(zip(batch.names, batch.sinks)):
                shard = shards[j % len(shards)]
                futs.append((name, shard, client.submit(
                    PipelineBatch([sink], [name]),
                    SubmitOptions(tenant="tenant-0",
                                  affinity=keys[shard]))))
            out = {}
            for name, shard, f in futs:
                res, rep = f.result(timeout=deadline.left())
                check(rep.shard_id == shard,
                      f"{name} ran on {rep.shard_id}, not {shard}")
                mix.update(rep.per_backend)
                out.update(scores_of(res))
            return out

        t0 = time.perf_counter()
        batch, specs = iteration1(args.rows, args.seed)
        it1 = run_jobs(batch)
        best = min(it1, key=it1.get)
        batch2, _ = second_iteration_batch(specs[best])
        it2 = run_jobs(batch2)
        phase(f"fabric_{n_shards}_shards", t0,
              f"({len(it1)} + {len(it2)} jobs, grid on {best})")
        print(f"  backend mix: {dict(sorted(mix.items()))}")
        check("jax-seg" in mix and "ref" not in mix,
              f"{n_shards}-shard backend mix {dict(mix)}")
        for sid, row in sorted(client.telemetry.per_shard().items()):
            pc = row.get("plan_cache", {})
            print(f"  {sid}: plan cache entries={pc.get('entries')} "
                  f"hits={pc.get('hits')} misses={pc.get('misses')} "
                  f"runtime_failures={pc.get('runtime_failures')} "
                  f"uncompilable={pc.get('uncompilable')}")
            check(pc.get("runtime_failures", 0) == 0
                  and pc.get("warm_failures", 0) == 0
                  and pc.get("uncompilable", 0) == 0,
                  f"{sid}: compiled segments failed")
    return {"best": best, **it1, **{f"grid:{k}": v for k, v in it2.items()}}


def run_four_chips(args, deadline: Deadline) -> None:
    import jax
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, JAX has "
          f"{len(devices)}")
    four = run_fabric(4, args, deadline)
    one = run_fabric(1, args, deadline)
    check(four.pop("best") == one.pop("best"),
          "the 4-shard and 1-shard runs chose different winners")
    worst = max(rel_diff(four[k], one[k]) for k in one)
    print(f"largest relative score difference, 4 shards vs 1 shard: "
          f"{worst:.3e} over {len(one)} pipelines")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices[:4]]
    print(f"peak_bytes_in_use per device: {peaks}")
    check(set(four) == set(one) and worst <= TOL,
          f"4-shard scores differ from 1-shard by {worst:.3e} > {TOL}")
    check(all(p > 0 for p in peaks), "a device held no memory")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Drive agentic pipeline search through StratumClient "
                    "on a TPU and check it against the python reference.")
    ap.add_argument("--rows", type=int, default=1_000_000,
                    help="rows of the generated UK-housing table")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated table and of the agent")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-shard vs 1-shard fabric path")
    args = ap.parse_args(argv)

    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: FAIL: JAX found no TPU (first device is "
              f"{device.platform!r})", file=sys.stderr)
        return 1
    sys.path[:0] = [os.path.join(REPO, "src"), REPO]
    try:
        import benchmarks.baselines  # noqa: F401
        import repro.client  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: FAIL: the repository is not beside this "
              f"script: {e}", file=sys.stderr)
        return 1
    from repro.data.tabular import UK_HOUSING_SCHEMA, ensure_files

    # a hung future must not outlive the budget: fail the whole process
    def _expired() -> None:
        print("chip_smoke: FAIL: time budget exhausted", file=sys.stderr,
              flush=True)
        os._exit(1)
    watchdog = threading.Timer(BUDGET_S + 30.0, _expired)
    watchdog.daemon = True
    watchdog.start()
    deadline = Deadline(BUDGET_S)

    print(f"device: {device.device_kind} ({device.platform}, "
          f"{len(jax.devices())} device(s))")
    print(f"rows: {args.rows}  seed: {args.seed}  "
          f"columns: {len(UK_HOUSING_SCHEMA)}")
    t0 = time.perf_counter()
    ensure_files("uk_housing", args.rows, args.seed)
    phase("set-up", t0, "(table generated from the seed)")
    try:
        if args.chips == 4:
            run_four_chips(args, deadline)
        else:
            run_one_chip(args, deadline)
    except BaseException as e:  # noqa: BLE001 — report, then exit at once
        if isinstance(e, SmokeFailure):
            print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        else:
            traceback.print_exc()
            print(f"chip_smoke: FAIL: {e!r}", file=sys.stderr)
        sys.stdout.flush()
        sys.stderr.flush()
        # service or reference threads may still be running: do not wait
        os._exit(1)
    watchdog.cancel()
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
