"""End-to-end agentic pipeline search (paper Fig. 6a) + the N-concurrent-
agent scaling benchmark for the multi-tenant execution service.

Workload (paper §6, verbatim structure): iteration 1 = 2 preprocessing
strategies × 4 models over UK-housing-like data; iteration 2 = grid search
on the winner.  Modes: Base (sequential AIDE), Base_par (naively parallel
AIDE), stratum (all optimizations), service (N agents multiplexed over one
StratumService — emitted to ``BENCH_service.json``).

``--mixed-priority`` measures the priority scheduler instead: an
interactive tenant issues sequential latency-sensitive probes while batch
tenants flood the service with bulk sweeps, once with the priority-aware
scheduler (WFQ bands + cooperative preemption) and once priority-blind
(plain round-robin).  Interactive p50/p99 latency for both modes is merged
into ``BENCH_service.json`` under ``"mixed_priority"``.

``--deadline`` measures deadline-aware scheduling instead: one tenant's
sequential probes carry a ``deadline_s`` SLO while bulk tenants flood the
SAME priority band with deadline-free sweeps; EDF tie-breaks, tight-slack
solo dispatch and shedding (aware) vs deadline-blind round-robin.  p99
attainment and batch-throughput parity land in ``BENCH_service.json``
under ``"deadline"``.

``--shards K`` measures the sharded fabric: agent cohorts over distinct
datasets submit open-loop sweeps through ``ShardedStratum`` at 1 shard vs
K shards; consistent-hash placement keeps each shard's intermediate cache
and cross-agent CSE hot for its cohorts, where a single shard LRU-thrashes
across all of them.  Aggregate throughput, signature-locality hit rate and
score agreement land in ``BENCH_service.json`` under ``"sharded"``.

    PYTHONPATH=src python benchmarks/e2e_agentic.py --agents 4
    PYTHONPATH=src python benchmarks/e2e_agentic.py --mixed-priority
    PYTHONPATH=src python benchmarks/e2e_agentic.py --shards 4 --agents 16
"""

from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import replace

import numpy as np

from repro.agents import paper_workload_batches
from repro.agents.aide import PipelineSpec, second_iteration_batch
from repro.core import PipelineBatch, Stratum
from repro.service import (AdmissionError, ControlPolicy, DeadlineExceeded,
                           Priority, StratumService)
import repro.tabular as T

try:
    from .baselines import run_base, run_base_par
except ImportError:          # executed as a script, not a package module
    from baselines import run_base, run_base_par


def _workload(n_rows: int, cv_k: int):
    name, batch, ctx = next(iter(paper_workload_batches(
        n_rows=n_rows, cv_k=cv_k)))
    return batch, ctx


def run(n_rows: int = 20_000, cv_k: int = 3, spill_dir: str | None = None,
        include_base_par: bool = True) -> dict:
    out = {}
    # materialize the data lake files once (setup, not measured)
    from repro.data.tabular import ensure_files
    ensure_files("uk_housing", n_rows, 0)

    # ---- Base ------------------------------------------------------------
    batch, ctx = _workload(n_rows, cv_k)
    res_base, t_base = run_base(batch.sinks)
    scores = {n: float(np.asarray(r)) for n, r in zip(batch.names, res_base)}
    best = min(scores, key=scores.get)
    b2, _ = second_iteration_batch(ctx["specs"][best])
    res2, t2 = run_base(b2.sinks)
    out["base_s"] = t_base + t2

    # ---- Base_par ----------------------------------------------------------
    if include_base_par:
        batch, ctx = _workload(n_rows, cv_k)
        _, tp1 = run_base_par(batch.sinks)
        _, tp2 = run_base_par(b2.sinks)
        out["base_par_s"] = tp1 + tp2

    # ---- stratum -----------------------------------------------------------
    batch, ctx = _workload(n_rows, cv_k)
    s = Stratum(memory_budget_bytes=4 << 30, spill_dir=spill_dir)
    t0 = time.perf_counter()
    res1, rep1 = s.run_batch(batch)
    best = min(res1, key=lambda k: float(np.asarray(res1[k])))
    b2s, _ = second_iteration_batch(ctx["specs"][best])
    res2s, rep2 = s.run_batch(b2s)
    out["stratum_s"] = time.perf_counter() - t0
    out["stratum_cold"] = not getattr(run, "_warmed", False)
    run._warmed = True

    out["speedup_vs_base"] = out["base_s"] / out["stratum_s"]
    if include_base_par:
        out["speedup_vs_base_par"] = out["base_par_s"] / out["stratum_s"]
    out["stratum_cache_hits"] = rep2.run.ops_from_cache
    out["stratum_cse_merged"] = rep1.rewrites.cse_merged

    # scores must agree across modes (same seeds; dtype tolerance)
    s_base = float(np.asarray(scores[best]))
    s_strat = float(np.asarray(res1[best]))
    out["score_rel_diff"] = abs(s_base - s_strat) / abs(s_base)
    return out


def rows() -> list:
    r = run()
    out = [("e2e_base", r["base_s"] * 1e6, ""),
           ("e2e_stratum", r["stratum_s"] * 1e6,
            f"speedup={r['speedup_vs_base']:.1f}x"),
           ("e2e_score_agreement", r["score_rel_diff"] * 1e6,
            "rel_diff_x1e-6")]
    if "base_par_s" in r:
        out.insert(1, ("e2e_base_par", r["base_par_s"] * 1e6,
                       f"speedup={r.get('speedup_vs_base_par', 0):.1f}x"))
    return out


# ---------------------------------------------------------------------------
# N-concurrent-agents scaling through the multi-tenant service
# ---------------------------------------------------------------------------

def _agent_iterations(n_rows: int, cv_k: int, agent_seed: int):
    """One agent's two-iteration AIDE workload.  Iteration 1 is the paper's
    8-pipeline sweep (identical across agents — the multi-tenant sharing
    scenario: every agent profiles the same dataset); iteration 2 is the
    grid on that agent's winner, re-seeded per agent so the model-fit work
    is tenant-unique while reads/preprocessing stay shareable."""
    _, batch, ctx = next(iter(paper_workload_batches(
        n_rows=n_rows, cv_k=cv_k)))

    def second(best_name: str):
        spec = replace(ctx["specs"][best_name], seed=7 + agent_seed)
        return second_iteration_batch(spec)[0]

    return batch, second


def _run_one_agent(run_batch, n_rows: int, cv_k: int, agent_seed: int
                   ) -> float:
    """Drive the two-iteration workload through ``run_batch`` (a callable
    with the Stratum/Session signature); returns the winning score."""
    batch, second = _agent_iterations(n_rows, cv_k, agent_seed)
    res1, _ = run_batch(batch)
    best = min(res1, key=lambda k: float(np.asarray(res1[k])))
    res2, _ = run_batch(second(best))
    return min(float(np.asarray(v)) for v in res2.values())


def run_service(n_agents: int = 4, n_rows: int = 20_000, cv_k: int = 3,
                warmup: bool = True) -> dict:
    """4-sequential-sessions baseline vs N agents through one service."""
    from repro.data.tabular import ensure_files
    ensure_files("uk_housing", n_rows, 0)

    if warmup:  # warm the XLA jit cache so neither mode pays compile time
        _run_one_agent(
            Stratum(memory_budget_bytes=4 << 30).run_batch, n_rows, cv_k, 0)

    # ---- baseline: N isolated, sequential Stratum sessions ---------------
    t0 = time.perf_counter()
    seq_scores = []
    for i in range(n_agents):
        session = Stratum(memory_budget_bytes=4 << 30)
        seq_scores.append(
            _run_one_agent(session.run_batch, n_rows, cv_k, i))
    sequential_s = time.perf_counter() - t0

    # ---- service: N concurrent agents over one optimizing runtime --------
    svc = StratumService(memory_budget_bytes=4 << 30,
                         coalesce_window_s=0.05,
                         n_executors=2)
    svc_scores = [None] * n_agents
    errors: list = []
    barrier = threading.Barrier(n_agents)

    def agent_main(i: int) -> None:
        try:
            session = svc.session(f"agent-{i}")
            barrier.wait()
            svc_scores[i] = _run_one_agent(
                session.run_batch, n_rows, cv_k, i)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=agent_main, args=(i,))
               for i in range(n_agents)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    service_s = time.perf_counter() - t0
    telemetry = {"global": svc.telemetry.global_snapshot(),
                 "per_tenant": svc.telemetry.snapshot()}
    report_text = svc.telemetry.report()
    svc.stop()
    if errors:
        raise errors[0]

    rel = max(abs(a - b) / max(abs(a), 1e-12)
              for a, b in zip(seq_scores, svc_scores))
    return {
        "agents": n_agents,
        "rows": n_rows,
        "sequential_s": sequential_s,
        "service_s": service_s,
        "speedup": sequential_s / service_s,
        "score_rel_diff": rel,
        "ops_deduped_cross_agent":
            telemetry["global"]["ops_deduped_cross_agent"],
        "shared_cache_hits": sum(t["cache_hits"]
                                 for t in telemetry["per_tenant"].values()),
        "telemetry": telemetry,
        "telemetry_report": report_text,
    }


def write_service_json(result: dict, path: str = "BENCH_service.json",
                       merge: bool = False) -> None:
    if merge and os.path.exists(path):
        with open(path) as f:
            prev = json.load(f)
        prev.update(result)
        result = prev
    with open(path, "w") as f:
        json.dump(result, f, indent=2, default=str)


# ---------------------------------------------------------------------------
# sharded-fabric scaling: N agents over 1 vs K consistent-hash shards
# ---------------------------------------------------------------------------

def _cohort_job(cohort_seed: int, n_rows: int, tail_idx: int
                ) -> PipelineBatch:
    """One agent-round job: an expensive preprocessing *prefix* shared by
    the whole cohort (read → TableVectorizer fit over the cohort's
    dataset — real encoder compute, not just IO) and a cheap
    per-(agent, round) unique *tail* (a metric on one vectorized column)
    — the regime where shard-local cache/CSE locality decides
    throughput."""
    from repro.data.tabular import feature_target_indices, schema_dict
    feats, tgt = feature_target_indices()
    x = T.read("uk_housing", n_rows, seed=cohort_seed)
    Xv = T.table_vectorizer(T.project(x, feats), schema_dict(), feats)
    y = T.project(x, [tgt])
    col = tail_idx % len(feats)
    kind = "mae" if (tail_idx // len(feats)) % 2 else "rmse"
    sink = T.metric(T.project(Xv, [col]), y, kind=kind)
    return PipelineBatch([sink], [f"tail{tail_idx}"])


def _balanced_cohort_keys(n_cohorts: int, n_shards: int, vnodes: int = 64
                          ) -> list:
    """Affinity keys placing ``n_cohorts`` work groups evenly on an
    ``n_shards`` ring.  Placement is deterministic (blake2b ring), so the
    scaling measurement is not at the mercy of hash luck on 4 draws; with
    many real datasets the ring balances statistically, which is what this
    emulates."""
    from repro.service.fabric import ConsistentHashRing
    ring = ConsistentHashRing([f"shard-{i}" for i in range(n_shards)],
                              vnodes=vnodes)
    keys, used = [], set()
    i = 0
    while len(keys) < n_cohorts and i < 100_000:
        key = f"cohort-{i}"
        shard = ring.route(key)
        if shard not in used or len(used) == n_shards:
            if shard in used:           # ring full: start a second lap
                used.clear()
            used.add(shard)
            keys.append(key)
        i += 1
    return keys


def _run_fabric_mode(n_shards: int, n_agents: int, n_cohorts: int,
                     rounds: int, n_rows: int,
                     ring_shards_for_keys: int) -> dict:
    from repro.service import ServiceConfig
    from repro.service.fabric import ShardedStratum
    # per-shard cache sized to hold ~1.3 cohort working sets (one host's
    # RAM holds its own cohort with headroom): a shard serving its cohort
    # stays hot, one server serving every cohort LRU-thrashes — the
    # single-server ceiling the fabric removes.  A looser budget lets the
    # single server keep 2 cohorts resident and the measurement bimodal.
    # ~900 B/row ≈ one cohort's cached TableVectorizer intermediates
    # (measured at 30k rows; scales linearly with rows)
    per_cohort = int(n_rows * 900)
    mem_budget = 256 << 20
    cfg = ServiceConfig(
        memory_budget_bytes=mem_budget,
        cache_fraction=min(0.5, 1.3 * per_cohort / mem_budget),
        coalesce_window_s=0.005,
        coalesce_max_jobs=2,
        max_jobs_per_tenant_per_round=1,
        # one executor per shard: per-shard resources are identical across
        # modes (the fabric's aggregate grows with shards, which is the
        # claim under test), and the single server's eviction pattern is
        # deterministic — with 2 executors, concurrently running
        # super-batches race each other's cache insertions and the
        # 1-shard number becomes a coin flip between thrash and reuse
        n_executors=1,
        # per-op dispatch: this experiment isolates INTERMEDIATE-cache
        # locality (shard-local working sets vs single-server LRU thrash).
        # Compiled segments make recompute ~4x cheaper, which shrinks the
        # thrash penalty and would entangle the two effects; the compiled
        # dispatch win is measured by its own section (--sections compiled)
        compiled_segments=False)
    keys = _balanced_cohort_keys(n_cohorts, ring_shards_for_keys)
    fab = ShardedStratum(n_shards=n_shards, config=cfg)
    sessions = [fab.session(f"agent-{i}") for i in range(n_agents)]
    scores = [[None] * rounds for _ in range(n_agents)]

    # open-loop: every agent's whole sweep is submitted up front, round by
    # round in agent order.  Adjacent submissions belong to *different*
    # cohorts (agent i → cohort i % n_cohorts), so a single shard sees a
    # strict cross-cohort interleave — the deterministic worst case for
    # its LRU cache — while each fabric shard's queue holds only its own
    # cohort's jobs.  (A closed loop measures the same effect but lets
    # same-cohort agents phase-lock into bursts, making the single-shard
    # number a coin flip.)
    t0 = time.perf_counter()
    futures = []
    for r in range(rounds):
        for i in range(n_agents):
            cohort = i % n_cohorts
            rank = i // n_cohorts           # position within the cohort
            tail = rank * rounds + r        # unique within the cohort
            futures.append((i, r, tail, sessions[i].submit(
                _cohort_job(cohort, n_rows, tail),
                affinity=keys[cohort])))
    for i, r, tail, fut in futures:
        res, _ = fut.result(timeout=600)
        scores[i][r] = float(np.asarray(res[f"tail{tail}"]))
    makespan = time.perf_counter() - t0
    g = fab.telemetry.global_snapshot()
    fab.stop()
    total_jobs = n_agents * rounds
    return {
        "shards": n_shards,
        "makespan_s": makespan,
        "throughput_jobs_per_s": total_jobs / makespan,
        "locality_hit_rate": g["signature_locality_hit_rate"],
        "super_batches": g["super_batches"],
        "envelopes_per_shard": {k: v["envelopes_routed"]
                                for k, v in g["per_shard"].items()},
        "scores": scores,
    }


def run_sharded(n_agents: int = 16, rounds: int = 3, n_rows: int = 30_000,
                n_cohorts: int = 4, shard_counts=(1, 4),
                warmup: bool = True) -> dict:
    """Aggregate throughput of the sharded fabric vs one service shard.

    ``n_agents`` agents in ``n_cohorts`` cohorts (one dataset each) submit
    open-loop multi-round sweeps.  Cohorts are pinned to ring
    positions via affinity keys, so with K shards each shard serves ~K-th
    of the cohorts and its intermediate cache stays hot; one shard serving
    every cohort thrashes its cache — the structural ceiling the ROADMAP's
    "shard the service across hosts" item targets.  Scores must be
    identical across shard counts (same deterministic pipelines)."""
    from repro.data.tabular import ensure_files
    for c in range(n_cohorts):
        ensure_files("uk_housing", n_rows, c)
    max_shards = max(shard_counts)

    if warmup:   # compile each op shape once so no mode pays XLA compile
        s = Stratum(memory_budget_bytes=256 << 20,
                    compiled_segments=False)   # match the modes' regime
        s.run_batch(_cohort_job(0, n_rows, 0))

    modes = {}
    for n_shards in shard_counts:
        modes[str(n_shards)] = _run_fabric_mode(
            n_shards, n_agents, n_cohorts, rounds, n_rows,
            ring_shards_for_keys=max_shards)

    lo = modes[str(min(shard_counts))]
    hi = modes[str(max(shard_counts))]
    scores_identical = all(
        abs(a - b) <= 1e-9 * max(abs(a), 1.0)
        for ra, rb in zip(lo["scores"], hi["scores"])
        for a, b in zip(ra, rb))
    out = {
        "agents": n_agents,
        "rounds": rounds,
        "rows": n_rows,
        "cohorts": n_cohorts,
        "modes": {k: {kk: vv for kk, vv in v.items() if kk != "scores"}
                  for k, v in modes.items()},
        "speedup": hi["throughput_jobs_per_s"] / lo["throughput_jobs_per_s"],
        "scores_identical": scores_identical,
    }
    return out


def sharded_rows(smoke: bool = False,
                 out: str = "BENCH_service.json") -> list:
    kw = dict(n_agents=16, rounds=3, n_rows=12_000) if smoke else {}
    r = run_sharded(**kw)
    key = "sharded_smoke" if smoke else "sharded"
    write_service_json({key: r}, out, merge=True)
    lo, hi = (r["modes"][str(k)] for k in (min(map(int, r["modes"])),
                                           max(map(int, r["modes"]))))
    return [
        (f"{key}_1shard_makespan", lo["makespan_s"] * 1e6,
         f"{lo['throughput_jobs_per_s']:.2f}_jobs_per_s"),
        (f"{key}_{hi['shards']}shard_makespan", hi["makespan_s"] * 1e6,
         f"{hi['throughput_jobs_per_s']:.2f}_jobs_per_s "
         f"(speedup={r['speedup']:.1f}x)"),
        (f"{key}_locality", hi["locality_hit_rate"] * 1e6, "hit_rate_x1e-6"),
        (f"{key}_scores_identical", float(r["scores_identical"]),
         "1=identical"),
    ]


# ---------------------------------------------------------------------------
# out-of-process fabric: CPU-bound cohort flood, 1 vs K worker processes
# ---------------------------------------------------------------------------

def _run_proc_mode(n_procs: int, n_agents: int, n_cohorts: int,
                   rounds: int, n_rows: int,
                   ring_shards_for_keys: int) -> dict:
    from repro.service import ServiceConfig
    from repro.service.fabric import ProcConfig, ProcStratumFabric
    cfg = ServiceConfig(
        memory_budget_bytes=256 << 20,
        coalesce_window_s=0.005,
        coalesce_max_jobs=2,
        max_jobs_per_tenant_per_round=1,
        n_executors=1,
        compiled_segments=False)
    keys = _balanced_cohort_keys(n_cohorts, ring_shards_for_keys)
    fab = ProcStratumFabric(n_shards=n_procs, config=cfg,
                            proc=ProcConfig(heartbeat_s=0.25,
                                            heartbeat_timeout_s=10.0))
    try:
        sessions = [fab.session(f"agent-{i}") for i in range(n_agents)]
        scores = [[None] * rounds for _ in range(n_agents)]
        submitted = n_agents * rounds
        t0 = time.perf_counter()
        futures = []
        for r in range(rounds):
            for i in range(n_agents):
                cohort = i % n_cohorts
                tail = (i // n_cohorts) * rounds + r
                futures.append((i, r, tail, sessions[i].submit(
                    _cohort_job(cohort, n_rows, tail),
                    affinity=keys[cohort])))
        completed = 0
        for i, r, tail, fut in futures:
            res, _ = fut.result(timeout=600)
            scores[i][r] = float(np.asarray(res[f"tail{tail}"]))
            completed += 1
        makespan = time.perf_counter() - t0
        g = fab.telemetry.global_snapshot()
    finally:
        fab.stop()
    return {
        "procs": n_procs,
        "makespan_s": makespan,
        "throughput_jobs_per_s": submitted / makespan,
        "completed_frac": completed / submitted,
        "worker_spawns": g["proc"]["spawns"],
        "worker_failures": g["proc"]["worker_failures"],
        "scores": scores,
    }


def run_proc_fabric(n_agents: int = 8, rounds: int = 3, n_rows: int = 20_000,
                    n_cohorts: int = 2, proc_counts=(1, 2)) -> dict:
    """CPU-bound cohort flood through 1 vs K *worker processes*.

    Same open-loop workload as the sharded section, but each shard is a
    real OS process (``ProcStratumFabric``): the K-process mode escapes
    the GIL and, on a multi-core host, approaches Kx aggregate
    throughput.  ``n_cpus`` is recorded alongside the speedup because the
    headline number is honest only relative to the cores available —
    on a single-core runner the K-process mode measures pure fabric
    overhead (framing, supervision, heartbeats), not parallelism, so the
    regression gate rides on ``completed_frac`` (zero job loss), which
    holds on any machine."""
    from repro.data.tabular import ensure_files
    for c in range(n_cohorts):
        ensure_files("uk_housing", n_rows, c)
    max_procs = max(proc_counts)

    modes = {}
    for n_procs in proc_counts:
        modes[str(n_procs)] = _run_proc_mode(
            n_procs, n_agents, n_cohorts, rounds, n_rows,
            ring_shards_for_keys=max_procs)

    lo = modes[str(min(proc_counts))]
    hi = modes[str(max(proc_counts))]
    scores_identical = all(
        abs(a - b) <= 1e-9 * max(abs(a), 1.0)
        for ra, rb in zip(lo["scores"], hi["scores"])
        for a, b in zip(ra, rb))
    return {
        "agents": n_agents,
        "rounds": rounds,
        "rows": n_rows,
        "cohorts": n_cohorts,
        "n_cpus": os.cpu_count(),
        "modes": {k: {kk: vv for kk, vv in v.items() if kk != "scores"}
                  for k, v in modes.items()},
        "speedup": hi["throughput_jobs_per_s"] / lo["throughput_jobs_per_s"],
        "completed_frac": min(lo["completed_frac"], hi["completed_frac"]),
        "scores_identical": scores_identical,
    }


def proc_fabric_rows(smoke: bool = False,
                     out: str = "BENCH_service.json") -> list:
    kw = (dict(n_agents=4, rounds=2, n_rows=3000)
          if smoke else {})
    r = run_proc_fabric(**kw)
    key = "fabric_proc_smoke" if smoke else "fabric_proc"
    write_service_json({key: r}, out, merge=True)
    lo, hi = (r["modes"][str(k)] for k in (min(map(int, r["modes"])),
                                           max(map(int, r["modes"]))))
    return [
        (f"{key}_1proc_makespan", lo["makespan_s"] * 1e6,
         f"{lo['throughput_jobs_per_s']:.2f}_jobs_per_s"),
        (f"{key}_{hi['procs']}proc_makespan", hi["makespan_s"] * 1e6,
         f"{hi['throughput_jobs_per_s']:.2f}_jobs_per_s "
         f"(speedup={r['speedup']:.2f}x on {r['n_cpus']} cpus)"),
        (f"{key}_completed", r["completed_frac"] * 1e6,
         "frac_x1e-6 (1e6=zero_loss)"),
        (f"{key}_scores_identical", float(r["scores_identical"]),
         "1=identical"),
    ]


# ---------------------------------------------------------------------------
# compiled plan-segment benchmark: repeated-structure workload, whole-segment
# jit + structural plan cache vs per-op dispatch
# ---------------------------------------------------------------------------

def _refinement_batch(round_i: int, n_variants: int, n_rows: int
                      ) -> PipelineBatch:
    """One round of AIDE-style refinements: ``n_variants`` pipelines with
    identical structure, differing only in tunable constants.  The clip
    quantile varies *early* in the DAG, so every downstream signature is
    fresh each round — the intermediate cache cannot short-circuit the
    work, and the measured gap is purely compiled-segment dispatch vs
    per-op dispatch over a warm structural plan cache."""
    from repro.data.tabular import feature_target_indices
    feats, tgt = feature_target_indices()
    cols = list(feats[:8])
    sinks, names = [], []
    x = T.read("uk_housing", n_rows, seed=0)
    y = T.project(x, [tgt])
    for j in range(n_variants):
        k = round_i * n_variants + j
        Xc = T.clip_outliers(T.project(x, cols), q=0.001 + 0.0004 * k)
        Xs = T.log1p(T.scale(T.impute(Xc)))
        w = T.ridge_fit(Xs, y, alpha=0.05 * (1 + k))
        sinks.append(T.metric(y, T.predict(w, Xs), kind="rmse"))
        names.append(f"r{round_i}v{j}")
    return PipelineBatch(sinks, names)


def _compiled_mode(compiled: bool, rounds: int, n_variants: int,
                   n_rows: int, **svc_kw) -> dict:
    svc = StratumService(memory_budget_bytes=2 << 30,
                         coalesce_window_s=0.0,
                         n_executors=1,
                         compiled_segments=compiled,
                         **svc_kw)
    try:
        ses = svc.session("agent")
        # two warmup rounds (indices past the measured range): the first
        # warms the per-op jit caches and the intermediate cache, the
        # second compiles the segment shape measured rounds actually see
        # (shared prefix ops become cache hits, changing the segment cut)
        for w in (rounds, rounds + 1):
            ses.submit(_refinement_batch(w, n_variants, n_rows)
                       ).result(timeout=600)
        scores = []
        round_times = []
        t0 = time.perf_counter()
        for r in range(rounds):
            r0 = time.perf_counter()
            res, _ = ses.submit(_refinement_batch(r, n_variants, n_rows)
                                ).result(timeout=600)
            round_times.append(time.perf_counter() - r0)
            scores.extend(float(np.asarray(res[f"r{r}v{j}"]))
                          for j in range(n_variants))
        makespan = time.perf_counter() - t0
        g = svc.telemetry.global_snapshot()
    finally:
        svc.stop()
    out = {
        "compiled_segments": compiled,
        "makespan_s": makespan,
        "round_median_s": float(np.median(round_times)),
        "pipelines_per_s": rounds * n_variants / makespan,
        "scores": scores,
    }
    if "plan_cache" in g:
        out["plan_cache"] = g["plan_cache"]
    return out


def run_compiled(rounds: int = 10, n_variants: int = 8,
                 n_rows: int = 4000) -> dict:
    """Compiled plan-segment backends vs per-op dispatch on the
    repeated-structure workload (structurally identical refinement rounds
    differing only in constants).  Scores must be identical — segmentation
    changes dispatch granularity, never semantics."""
    from repro.data.tabular import ensure_files
    ensure_files("uk_housing", n_rows, 0)
    per_op = _compiled_mode(False, rounds, n_variants, n_rows)
    comp = _compiled_mode(True, rounds, n_variants, n_rows)
    max_rel = max(abs(a - b) / max(abs(a), 1e-12)
                  for a, b in zip(comp["scores"], per_op["scores"]))
    out = {
        "rounds": rounds,
        "variants": n_variants,
        "rows": n_rows,
        "modes": {
            "per_op": {k: v for k, v in per_op.items() if k != "scores"},
            "compiled": {k: v for k, v in comp.items() if k != "scores"},
        },
        "speedup": per_op["makespan_s"] / comp["makespan_s"],
        # whole-segment XLA fusion may reassociate float32 reductions vs
        # the eager per-op order; 1e-6 relative is float32 parity, far
        # below any score-ranking significance
        "score_max_rel_diff": max_rel,
        "scores_identical": bool(max_rel <= 1e-6),
        "plan_cache_hit_rate":
            comp.get("plan_cache", {}).get("hit_rate", 0.0),
    }
    return out


def compiled_rows(smoke: bool = False,
                  out: str = "BENCH_service.json") -> list:
    kw = dict(rounds=5, n_variants=6, n_rows=2000) if smoke else {}
    r = run_compiled(**kw)
    key = "compiled_smoke" if smoke else "compiled"
    write_service_json({key: r}, out, merge=True)
    m = r["modes"]
    return [
        (f"{key}_per_op", m["per_op"]["makespan_s"] * 1e6,
         f"{m['per_op']['pipelines_per_s']:.1f}_pipelines_per_s"),
        (f"{key}_compiled", m["compiled"]["makespan_s"] * 1e6,
         f"{m['compiled']['pipelines_per_s']:.1f}_pipelines_per_s "
         f"(speedup={r['speedup']:.1f}x)"),
        (f"{key}_plan_cache_hit_rate", r["plan_cache_hit_rate"] * 1e6,
         "hit_rate_x1e-6"),
        (f"{key}_scores_identical", float(r["scores_identical"]),
         "1=identical"),
    ]


# ---------------------------------------------------------------------------
# batched variant solves: homogeneous variant fans traced ONCE and vmapped
# across the fan, vs the unrolled whole-segment jit and per-op dispatch
# ---------------------------------------------------------------------------

def _cold_in_process(fn, *args):
    """Run ``fn(*args)`` cold in this process and return its result.

    Cold-start numbers measured inside the long-lived multi-section
    bench process are fiction unless every compile is really paid: so
    JAX's in-memory compile caches are cleared and its persistent
    compilation cache is switched off for the call, then restored.  The
    measurement stays in this process because a child interpreter cannot
    take an accelerator this process already holds."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    try:
        return fn(*args)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _cold_first_touch_s(batch_variants: bool, n_variants: int,
                        n_rows: int) -> float:
    """Wall time of the FIRST structurally-fresh round on a fresh session
    with blocking compiles and no persistent XLA cache: pure trace+jit
    cost of one refinement fan (plus one warm execution).  A tiny jit
    call first charges backend/LLVM bring-up to setup, not to the
    measured fan.  Meaningful only cold — run it via
    ``_cold_in_process``."""
    import jax
    jax.block_until_ready(jax.jit(lambda v: v + 1.0)(
        np.zeros(8, np.float32)))
    st = Stratum(memory_budget_bytes=2 << 30, compiled_segments=True,
                 batch_variants=batch_variants)
    try:
        t0 = time.perf_counter()
        st.run_batch(_refinement_batch(97, n_variants, n_rows))
        return time.perf_counter() - t0
    finally:
        st.close()


def run_compiled_batched(rounds: int = 10, n_variants: int = 8,
                         n_rows: int = 4000) -> dict:
    """Batched variant solves on the repeated-structure workload: each
    AIDE-style refinement fan holds ``n_variants`` structurally identical
    pipelines, so with ``batch_variants=True`` the jax segment backend
    traces the fan ONCE and ``vmap``s it across the variants instead of
    unrolling ``n_variants`` copies into the traced body.  Warm
    throughput must at least match the unrolled compiled mode (one fused
    program either way — the work is compute-bound); the structural win
    is COLD COMPILE TIME, one traced body instead of ``n_variants``,
    measured as blocking first-touch wall time with the persistent XLA
    cache disabled.  Scores must match per-op dispatch to float32 parity
    (≤1e-6 relative): batching changes trace layout, never semantics.

    The gated ``speedup`` is the ratio of per-round MEDIANS, not total
    makespans: on a small shared CI box one OS-noise straggler round out
    of five can double a makespan, and a regression gate on that tail
    flakes; the median isolates the steady-state dispatch cost the
    section actually claims.  Makespans stay in the artifact."""
    from repro.data.tabular import ensure_files
    ensure_files("uk_housing", n_rows, 0)
    per_op = _compiled_mode(False, rounds, n_variants, n_rows)
    comp = _compiled_mode(True, rounds, n_variants, n_rows)
    bat = _compiled_mode(True, rounds, n_variants, n_rows,
                         batch_variants=True)
    max_rel = max(abs(a - b) / max(abs(a), 1e-12)
                  for a, b in zip(bat["scores"], per_op["scores"]))
    # cold-compile comparison: each layout with every compile cache
    # cleared, so neither the sections above nor the other layout's
    # compile can contaminate the first touch
    cold_unrolled = _cold_in_process(
        _cold_first_touch_s, False, n_variants, n_rows)
    cold_batched = _cold_in_process(
        _cold_first_touch_s, True, n_variants, n_rows)
    return {
        "rounds": rounds, "variants": n_variants, "rows": n_rows,
        "modes": {
            "per_op": {k: v for k, v in per_op.items() if k != "scores"},
            "compiled": {k: v for k, v in comp.items() if k != "scores"},
            "batched": {k: v for k, v in bat.items() if k != "scores"},
        },
        "speedup": per_op["round_median_s"] / bat["round_median_s"],
        "batched_over_compiled":
            comp["round_median_s"] / bat["round_median_s"],
        "cold_compile_unrolled_s": cold_unrolled,
        "cold_compile_batched_s": cold_batched,
        "cold_compile_speedup": cold_unrolled / cold_batched,
        "score_max_rel_diff": max_rel,
        "scores_identical": bool(max_rel <= 1e-6),
    }


def compiled_batched_rows(smoke: bool = False,
                          out: str = "BENCH_service.json") -> list:
    kw = dict(rounds=5, n_variants=6, n_rows=2000) if smoke else {}
    r = run_compiled_batched(**kw)
    key = "compiled_batched_smoke" if smoke else "compiled_batched"
    write_service_json({key: r}, out, merge=True)
    m = r["modes"]
    return [
        (f"{key}_batched", m["batched"]["makespan_s"] * 1e6,
         f"{m['batched']['pipelines_per_s']:.1f}_pipelines_per_s "
         f"(speedup={r['speedup']:.2f}x vs per_op, "
         f"{r['batched_over_compiled']:.2f}x vs unrolled)"),
        (f"{key}_cold_compile", r["cold_compile_batched_s"] * 1e6,
         f"vs_unrolled_{r['cold_compile_unrolled_s']:.2f}s "
         f"({r['cold_compile_speedup']:.1f}x_faster)"),
        (f"{key}_scores_identical", float(r["scores_identical"]),
         "1=identical"),
    ]


# ---------------------------------------------------------------------------
# async/speculative compilation: first-touch latency when pipeline structure
# keeps changing — blocking compiles vs background compiles + warm-up hints
# ---------------------------------------------------------------------------

def _cold_batch(struct_i: int, round_i: int, n_variants: int, n_rows: int
                ) -> PipelineBatch:
    """A refinement fan whose STRUCTURE changes with ``struct_i``: the
    post-scale ``log1p`` tower is ``struct_i + 1`` deep, so every new
    struct index is a fresh jax-segment structural signature (a plan
    cache miss), while ``round_i`` varies only tunable constants within
    it.  The clip quantile is GLOBALLY unique per (struct, round,
    variant): the shared prefix up to ``clip_outliers`` is structurally
    identical across the whole ladder, so a repeated quantile would make
    one variant's clip an intermediate-cache hit, silently changing that
    round's segment cut (and forcing a recompile) — a tiny offset keeps
    every signature fresh while leaving the quantile in range."""
    from repro.data.tabular import feature_target_indices
    feats, tgt = feature_target_indices()
    cols = list(feats[:8])
    sinks, names = [], []
    x = T.read("uk_housing", n_rows, seed=0)
    y = T.project(x, [tgt])
    for j in range(n_variants):
        k = (struct_i * 1000 + round_i) * n_variants + j
        Xc = T.clip_outliers(T.project(x, cols), q=0.001 + 1e-8 * k)
        Xs = T.scale(T.impute(Xc))
        for _ in range(struct_i + 1):
            Xs = T.log1p(Xs)
        w = T.ridge_fit(Xs, y, alpha=0.05 * (1 + (k % 997)))
        sinks.append(T.metric(y, T.predict(w, Xs), kind="rmse"))
        names.append(f"s{struct_i}r{round_i}v{j}")
    return PipelineBatch(sinks, names)


def _cold_mode(async_on: bool, n_structs: int, reps: int, n_variants: int,
               n_rows: int) -> dict:
    svc_kw = (dict(compile_async=True, speculative_depth=4)
              if async_on else {})
    svc = StratumService(memory_budget_bytes=2 << 30,
                         coalesce_window_s=0.0, n_executors=1,
                         compiled_segments=True, batch_variants=True,
                         **svc_kw)
    try:
        ses = svc.session("agent")
        # warm the per-op jits, the data files and (async mode) the
        # background worker on a throwaway structure the measured ladder
        # never revisits
        for w in (0, 1):
            ses.submit(_cold_batch(99, w, n_variants, n_rows)
                       ).result(timeout=600)
        cold, warm = [], []
        for s in range(n_structs):
            if async_on:
                # agent think time: speculatively warm the upcoming
                # structure (AIDE's speculate() hook sends the same hint
                # between rounds) and let the background compile land
                # before the next submit — none of this blocks the
                # measured path
                ses.precompile(_cold_batch(s, 998, n_variants, n_rows))
                svc.plan_cache.executor.drain(timeout=120.0)
            for rep in range(reps):
                t0 = time.perf_counter()
                ses.submit(_cold_batch(s, rep, n_variants, n_rows)
                           ).result(timeout=600)
                (cold if rep == 0 else warm).append(
                    time.perf_counter() - t0)
        g = svc.telemetry.global_snapshot()
    finally:
        svc.stop()
    pc = g.get("plan_cache") or {}
    return {
        "async": async_on,
        "cold_p50_s": float(np.median(cold)),
        "cold_p99_s": float(np.percentile(cold, 99)),
        "cold_max_s": max(cold),
        "warm_p50_s": float(np.median(warm)),
        "warm_p99_s": float(np.percentile(warm, 99)),
        "speculative_hits": pc.get("speculative_hits", 0),
        "async_compiles": pc.get("async_compiles", 0),
    }


def run_compiled_cold(n_structs: int = 4, reps: int = 6,
                      n_variants: int = 8, n_rows: int = 4000) -> dict:
    """First-touch latency when pipeline STRUCTURE keeps changing (an
    agent exploring new stages, not just retuning constants): a ladder of
    ``n_structs`` fresh structures, ``reps`` rounds each.  Blocking mode
    pays trace+jit inside the measured first round of every structure;
    ``compile_async=True`` plus a speculative warm-up hint during agent
    think time keeps the first touch on warm programs.  Each mode runs
    with every compile cache cleared and the persistent XLA cache off (no
    residue from other bench sections or from the other mode's compiles
    of the same structures) so every compile is real — see
    ``_cold_in_process``.

    Gating: ``speculative_hits`` (one per structure — deterministic:
    every measured first touch must land on a speculatively compiled
    program) and the median-based ``cold_p50_speedup``.  With only
    ``n_structs`` cold samples the p99 IS the max, and a single OS-noise
    outlier on a shared CI box would flake a tail gate; the p99s stay in
    the artifact as the headline datapoint, the medians carry the
    gate."""
    from repro.data.tabular import ensure_files
    ensure_files("uk_housing", n_rows, 0)
    blocking = _cold_in_process(
        _cold_mode, False, n_structs, reps, n_variants, n_rows)
    async_m = _cold_in_process(
        _cold_mode, True, n_structs, reps, n_variants, n_rows)
    # the conservative warm reference: slower of the two modes' warm p99
    warm_p99 = max(blocking["warm_p99_s"], async_m["warm_p99_s"])
    return {
        "structs": n_structs, "reps": reps,
        "variants": n_variants, "rows": n_rows,
        "modes": {"blocking": blocking, "async": async_m},
        "warm_p99_s": warm_p99,
        "cold_over_warm_blocking": blocking["cold_p99_s"] / warm_p99,
        "cold_over_warm_async": async_m["cold_p99_s"] / warm_p99,
        "cold_p99_speedup": blocking["cold_p99_s"] / async_m["cold_p99_s"],
        "cold_p50_speedup": blocking["cold_p50_s"] / async_m["cold_p50_s"],
        "speculative_hits": async_m["speculative_hits"],
    }


def compiled_cold_rows(smoke: bool = False,
                       out: str = "BENCH_service.json") -> list:
    kw = (dict(n_structs=3, reps=4, n_variants=6, n_rows=2000)
          if smoke else {})
    r = run_compiled_cold(**kw)
    key = "compiled_cold_smoke" if smoke else "compiled_cold"
    write_service_json({key: r}, out, merge=True)
    m = r["modes"]
    return [
        (f"{key}_blocking_p99", m["blocking"]["cold_p99_s"] * 1e6,
         f"{r['cold_over_warm_blocking']:.1f}x_warm"),
        (f"{key}_async_p99", m["async"]["cold_p99_s"] * 1e6,
         f"{r['cold_over_warm_async']:.1f}x_warm "
         f"(speedup={r['cold_p99_speedup']:.1f}x, "
         f"spec_hits={r['speculative_hits']})"),
        (f"{key}_warm_p99", r["warm_p99_s"] * 1e6, "s_x1e-6"),
    ]


# ---------------------------------------------------------------------------
# deadline-aware scheduling benchmark: SLO attainment under mixed load
# ---------------------------------------------------------------------------

def _deadline_mode(deadline_aware: bool, n_rows: int, n_cohorts: int,
                   n_bulk_agents: int, sweeps_per_agent: int,
                   probe_rows: int, deadline_s: float,
                   probe_interval_s: float) -> dict:
    """One mode of the deadline benchmark: bulk tenants flood the BATCH
    band with deadline-free cohort sweeps while one tenant submits
    sequential probes carrying ``deadline_s`` — the SAME band, so WFQ
    priorities cannot help and only deadline-awareness (EDF tie-break,
    tight-slack solo dispatch, shedding) separates the modes.

    Bulk jobs are ``_cohort_job``\\ s cycling across ``n_cohorts``
    datasets with the intermediate cache squeezed to (effectively)
    nothing: every job recomputes its TableVectorizer prefix, giving each
    bulk job a flat ~0.5s of real work.  (The sharded bench's ~1.3
    working-set squeeze is deliberately NOT used here: with two
    executors, cross-cohort eviction races make job cost — and therefore
    the mode's makespan — bimodal, which would drown the scheduling
    signal this benchmark isolates.)

    The flood is FIXED WORK (``sweeps_per_agent`` jobs each, closed-loop
    3 outstanding) and the prober is OPEN-LOOP (one probe every
    ``probe_interval_s`` until the flood drains): both modes execute the
    same bulk work under the same probe arrival process, so batch
    throughput = total work / makespan is directly comparable, and
    attainment differences come from scheduling alone."""
    mem_budget = 256 << 20
    svc = StratumService(memory_budget_bytes=mem_budget,
                         cache_fraction=1e-5,    # see docstring: flat cost
                         coalesce_window_s=0.02,
                         coalesce_max_jobs=2,
                         max_jobs_per_tenant_per_round=1,
                         n_executors=2,
                         aging_s=None,
                         deadline_aware=deadline_aware,
                         deadline_tight_slack_s=deadline_s)
    try:
        t_start = time.perf_counter()
        flood_done = threading.Event()
        n_flooders_done = [0]
        done_lock = threading.Lock()
        sweeps_done = [0] * n_bulk_agents
        flood_errors: list = []

        def flooder(a: int) -> None:
            try:
                ses = svc.session(f"bulk-{a}")
                from collections import deque
                inflight: "deque" = deque()
                for j in range(sweeps_per_agent):
                    cohort = (a + j) % n_cohorts
                    inflight.append(ses.submit(_cohort_job(
                        cohort, n_rows, a * 100_000 + j)))
                    while len(inflight) >= 3:
                        inflight.popleft().result(timeout=600)
                        sweeps_done[a] += 1
                while inflight:
                    inflight.popleft().result(timeout=600)
                    sweeps_done[a] += 1
            except Exception as e:      # noqa: BLE001
                flood_errors.append(e)
            finally:
                with done_lock:
                    n_flooders_done[0] += 1
                    if n_flooders_done[0] == n_bulk_agents:
                        flood_done.set()

        threads = [threading.Thread(target=flooder, args=(a,))
                   for a in range(n_bulk_agents)]
        for t in threads:
            t.start()
        time.sleep(1.0)            # let the flood reach the runtime
        ses = svc.session("deadline")
        probes: list = []          # (i, t_submit, future)
        done_t: dict = {}          # i -> completion instant (done callback)
        i = 0
        next_t = time.perf_counter()
        while not flood_done.is_set():
            now = time.perf_counter()
            if now >= next_t:
                fut = ses.submit(_probe_batch(i, probe_rows),
                                 deadline_s=deadline_s)
                idx = i
                fut.add_done_callback(
                    lambda f, idx=idx: done_t.setdefault(
                        idx, time.perf_counter()))
                probes.append((idx, now, fut))
                i += 1
                next_t += probe_interval_s
            time.sleep(0.01)
        for t in threads:
            t.join()
        makespan = time.perf_counter() - t_start   # bulk work is done
        lats, scores = [], []
        n_met = n_shed = 0
        for idx, t0, fut in probes:
            try:
                res, _ = fut.result(timeout=600)
                scores.append(float(np.asarray(res[f"probe{idx}"])))
                lat = done_t[idx] - t0
                if lat <= deadline_s:
                    n_met += 1
            except DeadlineExceeded:
                scores.append(None)     # shed = missed, no result at all
                lat = done_t.get(idx, time.perf_counter()) - t0
                n_shed += 1
            lats.append(lat)
        if flood_errors:
            raise flood_errors[0]
        g = svc.telemetry.global_snapshot()
    finally:
        svc.stop()
    return {
        "deadline_aware": deadline_aware,
        "probes_issued": len(lats),
        "attainment": (n_met / len(lats)) if lats else 0.0,
        "probes_met": n_met,
        "probes_shed": n_shed,
        "probe_p50_s": float(np.percentile(lats, 50)) if lats else 0.0,
        "probe_p99_s": float(np.percentile(lats, 99)) if lats else 0.0,
        "sweeps_completed": int(sum(sweeps_done)),
        "batch_makespan_s": makespan,
        "batch_throughput_jobs_per_s": float(sum(sweeps_done)) / makespan,
        "telemetry_deadline": g["deadline"],
        "scores": scores,
        "lats": lats,
    }


def run_deadline(n_rows: int = 30_000, n_cohorts: int = 6,
                 n_bulk_agents: int = 3, sweeps_per_agent: int = 30,
                 probe_rows: int = 2000, deadline_s: float = 0.6,
                 probe_interval_s: float = 1.0, reps: int = 2,
                 warmup: bool = True) -> dict:
    """Deadline-aware scheduling vs deadline-blind, same priority band.

    The claim under test (ROADMAP "deadline/SLO-based scheduling"): with
    EDF tie-breaks + tight-slack solo dispatch + shedding, p99 deadline
    attainment beats the blind scheduler while batch throughput stays
    within a few percent (deadline probes are a tiny fraction of the
    work either way)."""
    from repro.data.tabular import ensure_files
    for c in range(n_cohorts):
        ensure_files("uk_housing", n_rows, c)
    ensure_files("uk_housing", probe_rows, 0)

    if warmup:   # compile the jax kernels once so neither mode pays for it
        s = Stratum(memory_budget_bytes=4 << 30)
        s.run_batch(_cohort_job(0, n_rows, 0))
        for i in range(4):                  # probes rotate column sets;
            s.run_batch(_probe_batch(i, probe_rows))   # compile each shape

    args = (n_rows, n_cohorts, n_bulk_agents, sweeps_per_agent, probe_rows,
            deadline_s, probe_interval_s)
    # interleave repetitions (blind, aware, blind, aware) and pool: one
    # fixed-work run is short enough that XLA/GC noise moves its makespan
    # by whole seconds, and the modes must not sit on opposite sides of a
    # machine-state drift
    blind_runs, aware_runs = [], []
    for _ in range(reps):
        blind_runs.append(_deadline_mode(False, *args))
        aware_runs.append(_deadline_mode(True, *args))

    def _pool(runs: list) -> dict:
        lats = [l for r in runs for l in r["lats"]]
        n = sum(r["probes_issued"] for r in runs)
        met = sum(r["probes_met"] for r in runs)
        out = {
            "deadline_aware": runs[0]["deadline_aware"],
            "reps": len(runs),
            "probes_issued": n,
            "attainment": met / n if n else 0.0,
            "probes_met": met,
            "probes_shed": sum(r["probes_shed"] for r in runs),
            "probe_p50_s": float(np.percentile(lats, 50)) if lats else 0.0,
            "probe_p99_s": float(np.percentile(lats, 99)) if lats else 0.0,
            "sweeps_completed": sum(r["sweeps_completed"] for r in runs),
            "batch_makespan_s": sum(r["batch_makespan_s"] for r in runs),
            "telemetry_deadline": runs[-1]["telemetry_deadline"],
        }
        out["batch_throughput_jobs_per_s"] = (
            out["sweeps_completed"] / out["batch_makespan_s"])
        return out

    aware, blind = _pool(aware_runs), _pool(blind_runs)
    # scores must agree wherever BOTH modes produced a result (aware mode
    # sheds expired probes instead of running them late); compare within
    # each repetition pair — probe index i is deterministic given i
    scored = [(a, b)
              for ra, rb in zip(aware_runs, blind_runs)
              for a, b in zip(ra["scores"], rb["scores"])
              if a is not None and b is not None]
    scores_identical = all(abs(a - b) <= 1e-9 * max(abs(a), 1.0)
                           for a, b in scored)
    blind_tp = blind["batch_throughput_jobs_per_s"]
    aware_tp = aware["batch_throughput_jobs_per_s"]
    return {
        "rows": n_rows,
        "cohorts": n_cohorts,
        "sweeps": n_bulk_agents * sweeps_per_agent * reps,
        "deadline_s": deadline_s,
        "aware": aware,
        "blind": blind,
        "attainment_aware": aware["attainment"],
        "attainment_blind": blind["attainment"],
        "p99_latency_improvement":
            blind["probe_p99_s"] / aware["probe_p99_s"],
        "batch_throughput_ratio": aware_tp / blind_tp if blind_tp else 0.0,
        "scores_identical": scores_identical,
    }


def deadline_rows(smoke: bool = False,
                  out: str = "BENCH_service.json") -> list:
    # smoke: lighter flood AND a looser SLO (2s) — CI runners are slower
    # and more contended than the machines the full datapoint is measured
    # on, and the gated metric is the aware-mode attainment rate
    kw = (dict(n_rows=6000, n_cohorts=4, n_bulk_agents=2,
               sweeps_per_agent=14, deadline_s=2.0, reps=1)
          if smoke else {})
    r = run_deadline(**kw)
    key = "deadline_smoke" if smoke else "deadline"
    write_service_json({key: r}, out, merge=True)
    return [
        (f"{key}_attainment_aware", r["attainment_aware"] * 1e6,
         f"blind={r['attainment_blind']:.2f} "
         f"(p99 {r['p99_latency_improvement']:.1f}x better)"),
        (f"{key}_probe_p99", r["aware"]["probe_p99_s"] * 1e6,
         f"blind={r['blind']['probe_p99_s'] * 1e6:.0f}us"),
        (f"{key}_batch_throughput_ratio",
         r["batch_throughput_ratio"] * 1e6, "aware/blind_x1e-6"),
        (f"{key}_scores_identical", float(r["scores_identical"]),
         "1=identical"),
    ]


# ---------------------------------------------------------------------------
# closed-loop control benchmark: adaptive admission vs static config
# ---------------------------------------------------------------------------

def _control_mode(controlled: bool, n_rows: int, n_cohorts: int,
                  n_bulk_agents: int, steady_sweeps: int, flood_sweeps: int,
                  probe_rows: int, deadline_s: float,
                  probe_interval_s: float) -> dict:
    """One mode of the control benchmark: a two-phase workload against a
    service whose admission gate is deliberately small
    (``max_queued_total=16``).

    Phase 1 (steady mix): each bulk tenant keeps 2 cohort sweeps
    outstanding — the queue never fills, everyone is admitted.  Phase 2
    (batch flood): each bulk tenant jumps to 12 outstanding and retries
    ``AdmissionError`` every 20ms, pinning the queue at its cap.  An
    open-loop prober submits one INTERACTIVE tight-deadline probe every
    ``probe_interval_s`` throughout; probes do NOT retry — a
    latency-bound agent that can't get in has already missed.

    Static config rejects flood-phase probes at the edge ("queue full"),
    so attainment collapses.  The controller's INTERACTIVE admission
    reserve (standing floor clamp) keeps probes admitted mid-flood, and
    its AIMD gate shrinks the bulk bands' queue depth — visible as
    ``retuned`` actuations.  Bulk work is FIXED (steady + flood sweeps
    per agent, closed-loop), so batch throughput stays comparable."""
    mem_budget = 256 << 20
    control = None
    if controlled:
        # p99 target well under the probe SLO: bulk queue wait at the full
        # 16-deep gate (~depth/drain) breaches it, so the AIMD gate
        # actually actuates during the flood (observable retunes)
        # floor at 14: the INTERACTIVE reserve (not the shrink) is what
        # keeps probes admitted, so the gate only needs to shave the
        # flood's queue wait — a deep floor keeps both executors fed and
        # the bulk throughput near static's
        # reserve 2: probes arrive one at a time (interval >> service
        # time), so a tiny reserve already guarantees admission while
        # carving the least bulk capacity out of the shared gate
        control = ControlPolicy(dispatch_p99_target_s=deadline_s / 4.0,
                                interactive_reserve=2,
                                min_queued_total=14,
                                tick_interval_s=0.25,
                                cooldown_s=1.0)
    # ~512KB intermediate cache: holds a repeat probe's working set
    # (2000-row read + 3 projected cols) but not a cohort prefix (~5MB),
    # so bulk-job cost stays flat while repeat probes are served from
    # cache in BOTH modes — the bench compares scheduling policy, not
    # who pays the probes' recompute
    svc = StratumService(memory_budget_bytes=mem_budget,
                         cache_fraction=2e-3,
                         # 5ms window: at 0.1s/job a long gather would
                         # idle an executor slot every time a solo probe
                         # or a thin band pops (the slot is held while
                         # the window waits)
                         coalesce_window_s=0.005,
                         coalesce_max_jobs=2,
                         max_jobs_per_tenant_per_round=1,
                         n_executors=2,
                         aging_s=None,
                         max_queued_total=16,
                         deadline_aware=True,
                         # solo dispatch only for genuinely endangered
                         # probes: at tight_slack == deadline every probe
                         # would dispatch solo from t0 and the drains
                         # would serialize the executors
                         deadline_tight_slack_s=deadline_s / 4.0,
                         control=control)
    try:
        t_start = time.perf_counter()
        flood_done = threading.Event()
        n_flooders_done = [0]
        done_lock = threading.Lock()
        sweeps_done = [0] * n_bulk_agents
        flood_errors: list = []

        def _submit_retry(ses, batch):
            # bulk clients are throughput-bound: back off and retry until
            # the edge admits them (same behaviour in both modes; the
            # backoff is short so a shrunken gate measures the gate, not
            # the client's poll interval)
            while True:
                try:
                    return ses.submit(batch)
                except AdmissionError:
                    time.sleep(0.005)

        def flooder(a: int) -> None:
            try:
                ses = svc.session(f"bulk-{a}")
                from collections import deque
                inflight: "deque" = deque()
                for j in range(steady_sweeps + flood_sweeps):
                    outstanding = 2 if j < steady_sweeps else 12
                    inflight.append(_submit_retry(ses, _cohort_job(
                        (a + j) % n_cohorts, n_rows, a * 100_000 + j)))
                    while len(inflight) >= outstanding:
                        inflight.popleft().result(timeout=600)
                        sweeps_done[a] += 1
                while inflight:
                    inflight.popleft().result(timeout=600)
                    sweeps_done[a] += 1
            except Exception as e:      # noqa: BLE001
                flood_errors.append(e)
            finally:
                with done_lock:
                    n_flooders_done[0] += 1
                    if n_flooders_done[0] == n_bulk_agents:
                        flood_done.set()

        threads = [threading.Thread(target=flooder, args=(a,))
                   for a in range(n_bulk_agents)]
        for t in threads:
            t.start()
        time.sleep(1.0)            # let the steady phase reach the runtime
        ses = svc.session("probe")
        probes: list = []          # (i, t_submit, future) — admitted only
        done_t: dict = {}
        n_rejected = 0
        i = 0
        next_t = time.perf_counter()
        while not flood_done.is_set():
            now = time.perf_counter()
            if now >= next_t:
                try:
                    # rotate over the 4 pre-warmed probe variants: an
                    # unbounded index would make every probe a fresh JIT
                    # compile (~0.3s), and the bench would measure
                    # compilation backpressure instead of scheduling
                    fut = ses.submit(_probe_batch(i % 4, probe_rows),
                                     priority=Priority.INTERACTIVE,
                                     deadline_s=deadline_s)
                except AdmissionError:
                    n_rejected += 1     # rejected at the edge = missed
                else:
                    idx = i
                    fut.add_done_callback(
                        lambda f, idx=idx: done_t.setdefault(
                            idx, time.perf_counter()))
                    probes.append((idx, now, fut))
                i += 1
                next_t += probe_interval_s
            time.sleep(0.01)
        for t in threads:
            t.join()
        makespan = time.perf_counter() - t_start
        lats = []
        scores: dict = {}          # probe index -> score (admitted+done)
        n_met = n_shed = 0
        for idx, t0, fut in probes:
            try:
                res, _ = fut.result(timeout=600)
                scores[idx] = float(np.asarray(res[f"probe{idx % 4}"]))
                lat = done_t[idx] - t0
                if lat <= deadline_s:
                    n_met += 1
                lats.append(lat)
            except DeadlineExceeded:
                n_shed += 1
        if flood_errors:
            raise flood_errors[0]
        g = svc.telemetry.global_snapshot()
    finally:
        svc.stop()
    issued = len(probes) + n_rejected
    ctl = g.get("control") or {}
    return {
        "controlled": controlled,
        "probes_issued": issued,
        "probes_admitted": len(probes),
        "probes_rejected": n_rejected,
        "probes_met": n_met,
        "probes_shed": n_shed,
        "attainment": (n_met / issued) if issued else 0.0,
        "probe_p50_s": float(np.percentile(lats, 50)) if lats else 0.0,
        "probe_p99_s": float(np.percentile(lats, 99)) if lats else 0.0,
        "sweeps_completed": int(sum(sweeps_done)),
        "batch_makespan_s": makespan,
        "batch_throughput_jobs_per_s": float(sum(sweeps_done)) / makespan,
        "retunes": ctl.get("retunes", 0),
        "control_snapshot": ctl or None,
        "scores": scores,
        "lats": lats,
    }


def run_control(n_rows: int = 12_000, n_cohorts: int = 4,
                n_bulk_agents: int = 3, steady_sweeps: int = 10,
                flood_sweeps: int = 60, probe_rows: int = 2000,
                deadline_s: float = 1.5, probe_interval_s: float = 0.4,
                reps: int = 2, warmup: bool = True) -> dict:
    """Closed-loop control vs static config on a two-phase workload.

    The claim under test (ROADMAP "closed-loop control from observed
    latency"): when a batch flood saturates a statically-sized admission
    gate, the feedback controller — INTERACTIVE admission reserve + AIMD
    gate + WFQ rebalancing, all driven by the windowed collector — keeps
    tight-deadline probe attainment high while static config collapses
    to edge rejections, at near-parity batch throughput."""
    from repro.data.tabular import ensure_files
    for c in range(n_cohorts):
        ensure_files("uk_housing", n_rows, c)
    ensure_files("uk_housing", probe_rows, 0)

    if warmup:
        # compile the jax kernels once so neither mode pays for it
        s = Stratum(memory_budget_bytes=4 << 30)
        for c in range(n_cohorts):
            s.run_batch(_cohort_job(c, n_rows, c))
        for i in range(4):                  # probes rotate column sets
            s.run_batch(_probe_batch(i, probe_rows))
        # ... and warm the SERVICE path too: the first service instances
        # in a process run their jobs several times slower than steady
        # state (allocator/dispatch-cache warm-up), and the interleaved
        # rep order (static first) would book all of that cold cost to
        # the static mode, corrupting the throughput ratio
        warm = StratumService(memory_budget_bytes=256 << 20,
                              cache_fraction=1e-5,
                              coalesce_window_s=0.02, coalesce_max_jobs=2,
                              max_jobs_per_tenant_per_round=1,
                              n_executors=2, aging_s=None)
        try:
            ses = warm.session("warm")
            futs = [ses.submit(_cohort_job(c % n_cohorts, n_rows,
                                           10_000 + c))
                    for c in range(6 * n_cohorts)]
            futs += [ses.submit(_probe_batch(i, probe_rows))
                     for i in range(4)]
            for f in futs:
                f.result(timeout=600)
        finally:
            warm.stop()

    args = (n_rows, n_cohorts, n_bulk_agents, steady_sweeps, flood_sweeps,
            probe_rows, deadline_s, probe_interval_s)
    # interleave repetitions and pool (same rationale as the deadline
    # bench: fixed-work makespans drift with machine state), ALTERNATING
    # which mode runs first in each pair: in-process drift biases the
    # second slot of a pair by several percent, and a fixed order books
    # all of it to one mode
    import gc
    static_runs, controlled_runs = [], []
    for rep in range(reps):
        order = (False, True) if rep % 2 == 0 else (True, False)
        for controlled in order:
            gc.collect()
            r = _control_mode(controlled, *args)
            (controlled_runs if controlled else static_runs).append(r)

    def _pool(runs: list) -> dict:
        lats = [l for r in runs for l in r["lats"]]
        issued = sum(r["probes_issued"] for r in runs)
        met = sum(r["probes_met"] for r in runs)
        out = {
            "controlled": runs[0]["controlled"],
            "reps": len(runs),
            "probes_issued": issued,
            "probes_admitted": sum(r["probes_admitted"] for r in runs),
            "probes_rejected": sum(r["probes_rejected"] for r in runs),
            "probes_met": met,
            "probes_shed": sum(r["probes_shed"] for r in runs),
            "attainment": met / issued if issued else 0.0,
            "probe_p50_s": float(np.percentile(lats, 50)) if lats else 0.0,
            "probe_p99_s": float(np.percentile(lats, 99)) if lats else 0.0,
            "sweeps_completed": sum(r["sweeps_completed"] for r in runs),
            "batch_makespan_s": sum(r["batch_makespan_s"] for r in runs),
            "retunes": sum(r["retunes"] for r in runs),
            "control_snapshot": runs[-1]["control_snapshot"],
        }
        out["batch_throughput_jobs_per_s"] = (
            out["sweeps_completed"] / out["batch_makespan_s"])
        return out

    controlled, static = _pool(controlled_runs), _pool(static_runs)
    # scores must agree wherever BOTH modes admitted and completed the
    # same probe index within a repetition pair (probe i is deterministic)
    scored = [(rc["scores"][i], rs["scores"][i])
              for rc, rs in zip(controlled_runs, static_runs)
              for i in set(rc["scores"]) & set(rs["scores"])]
    scores_identical = bool(scored) and all(
        abs(a - b) <= 1e-9 * max(abs(a), 1.0) for a, b in scored)
    static_tp = static["batch_throughput_jobs_per_s"]
    ctl_tp = controlled["batch_throughput_jobs_per_s"]
    return {
        "rows": n_rows,
        "cohorts": n_cohorts,
        "sweeps": n_bulk_agents * (steady_sweeps + flood_sweeps) * reps,
        "deadline_s": deadline_s,
        "controlled": controlled,
        "static": static,
        "attainment_controlled": controlled["attainment"],
        "attainment_static": static["attainment"],
        "retunes": controlled["retunes"],
        "batch_throughput_ratio": ctl_tp / static_tp if static_tp else 0.0,
        "scores_identical": scores_identical,
    }


def control_rows(smoke: bool = False,
                 out: str = "BENCH_service.json") -> list:
    # smoke: lighter flood and a looser SLO (2s), same shape — the gated
    # metric is the controlled-mode attainment under the flood phase.
    # 4 reps: fixed-work makespans drift with machine state, and the
    # alternating first-slot order needs an even count to balance
    kw = (dict(n_rows=6000, n_cohorts=4, n_bulk_agents=2,
               steady_sweeps=8, flood_sweeps=45, probe_rows=1000,
               deadline_s=2.0, probe_interval_s=0.6, reps=4)
          if smoke else {})
    r = run_control(**kw)
    key = "control_smoke" if smoke else "control"
    write_service_json({key: r}, out, merge=True)
    return [
        (f"{key}_attainment_controlled", r["attainment_controlled"] * 1e6,
         f"static={r['attainment_static']:.2f} "
         f"({r['retunes']} retunes)"),
        (f"{key}_attainment_static", r["attainment_static"] * 1e6,
         "static collapses under flood (lower=expected)"),
        (f"{key}_batch_throughput_ratio",
         r["batch_throughput_ratio"] * 1e6, "controlled/static_x1e-6"),
        (f"{key}_retunes", float(r["retunes"]), "actuations>0"),
        (f"{key}_scores_identical", float(r["scores_identical"]),
         "1=identical"),
    ]


# ---------------------------------------------------------------------------
# mixed-priority scheduling benchmark: interactive probes under batch load
# ---------------------------------------------------------------------------

def _probe_batch(i: int, n_rows: int = 4000) -> PipelineBatch:
    """A small, unique, latency-sensitive pipeline (agent blocked on it)."""
    cols = [3 + (i % 5), 8 + (i % 7), 13 + (i % 3)]
    x = T.read("uk_housing", n_rows, seed=0)
    xs = T.scale(T.impute(T.project(x, cols)))
    y = T.project(x, [0])
    sink = T.metric(T.project(xs, [0]), y, kind="mae" if i % 2 else "rmse")
    return PipelineBatch([sink], [f"probe{i}"])


def _sweep_batch(agent: int, j: int, n_rows: int, cv_k: int
                 ) -> PipelineBatch:
    """One bulk sweep job: half the paper's iteration-1 grid (one preproc
    strategy × 4 models), re-seeded per (agent, job) so model fits are
    unique work while reads and preprocessing stay shareable through the
    cache."""
    preproc = ("manual", "table_vectorizer")[j % 2]
    specs = [PipelineSpec(preproc=preproc, model=m, cv_k=cv_k,
                          n_rows=n_rows, seed=1000 * agent + j)
             for m in ("ridge", "elasticnet", "gbt_xgboost",
                       "gbt_lightgbm")]
    names = [f"a{agent}_j{j}_{k}" for k in range(len(specs))]
    return PipelineBatch([s.build() for s in specs], names)


def _mixed_priority_mode(priority_aware: bool, n_rows: int, cv_k: int,
                         n_batch_agents: int,
                         n_probes: int, probe_rows: int) -> dict:
    # small super-batches (2 jobs) keep both executors continuously busy
    # with queued sweep work behind them — the contended regime the
    # scheduler exists for; aging is disabled so the measurement isolates
    # WFQ + preemption (the scavenger band still progresses via weight 1)
    svc = StratumService(memory_budget_bytes=4 << 30,
                         coalesce_window_s=0.02,
                         coalesce_max_jobs=2,
                         max_jobs_per_tenant_per_round=1,
                         n_executors=2,
                         priority_aware=priority_aware,
                         aging_s=None,
                         max_preemptions_per_job=32)
    try:
        t_start = time.perf_counter()
        # closed-loop flood: each bulk tenant keeps 2 sweeps outstanding
        # until the last probe is measured, so EVERY probe (in both modes)
        # is measured under sustained batch contention
        stop = threading.Event()
        sweeps_done = [0] * n_batch_agents
        flood_errors: list = []

        def flooder(a: int) -> None:
            try:
                ses = svc.session(f"bulk-{a}")
                from collections import deque
                inflight: "deque" = deque()
                j = 0
                while not stop.is_set():
                    inflight.append(
                        ses.submit(_sweep_batch(a, j, n_rows, cv_k),
                                   priority=Priority.SCAVENGER))
                    j += 1
                    while len(inflight) >= 2:
                        inflight.popleft().result(timeout=600)
                        sweeps_done[a] += 1
                while inflight:
                    inflight.popleft().result(timeout=600)
                    sweeps_done[a] += 1
            except Exception as e:      # noqa: BLE001
                flood_errors.append(e)

        threads = [threading.Thread(target=flooder, args=(a,))
                   for a in range(n_batch_agents)]
        for t in threads:
            t.start()
        time.sleep(1.0)            # let sweeps reach the runtime
        inter = svc.session("interactive")
        lats, scores = [], []
        for i in range(n_probes):
            t0 = time.perf_counter()
            res, _ = inter.submit(_probe_batch(i, probe_rows),
                                  priority=Priority.INTERACTIVE
                                  ).result(timeout=600)
            lats.append(time.perf_counter() - t0)
            scores.append(float(np.asarray(res[f"probe{i}"])))
            time.sleep(0.25)       # agent "thinks" between probes
        stop.set()
        for t in threads:
            t.join()
        makespan = time.perf_counter() - t_start
        if flood_errors:
            raise flood_errors[0]
        g = svc.telemetry.global_snapshot()
        snap = svc.telemetry.snapshot()
    finally:
        svc.stop()
    return {
        "interactive_p50_s": float(np.percentile(lats, 50)),
        "interactive_p99_s": float(np.percentile(lats, 99)),
        "interactive_mean_s": float(np.mean(lats)),
        "interactive_max_s": float(np.max(lats)),
        "sweeps_completed": int(sum(sweeps_done)),
        "batch_makespan_s": makespan,
        "batch_throughput_jobs_per_s":
            float(sum(sweeps_done)) / makespan,
        "preemptions": g["preemptions"],
        "interactive_queue_wait_s":
            snap["interactive"]["queue_wait_s"],
        "scores": scores,
    }


def run_mixed_priority(n_rows: int = 8000, cv_k: int = 2,
                       n_batch_agents: int = 2,
                       n_probes: int = 10, probe_rows: int = 4000,
                       warmup: bool = True) -> dict:
    """Priority-aware WFQ + preemption vs priority-blind round-robin."""
    from repro.data.tabular import ensure_files
    ensure_files("uk_housing", n_rows, 0)
    ensure_files("uk_housing", probe_rows, 0)

    if warmup:   # compile the jax kernels once so neither mode pays for it
        s = Stratum(memory_budget_bytes=4 << 30)
        s.run_batch(_sweep_batch(0, 0, n_rows, cv_k))
        s.run_batch(_probe_batch(0, probe_rows))

    blind = _mixed_priority_mode(False, n_rows, cv_k, n_batch_agents,
                                 n_probes, probe_rows)
    aware = _mixed_priority_mode(True, n_rows, cv_k, n_batch_agents,
                                 n_probes, probe_rows)
    scores_identical = all(
        abs(a - b) <= 1e-9 * max(abs(a), 1.0)
        for a, b in zip(aware["scores"], blind["scores"]))
    return {
        "rows": n_rows,
        "probes": n_probes,
        "priority_aware": aware,
        "priority_blind": blind,
        "p50_improvement":
            blind["interactive_p50_s"] / aware["interactive_p50_s"],
        "p99_improvement":
            blind["interactive_p99_s"] / aware["interactive_p99_s"],
        "scores_identical": scores_identical,
    }


def mixed_priority_rows(**kw) -> list:
    r = run_mixed_priority(**kw)
    write_service_json({"mixed_priority": r}, merge=True)
    a, b = r["priority_aware"], r["priority_blind"]
    return [
        ("priority_interactive_p50", a["interactive_p50_s"] * 1e6,
         f"blind={b['interactive_p50_s'] * 1e6:.0f}us "
         f"({r['p50_improvement']:.1f}x)"),
        ("priority_interactive_p99", a["interactive_p99_s"] * 1e6,
         f"blind={b['interactive_p99_s'] * 1e6:.0f}us "
         f"({r['p99_improvement']:.1f}x)"),
        ("priority_preemptions", float(a["preemptions"]), "cooperative"),
        ("priority_scores_identical", float(r["scores_identical"]),
         "1=identical"),
    ]


def service_rows(n_agents: int = 4, n_rows: int = 20_000,
                 smoke: bool = False,
                 out: str = "BENCH_service.json") -> list:
    r = run_service(n_agents=n_agents, n_rows=n_rows)
    prefix = "service_smoke" if smoke else "service"
    if smoke:      # CI-sized datapoint, gated by check_regression.py
        write_service_json({"service_smoke": r}, out, merge=True)
    else:
        write_service_json(r, out, merge=True)
    return [
        (f"{prefix}_sequential", r["sequential_s"] * 1e6,
         f"{r['agents']}_isolated_sessions"),
        (f"{prefix}_concurrent", r["service_s"] * 1e6,
         f"speedup={r['speedup']:.1f}x"),
        (f"{prefix}_deduped_ops", float(r["ops_deduped_cross_agent"]),
         "cross_agent"),
        (f"{prefix}_cache_hits", float(r["shared_cache_hits"]),
         "shared_cache"),
        (f"{prefix}_score_agreement", r["score_rel_diff"] * 1e6,
         "rel_diff_x1e-6"),
    ]


# ---------------------------------------------------------------------------
# observability overhead: traced vs untraced throughput, same workload
# ---------------------------------------------------------------------------

def _traced_mode(traced: bool, rounds: int, n_variants: int, n_rows: int,
                 trace_dir=None) -> dict:
    """One mode of the observability benchmark: the compiled section's
    repeated-structure refinement workload, with per-job lifecycle
    tracing (and, when ``trace_dir`` is set, the flushed JSONL event
    log) either on or off.  Everything else is held identical."""
    svc = StratumService(memory_budget_bytes=2 << 30,
                         coalesce_window_s=0.0,
                         n_executors=1,
                         trace=traced,
                         trace_dir=trace_dir if traced else None)
    try:
        ses = svc.session("agent")
        for w in (rounds, rounds + 1):        # warmup (see _compiled_mode)
            ses.submit(_refinement_batch(w, n_variants, n_rows)
                       ).result(timeout=600)
        # the measured window is a few milliseconds: a full collection of
        # the process heap (tens of ms, whichever mode's allocations
        # trigger it) would otherwise land in one mode's window
        import gc
        gc.collect()
        t0 = time.perf_counter()
        for r in range(rounds):
            _, rep = ses.submit(_refinement_batch(r, n_variants, n_rows)
                                ).result(timeout=600)
        makespan = time.perf_counter() - t0
        last_trace = rep.trace
    finally:
        svc.stop()
    return {
        "traced": traced,
        "makespan_s": makespan,
        "pipelines_per_s": rounds * n_variants / makespan,
        "last_trace_hops": len(last_trace),
    }


def run_observability(rounds: int = 8, n_variants: int = 6,
                      n_rows: int = 3000) -> dict:
    """Tracing overhead on the repeated-structure workload: full hop
    tracing + JSONL event log vs tracing off.  The gated metric is the
    throughput ratio ``traced_over_untraced`` — the committed baseline
    pins it at 1.0 (parity), so the CI gate enforces an absolute tracing
    overhead budget rather than drift against a noisy measurement."""
    import tempfile

    from repro.service.observability import replay

    from repro.data.tabular import ensure_files
    ensure_files("uk_housing", n_rows, 0)
    untraced = _traced_mode(False, rounds, n_variants, n_rows)
    with tempfile.TemporaryDirectory() as td:
        traced = _traced_mode(True, rounds, n_variants, n_rows,
                              trace_dir=td)
        timelines = replay.reassemble(replay.load_events(td))
        jobs_traced = len(timelines)
        replayable = all(
            hops and hops[-1]["event"] == "completed"
            for hops in timelines.values())
    return {
        "rounds": rounds,
        "variants": n_variants,
        "rows": n_rows,
        "modes": {"untraced": untraced, "traced": traced},
        "traced_over_untraced": (traced["pipelines_per_s"]
                                 / untraced["pipelines_per_s"]),
        "overhead_frac": max(0.0, 1.0 - traced["pipelines_per_s"]
                             / untraced["pipelines_per_s"]),
        # the traced run really produced a replayable event log: every
        # measured+warmup job reassembled to a completed timeline
        "jobs_traced": jobs_traced,
        "replayable": bool(replayable and jobs_traced >= rounds),
        "trace_hops_per_job": traced["last_trace_hops"],
    }


def observability_rows(smoke: bool = False,
                       out: str = "BENCH_service.json") -> list:
    kw = dict(rounds=4, n_variants=5, n_rows=2000) if smoke else {}
    r = run_observability(**kw)
    key = "observability_smoke" if smoke else "observability"
    write_service_json({key: r}, out, merge=True)
    m = r["modes"]
    return [
        (f"{key}_untraced", m["untraced"]["makespan_s"] * 1e6,
         f"{m['untraced']['pipelines_per_s']:.1f}_pipelines_per_s"),
        (f"{key}_traced", m["traced"]["makespan_s"] * 1e6,
         f"{m['traced']['pipelines_per_s']:.1f}_pipelines_per_s "
         f"(ratio={r['traced_over_untraced']:.3f})"),
        (f"{key}_overhead_frac", r["overhead_frac"] * 1e6,
         "frac_x1e-6"),
        (f"{key}_replayable", float(r["replayable"]),
         f"{r['jobs_traced']}_jobs_traced"),
    ]


# ---------------------------------------------------------------------------
# pre-flight analysis benchmark: admission-time rejection vs execute-to-fail
# ---------------------------------------------------------------------------

def _invalid_batch(i: int, n_rows: int) -> PipelineBatch:
    """A statically-invalid pipeline: an op no backend implements.  With
    admission analysis OFF the job travels the whole queue before the
    executor's compile step rejects it; ON, ``submit`` itself raises."""
    from repro.core.dag import LazyOp, TRANSFORM
    t = T.read("uk_housing", n_rows, seed=0)
    bad = LazyOp(f"no_such_op_{i % 3}", TRANSFORM, inputs=(t,)).out()
    return PipelineBatch([bad], [f"bad_{i}"])


def _analysis_mode(admission: bool, rounds: int, n_variants: int,
                   n_rows: int, invalid_every: int) -> dict:
    """One mode of the analysis benchmark: the compiled section's
    repeated-structure refinement flood with a fixed fraction of
    statically-invalid submissions mixed in, admission analysis either
    on or off.  Measures valid-traffic throughput and the wall time from
    ``submit`` to the invalid jobs' verdicts."""
    from repro.core.analysis import AnalysisError
    svc = StratumService(memory_budget_bytes=2 << 30,
                         coalesce_window_s=0.0,
                         n_executors=1,
                         admission_analysis=admission)
    try:
        ses = svc.session("agent")
        # invalid traffic rides its own tenant: with analysis off the
        # coalescer would otherwise merge a bad job into a valid cohort
        # and fail the whole merged compile
        bad_ses = svc.session("adversary")
        for w in (rounds, rounds + 1):        # warmup (see _compiled_mode)
            ses.submit(_refinement_batch(w, n_variants, n_rows)
                       ).result(timeout=600)
        verdicts: list = []
        vlock = threading.Lock()
        valid_futures = []
        n_invalid = sync_rejects = 0
        t0 = time.perf_counter()
        for r in range(rounds):
            valid_futures.append(
                ses.submit(_refinement_batch(r, n_variants, n_rows)))
            if (r + 1) % invalid_every:
                continue
            n_invalid += 1
            tb = time.perf_counter()
            try:
                fut = bad_ses.submit(_invalid_batch(r, n_rows))
            except AnalysisError:             # rejected at submit
                sync_rejects += 1
                with vlock:
                    verdicts.append(time.perf_counter() - tb)
            else:                             # verdict rides the future

                def _stamp(_f, tb=tb):
                    with vlock:
                        verdicts.append(time.perf_counter() - tb)
                fut.add_done_callback(_stamp)
        for f in valid_futures:
            f.result(timeout=600)
        makespan = time.perf_counter() - t0
        deadline = time.perf_counter() + 120
        while time.perf_counter() < deadline:
            with vlock:
                if len(verdicts) >= n_invalid:
                    break
            time.sleep(0.01)
        snap = svc.telemetry.global_snapshot().get("analysis", {})
    finally:
        svc.stop()
    return {
        "admission": admission,
        "makespan_s": makespan,
        "pipelines_per_s": rounds * n_variants / makespan,
        "n_invalid": n_invalid,
        "rejected_at_submit": sync_rejects,
        "verdict_mean_s": (sum(verdicts) / len(verdicts)) if verdicts
        else float("inf"),
        "verdict_max_s": max(verdicts) if verdicts else float("inf"),
        "telemetry": snap,
    }


def run_analysis(rounds: int = 8, n_variants: int = 6, n_rows: int = 3000,
                 invalid_every: int = 2, repeats: int = 2) -> dict:
    """Pre-flight static analysis at admission (docs/ANALYSIS.md) on an
    agent flood with a fixed invalid fraction.  Two gated metrics:

    * ``reject_speedup`` — how much sooner an invalid submission gets its
      verdict when rejected at submit instead of failing at the executor
      behind the queue (must stay well above 1);
    * ``valid_work_frac`` — 1 minus the fraction of the analyzed mode's
      makespan spent inside the analyzer (from the telemetry ``analysis``
      block, so cached verdicts count at their true ~zero cost); the
      committed baseline pins it at 1.0, so the 0.05 gate tolerance IS
      the analyzer-overhead budget (≤5% of valid-traffic wall time).

    ``analyzed_over_plain`` (end-to-end throughput ratio, on vs off) is
    also recorded, informationally: the true analyzer overhead is well
    under the run-to-run makespan noise at smoke sizes, so the ratio
    hovers around 1.0 and is not a stable gate."""
    from repro.data.tabular import ensure_files
    ensure_files("uk_housing", n_rows, 0)
    # alternate modes and keep each mode's best repeat: a single ~1s
    # makespan flakes on scheduler/compile noise, the min of two does not
    plain = analyzed = None
    for _ in range(repeats):
        p = _analysis_mode(False, rounds, n_variants, n_rows,
                           invalid_every)
        a = _analysis_mode(True, rounds, n_variants, n_rows,
                           invalid_every)
        if plain is None or p["makespan_s"] < plain["makespan_s"]:
            plain = p
        if analyzed is None or a["makespan_s"] < analyzed["makespan_s"]:
            analyzed = a
    analyzer_s = float(analyzed["telemetry"].get("time_s", 0.0))
    return {
        "rounds": rounds,
        "variants": n_variants,
        "rows": n_rows,
        "invalid_every": invalid_every,
        "modes": {"plain": plain, "analyzed": analyzed},
        "reject_speedup": (plain["verdict_mean_s"]
                           / max(analyzed["verdict_mean_s"], 1e-9)),
        "analyzed_over_plain": (analyzed["pipelines_per_s"]
                                / plain["pipelines_per_s"]),
        "valid_work_frac": max(
            0.0, 1.0 - analyzer_s / analyzed["makespan_s"]),
        # every invalid job was caught synchronously at submit, and every
        # valid job still completed (a false rejection would have raised)
        "all_rejected_at_submit": (analyzed["rejected_at_submit"]
                                   == analyzed["n_invalid"]),
        "analysis_telemetry": analyzed["telemetry"],
    }


def analysis_rows(smoke: bool = False,
                  out: str = "BENCH_service.json") -> list:
    kw = dict(rounds=6, n_variants=5, n_rows=2000, repeats=3) if smoke else {}
    r = run_analysis(**kw)
    key = "analysis_smoke" if smoke else "analysis"
    write_service_json({key: r}, out, merge=True)
    m = r["modes"]
    return [
        (f"{key}_verdict_plain", m["plain"]["verdict_mean_s"] * 1e6,
         f"{m['plain']['n_invalid']}_invalid_execute_to_fail"),
        (f"{key}_verdict_analyzed", m["analyzed"]["verdict_mean_s"] * 1e6,
         f"reject_speedup={r['reject_speedup']:.1f}x"),
        (f"{key}_throughput_ratio", r["analyzed_over_plain"] * 1e6,
         "analyzed_over_plain (informational)"),
        (f"{key}_valid_work_frac", r["valid_work_frac"] * 1e6,
         "1-analyzer_overhead (gate: >=0.95)"),
        (f"{key}_rejected_at_submit", float(r["all_rejected_at_submit"]),
         f"{m['analyzed']['rejected_at_submit']}_sync_rejects"),
    ]


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser()
    # None = "not passed": each mode picks its own default (service 4
    # agents / 20k rows, mixed-priority 8k rows, sharded 16 agents / 30k
    # rows — the parameters the committed BENCH_service.json entries and
    # the docs' numbers were measured at)
    ap.add_argument("--agents", type=int, default=None)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--cv", type=int, default=3)
    ap.add_argument("--out", default="BENCH_service.json")
    ap.add_argument("--mixed-priority", action="store_true",
                    help="interactive latency under batch load: priority-"
                         "aware WFQ+preemption vs priority-blind")
    ap.add_argument("--deadline", action="store_true",
                    help="SLO attainment under mixed load: deadline-aware "
                         "EDF+shedding vs deadline-blind (same band)")
    ap.add_argument("--control", action="store_true",
                    help="closed-loop admission/WFQ control vs static "
                         "config on a two-phase flood workload")
    ap.add_argument("--shards", type=int, default=0, metavar="N",
                    help="sharded-fabric scaling: compare 1 shard vs N "
                         "shards at --agents agents (default 16)")
    ap.add_argument("--rounds", type=int, default=3,
                    help="sweep rounds per agent, submitted open-loop "
                         "(--shards mode)")
    args = ap.parse_args()
    if args.shards:
        r = run_sharded(n_agents=args.agents or 16, rounds=args.rounds,
                        n_rows=args.rows or 30_000,
                        shard_counts=(1, args.shards))
        write_service_json({"sharded": r}, args.out, merge=True)
        for k in sorted(r["modes"], key=int):
            m = r["modes"][k]
            print(f"{k} shard(s): makespan {m['makespan_s']:.2f}s  "
                  f"{m['throughput_jobs_per_s']:.2f} jobs/s  "
                  f"locality={m['locality_hit_rate']:.2f}")
        print(f"aggregate throughput speedup: {r['speedup']:.1f}x  "
              f"scores identical: {r['scores_identical']}")
        print(f"wrote {args.out}")
        return
    if args.control:
        r = run_control(**(dict(n_rows=args.rows) if args.rows else {}))
        write_service_json({"control": r}, args.out, merge=True)
        c, s = r["controlled"], r["static"]
        print(f"attainment: controlled {r['attainment_controlled']:.2f} "
              f"vs static {r['attainment_static']:.2f} at deadline "
              f"{r['deadline_s'] * 1e3:.0f}ms "
              f"({r['retunes']} retunes, "
              f"{s['probes_rejected']} static edge rejections)")
        print(f"batch throughput ratio (controlled/static): "
              f"{r['batch_throughput_ratio']:.3f}")
        print(f"scores identical where both ran: {r['scores_identical']}")
        print(f"wrote {args.out}")
        return
    if args.deadline:
        r = run_deadline(n_rows=args.rows or 8000)
        write_service_json({"deadline": r}, args.out, merge=True)
        a, b = r["aware"], r["blind"]
        print(f"attainment: aware {r['attainment_aware']:.2f} vs blind "
              f"{r['attainment_blind']:.2f} at deadline "
              f"{r['deadline_s'] * 1e3:.0f}ms")
        print(f"probe p99: aware {a['probe_p99_s'] * 1e3:.0f}ms vs blind "
              f"{b['probe_p99_s'] * 1e3:.0f}ms "
              f"({r['p99_latency_improvement']:.1f}x)")
        print(f"batch throughput ratio (aware/blind): "
              f"{r['batch_throughput_ratio']:.3f}")
        print(f"scores identical where both ran: {r['scores_identical']}")
        print(f"wrote {args.out}")
        return
    if args.mixed_priority:
        r = run_mixed_priority(n_rows=args.rows or 8000, cv_k=args.cv)
        write_service_json({"mixed_priority": r}, args.out, merge=True)
        a, b = r["priority_aware"], r["priority_blind"]
        print(f"interactive p50: aware {a['interactive_p50_s'] * 1e3:.0f}ms"
              f" vs blind {b['interactive_p50_s'] * 1e3:.0f}ms"
              f"  ({r['p50_improvement']:.1f}x)")
        print(f"interactive p99: aware {a['interactive_p99_s'] * 1e3:.0f}ms"
              f" vs blind {b['interactive_p99_s'] * 1e3:.0f}ms"
              f"  ({r['p99_improvement']:.1f}x)")
        print(f"preemptions (aware): {a['preemptions']}  "
              f"batch makespan: aware {a['batch_makespan_s']:.1f}s "
              f"vs blind {b['batch_makespan_s']:.1f}s")
        print(f"probe scores identical across modes: "
              f"{r['scores_identical']}")
        print(f"wrote {args.out}")
        return
    n_agents = args.agents or 4
    r = run_service(n_agents=n_agents, n_rows=args.rows or 20_000,
                    cv_k=args.cv)
    write_service_json(r, args.out, merge=True)
    print(f"{n_agents} sequential sessions: {r['sequential_s']:.2f}s")
    print(f"{n_agents} agents via service:  {r['service_s']:.2f}s "
          f"({r['speedup']:.1f}x)")
    print(f"cross-agent ops deduped: {r['ops_deduped_cross_agent']}  "
          f"shared-cache hits: {r['shared_cache_hits']}")
    print(r["telemetry_report"])
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
