"""The control of a cell's correctness check, at the cell's own size: the
plain reference put in the program's place, breaking the configuration's
guarantee that a job's score is its pipeline's exact 3-fold score: each
fold's model is fit on half of the fold's training rows (an approximate
answer, the shortcut a faster fit would tempt).  Its scores go through
the run's own check (``bench/harness/check.py``), which has to come out
not correct.

    python3 bench/control.py --workload sweep.quarter250k --seeds 1,2,3

Prints one JSON line per seed: the check's numbers beside their limits,
the smallest gap of each family over its jobs, and ``correct``."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run as R  # noqa: E402


def window_jobs(gen, traffic: dict, config: dict, per_family: dict) -> list:
    """The first ``per_family[family]`` jobs of each model family that
    the window's agents send and the check compares."""
    from bench.harness.check import FAMILY, compared, families
    agents = [gen.agent_jobs(i) for i in range(traffic["agents"])]
    out, count = [], {fam: 0 for fam in families(traffic)}
    while any(count[fam] < per_family[fam] for fam in count):
        for a in agents:
            j = next(a)
            fam = FAMILY[j["model"]]
            if compared(j, config) and count[fam] < per_family[fam]:
                out.append(j)
                count[fam] += 1
    return out


def control_checks(jobs: list, lake: str, config: dict, traffic: dict,
                   seed: int) -> tuple:
    """``(checks, least gap per family)`` of the control's scores of
    ``jobs``, through the run's own comparison."""
    from bench.harness import check
    from bench.harness.drive import Record
    from bench.reference import pipeline as ref

    feats = check.Features(lake, config)
    records, least = [], {}
    for job in jobs:
        X, y = feats(job)
        got = ref.score(job, X, y, half=True)
        records.append(Record(job=job, due=0.0, submitted=0.0, done=0.0,
                              score=got))
        want = ref.score(job, X, y)
        gap = abs(got - want) / abs(want)
        fam = check.FAMILY[job["model"]]
        least[fam] = min(least.get(fam, float("inf")), gap)
    counts = {fam: len(jobs) for fam in check.families(traffic)}
    checks = check.compare(records, lake, config, counts, seed,
                           check.families(traffic))
    return checks, least


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--jobs", type=int, default=0,
                    help="jobs of every model family per seed (default: "
                         "as many of each as a run checks)")
    args = ap.parse_args(argv)
    bench, cell, config, traffic = R.load_cell(R.ROOT, args.workload)
    R.prepare_env(R.ROOT)
    from bench.harness import check, table
    from bench.harness.traffic import Traffic

    lake = os.environ["REPRO_DATA_LAKE"]
    per_family = ({fam: args.jobs for fam in check.families(traffic)}
                  if args.jobs else traffic["check_per_family"])
    for seed in (int(s) for s in args.seeds.split(",")):
        table.write(config, seed, lake)
        jobs = window_jobs(Traffic(traffic, config, seed), traffic, config,
                           per_family)
        checks, least = control_checks(jobs, lake, config, traffic, seed)
        print(json.dumps({"seed": seed, "checks": checks,
                          "least_gap": least,
                          "correct": check.passed(checks)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
