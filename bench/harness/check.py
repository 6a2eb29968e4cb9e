"""Whether the timed path's answers are correct: a sample of the jobs the
window completed, drawn from the seed, scored again by the plain
reference, and the widest relative gap of each model family held to its
limit.  Every family the traffic sends has to be checked: a family with
no completed job in the window has no reading and is not correct.

The limits depend on the table's size, so they sit in the configuration
file (``check_limits``, per family), set from readings on the chip as
PERF.md records: above the widest gap sound runs of the program gave,
and below the narrowest gap the control gave (the reference with each
fold's model fit on half its training rows, ``bench/control.py``).

``check_skip`` in the configuration lists the (preprocessing, family)
pairs whose answers are not compared: a GBT on target-encoded features.
The program sums each category's target in float32 on the device and the
reference in float64, so an encoding differs by a few units in the last
place; where that moves a row across a bin edge, a split whose gain ties
another's goes the other way and changes every later tree (PERF.md
gives the readings).  The GBT is compared on the other preprocessing,
whose features are exact in float32 on both sides, and the target
encodings through the linear families."""

from __future__ import annotations

import numpy as np

from bench.reference import pipeline as ref

FAMILY = {"ridge": "ridge", "elasticnet": "enet",
          "gbt_xgboost": "gbt", "gbt_lightgbm": "gbt"}


def compared(job: dict, config: dict) -> bool:
    """Whether the check compares this job's answer (``check_skip``)."""
    return [job["preproc"], FAMILY[job["model"]]] not in \
        config.get("check_skip", [])


def sample(records: list, per_family: dict, seed: int, config: dict) -> list:
    """Up to ``per_family[family]`` completed jobs of each family that the
    check compares, drawn from the seed, the longest-running job of each
    family always among them."""
    rng = np.random.default_rng([seed % (1 << 64), 7])
    by_family: dict = {}
    for r in records:
        if compared(r.job, config):
            by_family.setdefault(FAMILY[r.job["model"]], []).append(r)
    out = []
    for fam in sorted(by_family):
        recs = sorted(by_family[fam], key=lambda r: r.job["id"])
        longest = max(recs, key=lambda r: r.done - r.submitted)
        rest = [r for r in recs if r is not longest]
        pick = rng.permutation(len(rest))[:max(per_family[fam] - 1, 0)]
        out.append(longest)
        out.extend(rest[i] for i in sorted(pick))
    return out


class Features:
    """Host features of each job's table and preprocessing, computed once
    per distinct (table, preprocessing) in a run."""

    def __init__(self, lake: str, config: dict):
        self.lake = lake
        self.config = config
        self._tables: dict = {}
        self._feats: dict = {}

    def __call__(self, job: dict):
        key = (job["table"], job["preproc"],
               job["te_smoothing"] if job["preproc"] == "manual" else None,
               job["enc_seed"])
        if key not in self._feats:
            if job["table"] not in self._tables:
                self._tables[job["table"]] = np.load(ref.table_path(
                    self.lake, self.config["dataset"], job["rows"],
                    job["table"]))
            self._feats[key] = ref.features(self._tables[job["table"]], job,
                                            self.config["schema"])
        return self._feats[key]


def families(traffic: dict) -> list:
    """The model families a traffic mix sends."""
    return sorted({FAMILY[m] for m in traffic["grids"]})


def compare(records: list, lake: str, config: dict, per_family: dict,
            seed: int, sent: list) -> dict:
    """``{family: {"value": widest relative gap, "limit": ..., "jobs": n}}``
    over the sampled records, each scored by the reference; a family of
    ``sent`` with no record reads ``None``."""
    limits = config["check_limits"]
    feats = Features(lake, config)
    out = {fam: {"value": None, "limit": limits[fam], "jobs": 0}
           for fam in sent}
    for r in sample(records, per_family, seed, config):
        X, y = feats(r.job)
        want = ref.score(r.job, X, y)
        gap = abs(r.score - want) / abs(want)
        row = out[FAMILY[r.job["model"]]]
        row["value"] = max(row["value"] or 0.0, gap)
        row["jobs"] += 1
    return out


def passed(checks: dict) -> bool:
    """Every compared number read, and at or under its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
