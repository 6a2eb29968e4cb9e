"""The one traffic generator: a traffic file of parameters plus a
configuration plus a seed give the set-up's warm-up jobs and the window's
jobs.

A traffic file (``bench/traffic/<name>.json``) holds:

* ``agents``: agents in a closed loop on the configuration's first
  table, each with one job in flight;
* ``preprocs`` and ``grids``: the pipelines, every preprocessing with
  every point of each model's grid (the product of its lists, the first
  list turning fastest, so that two consecutive points hold both values
  of the first list, which with the second sets a fit's cost);
  ``tunables``: the hyperparameters of each model that the program hoists
  out of a pipeline's structure, so that every other one makes a
  structure of its own; ``te_smoothing``: the target encoder's smoothing;
* ``cv_seeds``: the pool of fold seeds, shared by every family; each job
  is one pipeline with one fold seed of the pool;
* ``warmup``: ``params`` and ``te_smoothing`` for the tunables, off the
  grid, so no warm-up job is a window job; ``shape_by_cv_seed``: the
  model whose fit's shape the fold seed sets (a row subsample of
  ``subsample`` drawn from it), with the grid keys that, with the
  preprocessing, make its other shapes; ``twin_from``: where the search
  for each pool seed's warm-up twin starts.

Each agent searches one (preprocessing, model) group: agent ``i`` the
group ``i mod groups``, so every group has the same number ``k`` of
agents (two, with 16 agents and the eight groups of Section 6).  At its
``j``-th step the ``k`` agents of group ``g`` take the ``k`` consecutive
grid points from position ``k·(t + g + j)``, and every agent the fold seed
``pool[(shift + j // S) mod len(pool)]``, with ``S`` the steps in which no
group's agents come back to a point.  The service coalesces the jobs in
flight, so the agents move in steps; each step sends every group's
``k`` points, and of the GBT groups half take the grid's shallow points
and half its deep ones.  So every step, for every seed, holds the same
mix of work; the seed draws ``t`` and ``shift``, which turn every
grid's order and the pool's, and no job repeats within a run.
"""

from __future__ import annotations

import itertools

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    """Independent streams of one run seed (any integer, negatives too)."""
    return np.random.default_rng([seed % (1 << 64), stream])


class Traffic:
    """Jobs of one cell, from its traffic file, configuration and seed."""

    def __init__(self, traffic: dict, config: dict, seed: int):
        self.t = traffic
        self.config = config
        self.seed = seed
        self._ids = itertools.count()

    # -- the pipelines ---------------------------------------------------------
    def groups(self) -> list:
        """``[((preproc, model), [params, ...])]`` in the file's order."""
        out = []
        for preproc in self.t["preprocs"]:
            for model, grid in self.t["grids"].items():
                keys = list(grid)
                points = [dict(zip(keys, v[::-1])) for v in
                          itertools.product(*list(grid.values())[::-1])]
                out.append(((preproc, model), points))
        return out

    def structure(self, preproc: str, model: str, params: dict) -> tuple:
        tun = set(self.t.get("tunables", {}).get(model, ()))
        return (preproc, model,
                tuple(sorted((k, v) for k, v in params.items()
                             if k not in tun)))

    def job(self, agent: str, preproc: str, model: str, params: dict,
            cv_seed: int, te_smoothing: float) -> dict:
        c = self.config
        return {"id": next(self._ids), "agent": agent,
                "table": int(c["tables"][0]), "rows": c["rows"],
                "preproc": preproc, "te_smoothing": float(te_smoothing),
                "model": model, "params": dict(params),
                "enc_seed": c["encoder_seed"], "cv_seed": int(cv_seed),
                "cv_k": c["cv_k"]}

    # -- the window --------------------------------------------------------------
    def agent_jobs(self, agent_index: int):
        """Endless job sequence of one closed-loop agent."""
        groups = self.groups()
        g = agent_index % len(groups)
        (preproc, model), points = groups[g]
        k = len(range(g, self.t["agents"], len(groups)))
        h = agent_index // len(groups)
        pool = self.t["cv_seeds"]
        steps = min(len(pts) // len(range(i, self.t["agents"], len(groups)))
                    for i, (_, pts) in enumerate(groups)
                    if i < self.t["agents"])
        rng = _rng(self.seed, 1)
        t, shift = int(rng.integers(1 << 16)), int(rng.integers(len(pool)))
        for j in itertools.count():
            point = points[(k * (t + g + j) + h) % len(points)]
            cv_seed = pool[(shift + j // steps) % len(pool)]
            yield self.job(f"agent-{agent_index}", preproc, model, point,
                           cv_seed, self.t["te_smoothing"])

    # -- set-up ------------------------------------------------------------------
    def twin(self, cv_seed: int, taken: set) -> int:
        """A fold seed outside the pool whose subsample keeps as many of a
        fold's training rows as ``cv_seed``'s: the same fit shape, and
        another pipeline."""
        shape = self.t["warmup"]["shape_by_cv_seed"]
        c = self.config
        n_train = c["rows"] - c["rows"] // c["cv_k"]

        def kept(s):
            return int((np.random.default_rng(s).random(n_train)
                        < shape["subsample"]).sum())
        want = kept(cv_seed)
        s = self.t["warmup"]["twin_from"]
        while s in taken or s in self.t["cv_seeds"] or kept(s) != want:
            s += 1
        return s

    def warmup_rounds(self) -> list:
        """Rounds of warm-up jobs, the same for every seed.  Rounds 0 and
        1 run every structure, with the twins of the first two pool seeds:
        round 1 finds round 0's intermediates cached, as window jobs find
        theirs, which changes the compiled segments it runs.  Each later
        round runs, with the next pool seed's twin, one job of every shape
        the fold seed sets."""
        warm = self.t["warmup"]
        shape = warm["shape_by_cv_seed"]
        structures, shapes = {}, {}
        for (preproc, model), points in self.groups():
            for p in points:
                params = dict(p, **warm["params"].get(model, {}))
                structures.setdefault(self.structure(preproc, model, p),
                                      (preproc, model, params))
                if model == shape["model"]:
                    key = (preproc,) + tuple(p[k] for k in shape["keys"])
                    shapes.setdefault(key, (preproc, model, params))
        taken: set = set()
        rounds = []
        for k, cv_seed in enumerate(self.t["cv_seeds"]):
            twin = self.twin(cv_seed, taken)
            taken.add(twin)
            jobs = structures if k < 2 else shapes
            rounds.append([self.job(f"warm-{i}", pre, model, params, twin,
                                    warm["te_smoothing"])
                           for i, (pre, model, params)
                           in enumerate(jobs.values())])
        return rounds
