"""The one generator: same jobs for one seed, other jobs for another, the
same mix of work for every seed, no job twice, and a warm-up that is the
same for every seed, covers every shape the window uses and repeats no
window job."""

from collections import Counter

import numpy as np

from bench import run as R
from bench.harness.traffic import Traffic

from conftest import CELL, ROOT


def _cell():
    _, _, config, traffic = R.load_cell(ROOT, CELL)
    return config, traffic


def _closed(seed, per_agent=5):
    config, traffic = _cell()
    t = Traffic(traffic, config, seed)
    agents = [t.agent_jobs(i) for i in range(traffic["agents"])]
    return [[next(a) for _ in range(per_agent)] for a in agents]


def _strip(job):
    return {k: v for k, v in job.items() if k != "id"}


def _key(j):
    return (j["preproc"], j["model"], tuple(sorted(j["params"].items())),
            j["cv_seed"])


def test_the_grid_is_section_6():
    config, traffic = _cell()
    t = Traffic(traffic, config, 1)
    pipelines = [(pre, model, tuple(sorted(p.items())))
                 for (pre, model), points in t.groups() for p in points]
    assert len(pipelines) == len(set(pipelines)) == 66
    structures = {t.structure(pre, model, dict(p))
                  for pre, model, p in pipelines}
    assert len(structures) == 36
    # the two agents of each group send each of its (point, fold seed)
    # pairs once in 16 steps: all of them, where the grid has 8 points
    jobs = [j for x in _closed(1, 16) for j in x]
    sent = {_key(j) for j in jobs}
    assert len(sent) == len(jobs) == 16 * traffic["agents"]
    grid8 = [(pre, model, tuple(sorted(p.items())), s)
             for (pre, model), points in t.groups() if len(points) == 8
             for p in points for s in traffic["cv_seeds"]]
    assert set(grid8) <= sent


def test_same_seed_same_jobs():
    a, b = _closed(2 ** 31 + 17), _closed(2 ** 31 + 17)
    assert [[_strip(j) for j in x] for x in a] == \
        [[_strip(j) for j in x] for x in b]


def test_every_step_holds_the_same_mix():
    """The jobs the agents send at one step: the same groups, trees and
    depths at every step and for every seed."""
    def steps(seed):
        jobs = _closed(seed, 6)
        return [Counter((j["preproc"], j["model"][:3],
                         j["params"].get("n_trees"), j["params"].get("depth"))
                        for j in (x[s] for x in jobs)) for s in range(6)]
    a, b = steps(5), steps(2 ** 31 + 11)
    gbt = lambda c: Counter({(k[2], k[3]): v for k, v in c.items()
                             if k[1] == "gbt"})
    assert all(gbt(c) == gbt(a[0]) for c in a + b)
    assert all(Counter(k[:2] for k in c.elements())
               == Counter(k[:2] for k in a[0].elements()) for c in a + b)


def test_other_seed_other_jobs_same_mix():
    a, b = _closed(5, 8), _closed(6, 8)
    assert [[_strip(j) for j in x] for x in a] != \
        [[_strip(j) for j in x] for x in b]
    mix = lambda jobs: Counter((j["preproc"], j["model"])
                               for x in jobs for j in x)
    ma, mb = mix(a), mix(b)
    assert set(ma) == set(mb)
    assert all(abs(ma[k] - mb[k]) <= 1 for k in ma), (ma, mb)
    trees = lambda jobs: sum(j["params"].get("n_trees", 0)
                             * j["params"].get("depth", 0)
                             for x in jobs for j in x)
    assert abs(trees(a) - trees(b)) <= 0.1 * trees(a)


def test_no_job_twice_and_every_family_shares_the_fold_pool():
    config, traffic = _cell()
    jobs = [j for x in _closed(2 ** 33 + 5, 16) for j in x]
    keys = [_key(j) for j in jobs]
    assert len(keys) == len(set(keys))
    for model in traffic["grids"]:
        seeds = {j["cv_seed"] for j in jobs if j["model"] == model}
        assert seeds == set(traffic["cv_seeds"]), model


def test_warmup_same_for_every_seed_covers_shapes_and_repeats_no_job():
    config, traffic = _cell()
    w1 = Traffic(traffic, config, 1).warmup_rounds()
    w2 = Traffic(traffic, config, 2).warmup_rounds()
    assert [[_strip(j) for j in r] for r in w1] == \
        [[_strip(j) for j in r] for r in w2]
    t = Traffic(traffic, config, 1)
    warm = [j for r in w1 for j in r]
    assert {t.structure(j["preproc"], j["model"], j["params"])
            for j in w1[0]} == {t.structure(p, m, dict(q))
                                for (p, m), pts in t.groups() for q in pts}
    window = {_key(j) for x in _closed(3, 12) for j in x}
    assert not window & {_key(j) for j in warm}
    # each pool seed's twin keeps as many rows in the xgboost subsample
    n = config["rows"] - config["rows"] // config["cv_k"]
    kept = lambda s: int((np.random.default_rng(s).random(n) < 0.9).sum())
    twins = [r[0]["cv_seed"] for r in w1]
    assert [kept(s) for s in twins] == [kept(s) for s in traffic["cv_seeds"]]
    xgb = {(j["preproc"], j["params"]["n_trees"], j["params"]["depth"],
            j["cv_seed"]) for j in warm if j["model"] == "gbt_xgboost"}
    assert len(xgb) == 2 * 4 * len(twins)


def test_fabric_blocks_repeat_the_one_chip_loop_on_other_points():
    """On four shards of 16 agents each, every shard's block is the
    one-chip cell's loop: shard 0's the very same jobs, each other's the
    same mix on other points, so at one step no two shards send the same
    job, and no shard sees a job twice in 16 steps."""
    config, traffic = _cell()
    per, shards = traffic["agents"], 4
    seed, n = 2 ** 33 + 9, 16
    t = Traffic(dict(traffic, agents=per * shards), config, seed, shards)
    agents = [t.agent_jobs(i) for i in range(per * shards)]
    fabric = [[next(a) for _ in range(n)] for a in agents]
    # agent i is pinned to shard floor(i * shards / agents)
    assert [t.shard_of(i) for i in range(per * shards)] == \
        [s for s in range(shards) for _ in range(per)]
    blocks = [fabric[s * per:(s + 1) * per] for s in range(shards)]
    assert [[_strip(j) for j in x] for x in blocks[0]] == \
        [[_strip(j) for j in x] for x in _closed(seed, n)]
    for step in range(n):
        sent = [{_key(x[step]) for x in b} for b in blocks]
        assert all(len(s) == per for s in sent)
        assert len(set().union(*sent)) == per * shards
    for b in blocks:                 # each shard's cache sees no repeat
        keys = [_key(j) for x in b for j in x]
        assert len(keys) == len(set(keys))
    mix = lambda b: Counter((j["preproc"], j["model"],
                             j["params"].get("n_trees"),
                             j["params"].get("depth"))
                            for x in b for j in x)
    assert all(mix(b) == mix(blocks[0]) for b in blocks)
