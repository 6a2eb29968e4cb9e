"""Tests of the benchmark's own code, run by path on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the data lake and compile cache of a run, set before the program is
# imported (it reads the lake's place once, at import)
from bench.run import prepare_env  # noqa: E402

prepare_env(ROOT)



CELL = "sweep.quarter250k"


def small(cell=CELL, rows=1500, agents=4, points=1, pool=2):
    """``(benchmark, cell, config, traffic)`` of a cell cut to a test's
    size: ``rows`` rows, ``agents`` agents, the last ``points`` points of
    each grid list and ``pool`` fold seeds."""
    from bench import run as R
    bench, c, config, traffic = R.load_cell(ROOT, cell)
    config = dict(config, rows=rows)
    grids = {m: {k: v[-points:] for k, v in g.items()}
             for m, g in traffic["grids"].items()}
    traffic = dict(traffic, agents=agents, grids=grids,
                   cv_seeds=traffic["cv_seeds"][:pool])
    return bench, c, config, traffic
