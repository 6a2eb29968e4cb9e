"""The GBT work counter counts the algorithm, not the form that runs it."""

import numpy as np
import pytest

from bench.harness import gbt_work

PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_counts_by_hand():
    # 2 trees x 3 levels x (10 rows x 4 features x 1 byte + 10 rows x 8)
    assert gbt_work.fit_bytes(10, 4, 2, 3) == 2 * 3 * (40 + 80)
    assert gbt_work.fit_flops(10, 4, 2, 3) == 2 * 3 * 2 * 40


def test_byte_bound():
    n, F, t, d = 666_667, 73, 20, 3
    ideal = gbt_work.ideal_seconds(n, F, t, d, PEAK)
    assert ideal == pytest.approx(gbt_work.fit_bytes(n, F, t, d) / 819e9)


@pytest.mark.parametrize("dense", [False, True])
def test_same_bytes_for_dense_and_scatter_forms(dense, monkeypatch):
    """Both forms of the program's fit are seen by the probe with one
    shape, so the counter gives them the same bytes."""
    from repro.tabular import gbt

    seen = []
    original = gbt._fit_jax_binned

    def spy(B, *args):
        seen.append((B.shape, args[4], args[5]))
        return original(B, *args)

    monkeypatch.setattr(gbt, "_fit_jax_binned", spy)
    rng = np.random.default_rng(0)
    X = rng.standard_normal((300, 5))
    y = rng.standard_normal(300)
    gbt.fit_jax(X, y, n_trees=3, depth=2, dense=dense)
    ((shape, n_trees, depth),) = seen
    assert gbt_work.fit_bytes(*shape, n_trees, depth) == \
        gbt_work.fit_bytes(300, 5, 3, 2)
