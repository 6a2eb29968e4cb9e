"""Tabular operator library: python-tier vs jax-tier equivalence, GBT
cross-implementation agreement, estimator sanity."""

import numpy as np
import pytest

from repro.core.dag import LazyOp, TRANSFORM
from repro.core.selection import impls_for
from repro.data.tabular import generate_uk_housing
from repro.tabular import gbt


def _table(n=400, seed=0):
    return np.asarray(generate_uk_housing(n, seed=seed))


def _run_both(op_name, spec, inputs, seed=None, atol=2e-3):
    op = LazyOp(op_name, TRANSFORM, spec=spec,
                inputs=(), seed=seed)
    impls = {i.backend: i for i in impls_for(op_name) if i.fidelity == "exact"}
    assert "python" in impls and "jax" in impls, op_name
    py = impls["python"].fn(op, inputs)
    jx = impls["jax"].fn(op, inputs)
    for a, b in zip(py, jx):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   atol=atol, rtol=2e-3)


@pytest.mark.parametrize("op_name,spec,make_inputs", [
    ("project", {"cols": (1, 3, 5)}, lambda X: [X]),
    ("cleaner", {}, lambda X: [X]),
    ("log1p", {}, lambda X: [np.abs(np.nan_to_num(X))]),
    ("impute_fit", {"strategy": "mean"}, lambda X: [X[:, 10:14]]),
    ("scaler_fit", {}, lambda X: [np.nan_to_num(X[:, 10:14])]),
    ("datetime_encode", {}, lambda X: [X[:, 1:2]]),
    ("onehot", {"cards": (5, 2)}, lambda X: [X[:, 2:4]]),
    ("string_encode", {"dim": 8}, lambda X: [X[:, 5:6]]),
])
def test_tier_equivalence(op_name, spec, make_inputs):
    X = _table()
    _run_both(op_name, spec, make_inputs(X),
              seed=0 if op_name == "string_encode" else None)


def test_scaler_apply_tiers():
    X = np.nan_to_num(_table()[:, 10:14])
    stats = np.stack([X.mean(0), X.std(0) + 1e-9])
    _run_both("scaler_apply", {}, [stats, X])


def test_target_encode_tiers():
    X = _table()
    col, y = X[:, 5:6], X[:, 0]
    op = LazyOp("target_encode_fit", TRANSFORM,
                spec={"card": 1100, "smoothing": 20.0}, seed=0)
    impls = {i.backend: i for i in impls_for("target_encode_fit")}
    t_py = impls["python"].fn(op, [col, y])[0]
    t_jx = impls["jax"].fn(op, [col, y])[0]
    np.testing.assert_allclose(np.asarray(t_py), np.asarray(t_jx),
                               rtol=2e-3, atol=2e-1)


def test_ridge_tiers_and_quality():
    X = np.nan_to_num(_table(1000)[:, 1:])
    y = np.log1p(_table(1000)[:, 0])
    op = LazyOp("ridge_fit", "estimator", spec={"alpha": 1.0}, seed=0)
    impls = {i.backend: i for i in impls_for("ridge_fit")}
    w_py = np.asarray(impls["python"].fn(op, [X, y])[0], np.float64)
    w_jx = np.asarray(impls["jax"].fn(op, [X, y])[0], np.float64)
    pred_py = X @ w_py[:-1] + w_py[-1]
    pred_jx = X @ w_jx[:-1] + w_jx[-1]
    # float32 solve differs in weights; predictions must agree closely
    np.testing.assert_allclose(pred_py, pred_jx, rtol=0.05, atol=0.05)
    ss_res = np.sum((y - pred_py) ** 2)
    ss_tot = np.sum((y - y.mean()) ** 2)
    assert 1 - ss_res / ss_tot > 0.3     # learns something real


def test_ridge_jax_tier_scores_the_manual_features_at_scale():
    """The §6 manual features put price-scale target encodings beside
    one-hot columns that sum to the bias column: ``XbᵀXb`` reaches a
    condition number near 1e16, and at 100,000 rows a float32 Cholesky of
    it returned NaN in some folds.  The jax tier's QR solve scores as the
    float64 python tier does."""
    from repro.agents.aide import PipelineSpec
    from repro.core import Stratum
    from repro.core.lowering import lower
    from repro.core.metadata import collect_metadata
    from repro.core.runtime import Runtime
    from repro.core.scheduler import SchedulerConfig, plan as make_plan
    from repro.core.selection import SelectionConfig, select
    sink = PipelineSpec(preproc="manual", model="ridge", n_rows=100_000,
                        cv_k=3, seed=7).build()
    jx, rep = Stratum().run(sink)
    assert rep.run.per_backend.get("python", 0) > 0
    sinks = lower([sink])
    collect_metadata(sinks)
    sel = select(sinks, SelectionConfig(allowed_backends=("python",)))
    plan = make_plan(sinks, sel, SchedulerConfig(enable_inter_op=False))
    py = Runtime(cache=None, parallel=False).execute(sinks, plan, sel)[0][0]
    np.testing.assert_allclose(float(np.asarray(jx)), float(np.asarray(py)),
                               rtol=1e-4)


def test_elasticnet_tiers_agree_in_loss():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(300, 8))
    w_true = np.array([2.0, -1.0, 0, 0, 0.5, 0, 0, 0])
    y = X @ w_true + 0.01 * rng.normal(size=300)
    op = LazyOp("elasticnet_fit", "estimator",
                spec={"alpha": 0.001, "l1_ratio": 0.5, "iters": 300}, seed=0)
    impls = {i.backend: i for i in impls_for("elasticnet_fit")}
    losses = {}
    for name, impl in impls.items():
        if impl.fidelity != "exact":
            continue
        w = np.asarray(impl.fn(op, [X, y])[0], np.float64)
        pred = X @ w[:-1] + w[-1]
        losses[name] = np.mean((pred - y) ** 2)
    assert losses["python"] < 0.01 and losses["jax"] < 0.01


def test_gbt_numpy_vs_jax_same_trees():
    rng = np.random.default_rng(1)
    X = rng.normal(size=(500, 6))
    y = X[:, 0] * 2 + (X[:, 1] > 0) * 3 + 0.01 * rng.normal(size=500)
    m_np = gbt.fit_numpy(X, y, n_trees=10, depth=3, subsample=1.0)
    m_jx = gbt.fit_jax(X, y, n_trees=10, depth=3, subsample=1.0)
    p_np = gbt.predict_numpy(m_np, X)
    p_jx = gbt.predict_jax(m_jx, X)
    # same algorithm, same bins — predictions nearly identical
    np.testing.assert_allclose(p_np, p_jx, rtol=1e-3, atol=1e-2)
    # and it learns
    assert np.mean((p_np - y) ** 2) < np.var(y) * 0.4


@pytest.mark.parametrize("depth", [2, 3])
def test_gbt_dense_form_grows_the_same_trees(depth):
    """The TPU's scatter-free form (matmul histograms, one-hot lookups)
    grows the trees and predictions of the segment_sum/gather form."""
    import jax.numpy as jnp
    rng = np.random.default_rng(2)
    X = rng.normal(size=(3000, 9))
    y = X[:, 0] * 2 + np.sin(X[:, 1]) + 0.1 * rng.normal(size=3000)
    B = jnp.asarray(gbt.bin_data(X, gbt.make_bins(X)))
    args = (B, jnp.asarray(y, jnp.float32), float(y.mean()), 0.1, 1.0, 12,
            depth, gbt.N_BINS)
    sparse, dense = (gbt._fit_jax_binned(*args, dense=d)
                     for d in (False, True))
    np.testing.assert_array_equal(sparse[0], dense[0])
    np.testing.assert_array_equal(sparse[1], dense[1])
    np.testing.assert_allclose(sparse[2], dense[2], rtol=1e-5, atol=1e-7)
    preds = [gbt._predict_jax(B, *sparse, float(y.mean()), depth, dense=d)
             for d in (False, True)]
    np.testing.assert_allclose(preds[0], preds[1], rtol=1e-6)


def _at_edges(col):
    """A float32 column's values, then each of its edges as float32 and
    the float32 values on either side of it (but subnormal ones)."""
    t = gbt.make_bins(col[:, None])[0]
    c = t.astype(np.float32)
    near = np.concatenate([c, np.nextafter(c, np.float32(np.inf)),
                           np.nextafter(c, np.float32(-np.inf))])
    near = near[(near == 0) | (np.abs(near) >= np.finfo(np.float32).tiny)]
    return np.concatenate([col, near]).astype(np.float32)


def _digitize_case(name):
    rng = np.random.default_rng(3)
    n = 997
    if name == "at_edges":                      # ties, hit edges exactly
        return _at_edges(np.round(rng.normal(size=n) * 3).astype(np.float32))
    if name == "unrepresentable_edges":         # lerps float32 cannot hold
        col = (np.float32(1 / 3) * rng.integers(0, 9, n)
               + np.float32(1e-3) * rng.normal(size=n)).astype(np.float32)
        return _at_edges(col)
    if name == "magnitude_jumps":
        # order statistics 2**35 apart where gamma >= 0.5: ``b - a`` is
        # inexact there, and NumPy's lerp from ``b`` rounds unlike one
        # from ``a``
        col = np.sort(rng.uniform(1, 2, n)).astype(np.float32)
        lo, frac = np.divmod((n - 1) * np.arange(1, gbt.N_BINS), gbt.N_BINS)
        for j in lo[2 * frac >= gbt.N_BINS][:3]:
            col[j + 1:] *= np.float32(2.0 ** 35)
        return rng.permutation(col)
    col = rng.normal(size=n).astype(np.float32)
    if name == "nan":
        col[::7] = np.nan
    elif name == "inf":                         # few enough for finite edges
        col[::97], col[1::89] = np.inf, -np.inf
    elif name == "signed_zero":
        col[::3], col[1::3] = 0.0, -0.0
    elif name == "constant":
        col[:] = 2.5
    elif name == "all_nan":
        col[:] = np.nan
    elif name == "nan_99":
        col[rng.random(n) < 0.99] = np.nan
    elif name == "single_number":
        col[1:] = np.nan
    return col


DIGITIZE_CASES = ["at_edges", "unrepresentable_edges", "nan", "inf",
                  "signed_zero", "constant", "all_nan", "nan_99",
                  "single_number", "magnitude_jumps"]


def _beside_other(col):
    other = np.random.default_rng(4).normal(size=col.size)
    return np.stack([col, other.astype(np.float32)], axis=1)


@pytest.mark.filterwarnings("ignore::RuntimeWarning")
@pytest.mark.parametrize("case", DIGITIZE_CASES)
def test_device_digitize_matches_host_bins_bit_for_bit(case):
    """``_digitize`` against the float32 ceilings of the float64 edges
    gives ``bin_data``'s bins exactly, beside an ordinary column."""
    import jax.numpy as jnp
    X = _beside_other(_digitize_case(case))
    bins = gbt.make_bins(X)
    edges = gbt._device_edges(bins)
    assert edges is not None and edges.dtype == np.float32
    assert np.all(edges[~np.isnan(bins)] >= bins[~np.isnan(bins)])
    np.testing.assert_array_equal(
        np.asarray(gbt._digitize(jnp.asarray(X), edges)),
        gbt.bin_data(X, bins))


def _host_case(name):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(500, 3)).astype(np.float32)
    tiny = np.finfo(np.float32).tiny
    if name == "float64":
        return X.astype(np.float64)
    if name == "subnormal_feature":
        X[::9, 1] = tiny / 4
    elif name == "subnormal_edge":              # median between 0 and tiny
        X[:, 1] = np.where(np.arange(500) % 2, tiny, 0).astype(np.float32)
    elif name == "edges_not_ascending":         # quantiles over ±inf: NaN
        X[::5, 1], X[1::5, 1] = np.inf, -np.inf
    return X


@pytest.mark.filterwarnings("ignore::RuntimeWarning")
@pytest.mark.parametrize("case", [
    *DIGITIZE_CASES, "subnormal_edge", "edges_not_ascending"])
def test_device_order_statistics_give_make_bins_edges_bit_for_bit(case):
    """NumPy's lerp on the host between the order statistics of the
    device's sort gives ``make_bins``'s float64 edges under ``==`` (NaN
    equal to NaN), ±inf, ties, NaN and columns of one number or none
    among them."""
    import jax
    X = (_host_case(case) if case in ("subnormal_edge", "edges_not_ascending")
         else _beside_other(_digitize_case(case)))
    edges = gbt._lerp_edges(*jax.device_get(gbt._order_stats(X)))
    assert edges.dtype == np.float64
    np.testing.assert_array_equal(edges, gbt.make_bins(X))


def _bin_spans(sink) -> list:
    return [(s[5]["on"], s[5].get("edges")) for s in sink
            if s[2] == "stratum.gbt.bin"]


@pytest.mark.filterwarnings("ignore::RuntimeWarning")
@pytest.mark.parametrize("case", [
    "float64", "subnormal_feature", "subnormal_edge", "edges_not_ascending"])
def test_features_the_device_cannot_digitize_exactly_bin_on_the_host(case):
    """Both forms digitize on the host here.  The TPU's form sorts on the
    device where the features allow it, and the CPU's does not; both
    give the same edges."""
    X = _host_case(case)
    bins = gbt.make_bins(X)
    assert X.dtype != np.float32 or gbt._subnormal(X) \
        or gbt._device_edges(bins) is None
    sorted_on_device = case in ("subnormal_edge", "edges_not_ascending")
    from repro.core import spans
    for dense in (False, True):
        sink: list = []
        with spans.collect(sink):
            model = gbt.fit_jax(X, X[:, 0].astype(np.float64), n_trees=2,
                                depth=2, dense=dense)
            gbt.predict_jax(model, X, dense=dense)
        edges = "device" if dense and sorted_on_device else "host"
        assert _bin_spans(sink) == [("host", edges), ("host", None)]
        np.testing.assert_array_equal(gbt.unpack(model, 3)[1], bins)


@pytest.mark.parametrize("dense", [False, True])
@pytest.mark.parametrize("subsample", [1.0, 0.9])
def test_fit_and_predict_on_float32_features_match_host_binning(
        dense, subsample):
    """``fit_jax`` packs and ``predict_jax`` outputs on float32 features,
    digitized on the device, equal those of the host-binned path bit for
    bit, the TPU's form with its edges from the device's sort."""
    import jax.numpy as jnp
    rng = np.random.default_rng(6)
    X = rng.normal(size=(800, 7)).astype(np.float32)
    X[:, 2] = np.round(X[:, 2] * 2)
    X[::13, 3] = np.nan
    y = X[:, 0] * 2.0 + np.sin(np.nan_to_num(X[:, 3])) \
        + 0.1 * rng.normal(size=800)
    T, depth, lr, reg, seed = 6, 3, 0.1, 1.0, 11
    sink: list = []
    from repro.core import spans
    with spans.collect(sink):
        model = gbt.fit_jax(X, y, n_trees=T, depth=depth, lr=lr, reg=reg,
                            subsample=subsample, seed=seed, dense=dense)
    # the TPU's form takes the edges from the device's sort
    assert _bin_spans(sink) == [("device", "device" if dense else "host")]
    bins = gbt.make_bins(X)
    assert gbt._device_edges(bins) is not None
    B, y_fit = gbt.bin_data(X, bins), y
    if subsample < 1.0:
        keep = np.random.default_rng(seed).random(800) < subsample
        B, y_fit = B[keep], y[keep]
    base = float(np.mean(y))
    feats, thrs, leaves = gbt._fit_jax_binned(
        jnp.asarray(B), jnp.asarray(y_fit, jnp.float32), base, lr, reg, T,
        depth, gbt.N_BINS, dense)
    expected = gbt.pack(base, bins, np.asarray(feats), np.asarray(thrs),
                        np.asarray(leaves, np.float64), depth)
    np.testing.assert_array_equal(model, expected)
    pred = gbt._predict_jax(
        *(jnp.asarray(a) for a in (gbt.bin_data(X, bins), np.asarray(feats),
                                   np.asarray(thrs),
                                   np.asarray(leaves, np.float64))),
        base, depth, dense)
    np.testing.assert_array_equal(gbt.predict_jax(model, X, dense=dense),
                                  np.asarray(pred))


@pytest.mark.parametrize("platform,form", [
    ("tpu", "gbt_jx_dense"), ("cpu", "gbt_jx"), ("gpu", "gbt_jx")])
def test_selection_picks_the_gbt_form_of_the_platform(platform, form):
    """The jax GBT form is an impl per platform, so the selection pass, and
    a ``platform`` set in its config, decides which form runs."""
    from repro.agents.aide import PipelineSpec
    from repro.core.lowering import lower
    from repro.core.metadata import collect_metadata
    from repro.core.selection import SelectionConfig, select
    sinks = lower([PipelineSpec(preproc="manual", model="gbt_xgboost",
                                n_rows=2000, cv_k=2, seed=0).build()])
    collect_metadata(sinks)
    sel = select(sinks, SelectionConfig(platform=platform,
                                        allowed_backends=("jax",)))
    chosen = {impl.fn.__name__ for impl in sel.values()
              if impl.op_name in ("gbt_fit", "gbt_predict")}
    pred = "gbtpred_jax_dense" if platform == "tpu" else "gbtpred_jax"
    assert chosen == {form, pred}


def test_kfold_split_partition_properties():
    X = _table(333)
    y = X[:, 0]
    op = LazyOp("kfold_split", TRANSFORM, spec={"k": 3, "fold": 1}, seed=9)
    impl = {i.backend: i for i in impls_for("kfold_split")}["python"]
    xtr, ytr, xte, yte = impl.fn(op, [X, y])
    assert len(xte) == 333 // 3
    assert len(xtr) + len(xte) == 333 - (333 - 3 * (333 // 3)) + (333 - 333 // 3 * 3)
    # folds are disjoint across fold ids (check via target values multiset)
    op2 = LazyOp("kfold_split", TRANSFORM, spec={"k": 3, "fold": 2}, seed=9)
    _, _, xte2, _ = impl.fn(op2, [X, y])
    rows1 = {tuple(np.round(r, 6)) for r in np.nan_to_num(xte)}
    rows2 = {tuple(np.round(r, 6)) for r in np.nan_to_num(xte2)}
    assert not (rows1 & rows2)
