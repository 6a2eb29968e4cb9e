"""Plain reference for one job: the score its pipeline should have.

Written from the semantics of the paper's Section 6 pipelines and of each
operator as the program documents it, and independent of the program: it
imports nothing of ``repro`` and reads only the job, the configuration's
schema and the table's file in the data lake.

* Preprocessing runs on the host in float64 NumPy, but for the target,
  ``log1p`` of the price, which is taken on the device in float32, as
  the program's device tier takes it: the TPU's float32 ``log1p`` departs
  from the exact value by up to 1e-4, and a GBT split turns on less.
* The folds, fits, predictions and the GBT's histograms run on the
  device in float32 with every matrix product at ``highest`` precision.
* ``half=True`` is the control: each fold's model fit on the first half
  of its training rows only, an approximate answer where the
  configuration promises an exact one.

Departures from the python tier of the program, each one the program's
own documented device semantics: the xgboost flavour subsamples the rows
once per fit, not once per tree; every node of a level takes its best
split (no minimum of 8 rows, no "gain must be positive"), as the jax tier
does; the elastic net's ``iters`` are steps of accelerated proximal
gradient, as the jax tier takes them, not the python tier's sweeps of
coordinate descent.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

N_BINS = 32
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.dot(a, b, precision=_HIGHEST)


# ---------------------------------------------------------------------------
# preprocessing (host, float64)
# ---------------------------------------------------------------------------

def hash_features(ids: np.ndarray, dim: int, seed: int) -> np.ndarray:
    """The string encoder's SplitMix64 hash of integer ids into ``dim``
    values in [-1, 1)."""
    m = np.uint64(0xFFFFFFFFFFFFFFFF)
    with np.errstate(over="ignore"):
        z = (ids[:, None].astype(np.uint64)
             + np.uint64(seed) * np.uint64(0x9E3779B97F4A7C15)
             + (np.arange(dim, dtype=np.uint64)[None, :] + np.uint64(1))
             * np.uint64(0xBF58476D1CE4E5B9)) & m
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    return (z.astype(np.float64) / 2.0 ** 64) * 2.0 - 1.0


def _codes(x: np.ndarray) -> np.ndarray:
    return np.nan_to_num(x).astype(np.int64)


def _impute_scale(xn: np.ndarray) -> np.ndarray:
    xn = np.where(np.isnan(xn), np.nan_to_num(np.nanmean(xn, axis=0)), xn)
    sd = np.std(xn, axis=0)
    sd[sd == 0] = 1.0
    return (xn - xn.mean(axis=0)) / sd


def _onehot(block: np.ndarray, cards: list) -> np.ndarray:
    return np.hstack([np.eye(card)[np.clip(_codes(block[:, j]), 0, card - 1)]
                      for j, card in enumerate(cards)])


def _datetime(days: np.ndarray) -> np.ndarray:
    return np.stack([days, days / 365.25, np.floor((days % 365.25) / 30.44),
                     days % 7], axis=1)


def features(table: np.ndarray, job: dict, schema: dict):
    """``(X, y)``: the model's input columns and the log1p target (taken
    on the device in float32)."""
    kinds, cards = schema["kinds"], schema["cards"]
    tgt = kinds.index("target")
    feats = [i for i, k in enumerate(kinds) if k != "target"]
    X = table[:, feats]
    price = table[:, tgt]
    kind = [kinds[c] for c in feats]
    card = [cards[c] for c in feats]
    num = [i for i, k in enumerate(kind) if k == "numeric"]
    low = [i for i, k in enumerate(kind) if k == "categorical"
           and card[i] <= 16]
    high = [i for i, k in enumerate(kind) if k == "categorical"
            and card[i] > 16]
    dts = [i for i, k in enumerate(kind) if k == "datetime"]
    parts = [_impute_scale(X[:, num])] if num else []
    if job["preproc"] == "table_vectorizer":
        parts.append(_onehot(X[:, low], [card[i] for i in low]))
        # one encoder over the high-cardinality block: column j seeded j
        parts += [hash_features(_codes(X[:, i]), 16, j)
                  for j, i in enumerate(high)]
    else:
        seed = job["enc_seed"]
        sm = job["te_smoothing"]
        for i in high:
            ids = np.clip(_codes(X[:, i]), 0, card[i] - 1)
            sums = np.bincount(ids, weights=price, minlength=card[i])
            counts = np.bincount(ids, minlength=card[i])
            te = (sums + sm * price.mean()) / (counts + sm)
            parts.append(te[ids][:, None])
            parts.append(hash_features(_codes(X[:, i]), 16, seed))
        parts.append(_onehot(X[:, low], [card[i] for i in low]))
    parts += [_datetime(X[:, i]) for i in dts]
    y = jnp.log1p(jnp.maximum(jnp.asarray(price, jnp.float32), 0.0))
    return np.hstack(parts), np.asarray(y, dtype=np.float64)


# ---------------------------------------------------------------------------
# linear models (device, float32)
# ---------------------------------------------------------------------------

@jax.jit
def _ridge(X, y, alpha):
    """Penalised least squares (bias penalised too) by one QR of the
    stacked ``[Xb; sqrt(alpha) I]``."""
    Xb = jnp.concatenate([X, jnp.ones((X.shape[0], 1), X.dtype)], axis=1)
    d = Xb.shape[1]
    A = jnp.concatenate([Xb, jnp.sqrt(alpha) * jnp.eye(d, dtype=X.dtype)])
    Q, R = jnp.linalg.qr(A)
    rhs = _mm(Q[:X.shape[0]].T, y)
    return jax.scipy.linalg.solve_triangular(R, rhs, lower=False)


@jax.jit
def _gram(X, y):
    """Standardised Gram matrix and moments of the elastic net."""
    mu, sd = X.mean(0), X.std(0)
    sd = jnp.where(sd == 0, 1.0, sd)
    Xs = (X - mu) / sd
    yc = y - y.mean()
    return _mm(Xs.T, Xs), _mm(Xs.T, yc), mu, sd, y.mean()


def _enet(X, y, alpha, l1_ratio, iters):
    """The elastic net as the job's ``iters`` names it: ``iters`` steps of
    accelerated proximal gradient (FISTA) from zero, step 1/L with L the
    Gram matrix's spectral norm plus l2 plus 1e-6, on standardised
    columns, for ½‖yc − Xs w‖² + l1‖w‖₁ + ½ l2‖w‖², l1 = alpha·l1_ratio·n,
    l2 = alpha·(1 − l1_ratio)·n; the iterations in float64."""
    n = X.shape[0]
    G, b, mu, sd, ym = (np.asarray(v, dtype=np.float64)
                        for v in _gram(X, y))
    l1, l2 = alpha * l1_ratio * n, alpha * (1 - l1_ratio) * n
    L = np.linalg.eigvalsh(G)[-1] + l2 + 1e-6
    w = z = np.zeros(len(b))
    t = 1.0
    for _ in range(iters):
        u = z - (G @ z - b + l2 * z) / L
        w_new = np.sign(u) * np.maximum(np.abs(u) - l1 / L, 0.0)
        t_new = (1 + np.sqrt(1 + 4 * t * t)) / 2
        z = w_new + ((t - 1) / t_new) * (w_new - w)
        w, t = w_new, t_new
    coef = w / sd
    return np.concatenate([coef, [ym - (mu / sd) @ w]]).astype(np.float32)


@jax.jit
def _linear_predict(X, w):
    return _mm(X, w[:-1]) + w[-1]


# ---------------------------------------------------------------------------
# histogram GBT (device, float32)
# ---------------------------------------------------------------------------

def bin_edges(X) -> np.ndarray:
    """(F, 31) split thresholds: NumPy's linear-interpolation quantiles of
    each column at 1/32 … 31/32, NaN ignored, from the column sorted on
    the device; each raised to the least float32 at or above it, so that
    ``x >= edge`` in float32 decides as the float64 comparison does."""
    S = np.asarray(jnp.sort(X, axis=0))           # NaN sort last
    m = (~np.isnan(S)).sum(axis=0)
    q = np.linspace(0, 1, N_BINS + 1)[1:-1]
    pos = (m[None, :] - 1) * q[:, None]           # (31, F)
    lo = np.floor(pos).astype(np.int64)
    hi = np.minimum(lo + 1, np.maximum(m - 1, 0)[None, :])
    cols = np.arange(S.shape[1])[None, :]
    a = S[lo, cols].astype(np.float64)
    b = S[hi, cols].astype(np.float64)
    t = pos - lo
    edges = np.where(t >= 0.5, b - (b - a) * (1 - t), a + (b - a) * t).T
    up = edges.astype(np.float32)
    up = np.where(up.astype(np.float64) < edges,
                  np.nextafter(up, np.float32(np.inf)), up)
    return up.astype(np.float32)


@jax.jit
def bin_ids(X, edges):
    """Bin of each value: how many edges lie at or below it (NaN: 0)."""
    def body(k, B):
        return B + (X >= edges[:, k][None, :]).astype(jnp.int32)
    return jax.lax.fori_loop(0, edges.shape[1], body,
                             jnp.zeros(X.shape, jnp.int32))


def _pick(B, col):
    """``B[i, col[i]]`` by a one-hot select (no per-row gather)."""
    return jnp.sum(jnp.where(jnp.arange(B.shape[1]) == col[:, None], B, 0),
                   axis=1)


def _lookup(table, idx):
    """``table[idx]`` for a small table, by a one-hot select."""
    return _pick(jnp.broadcast_to(table, (idx.shape[0], table.shape[0])),
                 idx)


@partial(jax.jit, static_argnames=("n_trees", "depth"))
def _gbt_fit(B, y, base, lr, reg, n_trees: int, depth: int):
    """Level-wise boosted trees on squared loss.  At each level the best
    (feature, bin) of each node maximises the split gain; the gain reads
    the gradient and row sums at or below each bin, summed up from each
    bin's own sums, which are a product with the bin's one-hot code.  Two
    candidate splits whose gains tie to float32 rounding are told apart
    by the order of these sums, so the sums are taken in the order a
    histogram is: bin by bin, then cumulatively."""
    n, F = B.shape
    bins = jnp.arange(N_BINS)

    def tree(pred, _):
        g = pred - y
        node = jnp.zeros(n, jnp.int32)
        feats = jnp.zeros(2 ** depth - 1, jnp.int32)
        thrs = jnp.zeros(2 ** depth - 1, jnp.int32)
        for d in range(depth):
            first, width = 2 ** d - 1, 2 ** d
            member = (node[:, None] - first
                      == jnp.arange(width)[None, :]).astype(jnp.float32)
            L = jnp.concatenate([member * g[:, None], member], axis=1)

            def per_bin(b_col):
                H = (b_col[:, None] == bins[None, :]).astype(jnp.float32)
                return _mm(L.T, H)                # (2·width, 32)

            S = jax.lax.map(per_bin, B.T)         # (F, 2·width, 32)
            hg = S[:, :width].transpose(1, 0, 2)  # (width, F, 32)
            hc = S[:, width:].transpose(1, 0, 2)
            cg = jnp.cumsum(hg, axis=-1)[..., :-1]
            cc = jnp.cumsum(hc, axis=-1)[..., :-1]
            gt = hg.sum(axis=-1, keepdims=True)
            ct = hc.sum(axis=-1, keepdims=True)
            gain = (cg ** 2 / (cc + reg) + (gt - cg) ** 2 / (ct - cc + reg)
                    - gt ** 2 / (ct + reg))
            best = jnp.argmax(gain.reshape(width, -1), axis=1)
            feats = feats.at[first:first + width].set(best // (N_BINS - 1))
            thrs = thrs.at[first:first + width].set(best % (N_BINS - 1))
            right = _pick(B, _lookup(feats, node)) > _lookup(thrs, node)
            node = 2 * node + 1 + right.astype(jnp.int32)
        leaf = node - (2 ** depth - 1)
        onehot = (leaf[:, None] == jnp.arange(2 ** depth)[None, :]
                  ).astype(jnp.float32)
        vals = -lr * _mm(onehot.T, g) / (onehot.sum(0) + reg)
        return pred + _lookup(vals, leaf), (feats, thrs, vals)

    _, trees = jax.lax.scan(tree, jnp.full((n,), base, jnp.float32), None,
                            length=n_trees)
    return trees


@partial(jax.jit, static_argnames=("depth",))
def _gbt_predict(B, feats, thrs, vals, base, depth: int):
    def tree(out, t):
        f, th, v = t
        node = jnp.zeros(B.shape[0], jnp.int32)
        for _ in range(depth):
            right = _pick(B, _lookup(f, node)) > _lookup(th, node)
            node = 2 * node + 1 + right.astype(jnp.int32)
        return out + _lookup(v, node - (2 ** depth - 1)), None
    out, _ = jax.lax.scan(tree, jnp.full((B.shape[0],), base, jnp.float32),
                          (feats, thrs, vals))
    return out


def _gbt(Xtr, ytr, Xte, params: dict, flavor: str, seed: int):
    edges = bin_edges(Xtr)
    e = jnp.asarray(edges)
    Btr, Bte = bin_ids(Xtr, e), bin_ids(Xte, e)
    base = float(np.mean(np.asarray(ytr, dtype=np.float64)))
    if flavor == "xgboost":
        keep = np.random.default_rng(seed).random(Btr.shape[0]) < 0.9
        idx = jnp.asarray(np.flatnonzero(keep))
        Btr, ytr = Btr[idx], ytr[idx]
    feats, thrs, vals = _gbt_fit(Btr, ytr, base, params["learning_rate"],
                                 1.0, params["n_trees"], params["depth"])
    return _gbt_predict(Bte, feats, thrs, vals, base, params["depth"])


# ---------------------------------------------------------------------------
# the job
# ---------------------------------------------------------------------------

def fold_rows(n: int, k: int, fold: int, seed: int):
    """Training and held-out rows of one of ``k`` equal folds."""
    size = n // k
    p = np.random.default_rng(seed).permutation(n)
    test = p[fold * size:(fold + 1) * size]
    train = np.concatenate([p[:fold * size], p[(fold + 1) * size:]])
    return train, test


def table_path(lake: str, dataset: str, rows: int, table: int) -> str:
    return os.path.join(lake, f"{dataset}_{rows}_{table}.npy")


def score(job: dict, X: np.ndarray, y: np.ndarray,
          half: bool = False) -> float:
    """Mean held-out RMSE over the job's folds, from its features;
    ``half``: the control, each model fit on half its training rows."""
    Xd = jnp.asarray(X, dtype=jnp.float32)
    yd = jnp.asarray(y, dtype=jnp.float32)
    model, params = job["model"], job["params"]
    rmses = []
    with jax.default_matmul_precision("highest"):
        for fold in range(job["cv_k"]):
            tr, te = fold_rows(X.shape[0], job["cv_k"], fold, job["cv_seed"])
            if half:
                tr = tr[:len(tr) // 2]
            tr_d, te_d = jnp.asarray(tr), jnp.asarray(te)
            Xtr, ytr, Xte = Xd[tr_d], yd[tr_d], Xd[te_d]
            if model == "ridge":
                w = _ridge(Xtr, ytr, params["alpha"])
                yhat = _linear_predict(Xte, w)
            elif model == "elasticnet":
                w = _enet(Xtr, ytr, params["alpha"], params["l1_ratio"],
                          params["iters"])
                yhat = _linear_predict(Xte, jnp.asarray(w))
            else:
                yhat = _gbt(Xtr, ytr, Xte, params, model[len("gbt_"):],
                            job["cv_seed"])
            resid = np.asarray(yhat, dtype=np.float64) - y[te]
            rmses.append(float(np.sqrt(np.mean(resid ** 2))))
    return float(np.mean(rmses))
