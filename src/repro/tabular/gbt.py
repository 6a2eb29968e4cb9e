"""Histogram gradient-boosted trees — the XGBoost/LightGBM stand-in.

Two implementations of the same algorithm (squared loss, level-wise growth on
quantile-binned features):

* :func:`fit_numpy` / :func:`predict_numpy` — naive per-node/per-feature
  Python loops over ``np.bincount`` histograms (the interpreted-library tier),
* :func:`fit_jax` / :func:`predict_jax` — one jitted program: ``lax.scan``
  over boosting rounds, level-wise split search fully vectorized over
  (nodes × features × bins) (the native-backend tier).

The model is a dense array pack so it can flow through the DAG/cache as a
plain tensor:  trees[t] = (feature[node], threshold_bin[node], leaf[node...]).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..core.spans import span

N_BINS = 32  # fixed power-of-two bin count


# ---------------------------------------------------------------------------
# shared: quantile binning
# ---------------------------------------------------------------------------

def make_bins(X: np.ndarray, n_bins: int = N_BINS) -> np.ndarray:
    """(F, n_bins-1) ascending split thresholds per feature."""
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    return np.nanquantile(X, qs, axis=0).T.copy()  # (F, n_bins-1)


def bin_data(X: np.ndarray, bins: np.ndarray) -> np.ndarray:
    """Digitize each column; NaN → bin 0."""
    out = np.empty(X.shape, dtype=np.int32)
    for j in range(X.shape[1]):
        out[:, j] = np.searchsorted(bins[j], X[:, j], side="right")
    out[np.isnan(X)] = 0
    return np.clip(out, 0, bins.shape[1])


# ---------------------------------------------------------------------------
# numpy ("python"-tier) implementation
# ---------------------------------------------------------------------------

def fit_numpy(X: np.ndarray, y: np.ndarray, *, n_trees: int = 30,
              depth: int = 3, lr: float = 0.1, reg: float = 1.0,
              subsample: float = 1.0, seed: int = 0) -> np.ndarray:
    n, F = X.shape
    bins = make_bins(X)
    B = bin_data(X, bins)                      # (n, F) int32
    rng = np.random.default_rng(seed)
    n_nodes = 2 ** depth - 1                   # internal nodes
    n_leaves = 2 ** depth
    base = float(np.mean(y))
    pred = np.full(n, base)
    # model pack: per tree: feat(n_nodes), thr(n_nodes), leaf(n_leaves)
    feats = np.zeros((n_trees, n_nodes), dtype=np.int32)
    thrs = np.zeros((n_trees, n_nodes), dtype=np.int32)
    leaves = np.zeros((n_trees, n_leaves))

    for t in range(n_trees):
        g = pred - y                           # gradient of 0.5*(pred-y)^2
        if subsample < 1.0:
            use = rng.random(n) < subsample
        else:
            use = np.ones(n, dtype=bool)
        node = np.zeros(n, dtype=np.int32)     # node id per row, level order
        for d in range(depth):
            for k in range(2 ** d):
                nid = 2 ** d - 1 + k
                rows = use & (node == nid)
                if rows.sum() < 8:
                    feats[t, nid] = 0
                    thrs[t, nid] = N_BINS      # everything goes left
                    continue
                gb = g[rows]
                Bn = B[rows]
                best = (0.0, 0, N_BINS)
                g_tot = gb.sum()
                c_tot = gb.shape[0]
                for f in range(F):             # naive per-feature loop
                    hist_g = np.bincount(Bn[:, f], weights=gb,
                                         minlength=N_BINS)
                    hist_c = np.bincount(Bn[:, f], minlength=N_BINS)
                    cg = np.cumsum(hist_g)[:-1]
                    cc = np.cumsum(hist_c)[:-1]
                    gain = (cg ** 2 / (cc + reg)
                            + (g_tot - cg) ** 2 / (c_tot - cc + reg)
                            - g_tot ** 2 / (c_tot + reg))
                    bi = int(np.argmax(gain))
                    if gain[bi] > best[0]:
                        best = (float(gain[bi]), f, bi)
                _, bf, bb = best
                feats[t, nid] = bf
                thrs[t, nid] = bb
            # level-order: children of nid are 2*nid+1 (left), 2*nid+2 (right)
            go_right = B[np.arange(n), feats[t, node]] > thrs[t, node]
            node = node * 2 + 1 + go_right.astype(np.int32)
        # leaves
        leaf_id = node - (2 ** depth - 1)
        for k in range(n_leaves):
            rows = use & (leaf_id == k)
            gs = g[rows]
            leaves[t, k] = -lr * gs.sum() / (gs.shape[0] + reg)
        pred = pred + leaves[t, np.clip(leaf_id, 0, n_leaves - 1)]

    return pack(base, bins, feats, thrs, leaves, depth)


def predict_numpy(model: np.ndarray, X: np.ndarray) -> np.ndarray:
    base, bins, feats, thrs, leaves, depth = unpack(model, X.shape[1])
    B = bin_data(X, bins)
    n = X.shape[0]
    out = np.full(n, base)
    for t in range(feats.shape[0]):
        node = np.zeros(n, dtype=np.int32)
        for _ in range(depth):
            go_right = B[np.arange(n), feats[t, node]] > thrs[t, node]
            node = node * 2 + 1 + go_right.astype(np.int32)
        out += leaves[t, node - (2 ** depth - 1)]
    return out


# ---------------------------------------------------------------------------
# model packing (model = flat float64 array → flows through cache/DAG)
# ---------------------------------------------------------------------------

def pack(base, bins, feats, thrs, leaves, depth) -> np.ndarray:
    T, n_nodes = feats.shape
    F = bins.shape[0]
    header = np.array([base, T, n_nodes, leaves.shape[1], F, depth],
                      dtype=np.float64)
    return np.concatenate([header, bins.ravel(), feats.ravel().astype(np.float64),
                           thrs.ravel().astype(np.float64), leaves.ravel()])


def unpack(model: np.ndarray, F_expected: int):
    base = float(model[0])
    T, n_nodes, n_leaves, F, depth = (int(model[i]) for i in range(1, 6))
    off = 6
    bins = model[off:off + F * (N_BINS - 1)].reshape(F, N_BINS - 1)
    off += F * (N_BINS - 1)
    feats = model[off:off + T * n_nodes].reshape(T, n_nodes).astype(np.int32)
    off += T * n_nodes
    thrs = model[off:off + T * n_nodes].reshape(T, n_nodes).astype(np.int32)
    off += T * n_nodes
    leaves = model[off:off + T * n_leaves].reshape(T, n_leaves)
    return base, bins, feats, thrs, leaves, depth


# ---------------------------------------------------------------------------
# jax ("native"-tier) implementation — one compiled program per shape/config
# ---------------------------------------------------------------------------

def _lookup(table, idx, dense: bool):
    """``table[idx]`` for a small table: a one-hot select when ``dense``."""
    if not dense:
        return table[idx]
    hit = idx[:, None] == jnp.arange(table.shape[0])
    return jnp.sum(jnp.where(hit, table, 0), axis=1).astype(table.dtype)


def _row_values(B, col, dense: bool):
    """``B[i, col[i]]`` for every row i: a one-hot select when ``dense``."""
    if not dense:
        return B[jnp.arange(B.shape[0]), col]
    hit = jnp.arange(B.shape[1]) == col[:, None]
    return jnp.sum(jnp.where(hit, B, 0), axis=1)


def _histograms(B, level_node, g, width: int, n_bins: int, dense: bool):
    """Gradient sums and row counts per (node, feature, bin) of one tree
    level, each ``(width, F, n_bins)``.

    ``dense`` builds them as one ``(2·width × n)·(n × n_bins)`` matmul per
    feature; otherwise as ONE flat segment_sum over (node, feature, bin)
    ids — O(n·F) adds, no (n, F, bins) one-hot materialization, but a
    scatter whose colliding adds a TPU serialises."""
    n, F = B.shape
    if dense:
        node_oh = jax.nn.one_hot(level_node, width, dtype=jnp.float32)
        L = jnp.concatenate([node_oh * g[:, None], node_oh], axis=1)

        def per_feature(b):
            return jnp.dot(L.T, jax.nn.one_hot(b, n_bins, dtype=jnp.float32),
                           precision=jax.lax.Precision.HIGHEST)

        h = jax.lax.map(per_feature, B.T).transpose(1, 0, 2)
        return h[:width], h[width:]
    feat_ids = jnp.arange(F, dtype=jnp.int32)[None, :]
    # flat segment id: ((node·F) + f)·bins + bin
    seg = ((level_node[:, None] * F + feat_ids) * n_bins + B).reshape(-1)
    n_segs = width * F * n_bins
    gf = jnp.broadcast_to(g[:, None], (n, F)).reshape(-1)
    hist_g = jax.ops.segment_sum(gf, seg, num_segments=n_segs)
    hist_c = jax.ops.segment_sum(jnp.ones_like(gf), seg, num_segments=n_segs)
    return (hist_g.reshape(width, F, n_bins),
            hist_c.reshape(width, F, n_bins))


@partial(jax.jit, static_argnames=("n_trees", "depth", "n_bins", "dense"))
def _fit_jax_binned(B, g0_y, base, lr, reg, n_trees: int, depth: int,
                    n_bins: int, dense: bool = False):
    """B: (n,F) int32 binned features; returns (feats, thrs, leaves).

    ``dense`` (the TPU's form) uses no scatter and no per-row gather:
    histograms by matmul, node lookups by one-hot selects."""
    n, F = B.shape
    n_nodes = 2 ** depth - 1
    n_leaves = 2 ** depth

    def tree_round(pred, _):
        g = (pred - g0_y).astype(jnp.float32)             # (n,)
        node = jnp.zeros(n, dtype=jnp.int32)
        feats = jnp.zeros(n_nodes, dtype=jnp.int32)
        thrs = jnp.zeros(n_nodes, dtype=jnp.int32)

        def level(d, carry):
            node, feats, thrs = carry
            first = 2 ** d - 1
            width = 2 ** d
            level_node = jnp.clip(node - first, 0, width - 1)  # (n,)
            hist_g, hist_c = _histograms(B, level_node, g, width, n_bins,
                                         dense)
            cg = jnp.cumsum(hist_g, axis=-1)[..., :-1]
            cc = jnp.cumsum(hist_c, axis=-1)[..., :-1]
            g_tot = hist_g.sum(axis=-1, keepdims=True)
            c_tot = hist_c.sum(axis=-1, keepdims=True)
            gain = (cg ** 2 / (cc + reg)
                    + (g_tot - cg) ** 2 / (c_tot - cc + reg)
                    - g_tot ** 2 / (c_tot + reg))          # (width,F,bins-1)
            flat = gain.reshape(width, -1)
            bi = jnp.argmax(flat, axis=1)
            bf = (bi // (n_bins - 1)).astype(jnp.int32)
            bb = (bi % (n_bins - 1)).astype(jnp.int32)
            idx = first + jnp.arange(width)
            feats = feats.at[idx].set(bf)
            thrs = thrs.at[idx].set(bb)
            go_right = (_row_values(B, _lookup(feats, node, dense), dense)
                        > _lookup(thrs, node, dense))
            node = node * 2 + 1 + go_right.astype(jnp.int32)
            return node, feats, thrs

        # static unroll over depth (bounded, ≤ 4)
        carry = (node, feats, thrs)
        for d in range(depth):
            carry = level(d, carry)
        node, feats, thrs = carry

        leaf_id = node - (2 ** depth - 1)
        Loh = jax.nn.one_hot(leaf_id, n_leaves, dtype=jnp.float32)
        # full float32: a default-precision TPU matmul rounds the
        # gradients to bf16
        gs = jnp.dot(Loh.T, g,
                     precision=jax.lax.Precision.HIGHEST)  # (leaves,)
        cs = Loh.sum(axis=0)
        leaf_vals = (-lr * gs / (cs + reg)).astype(pred.dtype)
        pred = pred + _lookup(leaf_vals, leaf_id, dense)
        return pred, (feats, thrs, leaf_vals)

    pred0 = jnp.full((n,), base, dtype=jnp.float64
                     if g0_y.dtype == jnp.float64 else jnp.float32)
    _, (feats, thrs, leaves) = jax.lax.scan(
        tree_round, pred0, None, length=n_trees)
    return feats, thrs, leaves


def fit_jax(X: np.ndarray, y: np.ndarray, *, n_trees: int = 30,
            depth: int = 3, lr: float = 0.1, reg: float = 1.0,
            subsample: float = 1.0, seed: int = 0,
            dense: bool = False) -> np.ndarray:
    # binning on host (one pass), training compiled
    n, F = X.shape
    with span("stratum.gbt.bin", n=n, F=F):
        bins = make_bins(X)
        B = bin_data(X, bins)
    base = float(np.mean(y))
    if subsample < 1.0:
        # deterministic row subsample per seed (applied once — cheaper than
        # per-round; documented deviation of the fast tier)
        rng = np.random.default_rng(seed)
        keep = rng.random(X.shape[0]) < subsample
        B_fit, y_fit = B[keep], y[keep]
    else:
        B_fit, y_fit = B, y
    with span("stratum.gbt.put", bytes=int(B_fit.nbytes) + 4 * y_fit.size):
        B_dev = jnp.asarray(B_fit)
        y_dev = jnp.asarray(y_fit, dtype=jnp.float32)
    with span("stratum.gbt.fit", n=int(B_fit.shape[0]), F=F,
              n_trees=n_trees, depth=depth):
        feats, thrs, leaves = _fit_jax_binned(
            B_dev, y_dev, base, lr, reg, n_trees, depth, N_BINS, dense)
        feats, thrs = np.asarray(feats), np.asarray(thrs)
        leaves = np.asarray(leaves, dtype=np.float64)
    return pack(base, bins, feats.reshape(n_trees, -1),
                thrs.reshape(n_trees, -1), leaves.reshape(n_trees, -1),
                depth)


@partial(jax.jit, static_argnames=("depth", "dense"))
def _predict_jax(B, feats, thrs, leaves, base, depth: int,
                 dense: bool = False):
    n = B.shape[0]

    def one_tree(carry, tree):
        f, th, lv = tree
        node = jnp.zeros(n, dtype=jnp.int32)
        for _ in range(depth):
            go_right = (_row_values(B, _lookup(f, node, dense), dense)
                        > _lookup(th, node, dense))
            node = node * 2 + 1 + go_right.astype(jnp.int32)
        return carry + _lookup(lv, node - (2 ** depth - 1), dense), None

    out, _ = jax.lax.scan(one_tree, jnp.full((n,), base, dtype=leaves.dtype),
                          (feats, thrs, leaves))
    return out


def predict_jax(model: np.ndarray, X: np.ndarray, *,
                dense: bool = False) -> np.ndarray:
    base, bins, feats, thrs, leaves, depth = unpack(model, X.shape[1])
    with span("stratum.gbt.bin", n=X.shape[0], F=X.shape[1]):
        B = bin_data(X, bins)
    host = (B, feats, thrs, leaves)
    with span("stratum.gbt.put", bytes=sum(int(a.nbytes) for a in host)):
        dev = [jnp.asarray(a) for a in host]
    with span("stratum.gbt.predict", n=X.shape[0], n_trees=feats.shape[0],
              depth=depth):
        return np.asarray(_predict_jax(*dev, base, depth, dense))
