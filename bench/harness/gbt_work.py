"""The work one histogram-GBT fit needs, counted from the algorithm and
not from any one implementation of it.

Each level of each tree reads every row's bin id of every feature once
(one byte: there are 32 bins) and the row's gradient and node (four bytes
each), and adds each (row, feature) into a histogram for the gradient
sum and the row count.  So a fit of ``n`` rows, ``F`` features,
``n_trees`` trees of ``depth`` levels needs at least

    bytes = n_trees · depth · (n·F + 8·n)
    flops = n_trees · depth · 2·n·F

whether it builds the histograms by scatter-adds, by one-hot matrix
products or by a kernel: a faster form cannot read above 100% of this
roofline, and a form that does less wasted arithmetic reads higher."""

from __future__ import annotations

BIN_ID_BYTES = 1         # 32 bins fit in a byte
ROW_STATE_BYTES = 8      # float32 gradient + int32 node


def fit_bytes(n: int, F: int, n_trees: int, depth: int) -> float:
    return float(n_trees) * depth * (n * F * BIN_ID_BYTES
                                     + n * ROW_STATE_BYTES)


def fit_flops(n: int, F: int, n_trees: int, depth: int) -> float:
    return float(n_trees) * depth * 2.0 * n * F


def ideal_seconds(n: int, F: int, n_trees: int, depth: int,
                  peak: dict) -> float:
    """The least time the chip could take: the larger of the byte and the
    operation bound."""
    return max(fit_bytes(n, F, n_trees, depth) / peak["hbm_bytes_per_s"],
               fit_flops(n, F, n_trees, depth) / peak["bf16_flops_per_s"])
