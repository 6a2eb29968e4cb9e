"""Megabytes that crossed between the host and device tiers (the run
report's ``h2d_bytes`` and ``d2h_bytes``: numpy inputs of device-tier ops
and device arrays handed to python-tier ops) per scored pipeline, over
the super-batches of the window's completed jobs."""

from bench.metrics import _super_batches as sb


def read(ctx):
    return sb.per_pipeline(
        ctx, lambda run: (run.h2d_bytes + run.d2h_bytes) / 1e6)
