"""Seconds in the optimizer (``stratum.compile_batch`` spans: lowering,
rewrites, selection and planning of each super-batch) per scored
pipeline, over the super-batches of the window's completed jobs."""

from bench.metrics import _super_batches as sb


def read(ctx):
    return sb.per_pipeline(
        ctx, lambda run: sb.span_seconds(run, "stratum.compile_batch"))
