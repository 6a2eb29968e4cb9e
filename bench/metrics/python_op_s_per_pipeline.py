"""Seconds of python-tier operations (``stratum.op`` spans with
``tier == "python"``) per scored pipeline, over the super-batches of the
window's completed jobs."""

from bench.metrics import _super_batches as sb


def read(ctx):
    return sb.per_pipeline(
        ctx, lambda run: sb.span_seconds(run, "stratum.op", tier="python"))
