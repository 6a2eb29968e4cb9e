"""Host seconds spent binning GBT inputs (``stratum.gbt.bin`` spans:
quantile edges and digitizing, in ``tabular/gbt.py``) per scored
pipeline, over the super-batches of the window's completed jobs."""

from bench.metrics import _super_batches as sb


def read(ctx):
    return sb.per_pipeline(
        ctx, lambda run: sb.span_seconds(run, "stratum.gbt.bin"))
