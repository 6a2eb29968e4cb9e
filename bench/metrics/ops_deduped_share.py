"""Share of the window's operations that the coalescer saved by merging
jobs of different agents (the service's ``ops_deduped_cross_agent``
counter), over the operations of the jobs completed in the window (each
job's report: executed per backend, cache hits, salvage)."""


def read(ctx):
    ops = sum(sum(r.report.per_backend.values()) + r.report.cache_hits
              + r.report.ops_salvaged for r in ctx.completed)
    if not ops:
        return None
    saved = (ctx.after["ops_deduped_cross_agent"]
             - ctx.before["ops_deduped_cross_agent"])
    return 100.0 * saved / ops
