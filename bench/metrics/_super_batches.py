"""Shared by the per-pipeline readers (not a metric): the super-batches
of the jobs completed in the window, read from the spans and counters the
program records on each job's run report.

All jobs of a super-batch share one run report and commit together, so a
super-batch's spans pair exactly with its jobs, counted by its
``stratum.dispatch`` span.  A program that records no spans gives no
super-batch, and the readers read nothing."""


def runs(ctx) -> list:
    """The distinct run reports, with spans, of the window's completed
    jobs."""
    seen = {}
    for r in ctx.completed:
        run = getattr(r.report, "run", None)
        if getattr(run, "spans", None):
            seen[id(run)] = run
    return list(seen.values())


def jobs_of(run) -> int:
    return sum(s[5].get("n_jobs", 0) for s in run.spans
               if s[2] == "stratum.dispatch")


def span_seconds(run, name: str, **match) -> float:
    """Summed seconds of ``run``'s spans called ``name`` whose attributes
    hold ``match`` (spans on parallel threads each count in full)."""
    return sum((s[4] - s[3]) * 1e-9 for s in run.spans
               if s[2] == name
               and all(s[5].get(k) == v for k, v in match.items()))


def per_pipeline(ctx, value_of):
    """``value_of(run)`` summed over the super-batches, over their jobs."""
    rs = runs(ctx)
    jobs = sum(jobs_of(r) for r in rs)
    if not jobs:
        return None
    return sum(value_of(r) for r in rs) / jobs
