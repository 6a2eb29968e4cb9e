"""Share of the executed operations of the jobs completed in the window
that ran on the python tier (each job's report, by backend)."""


def read(ctx):
    total = sum(sum(r.report.per_backend.values()) for r in ctx.completed)
    if not total:
        return None
    py = sum(r.report.per_backend.get("python", 0) for r in ctx.completed)
    return 100.0 * py / total
