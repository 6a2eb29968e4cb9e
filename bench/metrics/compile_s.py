"""Seconds the program's process spent tracing, lowering and compiling
JAX programs in the window: the change of its process-wide compile
counter (``global_snapshot()["compile"]["s"]``), which sees the GBT's
direct jits and background compiles as well as compiled segments."""


def read(ctx):
    before, after = ctx.before.get("compile"), ctx.after.get("compile")
    if before is None or after is None:
        return None
    return after["s"] - before["s"]
