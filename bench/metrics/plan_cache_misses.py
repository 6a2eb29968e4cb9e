"""Compiled-segment plan-cache misses in the window (the service's
counter): each one traces and compiles a program in the window."""


def read(ctx):
    return (ctx.after["plan_cache"]["misses"]
            - ctx.before["plan_cache"]["misses"])
