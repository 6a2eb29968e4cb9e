"""Scored pipelines of the window over its seconds (host clock): every job
that ran in the window counts, the jobs in flight when it closed run to
their end with nothing more sent, and the seconds run from the window's
start until the last of them has ended."""


def read(ctx):
    w = ctx.window
    return len(w.completed()) / (w.drained - w.start)
