"""Process start to the first timed submit: table files, service start,
warm-up jobs and every compile they cause (host clock)."""


def read(ctx):
    return ctx.setup_s
