"""Share of the traced window in which no program ran on the chip: one
minus the union of the device's program intervals over the window
(profiler trace)."""


def read(ctx):
    s = ctx.summary
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
