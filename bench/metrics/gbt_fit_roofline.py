"""The GBT fit program's share of its roofline: the least time the chip
needs for the fits that ran in the traced window, counted from the
algorithm (``bench/harness/gbt_work.py``), over the device seconds of
their ``_fit_jax_binned`` program events in the trace.

The chip runs programs in the order they were dispatched, so the k-th
fit event of the trace is the k-th fit the probe saw dispatched after the
trace started; a trace with more fit events than such calls reads
nothing."""

from bench.harness import gbt_work

PROGRAM = "_fit_jax_binned"


def read(ctx):
    s, p = ctx.summary, ctx.probe
    if s is None or p is None:
        return None
    events = s.program_events(PROGRAM)
    fits = [f for f in p.fits if f[0] >= ctx.trace_start]
    if not events or len(events) > len(fits):
        return None
    seconds = sum(e.dur for e in events)
    ideal = sum(gbt_work.ideal_seconds(n, F, t, d, ctx.peak)
                for _, n, F, t, d in fits[:len(events)])
    return 100.0 * ideal / seconds
