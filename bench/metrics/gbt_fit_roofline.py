"""The GBT fit program's share of its roofline: the least time the chips
need for the fits that ran in the traced window, counted from the
algorithm (``bench/harness/gbt_work.py``), over the device seconds of
their ``_fit_jax_binned`` program events in the trace.

Each chip runs programs in the order they were dispatched to it, so the
k-th fit event of a chip's trace is the k-th fit the probe saw dispatched
to that chip after the trace started; both sums run over the chips.  A
chip whose trace has more fit events than such calls reads nothing."""

from bench.harness import gbt_work

PROGRAM = "_fit_jax_binned"


def read(ctx):
    s, p = ctx.summary, ctx.probe
    if s is None or p is None:
        return None
    seconds = ideal = 0.0
    for chip, events in s.program_events(PROGRAM).items():
        fits = [f for f in p.fits if f[0] >= ctx.trace_start and f[5] == chip]
        if len(events) > len(fits):
            return None
        seconds += sum(e.dur for e in events)
        ideal += sum(gbt_work.ideal_seconds(n, F, t, d, ctx.peak)
                     for _, n, F, t, d, _ in fits[:len(events)])
    if not seconds:
        return None
    return 100.0 * ideal / seconds
