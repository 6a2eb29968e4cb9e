"""The reduction from a device trace to the idle share, program time and
``breakdown``, and the GBT roofline's pairing of fit programs with the
fits dispatched to each chip: on hand-made events, and on a small trace
recorded on a TPU v5e (``data/``, written by ``record_trace.py``)."""

import json
import os

import pytest

from conftest import ROOT

import bench.run as R
from bench.harness import gbt_work
from bench.harness.probes import Probes
from bench.harness.trace import (Event, gaps, summarize, union_seconds,
                                 window_of)

DATA = os.path.join(os.path.dirname(__file__), "data")
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
FIT = "jit__fit_jax_binned"


def test_union_merges_overlaps():
    assert union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert union_seconds([]) == 0.0


def test_gaps_are_the_uncovered_stretches():
    assert gaps([(1, 2), (1.5, 3), (5, 6)], 0, 7) == [(0, 1), (3, 5),
                                                      (6, 7)]
    assert gaps([(0, 10)], 2, 8) == []


def test_summary_by_hand():
    dev = {0: [Event("jit__fit_jax_binned", 1.0, 2.0),
               Event("jit_other", 2.5, 1.0),
               Event("jit__fit_jax_binned", 6.0, 1.0)]}
    host = [Event("PjitFunction(bin_data)", 3.6, 2.0),
            Event("short", 3.5, 0.1)]
    s = summarize(dev, host, (0.0, 8.0))
    assert s.busy_s == pytest.approx(3.5)          # [1, 3.5) and [6, 7)
    assert s.window_s == 8.0
    assert s.device_ops[0] == ["jit__fit_jax_binned", 3.0]
    assert s.modules["jit_other"] == [1.0, 1]
    # gaps: [0,1) 1 s, [3.5,6) 2.5 s, [7,8) 1 s; the longest first
    assert s.idle_gaps[0] == ["PjitFunction(bin_data)", pytest.approx(2.5)]
    assert [round(g, 6) for _, g in s.idle_gaps] == [2.5, 1.0, 1.0]
    assert [e.start for e in s.program_events("_fit_jax_binned")[0]] == \
        [1.0, 6.0]


def test_events_outside_the_window_are_left_out():
    dev = {0: [Event("a", -1.0, 0.5), Event("b", 0.5, 1.0),
               Event("c", 9.0, 1.0)]}
    s = summarize(dev, [], (0.0, 2.0))
    assert s.busy_s == pytest.approx(1.0)
    assert list(s.modules) == ["b"]


def _recorded():
    with open(os.path.join(DATA, "tpu_trace_events.json")) as f:
        doc = json.load(f)
    devices = {chip: [Event(*e) for e in evs]
               for chip, evs in enumerate(doc["devices"])}
    host = [Event(*e) for e in doc["host"]]
    return doc, devices, host


def test_recorded_tpu_trace():
    doc, devices, host = _recorded()
    window = window_of(host)
    assert window == tuple(doc["window"])
    s = summarize(devices, host, window)
    (fits,) = s.program_events("_fit_jax_binned").values()
    # three fits, a sort, one more fit; this trace's device clock runs
    # 1.4 ms ahead of its host clock, so the fits launched in the window's
    # first 1.4 ms fall before the window's host span
    assert 2 <= len(fits) <= 4
    assert fits[-1].start > s.program_events("jit__lambda")[0][0].start
    assert 0 < s.busy_s < s.window_s
    by_name = dict(s.device_ops)
    assert len(by_name) == 2                 # the fit and the sort
    assert by_name[fits[0].name] == pytest.approx(sum(e.dur for e in fits))
    seconds = [sec for _, sec in s.device_ops]
    assert seconds == sorted(seconds, reverse=True)
    assert s.idle_gaps[0][1] >= 0.04         # the 50 ms sleep
    assert len(s.device_ops) <= 10 and len(s.idle_gaps) <= 10


def test_recorded_xplane_reads_as_its_event_list():
    from bench.harness.trace import read_xplane
    doc, devices, _ = _recorded()
    raw, _ = read_xplane(os.path.join(DATA, "tpu_trace.xplane.pb"))
    assert list(raw) == [0]                  # the plane /device:TPU:0
    assert [[(e.name, e.start, e.dur) for e in evs]
            for evs in raw.values()] == \
        [[(e.name, e.start, e.dur) for e in evs]
         for evs in devices.values()]


def _roofline(summary, fits, trace_start=0.0):
    probe = Probes()
    probe.fits = fits
    ctx = R.Context(summary=summary, probe=probe, trace_start=trace_start,
                    peak=PEAK)
    return R.reader(ROOT, "gbt_fit_roofline")(ctx)


def test_two_chip_roofline_pairs_each_chips_fits():
    # chip 1 ran a small fit, then a large one; chip 0 one large fit.
    # Dispatch order interleaves the chips, so a pairing across chips
    # would give chip 1's first event the large fit's work.
    big, small = (100_000, 50, 40, 3), (1_000, 50, 20, 2)
    fits = [(-1.0, *big, 0),                 # before the trace: left out
            (0.1, *big, 0), (0.2, *small, 1), (0.3, *big, 1),
            (0.4, *small, 0)]                # chip 0's, not yet on its trace
    dev = {0: [Event(FIT, 1.0, 0.5)],
           1: [Event(FIT, 1.1, 0.01), Event(FIT, 1.2, 0.4),
               Event("jit_other", 2.0, 0.1)]}
    s = summarize(dev, [], (0.0, 3.0))
    assert s.busy_s == pytest.approx((0.5 + 0.51) / 2)
    assert {c: len(e) for c, e in s.program_events("_fit").items()} == \
        {0: 1, 1: 2}
    ideal = (2 * gbt_work.ideal_seconds(*big, PEAK)
             + gbt_work.ideal_seconds(*small, PEAK))
    assert _roofline(s, fits) == pytest.approx(100 * ideal / 0.91)
    # a chip whose trace holds more fits than were dispatched to it reads
    # nothing, even where another chip had fits to spare
    assert _roofline(s, [(0.1, *big, 0), (0.2, *small, 0),
                         (0.3, *big, 0)]) is None


def test_recorded_trace_reads_as_before():
    """The one-chip trace gives the numbers the first chip's reduction
    gave before the chips were kept apart."""
    _, devices, host = _recorded()
    s = summarize(devices, host, window_of(host))
    assert s.busy_s == 0.0004216189999999981
    assert s.window_s == 0.05584852700000001
    assert s.device_ops == [
        ["jit__lambda(17064782472079122294)", 0.000319466],
        ["jit__fit_jax_binned(18044654808549514128)",
         0.00010215300000000001]]
    assert s.idle_gaps == [
        ["PjitFunction(_fit_jax_binned)", 0.051916922000000004],
        ["PjitFunction(<lambda>)", 0.0020031130000000147],
        ["PjitFunction(_fit_jax_binned)", 0.000762203000000003],
        ["no host event", 0.000744669999999989]]
    events = s.program_events("_fit_jax_binned")[0]
    assert [(e.start, e.dur) for e in events] == [
        (0.049064641000000006, 4.8150000000000005e-05),
        (0.102093849, 5.4003000000000005e-05)]
    # the fits the probe saw dispatched after the trace started, and the
    # reading the first chip's pairing gave
    shape = (1024, 1024, 1, 4)
    fits = [(0.01, *shape, 0), (0.02, *shape, 0), (0.03, *shape, 0)]
    before = 100.0 * sum(gbt_work.ideal_seconds(*shape, PEAK)
                         for _ in events) / sum(e.dur for e in events)
    assert _roofline(s, fits) == before
    assert _roofline(s, fits[:1]) is None
