"""The reduction from a device trace to the idle share, program time and
``breakdown``: on hand-made events, and on a small trace recorded on a
TPU v5e (``data/``, written by ``record_trace.py``)."""

import json
import os

import pytest

from bench.harness.trace import (Event, gaps, summarize, union_seconds,
                                 window_of)

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_merges_overlaps():
    assert union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert union_seconds([]) == 0.0


def test_gaps_are_the_uncovered_stretches():
    assert gaps([(1, 2), (1.5, 3), (5, 6)], 0, 7) == [(0, 1), (3, 5),
                                                      (6, 7)]
    assert gaps([(0, 10)], 2, 8) == []


def test_summary_by_hand():
    dev = [[Event("jit__fit_jax_binned", 1.0, 2.0),
            Event("jit_other", 2.5, 1.0), Event("jit__fit_jax_binned", 6.0,
                                                1.0)]]
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
    assert [e.start for e in s.program_events("_fit_jax_binned")] == [1.0,
                                                                      6.0]


def test_events_outside_the_window_are_left_out():
    dev = [[Event("a", -1.0, 0.5), Event("b", 0.5, 1.0),
            Event("c", 9.0, 1.0)]]
    s = summarize(dev, [], (0.0, 2.0))
    assert s.busy_s == pytest.approx(1.0)
    assert list(s.modules) == ["b"]


def _recorded():
    with open(os.path.join(DATA, "tpu_trace_events.json")) as f:
        doc = json.load(f)
    devices = [[Event(*e) for e in evs] for evs in doc["devices"]]
    host = [Event(*e) for e in doc["host"]]
    return doc, devices, host


def test_recorded_tpu_trace():
    doc, devices, host = _recorded()
    window = window_of(host)
    assert window == tuple(doc["window"])
    s = summarize(devices, host, window)
    fits = s.program_events("_fit_jax_binned")
    # three fits, a sort, one more fit; this trace's device clock runs
    # 1.4 ms ahead of its host clock, so the fits launched in the window's
    # first 1.4 ms fall before the window's host span
    assert 2 <= len(fits) <= 4
    assert fits[-1].start > s.program_events("jit__lambda")[0].start
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
    assert [[(e.name, e.start, e.dur) for e in evs] for evs in raw] == \
        [[(e.name, e.start, e.dur) for e in evs] for evs in devices]
