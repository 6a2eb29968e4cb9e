"""The profiler window and its reduction to numbers.

``Profiler`` traces the measured window with JAX's profiler.  ``reduce``
reads the device's events from the written ``.xplane.pb`` and gives

* ``busy_s``: the union of the intervals in which a program ran on the
  device (the ``XLA Modules`` line of each TPU plane), averaged over the
  chips, and ``window_s``, the traced window's length;
* ``modules``: summed device seconds and event count per program name;
* ``device_ops`` and ``idle_gaps`` for the ``breakdown``: the programs
  that took most device time, and the longest gaps between device work,
  each named by the host event that overlaps it most.

The window is the host span ``bench.window`` that ``Profiler`` opens and
closes around it; device and host events share the trace's clock to
about a millisecond.

The reduction works on plain event lists, so it is tested on a small
recorded trace (``bench/tests/data``)."""

from __future__ import annotations

import glob
import os
import time
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


@dataclass
class Event:
    name: str
    start: float        # seconds, trace clock
    dur: float


@dataclass
class Summary:
    busy_s: float
    window_s: float
    modules: dict = field(default_factory=dict)  # name -> [seconds, count]
    device_ops: list = field(default_factory=list)
    idle_gaps: list = field(default_factory=list)
    programs: list = field(default_factory=list)  # first chip's, in order

    def program_events(self, part: str) -> list:
        """The first chip's events of programs whose name holds ``part``,
        in the order they ran."""
        return [e for e in self.programs if part in e.name]


def union_seconds(intervals: list) -> float:
    """Length of the union of ``[start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: list, lo: float, hi: float) -> list:
    """``[(start, end)]`` of the stretches of ``[lo, hi)`` that no
    interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def _label(gap: tuple, host: list) -> str:
    best, best_overlap = "no host event", 0.0
    for ev in host:
        overlap = min(gap[1], ev.start + ev.dur) - max(gap[0], ev.start)
        if overlap > best_overlap:
            best, best_overlap = ev.name, overlap
    return best


def summarize(devices: list, host: list, window: tuple,
              top: int = 10) -> Summary:
    """``devices``: per chip, its program events; ``host``: host events;
    ``window``: ``(lo, hi)`` of the traced window on the trace clock."""
    lo, hi = window
    busy, modules = [], {}
    merged = []
    devices = [sorted((e for e in events if lo <= e.start < hi),
                      key=lambda e: e.start) for events in devices]
    for events in devices:
        iv = [(e.start, min(e.start + e.dur, hi)) for e in events]
        busy.append(union_seconds(iv))
        merged.extend(iv)
        for e in events:
            row = modules.setdefault(e.name, [0.0, 0])
            row[0] += e.dur
            row[1] += 1
    ops = sorted(((name, sec) for name, (sec, _) in modules.items()),
                 key=lambda x: -x[1])[:top]
    # gaps of the first chip: with one chip per cell this is the chip
    idle = gaps([(s, e) for s, e in merged], lo, hi) if devices else []
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    hosts = [h for h in host if h.dur > 0 and h.name != WINDOW_SPAN]
    return Summary(
        busy_s=sum(busy) / max(len(busy), 1),
        window_s=hi - lo, modules=modules,
        device_ops=[[n, s] for n, s in ops],
        idle_gaps=[[_label(g, hosts), g[1] - g[0]] for g in longest],
        programs=devices[0] if devices else [])


def read_xplane(path: str):
    """``(devices, host)`` event lists of one ``.xplane.pb``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    devices.append([Event(e.name, e.start_ns * 1e-9,
                                          e.duration_ns * 1e-9)
                                    for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns * 1e-9,
                                  e.duration_ns * 1e-9)
                            for e in line.events if e.duration_ns > 0)
    return devices, host


def window_of(host: list):
    """The traced window on the trace clock: the host span the profiler
    opens at the window's start and closes at its end."""
    for e in host:
        if e.name == WINDOW_SPAN:
            return e.start, e.start + e.dur
    raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


class Profiler:
    """JAX's profiler around the window, which it marks with one host
    span (``bench.window``) so the window's bounds are known on the
    trace's own clock."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.t0 = self.t1 = None
        self._span = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.t1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self) -> Summary:
        paths = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise RuntimeError(f"no trace written under {self.out_dir}")
        devices, host = read_xplane(paths[-1])
        return summarize(devices, host, window_of(host))
