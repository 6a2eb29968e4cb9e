"""Drive one measured window through ``StratumClient`` and record every
job: when it was due, submitted and done, and what it returned.

One driver thread submits; completions arrive through the futures' done
callbacks, which only stamp the clock and queue the record."""

from __future__ import annotations

import queue
import time
from dataclasses import dataclass, field
from typing import Any, Optional

# a job still running this long after the window closed counts as failed
DRAIN_S = 60.0


@dataclass
class Record:
    job: dict
    due: float                      # perf_counter when it was due
    submitted: float = 0.0
    done: Optional[float] = None
    score: Optional[float] = None
    report: Any = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.done is not None and self.error is None


@dataclass
class Window:
    start: float
    seconds: float
    records: list = field(default_factory=list)
    give_up: float = 0.0            # when the driver stops waiting
    drained: float = 0.0            # clock read once the last job ended

    @property
    def end(self) -> float:
        return self.start + self.seconds

    def completed_in_window(self) -> list:
        return [r for r in self.records if r.ok and r.done <= self.end]

    def completed(self) -> list:
        """Every job of the window that ended with an answer, those in
        flight when it closed included."""
        return [r for r in self.records if r.ok]


def _watch(fut, rec: Record, done_q: "queue.Queue") -> None:
    def cb(f) -> None:
        rec.done = time.perf_counter()
        try:
            results, report = f.result(timeout=0)
            (value,) = results.values()
            rec.score = float(value)
            rec.report = report
        except Exception as e:  # noqa: BLE001 — a failed job is a record
            rec.error = repr(e)
        done_q.put(rec)
    fut.add_done_callback(cb)


def submit(client, rec: Record, build, done_q: "queue.Queue") -> None:
    batch = build(rec.job)
    rec.submitted = time.perf_counter()
    _watch(client.session(rec.job["agent"]).submit(batch), rec, done_q)


def run_rounds(client, rounds: list, build, timeout_s: float) -> list:
    """Set-up: rounds one after another, a round's jobs submitted
    together.  Returns every record."""
    out = []
    for jobs in rounds:
        done_q: queue.Queue = queue.Queue()
        recs = [Record(job=j, due=time.perf_counter()) for j in jobs]
        for r in recs:
            submit(client, r, build, done_q)
        for _ in recs:
            done_q.get(timeout=timeout_s)
        out.extend(recs)
    return out


def closed_loop(client, agents: list, build, seconds: float,
                on_open, on_close) -> Window:
    """Each agent (a job iterator) keeps one job in flight.  The window
    opens once every agent has had a job completed, so it measures the
    loop in its steady state (``on_open()`` is called then: the ramp is
    set-up), and closes ``seconds`` later (``on_close()``): nothing more
    is sent, the jobs in flight run to their end, and the clock is read
    once the last has ended (``drained``; a job still running
    ``DRAIN_S`` after the close is given up and counts as failed).  The
    window's records are the jobs that ended in it or were still running
    when it closed."""
    done_q: queue.Queue = queue.Queue()
    records = []
    agent_of = {}

    def send(i: int) -> None:
        rec = Record(job=next(agents[i]), due=time.perf_counter())
        agent_of[id(rec)] = i
        records.append(rec)
        submit(client, rec, build, done_q)

    for i in range(len(agents)):
        send(i)
    waiting = set(range(len(agents)))
    while waiting:
        rec = done_q.get(timeout=DRAIN_S * 10)
        waiting.discard(agent_of[id(rec)])
        send(agent_of[id(rec)])
    on_open()
    w = Window(start=time.perf_counter(), seconds=seconds)
    w.give_up = w.end + DRAIN_S
    in_flight = len(agents)
    closed = False
    while in_flight:
        now = time.perf_counter()
        if not closed and now >= w.end:
            on_close()
            closed = True
        until = w.end if not closed else w.give_up
        try:
            rec = done_q.get(timeout=max(until - now, 1e-3))
        except queue.Empty:
            if closed:
                break
            continue
        in_flight -= 1
        if time.perf_counter() < w.end:
            send(agent_of[id(rec)])
            in_flight += 1
    if not closed:
        on_close()
    w.drained = time.perf_counter()
    w.records = [r for r in records if r.done is None or r.done >= w.start]
    return w
