"""Drive one measured window through ``StratumClient`` and record every
job: when it was due, submitted and done, and what it returned.

One driver thread submits; completions arrive through the futures' done
callbacks, which only stamp the clock and queue the record.

On a fabric each job is pinned to a shard (``Record.pin``: the shard's id
and a routing key the ring sends there); a job whose report names another
shard ends as a failed record."""

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
    pin: Optional[tuple] = None     # (shard id, routing key) on a fabric

    @property
    def ok(self) -> bool:
        return self.done is not None and self.error is None


@dataclass
class Window:
    start: float
    seconds: float
    records: list = field(default_factory=list)
    give_up: float = 0.0            # when the driver stops waiting
    drained: float = 0.0            # when the window's last job ended

    @property
    def end(self) -> float:
        return self.start + self.seconds

    def completed_in_window(self) -> list:
        return [r for r in self.records if r.ok and r.done <= self.end]

    def completed(self) -> list:
        """Every job of the window that ended with an answer, those in
        flight when it closed included."""
        return [r for r in self.records if r.ok]

    def batches(self) -> list:
        """The super-batches the service ran the window's answered jobs
        in (the jobs of one share their report's ``run``), in the order
        they ended: ``(jobs, first sent, last ended)``, seconds from the
        window's start."""
        groups: dict = {}
        for r in self.completed():
            groups.setdefault(id(getattr(r.report, "run", r)), []).append(r)
        out = [(len(g), min(r.submitted for r in g) - self.start,
                max(r.done for r in g) - self.start)
               for g in groups.values()]
        return sorted(out, key=lambda b: b[2])


def _watch(fut, rec: Record, done_q: "queue.Queue") -> None:
    def cb(f) -> None:
        rec.done = time.perf_counter()
        try:
            results, report = f.result(timeout=0)
            (value,) = results.values()
            rec.score = float(value)
            rec.report = report
            if rec.pin is not None and report.shard_id != rec.pin[0]:
                rec.error = (f"ran on {report.shard_id}, pinned to "
                             f"{rec.pin[0]}")
        except Exception as e:  # noqa: BLE001 — a failed job is a record
            rec.error = repr(e)
        done_q.put(rec)
    fut.add_done_callback(cb)


def submit(client, rec: Record, build, done_q: "queue.Queue") -> None:
    batch = build(rec.job)
    rec.submitted = time.perf_counter()
    session = client.session(rec.job["agent"])
    if rec.pin is None:
        fut = session.submit(batch)
    else:
        from repro.client import SubmitOptions
        fut = session.submit(batch, SubmitOptions(affinity=rec.pin[1]))
    _watch(fut, rec, done_q)


def run_rounds(client, rounds: list, build, timeout_s: float,
               pins: list = (None,)) -> float:
    """Set-up: rounds one after another, a round's jobs submitted
    together, once under each of ``pins`` (each shard of a fabric
    compiles its own programs).  ``timeout_s`` is the longest wait for
    any one job to complete; returns the longest wait there was."""
    longest = 0.0
    for jobs in rounds:
        done_q: queue.Queue = queue.Queue()
        recs = [Record(job=j, due=time.perf_counter(), pin=p)
                for p in pins for j in jobs]
        for r in recs:
            submit(client, r, build, done_q)
        for _ in recs:
            t0 = time.perf_counter()
            done_q.get(timeout=timeout_s)
            longest = max(longest, time.perf_counter() - t0)
    return longest


def closed_loop(client, agents: list, build, seconds: float,
                on_open, on_close, pins: Optional[list] = None) -> Window:
    """Each agent (a job iterator) keeps one job in flight.  The window
    opens once every agent has had a job completed, so it measures the
    loop in its steady state (``on_open()`` is called then: the ramp is
    set-up), and closes ``seconds`` later (``on_close()``): nothing more
    is sent, the jobs in flight run to their end, and ``drained`` is when
    the last of them ended, as its record stamps it (a job still running
    ``DRAIN_S`` after ``on_close()`` returns is given up and counts as
    failed).  So neither end counts the close's own work, such as writing
    the trace of a traced run.  The
    window's records are the jobs that ended in it or were still running
    when it closed.  ``pins[i]``, where given, pins agent ``i``'s jobs."""
    done_q: queue.Queue = queue.Queue()
    records = []
    agent_of = {}

    def send(i: int) -> None:
        rec = Record(job=next(agents[i]), due=time.perf_counter(),
                     pin=pins[i] if pins else None)
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
    in_flight = len(agents)
    closed = False
    while in_flight:
        now = time.perf_counter()
        if not closed and now >= w.end:
            on_close()
            closed = True
            now = time.perf_counter()
            w.give_up = now + DRAIN_S
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
    w.records = [r for r in records if r.done is None or r.done >= w.start]
    w.drained = max([w.end] + [r.done for r in w.records
                               if r.done is not None])
    return w
