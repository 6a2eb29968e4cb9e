"""``bench/harness/drive.py`` on a stand-in client whose jobs end after a
set delay: the drain is counted from when the close's work returns, the
window's end is when its last job ended, a job that ran on another shard
than its pin fails, and the warm-up runs each round once per pin."""

import threading
import time
from concurrent.futures import Future
from types import SimpleNamespace

from bench.harness import drive


class Client:
    """Each job ends ``delay`` seconds after it is sent, on the shard
    ``route(affinity)`` names."""

    def __init__(self, delay: float, route=lambda key: "shard-0"):
        self.delay, self.route = delay, route
        self.sent = []

    def session(self, agent):
        return SimpleNamespace(submit=lambda batch, opts=None:
                               self._submit(agent, opts))

    def _submit(self, agent, opts):
        key = opts.affinity if opts is not None else None
        self.sent.append((agent, key))
        fut = Future()
        report = SimpleNamespace(shard_id=self.route(key))
        threading.Timer(self.delay, fut.set_result,
                        [({"score": 1.0}, report)]).start()
        return fut


def _agents(n):
    def jobs(i):
        while True:
            yield {"agent": f"agent-{i}"}
    return [jobs(i) for i in range(n)]


def test_drain_counts_from_when_the_close_returns(monkeypatch):
    # jobs take 1 s; the window's second jobs are sent about 1 s in and
    # end 0.8 s after the close, which takes 0.6 s; counted from the
    # close itself a drain of 0.5 s would give them up
    monkeypatch.setattr(drive, "DRAIN_S", 0.5)
    client = Client(delay=1.0)
    w = drive.closed_loop(client, _agents(2), lambda job: None, 1.2,
                          lambda: None, lambda: time.sleep(0.6))
    assert len(w.records) == 4 and all(r.ok for r in w.records)
    assert max(r.done for r in w.records) - w.end > 0.6


def test_drained_is_when_the_last_job_ended_not_when_the_close_returned():
    # the close takes 1.5 s, as writing a trace does; the jobs in flight
    # end 0.8 s after the close, and the rate's clock stops there
    client = Client(delay=1.0)
    t0 = time.perf_counter()
    w = drive.closed_loop(client, _agents(2), lambda job: None, 1.2,
                          lambda: None, lambda: time.sleep(1.5))
    assert time.perf_counter() - t0 > 2.7
    assert w.drained == max(r.done for r in w.records)
    assert w.drained - w.end < 1.2


def test_a_job_that_ran_on_another_shard_fails():
    client = Client(delay=0.05, route=lambda key: "shard-1")
    pins = [("shard-0", "k0"), ("shard-1", "k1")]
    w = drive.closed_loop(client, _agents(2), lambda job: None, 0.3,
                          lambda: None, lambda: None, pins=pins)
    by_pin = {r.pin[0]: r for r in w.records}
    assert not by_pin["shard-0"].ok
    assert "ran on shard-1, pinned to shard-0" in by_pin["shard-0"].error
    assert by_pin["shard-1"].ok
    assert {key for _, key in client.sent} == {"k0", "k1"}


def test_warmup_runs_each_round_once_per_pin():
    client = Client(delay=0.05)
    rounds = [[{"agent": "warm-0"}, {"agent": "warm-1"}],
              [{"agent": "warm-2"}]]
    pins = [("shard-0", "k0"), ("shard-1", "k1"), ("shard-2", "k2")]
    longest = drive.run_rounds(client, rounds, lambda job: None, 5.0, pins)
    assert sorted(client.sent) == sorted(
        (j["agent"], key) for r in rounds for _, key in pins for j in r)
    assert 0.0 < longest < 5.0
    client.sent.clear()
    drive.run_rounds(client, rounds, lambda job: None, 5.0)
    assert client.sent == [("warm-0", None), ("warm-1", None),
                           ("warm-2", None)]


def test_batches_group_the_jobs_that_share_a_run_report():
    w = drive.Window(start=10.0, seconds=5.0)
    runs = [object(), object()]

    def rec(run, submitted, done):
        return drive.Record(job={}, due=submitted, submitted=submitted,
                            done=done, score=1.0,
                            report=SimpleNamespace(run=run))
    w.records = [rec(runs[1], 14.0, 17.0), rec(runs[0], 10.0, 13.0),
                 rec(runs[0], 10.5, 13.0), rec(runs[1], 13.25, 17.0),
                 drive.Record(job={}, due=14.0, submitted=14.0)]
    assert w.batches() == [(2, 0.0, 3.0), (2, 3.25, 7.0)]
