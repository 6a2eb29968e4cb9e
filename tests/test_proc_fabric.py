"""Out-of-process shard fabric: stream framing under adversity, wire
exception fidelity across real process boundaries, supervised failover
(``kill -9`` loses zero jobs), graceful shutdown with no orphans, the
synchronous admission window, warm cache hand-off on scale-down, and
elastic autoscaling."""

import base64
import os
import pickle
import signal
import subprocess
import sys
import time
from concurrent.futures import CancelledError

import pytest

from repro.core import PipelineBatch
from repro.service.fabric import (CodecError, JobEnvelope, ProcConfig,
                                  ProcStratumFabric, ShardedStratum,
                                  decode_job, encode_job, encode_result,
                                  ResultEnvelope)
from repro.service.fabric.proc.frames import (BYE, CONFIG, DRAIN,
                                              HANDOFF_DATA, HANDOFF_PUT,
                                              HANDOFF_REQ, HEARTBEAT, HELLO,
                                              FrameDecoder, FrameError,
                                              decode_control, encode_control)
from repro.service.queue import AdmissionError, DeadlineExceeded
import repro.tabular as T

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")

N_ROWS = 1200


def _pipeline(data_seed=0, cols=(10, 11, 12), kind="mae"):
    x = T.read("uk_housing", N_ROWS, seed=data_seed)
    xs = T.scale(T.impute(T.project(x, list(cols))))
    y = T.project(x, [0])
    return T.metric(T.project(xs, [0]), y, kind=kind)


def _batch(name="p", **kw):
    return PipelineBatch([_pipeline(**kw)], [name])


@pytest.fixture(scope="module", autouse=True)
def _datasets():
    # workers read the shared data lake; generate every seed up front so
    # no worker ever races the atomic-write path mid-test
    from repro.data.tabular import ensure_files
    for seed in range(16):
        ensure_files("uk_housing", N_ROWS, seed=seed)


def _proc_fabric(n_shards=2, proc=None, **kw):
    kw.setdefault("memory_budget_bytes", 1 << 30)
    kw.setdefault("n_executors", 1)
    kw.setdefault("coalesce_window_s", 0.0)
    proc = proc or ProcConfig(heartbeat_s=0.1, heartbeat_timeout_s=3.0,
                              reconnect_grace_s=0.5)
    return ProcStratumFabric(n_shards=n_shards, proc=proc, **kw)


def _frames_with_prefix(frames):
    out = bytearray()
    for f in frames:
        out += len(f).to_bytes(4, "big") + f
    return bytes(out)


# ---------------------------------------------------------------------------
# stream framing under adversity
# ---------------------------------------------------------------------------

def test_frame_decoder_reassembles_one_byte_feeds():
    frame = encode_control(HEARTBEAT, {"queue_depth": 3})
    stream = _frames_with_prefix([frame])
    dec = FrameDecoder()
    got = []
    for i in range(len(stream)):
        got += dec.feed(stream[i:i + 1])
    assert got == [frame]
    assert dec.pending_bytes() == 0


def test_frame_decoder_interleaved_kinds_in_one_chunk():
    job = encode_job(JobEnvelope(envelope_id="e-0", tenant="t",
                                 priority=1, routing_key="k",
                                 batch=_batch()))
    result = encode_result(ResultEnvelope(envelope_id="e-0", tenant="t",
                                          shard_id="s", ok=False,
                                          error=RuntimeError("x")))
    beat = encode_control(HEARTBEAT, {"inflight": 1})
    stream = _frames_with_prefix([job, beat, result])
    dec = FrameDecoder()
    # split at an arbitrary unaligned point: partial tail carries over
    got = dec.feed(stream[:len(stream) // 3])
    got += dec.feed(stream[len(stream) // 3:])
    assert got == [job, beat, result]


def test_frame_decoder_oversize_length_word_raises():
    dec = FrameDecoder(max_frame_bytes=1024)
    with pytest.raises(FrameError):
        dec.feed((1 << 20).to_bytes(4, "big") + b"xxxx")


def test_checksum_corruption_poisons_one_frame_not_the_stream():
    a = encode_control(HEARTBEAT, {"n": 1})
    b = encode_control(HEARTBEAT, {"n": 2})
    corrupted = a[:-1] + bytes([a[-1] ^ 0xFF])   # flip payload byte
    dec = FrameDecoder()
    frames = dec.feed(_frames_with_prefix([corrupted, b]))
    assert len(frames) == 2                      # framing stays in sync
    with pytest.raises(CodecError):
        decode_control(frames[0])                # poisoned alone
    assert decode_control(frames[1]) == (HEARTBEAT, {"n": 2})


def test_control_codec_round_trip_every_kind():
    for kind in (HELLO, CONFIG, HEARTBEAT, DRAIN, BYE,
                 HANDOFF_REQ, HANDOFF_DATA, HANDOFF_PUT):
        obj = {"kind": kind, "blob": b"\x00\xff" * 8}
        assert decode_control(encode_control(kind, obj)) == (kind, obj)
    with pytest.raises(ValueError):
        encode_control(0x01, {})                 # data-plane kind refused
    with pytest.raises(CodecError):
        decode_control(encode_job(JobEnvelope(
            envelope_id="e", tenant="t", priority=1, routing_key="k",
            batch=_batch())))


# ---------------------------------------------------------------------------
# wire-crossing exceptions survive a REAL process boundary
# ---------------------------------------------------------------------------

def _unpickles_in_fresh_process(obj, check: str) -> None:
    """Pickle here, unpickle in a clean interpreter, run ``check`` there."""
    blob = base64.b64encode(pickle.dumps(obj)).decode()
    code = (f"import base64, pickle\n"
            f"e = pickle.loads(base64.b64decode('{blob}'))\n"
            f"{check}\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_execution_error_crosses_process_with_op_and_cause():
    from repro.core.runtime import ExecutionError
    op = _pipeline().op
    err = ExecutionError(op, ValueError("original cause"))
    _unpickles_in_fresh_process(
        err,
        "assert type(e).__name__ == 'ExecutionError'\n"
        "assert e.op is not None and e.op.op_name\n"
        "assert isinstance(e.cause, ValueError)")


def test_execution_preempted_crosses_process_with_payload():
    from repro.core.runtime import ExecutionPreempted
    p = ExecutionPreempted(salvage={"sig": (1, 2)}, waves_done=3)
    _unpickles_in_fresh_process(
        p,
        "assert e.salvage == {'sig': (1, 2)} and e.waves_done == 3")


def test_admission_and_deadline_errors_cross_process():
    _unpickles_in_fresh_process(
        AdmissionError("queue full"),
        "assert type(e).__name__ == 'AdmissionError'\n"
        "assert 'queue full' in str(e)")
    _unpickles_in_fresh_process(
        DeadlineExceeded("too late"),
        "assert type(e).__name__ == 'DeadlineExceeded'")


def test_execution_error_with_unpicklable_cause_degrades_not_drops():
    from repro.core.runtime import ExecutionError
    from repro.service.fabric.envelope import decode_result

    class Unpicklable(Exception):
        def __reduce__(self):
            raise TypeError("nope")

    err = ExecutionError(_pipeline().op, Unpicklable("device handle"))
    data = encode_result(ResultEnvelope(envelope_id="e", tenant="t",
                                        shard_id="s", ok=False, error=err))
    out = decode_result(data).error
    # .op and .cause survive; the unpicklable cause is stringified
    assert type(out).__name__ == "ExecutionError"
    assert out.op.op_name == err.op.op_name
    assert "device handle" in repr(out.cause)


# ---------------------------------------------------------------------------
# end-to-end over real worker processes
# ---------------------------------------------------------------------------

def test_proc_fabric_matches_in_process_fabric():
    local = ShardedStratum(n_shards=1, memory_budget_bytes=1 << 30,
                           n_executors=1, coalesce_window_s=0.0)
    try:
        want, _ = local.session("t").submit(_batch()).result(timeout=120)
    finally:
        local.stop()
    fab = _proc_fabric(n_shards=2)
    try:
        got, report = fab.session("t").submit(_batch()).result(timeout=120)
        assert float(got["p"]) == pytest.approx(float(want["p"]))
        assert report.shard_id in fab.shard_ids()
    finally:
        fab.stop()


def test_client_processes_true_is_the_same_surface():
    from repro.client import StratumConfig, SubmitOptions, connect
    cfg = StratumConfig.make(memory_budget_bytes=1 << 30, n_shards=2,
                             processes=True, n_executors=1,
                             coalesce_window_s=0.0)
    with connect("fabric", cfg) as client:
        value, report = client.run(_pipeline(),
                                   options=SubmitOptions(deadline_s=120.0))
        assert report.deadline_met is True
        snap = client.telemetry.global_snapshot()
        assert len(snap["proc"]["workers"]) == 2
        for pid in snap["proc"]["workers"].values():
            os.kill(pid, 0)                     # live worker processes


def test_sigkill_mid_flood_loses_zero_jobs_and_keeps_deadlines():
    fab = _proc_fabric(n_shards=2)
    try:
        sess = fab.session("agent-0")
        futs = [sess.submit(_batch(data_seed=s), deadline_s=300.0)
                for s in range(10)]
        victim = fab.shard_ids()[-1]
        os.kill(fab.supervisor.live_workers()[victim], signal.SIGKILL)
        reports = [f.result(timeout=300)[1] for f in futs]
        assert len(reports) == 10               # zero loss
        g = fab.telemetry.global_snapshot()
        assert g["shards_failed"] == 1
        assert g["failover_requeues"] > 0
        retried = [r for r in reports if r.attempt > 0]
        assert retried, "the killed shard's jobs must have been requeued"
        for r in retried:
            # deadline budgets shrink across failover, never reset: the
            # requeued attempt saw strictly less than the original SLO
            assert r.deadline_s is not None and r.deadline_s < 300.0
        assert all(r.deadline_met for r in reports)
    finally:
        fab.stop()


def test_hung_worker_detected_by_heartbeat_timeout_and_failed_over():
    proc = ProcConfig(heartbeat_s=0.1, heartbeat_timeout_s=1.0,
                      reconnect_grace_s=0.5)
    fab = _proc_fabric(n_shards=2, proc=proc)
    try:
        sess = fab.session("agent-0")
        futs = [sess.submit(_batch(data_seed=s)) for s in range(6)]
        victim = fab.shard_ids()[-1]
        pid = fab.supervisor.live_workers()[victim]
        os.kill(pid, signal.SIGSTOP)            # alive but silent
        try:
            for f in futs:
                f.result(timeout=300)           # zero loss despite the hang
        finally:
            try:
                os.kill(pid, signal.SIGCONT)
            except ProcessLookupError:
                pass                            # supervisor already killed it
        assert any(sid == victim for sid, _ in fab.supervisor.failures)
        assert victim not in fab.shard_ids()
    finally:
        fab.stop()


def test_graceful_stop_exits_zero_and_leaves_no_orphans():
    fab = _proc_fabric(n_shards=2)
    sess = fab.session("t")
    for s in range(3):
        sess.submit(_batch(data_seed=s)).result(timeout=120)
    pids = dict(fab.supervisor.live_workers())
    fab.stop()
    assert set(fab.supervisor.reaped) == set(pids)
    for sid, rc in fab.supervisor.reaped.items():
        assert rc == 0, f"worker {sid} exited {rc}, not a clean drain"
    for pid in pids.values():                   # process-table check
        with pytest.raises(ProcessLookupError):
            os.kill(pid, 0)


def test_admission_window_raises_synchronously_at_submit():
    proc = ProcConfig(heartbeat_s=0.1, heartbeat_timeout_s=3.0, window=1)
    fab = _proc_fabric(n_shards=1, proc=proc)
    try:
        sess = fab.session("t")
        futs, rejected = [], 0
        for s in range(8):
            try:
                futs.append(sess.submit(_batch(data_seed=s)))
            except AdmissionError:
                rejected += 1                   # raised AT THE CALL SITE
        assert rejected > 0, "window=1 must push back synchronously"
        for f in futs:
            f.result(timeout=120)               # admitted work completes
    finally:
        fab.stop()


def test_scale_down_hands_hot_cache_to_ring_successor():
    fab = _proc_fabric(n_shards=2)
    try:
        sess = fab.session("t")
        victim = fab.newest_shard()
        victim_seeds = []
        for s in range(8):
            _, rep = sess.submit(_batch(data_seed=s)).result(timeout=120)
            if rep.shard_id == victim:
                victim_seeds.append(s)
        assert victim_seeds, "hash spread should hit both shards"
        fab.scale_down(victim)
        assert fab.supervisor.handoff_entries_shipped > 0
        assert fab.shard_ids() == [s for s in fab.shard_ids()
                                   if s != victim]
        # a pipeline only the departed shard ever computed now hits warm
        # cache on the survivor — the hand-off carried the entries over
        _, rep = sess.submit(
            _batch(data_seed=victim_seeds[0])).result(timeout=120)
        assert rep.shard_id != victim
        assert rep.cache_hits > 0
    finally:
        fab.stop()


def test_autoscaler_grows_under_backlog_and_drains_idle():
    fab = ProcStratumFabric(
        n_shards=1, memory_budget_bytes=1 << 30, n_executors=1,
        coalesce_window_s=0.0, autoscale=(1, 2),
        proc=ProcConfig(heartbeat_s=0.1, heartbeat_timeout_s=3.0))
    try:
        fab.autoscaler.policy.scale_up_backlog_per_shard = 2.0
        fab.autoscaler.policy.scale_down_idle_s = 1.0
        sess = fab.session("t")
        futs = [sess.submit(_batch(data_seed=s)) for s in range(10)]
        for f in futs:
            f.result(timeout=300)
        assert fab.autoscaler.scale_ups >= 1
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and (
                len(fab.shard_ids()) > 1 or fab.autoscaler.scale_downs < 1):
            time.sleep(0.2)
        assert fab.shard_ids() == ["shard-0"]   # drained back to min
        assert fab.autoscaler.scale_downs >= 1
    finally:
        fab.stop()


def test_cancel_crosses_the_wire_to_the_owning_worker():
    fab = _proc_fabric(n_shards=1, coalesce_max_jobs=1,
                       max_jobs_per_tenant_per_round=1)
    try:
        sess = fab.session("t")
        futs = [sess.submit(_batch(data_seed=s)) for s in range(6)]
        futs[-1].cancel()       # remote: confirmation is asynchronous
        for f in futs[:-1]:
            f.result(timeout=120)
        assert futs[-1]._event.wait(timeout=60)
        assert futs[-1].cancelled()
        with pytest.raises(CancelledError):
            futs[-1].result(timeout=1)
        assert fab.router.cancels_sent == 1
    finally:
        fab.stop()


# ---------------------------------------------------------------------------
# worker entrypoint hygiene
# ---------------------------------------------------------------------------

def test_worker_entrypoint_help_runs():
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run(
        [sys.executable, "-m", "repro.service.fabric.proc.worker",
         "--help"], env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0
    assert "shard" in r.stdout


def test_worker_exits_nonzero_when_supervisor_is_gone():
    import socket as _socket
    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()                                   # nothing listens here
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run(
        [sys.executable, "-m", "repro.service.fabric.proc.worker",
         "--port", str(port), "--shard-id", "s0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0                    # never a silent orphan


# ---------------------------------------------------------------------------
# observability under chaos: heartbeat windows, traced frames, kill -9 traces
# ---------------------------------------------------------------------------

def test_heartbeat_with_windowed_stats_survives_byte_feeds():
    from repro.service.observability import ThroughputCollector
    col = ThroughputCollector(window_s=0.5, n_windows=8)
    col.record_submit()
    col.record_dispatch(0.012, queue_depth=4)
    col.record_completion()
    # the exact payload shape the worker heartbeat thread ships
    beat = {"shard_id": "shard-0", "pid": 4242, "t": 1.0,
            "queue_depth": 0, "inflight": 1, "tenants": {},
            "global": {"windows": col.snapshot()}}
    frame = encode_control(HEARTBEAT, beat)
    stream = _frames_with_prefix([frame])
    dec = FrameDecoder()
    got = []
    for i in range(len(stream)):
        got += dec.feed(stream[i:i + 1])
    assert got == [frame] and dec.pending_bytes() == 0
    kind, payload = decode_control(got[0])
    assert kind == HEARTBEAT
    win = payload["global"]["windows"]
    assert win["submitted"] == 1 and win["completed"] == 1
    assert win["dispatch_p99_s"] == pytest.approx(0.012)
    assert win["queue_depth_max"] == 4
    assert win["per_window"]                    # ring detail survives too


def test_traced_job_frame_corruption_poisons_one_frame_not_stream():
    from repro.service.observability import ROUTED, SUBMITTED, make_hop
    env = JobEnvelope(envelope_id="e-t", tenant="t", priority=1,
                      routing_key="k", batch=_batch(),
                      hops=(make_hop(SUBMITTED, t=1.0, slack=5.0),
                            make_hop(ROUTED, shard="shard-0", t=2.0,
                                     attempt=0)))
    job = encode_job(env)
    beat = encode_control(HEARTBEAT, {"n": 1})
    corrupted = job[:-1] + bytes([job[-1] ^ 0xFF])   # flip payload byte
    dec = FrameDecoder()
    frames = dec.feed(_frames_with_prefix([corrupted, beat]))
    assert len(frames) == 2                     # framing stays in sync
    with pytest.raises(CodecError):
        decode_job(frames[0])                   # poisoned alone
    assert decode_control(frames[1]) == (HEARTBEAT, {"n": 1})
    # the uncorrupted frame round-trips the hop log byte-exactly
    assert decode_job(job).hops == env.hops


def test_live_view_renders_synthetic_proc_snapshot():
    from repro.service.observability import top
    frame = top.render(top.demo_snapshot())
    assert "proc:" in frame and "autoscale" in frame
    assert "shard0" in frame and "retired" in frame
    assert "p99" in frame


def _key_for_shard(fab, shard_id, tag="k"):
    for i in range(10_000):
        key = f"{tag}-{i}"
        if fab.router._ring.route(key) == shard_id:
            return key
    raise AssertionError("no key found")  # pragma: no cover


def _chaos_trace_dir(tmp_path):
    """Trace dir for kill -9 tests; CI sets STRATUM_TEST_TRACE_DIR so the
    JSONL logs survive the run and upload as a failure artifact."""
    base = os.environ.get("STRATUM_TEST_TRACE_DIR")
    if base:
        import tempfile
        os.makedirs(base, exist_ok=True)
        return tempfile.mkdtemp(prefix="trace-", dir=base)
    return str(tmp_path)


def test_sigkill_mid_dispatch_trace_survives_and_replays(tmp_path):
    from repro.service.observability import replay
    from repro.service.observability import (COMPLETED, DISPATCHED,
                                             FAILOVER)
    tdir = _chaos_trace_dir(tmp_path)
    fab = _proc_fabric(n_shards=2, trace=True, trace_dir=tdir)
    try:
        victim = fab.shard_ids()[0]
        sess = fab.session("agent-0")
        futs = [sess.submit(_batch(data_seed=s), deadline_s=600.0,
                            affinity=_key_for_shard(fab, victim, f"v{s}"))
                for s in range(6)]
        # sensor: the victim worker flushes every hop to its JSONL, so
        # poll the trace dir for a dispatched-but-not-completed job and
        # SIGKILL the worker while it holds that job
        deadline = time.monotonic() + 120.0
        armed = False
        while time.monotonic() < deadline and not armed:
            recs = replay.load_events(tdir)
            done = {r["job"] for r in recs if r["event"] == COMPLETED}
            armed = any(r["event"] == DISPATCHED and r["shard"] == victim
                        and r["job"] not in done for r in recs)
            if not armed:
                time.sleep(0.02)
        assert armed, "victim never dispatched a job"
        os.kill(fab.supervisor.live_workers()[victim], signal.SIGKILL)
        reports = [f.result(timeout=300)[1] for f in futs]
        assert len(reports) == 6                # zero loss, as ever
        survivor = fab.shard_ids()[0]
        assert survivor != victim
    finally:
        fab.stop()

    # postmortem: the killed worker's flushed hops + the survivor's hops
    # reassemble into full timelines
    timelines = replay.reassemble(replay.load_events(tdir))
    crossed = []
    for key, hops in timelines.items():
        ev = [r["event"] for r in hops]
        disp_shards = [r["shard"] for r in hops if r["event"] == DISPATCHED]
        if FAILOVER in ev and victim in disp_shards:
            crossed.append((key, hops))
    assert crossed, \
        "no job was dispatched on the victim and failed over"
    for key, hops in crossed:
        ev = [r["event"] for r in hops]
        # dispatch on the victim, then failover, then completion on the
        # ring successor — nothing lost, nothing duplicated out of order
        assert ev[-1] == COMPLETED, (key, ev)
        i_disp = next(i for i, r in enumerate(hops)
                      if r["event"] == DISPATCHED and r["shard"] == victim)
        i_fo = next(i for i, r in enumerate(hops)
                    if r["event"] == FAILOVER)
        assert i_disp < i_fo < len(hops) - 1, (key, ev)
        last_disp = [r for r in hops if r["event"] == DISPATCHED][-1]
        assert last_disp["shard"] == survivor
        assert hops[-1]["shard"] == survivor
        ts = [r["t"] for r in hops]
        assert ts == sorted(ts), (key, ts)      # monotone timestamps
        slacks = [r["slack"] for r in hops if r["slack"] is not None]
        for a, b in zip(slacks, slacks[1:]):    # budget shrinks, never grows
            assert b <= a + 0.25, (key, slacks)
    # the gantt view attributes the victim's cut-short span as lost work
    gantt = replay.shard_gantt(timelines)
    assert victim in gantt and survivor in gantt
    summary = replay.summarize(timelines)
    assert summary["failovers"] >= 1
    assert summary["outcomes"].get(COMPLETED, 0) >= 6


class _HelloOnlyWorker:
    """Stands in for a worker process: dials the supervisor and sends one
    HELLO naming a platform (tpu for shard "s0", cpu for every other)."""

    def __init__(self, argv, env=None, start_new_session=False):
        import socket
        import threading
        from repro.service.fabric.proc.frames import write_frame
        shard_id = argv[argv.index("--shard-id") + 1]
        port = int(argv[argv.index("--port") + 1])
        platform = "tpu" if shard_id == "s0" else "cpu"
        hello = {"shard_id": shard_id, "pid": 0, "platform": platform,
                 "device_kind": "TPU v5 lite" if platform == "tpu"
                 else "cpu"}
        self.pid = None
        self.returncode = None
        self._sock = socket.create_connection(("127.0.0.1", port))
        threading.Thread(target=write_frame, daemon=True, args=(
            self._sock, encode_control(HELLO, hello))).start()

    def poll(self):
        return self.returncode

    def kill(self):
        self.returncode = -9

    def terminate(self):
        self.returncode = -15

    def wait(self, timeout=None):
        if self.returncode is None:
            self.returncode = 0
        self._sock.close()
        return self.returncode


def test_supervisor_refuses_a_worker_on_another_platform(monkeypatch):
    """One process per chip: a worker that lost the chip to the fabric's
    first worker reports the CPU in its HELLO, and spawn raises instead
    of carrying on with a CPU shard."""
    from repro.service import ServiceConfig
    from repro.service.fabric.proc import (WorkerPlatformError,
                                           WorkerSupervisor)
    from repro.service.fabric.proc import supervisor as sup_mod
    monkeypatch.setattr(sup_mod.subprocess, "Popen", _HelloOnlyWorker)
    sup = WorkerSupervisor(ProcConfig(spawn_timeout_s=10.0,
                                      heartbeat_timeout_s=60.0))
    try:
        sup.spawn("s0", ServiceConfig())
        assert sup.device == ("tpu", "TPU v5 lite")
        with pytest.raises(WorkerPlatformError, match="'cpu'"):
            sup.spawn("s1", ServiceConfig())
        assert set(sup.live_workers()) == {"s0"}
    finally:
        sup.shutdown()


def test_worker_hello_reports_its_jax_platform():
    """A real worker's HELLO names the platform JAX gave it; on the CPU
    the fabric's workers all agree and the fabric runs."""
    with _proc_fabric(n_shards=2) as fab:
        res, _ = fab.session("t").submit(_batch()).result(timeout=120)
        assert float(res["p"]) == float(res["p"])      # not NaN
        assert fab.supervisor.device[0] == "cpu"
