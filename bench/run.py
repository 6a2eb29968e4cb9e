"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic come from ``BENCHMARK.json``
and the files it names (``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json``); each metric is read by
``bench/metrics/<metric>.py``.  A run:

1. refuses to run (exit 1, no result line) unless JAX's devices are TPUs,
   as many as the cell asks for, of a kind ``bench/peaks.json`` knows;
2. makes the configuration's table from the seed and writes it into the
   data lake under ``bench/lake``, warms every shape the window uses
   through the service and lets every agent finish one job: set-up, timed
   as ``setup_s`` from process start to the window's start;
3. drives the cell's traffic through ``StratumClient.submit`` on
   ``connect("service", StratumConfig.make())``, or on the fabric, one
   shard per chip, where the configuration's ``client`` key asks for it
   (``bench/harness/target.py``), for ``--seconds``, under JAX's profiler
   with ``--trace 1``;
4. closes the service, scores a sample of the window's jobs with the plain
   reference (``bench/reference``) and decides ``correct``;
5. prints each compared number beside its limit on standard error, and
   as its last line of standard output one JSON object: ``correct``,
   ``attempted``, ``failed``, ``metrics`` (end-to-end with ``--trace 0``,
   per-layer with ``--trace 1``), ``device``, with ``--trace 1``
   ``breakdown``, and last ``checks``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
# the longest wait for any one warm-up job to complete
SETUP_TIMEOUT_S = 900.0


class Refused(Exception):
    """The run cannot be made here (exit 1 or 2, no result line)."""

    def __init__(self, message: str, code: int = 1):
        super().__init__(message)
        self.code = code


# ---------------------------------------------------------------------------
# the benchmark's own files
# ---------------------------------------------------------------------------

def load_cell(root: str, workload: str) -> tuple:
    """``(benchmark, cell, config, traffic)`` from ``BENCHMARK.json``."""
    try:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cells = {c["name"]: c for c in bench["workloads"]}
        if workload not in cells:
            raise Refused(f"no workload {workload!r} in BENCHMARK.json", 2)
        cell = cells[workload]
        config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
        with open(os.path.join(root, config_entry["file"])) as f:
            config = json.load(f)
        with open(os.path.join(root, "bench", "traffic",
                               cell["traffic"] + ".json")) as f:
            traffic = json.load(f)
    except (OSError, KeyError, ValueError) as e:
        raise Refused(f"cannot read the benchmark's files: {e!r}", 2)
    return bench, cell, config, traffic


def check_target(config: dict, cell: dict, traffic: dict) -> None:
    """Refuse (exit 2), before any JAX work, a configuration whose
    ``client`` does not fit the cell (``bench/harness/target.py``)."""
    from bench.harness import target
    try:
        target.shards(config, cell["chips"], traffic["agents"])
    except ValueError as e:
        raise Refused(f"{cell['name']}: {e}", 2)


def metrics_for(bench: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(root: str, name: str):
    """``bench/metrics/<name>.py``, or else the reader of the name's stem
    (``device_idle_share`` for ``device_idle_share.sweep``): the cells a
    metric is read in are its ``workloads``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(root, "bench", "metrics",
                            name.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ---------------------------------------------------------------------------
# environment
# ---------------------------------------------------------------------------

def prepare_env(root: str) -> None:
    """Fixed places inside the checkout: the data lake, and JAX's
    persistent compilation cache where ``repro.core.api`` puts it, every
    program cached whatever its compile time."""
    os.environ["REPRO_DATA_LAKE"] = os.path.join(root, "bench", "lake")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                           ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # no size limit: the limit's eviction races between the service's
    # compiling threads and drops entries
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (root, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def device_info(chips: int, peaks: dict) -> dict:
    import jax
    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise Refused(f"JAX found no TPU (first device: {first.platform})")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX has "
                      f"{len(devices)}")
    if first.device_kind not in peaks:
        raise Refused(f"device kind {first.device_kind!r} is not in "
                      f"bench/peaks.json")
    return {"platform": first.platform, "kind": first.device_kind,
            "count": chips}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class Context:
    """What the metric readers read (``bench/metrics/*.py``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run_cell(root: str, bench: dict, cell: dict, config: dict,
             traffic: dict, seed: int, seconds: float, trace: bool,
             device: dict, peak: dict, t_start: float) -> dict:
    from bench.harness import check, drive, probes, table, target
    from bench.harness.jobs import build_batch
    from bench.harness.trace import Profiler
    from bench.harness.traffic import Traffic

    lake = os.environ["REPRO_DATA_LAKE"]
    table.write(config, seed, lake)
    t_table = time.perf_counter()

    def build(job):
        return build_batch(job, config["schema"], config["dataset"])

    n_shards = target.shards(config, cell["chips"], traffic["agents"])
    gen = Traffic(traffic, config, seed, max(n_shards, 1))
    client = target.connect(n_shards)
    try:
        pins = target.pins(client, n_shards)
        rounds = gen.warmup_rounds()
        wait = drive.run_rounds(client, rounds, build, SETUP_TIMEOUT_S,
                                pins)
        t_warm = time.perf_counter()
        n_agents = traffic["agents"]
        agents = [gen.agent_jobs(i) for i in range(n_agents)]
        gc.collect()
        snaps, probe = {}, None
        profiler = Profiler(tempfile.mkdtemp(prefix="trace-") if trace
                            else None)

        def on_open():
            nonlocal probe
            snaps["before"] = client.telemetry.global_snapshot()
            now = time.perf_counter()
            snaps["setup_s"] = now - t_start
            print(f"set-up: start {t_table - t_start:.3f} s (imports, "
                  f"table), warm-up {t_warm - t_table:.3f} s "
                  f"({sum(map(len, rounds)) * len(pins)} jobs, the "
                  f"longest wait {wait:.3f} s), ramp "
                  f"{now - t_warm:.3f} s", file=sys.stderr, flush=True)
            if trace:
                probe = probes.install()
                profiler.start()

        def on_close():
            snaps["after"] = client.telemetry.global_snapshot()
            if trace:
                profiler.stop()

        window = drive.closed_loop(
            client, agents, build, seconds, on_open, on_close,
            [pins[gen.shard_of(i)] for i in range(n_agents)]
            if n_shards else None)
        if probe is not None:
            probe.remove()
        import jax
        peaks_in_use = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                        for d in jax.devices()[:device["count"]]]
    finally:
        client.close()
    del client
    gc.collect()

    done = window.completed_in_window()
    failed = [r for r in window.records if not r.ok]
    if n_shards:
        # the jobs ``pipelines_per_s`` counts, by the shard each ran on
        per_shard = {pin[0]: 0 for pin in pins}
        for r in window.completed():
            per_shard[r.pin[0]] += 1
        print(f"window jobs per shard: {per_shard}; drained "
              f"{window.drained - window.end:.3f} s after the close",
              file=sys.stderr, flush=True)
    print(f"window: {len(window.completed())} jobs in super-batches "
          + ", ".join(f"{n}@{sent:.2f}-{end:.2f}s"
                      for n, sent, end in window.batches()),
          file=sys.stderr, flush=True)
    checks = check.compare(window.completed(), lake, config,
                           traffic["check_per_family"], seed,
                           check.families(traffic))
    checks["failed_jobs"] = {"value": len(failed), "limit": 0}
    correct = check.passed(checks)

    summary = None
    if trace:
        summary = profiler.summary()
        shutil.rmtree(profiler.out_dir, ignore_errors=True)
    ctx = Context(window=window, completed=done, setup_s=snaps["setup_s"],
                  before=snaps["before"], after=snaps["after"],
                  probe=probe, summary=summary, trace_start=profiler.t0,
                  peak=peak, config=config, seconds=seconds)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_for(bench, cell["name"], kind):
        value = reader(root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    dev = dict(device, memory_peak_bytes=int(max(peaks_in_use)))
    out = {"correct": correct, "attempted": len(window.records),
           "failed": len(failed), "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench, cell, config, traffic = load_cell(ROOT, args.workload)
        with open(os.path.join(BENCH, "peaks.json")) as f:
            peaks = json.load(f)
        prepare_env(ROOT)
        check_target(config, cell, traffic)
        try:
            import repro.client  # noqa: F401
        except ImportError as e:
            raise Refused(f"the program is not beside the benchmark: {e}", 2)
        device = device_info(cell["chips"], peaks)
        result = run_cell(ROOT, bench, cell, config, traffic, args.seed,
                          args.seconds, bool(args.trace), device,
                          peaks[device["kind"]], T_START)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return e.code
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
