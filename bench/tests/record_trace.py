"""Record the small device trace that ``test_trace.py`` reduces.

Run on a machine with a TPU, from the repository root:

    python3 bench/tests/record_trace.py bench/tests/data

It traces two small jitted programs, one of them named like the GBT fit
program, with an idle gap between them, and writes the events of the
device's ``XLA Modules`` line and of the host threads to
``<out>/tpu_trace_events.json`` beside the raw ``.xplane.pb``."""

import glob
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from bench.harness import trace  # noqa: E402


def _fit_jax_binned(x):
    for _ in range(4):
        x = jnp.tanh(x @ x) + 1.0
    return x


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    os.makedirs(out, exist_ok=True)
    fit = jax.jit(_fit_jax_binned)
    other = jax.jit(lambda x: jnp.sort(x, axis=0))
    x = jnp.ones((1024, 1024), jnp.float32)
    fit(x).block_until_ready()
    other(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    p = trace.Profiler(tmp)
    p.start()
    for _ in range(3):
        fit(x).block_until_ready()
    time.sleep(0.05)                       # an idle gap
    other(x).block_until_ready()
    fit(x).block_until_ready()
    p.stop()
    (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("plane", plane.name, [(ln.name, len(list(ln.events)))
                                    for ln in plane.lines])
    devices, host = trace.read_xplane(path)
    doc = {"window": trace.window_of(host),
           "devices": [[[e.name, e.start, e.dur] for e in evs]
                       for evs in devices],
           "host": [[e.name, e.start, e.dur] for e in host]}
    with open(os.path.join(out, "tpu_trace_events.json"), "w") as f:
        json.dump(doc, f, indent=0)
    shutil.copy(path, os.path.join(out, "tpu_trace.xplane.pb"))
    s = p.summary()
    print("summary", s.busy_s, s.window_s, s.device_ops, s.idle_gaps)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
