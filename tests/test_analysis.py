"""Loop-aware HLO cost pass + serving batcher + data-lake tiers."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_cost import analyze, parse_module
from repro.serve.batcher import Batcher, Request


# ---------------------------------------------------------------------------
# hlo_cost: the roofline's data source must stay trustworthy
# ---------------------------------------------------------------------------

def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_scan_flops_match_unrolled():
    A = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def scan_mm(x):
        def body(c, _):
            return c @ c, None
        out, _ = jax.lax.scan(body, x, None, length=7)
        return out

    def unroll_mm(x):
        for _ in range(7):
            x = x @ x
        return x

    fs = analyze(_compile(scan_mm, A).as_text()).flops
    fu = analyze(_compile(unroll_mm, A).as_text()).flops
    assert fs == fu == 7 * 2 * 256 ** 3


def test_nested_scan_multiplicity():
    A = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def nested(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ c2, None
            c, _ = jax.lax.scan(inner, c, None, length=4)
            return c, None
        out, _ = jax.lax.scan(outer, x, None, length=3)
        return out

    f = analyze(_compile(nested, A).as_text()).flops
    assert f == 12 * 2 * 128 ** 3


def test_collective_trip_weighting():
    """A psum inside a scan must count once per iteration."""
    import os
    import subprocess
    import sys
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
sys_path = %r
import sys; sys.path.insert(0, sys_path)
from repro.launch.hlo_cost import analyze
from repro.launch.mesh import make_debug_mesh
from jax import shard_map
mesh = make_debug_mesh((1, 4), ("data", "model"))

@partial(shard_map, mesh=mesh, in_specs=P(None, "model"),
         out_specs=P(None, "model"), check_vma=False)
def inner(x):
    def body(c, _):
        return jax.lax.psum(c, "model") * 0.5 + c, None
    out, _ = jax.lax.scan(body, x, None, length=5)
    return out

co = jax.jit(inner).lower(
    jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
c = analyze(co.as_text())
n = sum(c.collective_count_by_kind.values())
print("COUNT", int(n))
""" % (str(jax.__file__ and __import__("os").path.join(
        __import__("os").path.dirname(__file__), "..", "src")))
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    count = int(out.stdout.strip().split()[-1])
    assert count == 5, out.stdout


def test_parse_module_shapes():
    co = _compile(lambda x: (x @ x).sum(),
                  jax.ShapeDtypeStruct((64, 64), jnp.float32))
    comps, shapes = parse_module(co.as_text())
    assert comps and shapes
    assert any("64,64" in s for s in shapes.values())


# ---------------------------------------------------------------------------
# serving batcher
# ---------------------------------------------------------------------------

def test_batcher_lifecycle():
    b = Batcher(n_lanes=2, max_len=32)
    for rid in range(5):
        b.submit(Request(rid=rid, prompt=np.arange(4, dtype=np.int32),
                         max_new_tokens=2))
    admitted = b.admit()
    assert len(admitted) == 2
    assert b.active_lanes() == [0, 1]
    b.record_tokens(np.array([7, 8]))
    b.record_tokens(np.array([9, 10]))       # both lanes hit max_new → retire
    assert b.active_lanes() == []
    assert len(b.finished) == 2
    assert b.finished[0].generated == [7, 9]
    # next wave admits from the queue
    assert len(b.admit()) == 2
    assert not b.idle


def test_batcher_eos_retires_early():
    b = Batcher(n_lanes=1, max_len=32, eos_id=0)
    b.submit(Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                     max_new_tokens=10))
    b.admit()
    b.record_tokens(np.array([5]))
    b.record_tokens(np.array([0]))            # EOS
    assert b.finished and b.finished[0].generated == [5, 0]


# ---------------------------------------------------------------------------
# data lake tiers
# ---------------------------------------------------------------------------

def test_csv_and_binary_tiers_agree():
    from repro.data.tabular import ensure_files, load_binary, load_csv
    ensure_files("uk_housing", 500, 0)
    a = load_csv("uk_housing", 500, 0)
    b = load_binary("uk_housing", 500, 0)
    np.testing.assert_allclose(np.nan_to_num(a), np.nan_to_num(b),
                               rtol=1e-6)
