"""Compile the main path's segment programs for a described TPU v5e chip.

The paper's §6 iteration-1 batch at 1,000,000 rows is planned exactly as
the service plans it, and each jitted segment program the compiled-segment
backend builds for it (the projection head, the impute/scale head, and the
ridge + elasticnet fit fan) is lowered and compiled for one chip of a
described ``v5e:2x2`` topology.  Nothing runs: the compiler refuses here
what the chip would refuse, and ``memory_analysis()`` must fit in the
chip's 16 GB.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker given this file
loads the TPU compiler.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

ROWS = 1_000_000
HBM_BYTES = 16 * 10**9          # TPU v5e: 16 GB per chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def iteration1_plan():
    """The service's plan for the §6 iteration-1 batch at ``ROWS``."""
    from repro.agents import paper_workload_batches
    from repro.core import Stratum
    _, batch, _ = next(paper_workload_batches(n_rows=ROWS, cv_k=3))
    st = Stratum()
    _sinks, sel, plan, *_ = st.compile_batch(batch)
    return st._backends["jax"], sel, plan


@pytest.mark.parametrize("op_name", ["project", "scaler_apply", "ridge_fit"])
def test_segment_compiles_for_v5e(op_name, iteration1_plan, one_chip,
                                  no_persistent_cache):
    import jax
    backend, sel, plan = iteration1_plan
    segs = [s for s in plan.segments if s.kind == "jax"
            and any(op.op_name == op_name for w in s.waves for op in w.ops)]
    assert segs, f"no compiled segment holds {op_name} at {ROWS} rows"
    _key, _fn, jitted, ext_refs, hoists = backend.segment_program(segs[0],
                                                                  sel)
    # runtime arguments are host float64 or device float32 arrays; with
    # 64-bit mode off both arrive as float32
    args = tuple(
        jax.ShapeDtypeStruct(
            tuple(t.shape),
            jax.dtypes.canonicalize_dtype(np.dtype(t.dtype)),
            sharding=one_chip)
        for t in (r.op.meta.outputs[r.index] for r in ext_refs))
    assert any(a.shape[0] >= ROWS * 2 // 3 for a in args)
    compiled = jitted.lower(args, hoists).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES


def test_gbt_fit_compiles_for_v5e(iteration1_plan, one_chip,
                                  no_persistent_cache):
    """The widest GBT fit of the batch (a fold's training rows), in the
    scatter-free form the TPU runs."""
    import jax
    import jax.numpy as jnp
    from repro.tabular import gbt
    _backend, _sel, plan = iteration1_plan
    fits = [op for s in plan.segments for w in s.waves for op in w.ops
            if op.op_name == "gbt_fit"]
    assert fits, f"no gbt_fit at {ROWS} rows"
    op = max(fits, key=lambda o: o.inputs[0].op.meta.outputs[
        o.inputs[0].index].shape[1])
    rows, cols = op.inputs[0].op.meta.outputs[op.inputs[0].index].shape
    assert rows >= ROWS * 2 // 3
    B = jax.ShapeDtypeStruct((rows, cols), jnp.int32, sharding=one_chip)
    y = jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=one_chip)
    compiled = gbt._fit_jax_binned.lower(
        B, y, 0.0, op.spec["learning_rate"], op.spec["reg"],
        op.spec["n_trees"], op.spec["depth"], gbt.N_BINS,
        dense=True).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES


def test_gbt_digitize_compiles_for_v5e_without_a_per_edge_tensor(
        iteration1_plan, one_chip, no_persistent_cache):
    """The device digitize of the widest GBT fit's features: one fused
    pass that holds no ``(rows, features, edges)`` temporary, its bin ids
    in the donated features' buffer."""
    import jax
    import jax.numpy as jnp
    from repro.tabular import gbt
    _backend, _sel, plan = iteration1_plan
    rows, cols = max(
        (op.inputs[0].op.meta.outputs[op.inputs[0].index].shape
         for s in plan.segments for w in s.waves for op in w.ops
         if op.op_name == "gbt_fit"), key=lambda shape: shape[1])
    X = jax.ShapeDtypeStruct((rows, cols), jnp.float32, sharding=one_chip)
    edges = jax.ShapeDtypeStruct((cols, gbt.N_BINS - 1), jnp.float32,
                                 sharding=one_chip)
    mem = gbt._digitize.lower(X, edges).compile().memory_analysis()
    assert mem.output_size_in_bytes >= rows * cols * 4
    assert mem.temp_size_in_bytes < rows * cols * 4
    assert mem.alias_size_in_bytes == mem.output_size_in_bytes


def test_gbt_order_statistics_compile_for_v5e_within_three_copies(
        one_chip, no_persistent_cache):
    """The device sort behind a GBT fit's quantile edges, at the widest
    fit of the benchmark's one-chip cell (a 3-fold training split of
    250,000 rows, 151 features): only the order statistics and the counts
    come back, and its temporaries stay under three copies of the
    features."""
    import jax
    import jax.numpy as jnp
    from repro.tabular import gbt
    rows, cols = 166_667, 151
    X = jax.ShapeDtypeStruct((rows, cols), jnp.float32, sharding=one_chip)
    mem = gbt._order_stats.lower(X).compile().memory_analysis()
    assert 0 < mem.output_size_in_bytes < rows * cols * 4 // 100
    assert mem.temp_size_in_bytes < 3 * rows * cols * 4
