"""Distributed correctness tests — run in subprocesses with a forced
8-device host platform (the main test process must keep 1 device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    line = out.stdout.strip().splitlines()[-1]
    return json.loads(line)


PREAMBLE = """
import json
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_config, reduced
from repro.models.config import ShapeConfig
from repro.models.model import param_specs, init_params
from repro.distributed.policy import (make_policy, param_pspecs,
                                      tree_shardings, input_pspecs)
from repro.distributed.context import use_context
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh((2, 4), ("data", "model"))
"""


@pytest.mark.slow
def test_sharded_train_matches_single_device():
    """Same seeds, same batch: sharded loss == single-device loss."""
    code = PREAMBLE + textwrap.dedent("""
    from repro.train.step import make_train_step
    from repro.optim import adamw
    from repro.launch.specs import train_input_specs
    cfg = dataclasses.replace(reduced(get_config("qwen2-7b")),
                              d_model=128, n_heads=4, n_kv_heads=2,
                              vocab=512, dtype="float32")
    shape = ShapeConfig("t", 64, 8, "train")
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, 512, (2, 4, 64)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, 512, (2, 4, 64)), jnp.int32)}
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw(lr=1e-3)

    # single-device reference
    step0 = make_train_step(cfg, opt, policy=None)
    o0 = step0.init_opt_state(params)
    p0, _, m0 = jax.jit(step0)(params, o0, batch)

    # sharded
    pol = make_policy(cfg, shape, mesh, tp=True, fsdp=True, microbatches=2)
    with use_context(pol.context()):
        step1 = make_train_step(cfg, opt, policy=pol)
        pshard = tree_shardings(param_pspecs(params, pol, cfg), pol)
        o1 = step1.init_opt_state(params)
        oshard = tree_shardings(param_pspecs(o1, pol, cfg), pol)
        bshard = tree_shardings(input_pspecs(batch, pol, "train"), pol)
        fn = jax.jit(step1, in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard, None))
        p1, _, m1 = fn(jax.device_put(params, pshard),
                       jax.device_put(o1, oshard),
                       jax.device_put(batch, bshard))
    d_loss = abs(float(m0["loss"]) - float(m1["loss"]))
    d_par = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)))
    print(json.dumps({"d_loss": d_loss, "d_par": d_par}))
    """)
    out = _run(code)
    assert out["d_loss"] < 2e-4, out
    assert out["d_par"] < 2e-3, out


@pytest.mark.slow
def test_vocab_parallel_ce_matches_fused():
    code = PREAMBLE + textwrap.dedent("""
    from repro.distributed.vocab_ce import vocab_parallel_ce
    from repro.kernels import fused_cross_entropy
    from repro.distributed.context import use_context, ShardingContext
    rng = np.random.default_rng(1)
    T, D, V = 64, 32, 512
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(D, V)) * 0.1, jnp.float32)
    lab = jnp.asarray(rng.integers(0, 500, T), jnp.int32)
    val = jnp.ones((T,), bool)
    ref = float(fused_cross_entropy(x, w, lab, valid=val, n_valid=500))
    ctx = ShardingContext(mesh=mesh, rules={})
    with use_context(ctx):
        got = float(vocab_parallel_ce(x, w, lab, val, n_valid=500))
        # grads too
        g1 = jax.grad(lambda x: fused_cross_entropy(x, w, lab, valid=val,
                                                    n_valid=500))(x)
        g2 = jax.grad(lambda x: vocab_parallel_ce(x, w, lab, val,
                                                  n_valid=500))(x)
    d_g = float(jnp.max(jnp.abs(g1 - g2)))
    print(json.dumps({"ref": ref, "got": got, "d_g": d_g}))
    """)
    out = _run(code)
    assert abs(out["ref"] - out["got"]) < 1e-4, out
    assert out["d_g"] < 1e-4, out


@pytest.mark.slow
def test_moe_ep_matches_local():
    code = PREAMBLE + textwrap.dedent("""
    from repro.models.moe import moe_ffn
    from repro.distributed.context import use_context, ShardingContext
    from jax.sharding import NamedSharding, PartitionSpec as P
    cfg = dataclasses.replace(reduced(get_config("granite-moe-3b-a800m")),
                              d_model=64, n_heads=4, n_kv_heads=2,
                              n_experts=8, top_k=2, d_ff_expert=32,
                              dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda x: x[0], params["layers"])["moe"]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 16, 64)), jnp.float32)
    y_local = moe_ffn(lp, x, cfg)
    ctx = ShardingContext(mesh=mesh, rules={}, ep_axis="model")
    with use_context(ctx):
        y_ep = jax.jit(lambda lp, x: moe_ffn(lp, x, cfg))(lp, x)
    d = float(jnp.max(jnp.abs(y_local - y_ep)))
    print(json.dumps({"d": d}))
    """)
    out = _run(code)
    assert out["d"] < 2e-4, out


@pytest.mark.slow
def test_compressed_psum_matches_psum():
    code = PREAMBLE + textwrap.dedent("""
    from functools import partial
    from repro.optim.compress import compressed_psum
    from jax.sharding import PartitionSpec as P
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.normal(size=(8, 512)), jnp.float32)

    from jax import shard_map

    @partial(shard_map, mesh=mesh, in_specs=P(("data", "model")),
             out_specs=P(("data", "model")), check_vma=False)
    def exact(g):
        return jax.lax.psum(g, ("data", "model")) / 8 + 0 * g

    @partial(shard_map, mesh=mesh, in_specs=P(("data", "model")),
             out_specs=P(("data", "model")), check_vma=False)
    def compressed(g):
        return compressed_psum(g, ("data", "model")) / 8 + 0 * g

    a = exact(g)
    b = compressed(g)
    rel = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-9))
    print(json.dumps({"rel": rel}))
    """)
    out = _run(code)
    assert out["rel"] < 0.02, out       # int8 quantization error bound


@pytest.mark.slow
def test_elastic_restart_across_meshes():
    """Checkpoint on a (2,4) mesh, restore onto (1,4) with 4 devices."""
    import tempfile
    tmp = tempfile.mkdtemp()
    save_code = PREAMBLE + textwrap.dedent(f"""
    from repro.ckpt import save_checkpoint
    cfg = dataclasses.replace(reduced(get_config("qwen2-7b")),
                              d_model=128, n_heads=4, n_kv_heads=2,
                              vocab=512, dtype="float32")
    shape = ShapeConfig("t", 32, 8, "train")
    pol = make_policy(cfg, shape, mesh, tp=True, fsdp=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    pshard = tree_shardings(param_pspecs(params, pol, cfg), pol)
    params = jax.device_put(params, pshard)
    save_checkpoint({tmp!r}, 1, params)
    print(json.dumps({{"sum": float(sum(jnp.sum(jnp.abs(l))
                                        for l in jax.tree.leaves(params)))}}))
    """)
    a = _run(save_code)

    restore_code = textwrap.dedent(f"""
    import json
    import jax, jax.numpy as jnp, dataclasses
    from repro.configs import get_config, reduced
    from repro.models.config import ShapeConfig
    from repro.models.model import param_specs
    from repro.distributed.policy import make_policy, param_pspecs, tree_shardings
    from repro.launch.mesh import make_debug_mesh
    from repro.ckpt import load_checkpoint
    mesh = make_debug_mesh((1, 4), ("data", "model"))   # DIFFERENT mesh
    cfg = dataclasses.replace(reduced(get_config("qwen2-7b")),
                              d_model=128, n_heads=4, n_kv_heads=2,
                              vocab=512, dtype="float32")
    shape = ShapeConfig("t", 32, 8, "train")
    pol = make_policy(cfg, shape, mesh, tp=True, fsdp=True)
    pstruct = param_specs(cfg)
    pshard = tree_shardings(param_pspecs(pstruct, pol, cfg), pol)
    tree, _ = load_checkpoint({tmp!r}, 1, pstruct, shardings=pshard)
    print(json.dumps({{"sum": float(sum(jnp.sum(jnp.abs(l))
                                        for l in jax.tree.leaves(tree)))}}))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", restore_code], env=env,
                         capture_output=True, text=True, timeout=480)
    assert out.returncode == 0, out.stderr[-3000:]
    b = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(a["sum"] - b["sum"]) / a["sum"] < 1e-5


@pytest.mark.slow
def test_seq_sharded_flash_decode_matches_single_device():
    """§Perf H4: distributed flash-decode (LSE merge over a seq-sharded
    cache) must reproduce single-device decode logits."""
    code = PREAMBLE + textwrap.dedent("""
    from repro.models.model import (init_params, prefill, decode_step,
                                    init_decode_state)
    from repro.distributed.policy import decode_state_pspecs
    cfg = dataclasses.replace(reduced(get_config("qwen2-7b")),
                              d_model=128, n_heads=4, n_kv_heads=2,
                              vocab=512, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S, MAX = 8, 12, 16
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S + 1)), jnp.int32)
    _, st0 = prefill(params, {"tokens": toks[:, :S]}, cfg, max_len=MAX)
    ref, _ = decode_step(params, st0, toks[:, S:S + 1], cfg)

    shape = ShapeConfig("dec", MAX, B, "decode")
    pol = make_policy(cfg, shape, mesh, tp=True)
    with use_context(pol.context()):
        pshard = tree_shardings(param_pspecs(params, pol, cfg), pol)
        sstruct = jax.eval_shape(lambda: init_decode_state(cfg, B, MAX))
        sshard = tree_shardings(decode_state_pspecs(sstruct, pol, B), pol)
        pf = jax.jit(lambda p, i: prefill(p, i, cfg, max_len=MAX),
                     out_shardings=(None, sshard))
        _, st1 = pf(jax.device_put(params, pshard), {"tokens": toks[:, :S]})
        dec = jax.jit(lambda p, s, t: decode_step(p, s, t, cfg),
                      in_shardings=(pshard, sshard, None),
                      out_shardings=(None, sshard))
        got, _ = dec(jax.device_put(params, pshard), st1, toks[:, S:S + 1])
    err = float(jnp.max(jnp.abs(ref - got)))
    print(json.dumps({"err": err}))
    """)
    out = _run(code)
    assert out["err"] < 2e-3, out
