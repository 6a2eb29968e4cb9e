"""Mixture-of-Experts FFN with expert parallelism.

Routing: top-k softmax over real experts (padding experts masked to -inf —
granite's 40 experts are padded to 48 so EP divides a 16-way axis).

Expert parallelism (DESIGN.md §5): activations are replicated across the
``model`` axis at the FFN boundary, so each model-rank routes the *same*
local tokens and serves only its E/ep slice of experts; partial outputs are
psum-combined.  This trades one all-to-all pair for a psum that fuses with
the TP reduction — the right trade at inference/train batch sizes where the
router table is tiny (the redundant routing costs T·E flops).

Capacity: each (rank, expert) processes at most C = ⌈T_loc·k/E·cf⌉ tokens;
overflow tokens are dropped for that expert (standard GShard-style dropping,
cf = 1.25).  The per-expert compute runs through the ``moe_gmm`` grouped
matmul kernel with equal group sizes.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..distributed import current_context
from ..kernels import moe_gmm
from .config import ModelConfig

CAPACITY_FACTOR = 1.25


def _route(params, x_flat, cfg: ModelConfig):
    """x_flat: (T, D) → (weights (T, k), experts (T, k))."""
    logits = x_flat.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    e_pad = cfg.n_experts_padded
    if e_pad > cfg.n_experts:
        pad_mask = jnp.arange(e_pad) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, :], -jnp.inf, logits)
    weights, experts = jax.lax.top_k(logits, cfg.top_k)
    weights = jax.nn.softmax(weights, axis=-1)
    return weights, experts


def _expert_compute(params_local, xe, cfg: ModelConfig, n_local: int,
                    capacity: int):
    """xe: (n_local·C, D) expert-sorted rows (equal groups of C)."""
    sizes = jnp.full((n_local,), capacity, dtype=jnp.int32)
    h_gate = moe_gmm(xe, params_local["w_gate"], sizes,
                     equal_groups=capacity)
    h_up = moe_gmm(xe, params_local["w_up"], sizes, equal_groups=capacity)
    h = jax.nn.silu(h_gate) * h_up
    return moe_gmm(h, params_local["w_down"], sizes, equal_groups=capacity)


def _moe_local(params, x_flat, cfg: ModelConfig, n_local: int,
               expert_offset: int):
    """Dispatch/compute/combine for the local expert slice.
    params weights are the local slice (n_local, D, F)."""
    T, D = x_flat.shape
    k = cfg.top_k
    # capacity per expert sized over REAL experts (padding never receives
    # tokens, so sizing over E_padded would undersize every real bucket)
    capacity = int(max(1, -(-T * k // cfg.n_experts) * CAPACITY_FACTOR))

    weights, experts = _route(params, x_flat, cfg)     # (T,k) each

    tok = jnp.repeat(jnp.arange(T), k)                  # (T·k,)
    exp = experts.reshape(-1) - expert_offset           # local expert ids
    wgt = weights.reshape(-1)
    mine = (exp >= 0) & (exp < n_local)

    # position of each assignment within its expert's capacity-C buffer;
    # non-local assignments get the sentinel key n_local so the sort key is
    # globally monotone (searchsorted requires it)
    key = jnp.where(mine, exp, n_local)
    order = jnp.argsort(key, stable=True)
    key_sorted = key[order]
    tok_sorted = tok[order]
    wgt_sorted = wgt[order]
    mine_sorted = mine[order]
    # rank within expert via segmented iota
    pos_in_e = jnp.arange(T * k) - jnp.searchsorted(
        key_sorted, key_sorted, side="left")
    keep = mine_sorted & (pos_in_e < capacity)
    slot = jnp.where(keep, key_sorted * capacity + pos_in_e,
                     n_local * capacity)

    # scatter tokens into the (n_local·C, D) dispatch buffer (+1 overflow row)
    buf = jnp.zeros((n_local * capacity + 1, D), x_flat.dtype)
    buf = buf.at[slot].set(x_flat[tok_sorted], mode="drop")
    xe = buf[:-1]

    ye = _expert_compute(params, xe, cfg, n_local, capacity)

    # combine: weighted scatter-add back to tokens
    contrib = jnp.where(keep[:, None], ye[jnp.clip(slot, 0,
                                                   n_local * capacity - 1)]
                        * wgt_sorted[:, None], 0.0)
    out = jnp.zeros((T, D), jnp.float32)
    out = out.at[tok_sorted].add(contrib, mode="drop")
    return out.astype(x_flat.dtype)


def moe_ffn(params, x, cfg: ModelConfig):
    """x: (B, S, D) → (B, S, D).  Uses EP shard_map when a sharding context
    with an ep_axis is active; otherwise runs all experts locally."""
    B, S, D = x.shape
    x_flat = x.reshape(-1, D)
    ctx = current_context()
    E = cfg.n_experts_padded

    dense = None
    if cfg.moe_dense_residual:
        from .layers import mlp_block
        dense = mlp_block(params["dense"], x, cfg)

    if ctx is not None and ctx.ep_axis is not None:
        axis = ctx.ep_axis
        ep = ctx.mesh.shape[axis]
        n_local = E // ep

        orig_dtype = x_flat.dtype

        def local_fn(xf, router, wg, wu, wd):
            idx = jax.lax.axis_index(axis)
            p = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
            y = _moe_local(p, xf.astype(orig_dtype), cfg, n_local,
                           idx * n_local)
            return jax.lax.psum(y.astype(jnp.float32), axis)

        # f32 at the shard_map boundary: XLA-CPU's AllReducePromotion pass
        # aborts on the bf16 replication all-reduce it would otherwise emit
        # (same workaround as distributed/vocab_ce.py); expert matmuls still
        # run in the model dtype inside.
        y_flat = shard_map(
            local_fn, mesh=ctx.mesh,
            in_specs=(P(), P(), P(axis), P(axis), P(axis)),
            out_specs=P(), axis_names={axis}, check_vma=False,
        )(x_flat.astype(jnp.float32), params["router"], params["w_gate"],
          params["w_up"], params["w_down"]).astype(orig_dtype)
    elif (ctx is not None and ctx.dp_axes
          and x_flat.shape[0] % _axes_size(ctx.mesh, ctx.dp_axes) == 0):
        # EP off (small-model pure DP, §Perf H2): keep the dispatch LOCAL
        # per batch shard — every device holds all experts and routes only
        # its tokens; no collectives at all.  (Under plain GSPMD the
        # data-dependent dispatch gathers shred into giant all-reduces.)
        axes = ctx.dp_axes
        orig_dtype = x_flat.dtype

        def local_dp(xf, router, wg, wu, wd):
            p = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
            return _moe_local(p, xf.astype(orig_dtype), cfg, E, 0) \
                .astype(jnp.float32)

        y_flat = shard_map(
            local_dp, mesh=ctx.mesh,
            in_specs=(P(axes), P(), P(), P(), P()),
            out_specs=P(axes), axis_names=set(axes), check_vma=False,
        )(x_flat.astype(jnp.float32), params["router"], params["w_gate"],
          params["w_up"], params["w_down"]).astype(orig_dtype)
    else:
        y_flat = _moe_local(params, x_flat, cfg, E, 0)

    y = y_flat.reshape(B, S, D)
    if dense is not None:
        y = y + dense
    return y


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
