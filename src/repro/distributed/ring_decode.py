"""Distributed flash-decode: single-token attention over a sequence-sharded
KV cache without gathering it (§Perf H4).

Baseline GSPMD behaviour for decode with the cache sharded (batch→data,
seq→model): the attention einsum forces an all-gather of the WHOLE cache
shard per layer — 16.9 GB/step/device of the 22 GB decode collective total
for llama3-405b/32k/128 (measured from the partitioned HLO).

Instead, each model-rank computes *partial* attention over its local
S/tp cache slice and the ranks merge O(B·H·dh)-sized statistics:

    m_i, l_i, o_i   = local max / sumexp / unnormalized context
    M               = pmax_i m_i
    w_i             = exp(m_i − M)
    out             = Σ_i o_i·w_i  /  Σ_i l_i·w_i          (psum, exact)

— the same log-sum-exp merge the Pallas decode kernel emits (`return_lse`),
lifted to the mesh.  The new token's K/V are also written inside the same
manual region (only the owning shard writes), which removes the
"involuntary full rematerialization" resharding XLA warned about.

Merge traffic: two psums + one pmax of (B, H, dh)-sized tensors per layer
(~1 MB) versus the 134 MB/layer cache gather — ≈100× less.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


from .context import current_context


def seq_sharded_decode(q, k_cache, v_cache, cache_len, new_k, new_v,
                       scale: float):
    """q: (B, Hq, dh) — post-rope query for the new token.
    k_cache/v_cache: (B, S, Hkv, dh), seq-sharded over `model`.
    cache_len: (B,) int32 — per-lane current lengths.
    new_k/new_v: (B, Hkv, dh) — the new token's K/V to insert at cache_len.
    Returns (out (B, Hq, dh), k_cache, v_cache)."""
    ctx = current_context()
    mesh = ctx.mesh
    tp = mesh.shape["model"]
    B, S, Hkv, dh = k_cache.shape
    Hq = q.shape[1]
    assert S % tp == 0
    s_loc = S // tp
    group = Hq // Hkv

    def local(qf, kc, vc, lens, nk, nv):
        idx = jax.lax.axis_index("model")
        lo = idx * s_loc
        lane = jnp.arange(B)
        # -- insert the new token on the owning shard only ----------------
        pos_local = lens - lo                       # (B,)
        owns = (pos_local >= 0) & (pos_local < s_loc)
        wpos = jnp.clip(pos_local, 0, s_loc - 1)
        kc = jnp.where(
            owns[:, None, None, None],
            kc.at[lane, wpos].set(nk.astype(kc.dtype), mode="drop"), kc)
        vc = jnp.where(
            owns[:, None, None, None],
            vc.at[lane, wpos].set(nv.astype(vc.dtype), mode="drop"), vc)

        # -- partial attention over the local slice -----------------------
        kq = jnp.repeat(kc, group, axis=2)          # (B, s_loc, Hq, dh)
        vq = jnp.repeat(vc, group, axis=2)
        s = jnp.einsum("bhd,bshd->bhs", qf.astype(jnp.float32),
                       kq.astype(jnp.float32)) * scale
        valid = (lo + jnp.arange(s_loc))[None, None, :] \
            < (lens + 1)[:, None, None]
        s = jnp.where(valid, s, -1e30)
        m = s.max(axis=-1)                          # (B, Hq)
        p = jnp.exp(s - m[..., None])
        p = jnp.where(valid, p, 0.0)
        l = p.sum(axis=-1)
        o = jnp.einsum("bhs,bshd->bhd", p, vq.astype(jnp.float32))

        # -- LSE merge across shards --------------------------------------
        M = jax.lax.pmax(m, "model")
        w = jnp.exp(m - M)
        l_tot = jax.lax.psum(l * w, "model")
        o_tot = jax.lax.psum(o * w[..., None], "model")
        out = o_tot / jnp.maximum(l_tot, 1e-30)[..., None]
        return out, kc, vc

    # f32 at the boundary for replicated operands (XLA-CPU bf16 promotion
    # abort — see distributed/vocab_ce.py); the cache stays in its dtype
    # (sharded operands don't hit the replication all-reduce path).
    out, kc, vc = shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(None, "model"), P(None, "model"), P(), P(), P()),
        out_specs=(P(), P(None, "model"), P(None, "model")),
        axis_names={"model"}, check_vma=False,
    )(q.astype(jnp.float32), k_cache, v_cache,
      cache_len.astype(jnp.int32), new_k.astype(jnp.float32),
      new_v.astype(jnp.float32))
    return out.astype(q.dtype), kc, vc
