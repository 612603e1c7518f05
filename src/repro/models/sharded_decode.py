"""Distributed flash-decode: attention over a sequence-sharded KV cache.

With ``decode_kv_shard_seq`` the cache's sequence dim is sharded over the
model (and, for batch=1 long-context cells, also the data) axis.  Under
pjit autosharding, XLA resolves the softmax over the sharded dim by
ALL-GATHERING the per-layer KV cache every step — ~KV_bytes/chip of ICI
traffic per layer per token, which makes decode collective-bound.

This module is the beyond-paper replacement: a fully-manual ``shard_map``
where each shard computes a *partial* softmax (m, l, acc) over its local
KV rows and the shards merge with an LSE combine — ``pmax`` of the max and
``psum`` of (l, acc), i.e. O(B*H*Dh) bytes instead of O(B*S*H*Dh).  The
cache-slot write is also local (only the owning shard writes).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import KVSlice

F32 = jnp.float32
NEG_INF = -1e30


def _seq_axes_of(pspec: P) -> Tuple[str, ...]:
    """Mesh axes the cache's seq dim (dim 1) is sharded over."""
    if len(pspec) < 2 or pspec[1] is None:
        return ()
    e = pspec[1]
    return e if isinstance(e, tuple) else (e,)


def sharded_decode_attention(
    ctx, q, cache: KVSlice, new_k, new_v, pos, *,
    window: Optional[int] = None,
) -> Tuple[jnp.ndarray, KVSlice]:
    """q: (B,1,Hq,Dh); cache k/v: (B,S_c,Hkv,Dh); new_k/v: (B,1,Hkv,Dh);
    pos: (B,) absolute positions.  Returns (out (B,1,Hq,Dh), new cache)."""
    B, S_c, Hkv, Dh = cache.k.shape
    Hq = q.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(Dh)

    kv_spec = ctx.pspec(
        ("batch", "kv_seq", None, None), cache.k.shape
    )
    sp_spec = ctx.pspec(
        ("batch", "kv_seq" if kv_spec[1] is not None else None),
        cache.slot_pos.shape,
    )
    seq_axes = _seq_axes_of(kv_spec)
    if not seq_axes:
        raise ValueError("cache seq dim is not sharded; use the ref path")
    mesh_sizes = ctx.axis_sizes
    n_seq_shards = 1
    for a in seq_axes:
        n_seq_shards *= mesh_sizes[a]
    S_loc = S_c // n_seq_shards

    def local_fn(q, k_c, v_c, sp, nk, nv, pos):
        # shard rank along the seq sharding (major-to-minor order)
        r = jnp.int32(0)
        for a in seq_axes:
            r = r * mesh_sizes[a] + jax.lax.axis_index(a)
        B_l = q.shape[0]
        bidx = jnp.arange(B_l)

        # --- local cache-slot write -----------------------------------
        if window is not None and S_c <= window:
            slot = pos % S_c
        else:
            slot = jnp.minimum(pos, S_c - 1)
        idx = slot - r * S_loc
        mine = (idx >= 0) & (idx < S_loc)
        safe = jnp.clip(idx, 0, S_loc - 1)
        old_k = k_c[bidx, safe]
        old_v = v_c[bidx, safe]
        old_sp = sp[bidx, safe]
        k_c = k_c.at[bidx, safe].set(
            jnp.where(mine[:, None, None], nk[:, 0], old_k))
        v_c = v_c.at[bidx, safe].set(
            jnp.where(mine[:, None, None], nv[:, 0], old_v))
        sp = sp.at[bidx, safe].set(jnp.where(mine, pos, old_sp))

        # --- local partial softmax -------------------------------------
        qg = q[:, 0].reshape(B_l, Hkv, G, Dh).astype(F32)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_c.astype(F32)) * scale
        kv_len = pos + 1
        valid = (sp >= 0) & (sp < kv_len[:, None])
        if window is not None:
            valid &= sp > (kv_len[:, None] - 1 - window)
        s = jnp.where(valid[:, None, None], s, NEG_INF)
        m_l = s.max(axis=-1)                                  # (B,Hkv,G)
        p_ = jnp.exp(s - m_l[..., None])
        p_ = jnp.where(valid[:, None, None], p_, 0.0)
        l_l = p_.sum(axis=-1)
        acc = jnp.einsum("bhgk,bkhd->bhgd", p_, v_c.astype(F32))

        # --- LSE combine across seq shards ------------------------------
        m_g = jax.lax.pmax(m_l, seq_axes)
        corr = jnp.exp(m_l - m_g)
        l_g = jax.lax.psum(l_l * corr, seq_axes)
        acc_g = jax.lax.psum(acc * corr[..., None], seq_axes)
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        out = out.reshape(B_l, 1, Hq, Dh).astype(q.dtype)
        return out, k_c, v_c, sp

    q_spec = ctx.pspec(("batch", None, None, None), q.shape)
    nk_spec = ctx.pspec(("batch", None, None, None), new_k.shape)
    pos_spec = ctx.pspec(("batch",), pos.shape)
    fn = jax.shard_map(
        local_fn,
        mesh=ctx.mesh,
        in_specs=(q_spec, kv_spec, kv_spec, sp_spec, nk_spec, nk_spec, pos_spec),
        out_specs=(q_spec, kv_spec, kv_spec, sp_spec),
        axis_names=ctx.manual_axes,
        check_vma=False,
    )
    out, k_new, v_new, sp_new = fn(
        q, cache.k, cache.v, cache.slot_pos, new_k, new_v,
        pos.astype(jnp.int32),
    )
    return out, KVSlice(k=k_new, v=v_new, slot_pos=sp_new)
