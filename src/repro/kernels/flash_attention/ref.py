"""Pure-jnp oracle for flash attention (naive full-matrix softmax)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.ref import page_positions, paged_pages


def attention_ref(
    q, k, v, *, causal: bool = True, window: Optional[int] = None,
):
    """q: (B, Hq, Sq, Dh); k, v: (B, Hkv, Skv, Dh) -> (B, Hq, Sq, Dh).

    GQA by head grouping (head h uses kv head h // (Hq//Hkv)).
    """
    B, Hq, Sq, Dh = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    kf = jnp.repeat(k, G, axis=1)
    vf = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf.astype(jnp.float32))
    s = s / math.sqrt(Dh)
    q_pos = jnp.arange(Sq)[:, None] + (Skv - Sq)   # align ends (q suffix)
    k_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_extend_attention_ref(
    q, k_arena, v_arena, block_table, pos, layer,
    *, k_scale=None, v_scale=None,
):
    """Pure-jnp oracle for the paged extend kernel (same signature).

    q: (B, Hq, Sq, Dh); k/v_arena: (N, L, Hkv, P, Dh); block_table:
    (B, n_log) int32 (>= N unmapped); pos: (B,) absolute offset of each
    row's first query; layer: () int32.  Slot i of mapped logical page j
    holds position j*P + i and is attended iff that position is <= the
    query's absolute position.  Returns (B, Hq, Sq, Dh).
    """
    B, Hq, Sq, Dh = q.shape
    N, P = k_arena.shape[0], k_arena.shape[3]
    k = paged_pages(k_arena, block_table, layer, k_scale)   # (B, Hkv, K, Dh)
    v = paged_pages(v_arena, block_table, layer, v_scale)
    kpos = page_positions(block_table, N, P)                # (B, K)
    G = Hq // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(Dh)
    q_pos = pos[:, None] + jnp.arange(Sq)[None, :]          # (B, Sq)
    valid = (kpos[:, None, :] >= 0) & (kpos[:, None, :] <= q_pos[:, :, None])
    s = jnp.where(valid[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
