"""Pure-jnp oracle for single-token decode attention over a KV cache."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def decode_attention_ref(q, k_cache, v_cache, kv_len):
    """q: (B, Hq, Dh); k/v_cache: (B, S, Hkv, Dh); kv_len: (B,) valid count.

    Returns (B, Hq, Dh).  Slot i holds position i; positions >= kv_len are
    masked.
    """
    B, S, Hkv, Dh = k_cache.shape
    Hq = q.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache.astype(jnp.float32))
    s = s / math.sqrt(Dh)
    valid = jnp.arange(S)[None] < kv_len[:, None]               # (B, S)
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, Hq, Dh).astype(q.dtype)


def paged_pages(arena, block_table, layer, scale=None):
    """One arena layer's pages along each block-table row, as a dense
    (B, Hkv, n_log*P, Dh) view (sentinels clamp to the last page; callers
    mask them).  ``scale`` (N, L) dequantizes int8 arenas."""
    N = arena.shape[0]
    B, n_log = block_table.shape
    btc = jnp.minimum(block_table, N - 1)
    x = jnp.take(arena, layer, axis=1)[btc]            # (B, n_log, Hkv, P, Dh)
    if scale is not None:
        s = jnp.take(scale, layer, axis=1)[btc]        # (B, n_log)
        x = x.astype(jnp.float32) * s[..., None, None, None]
    _, _, Hkv, P, Dh = x.shape
    return x.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, n_log * P, Dh)


def page_positions(block_table, num_pages: int, page_size: int):
    """(B, n_log*P) absolute position of every slot along each row, -1
    where the logical page is unmapped (absolute-position layout)."""
    n_log = block_table.shape[1]
    pos = jnp.arange(n_log * page_size, dtype=jnp.int32)[None]
    mapped = jnp.repeat(block_table < num_pages, page_size, axis=1)
    return jnp.where(mapped, pos, -1)


def paged_decode_attention_ref(
    q, k_arena, v_arena, block_table, kv_len, layer,
    *, k_scale=None, v_scale=None,
):
    """Pure-jnp oracle for the paged decode kernel (same signature).

    q: (B, Hq, Dh); k/v_arena: (N, L, Hkv, P, Dh); block_table: (B, n_log)
    int32, entries >= N unmapped; kv_len: (B,); layer: () int32.
    k/v_scale: (N, L) per-(page, layer) int8 scales or None.  Slot i of
    mapped logical page j holds position j*P + i and is attended iff that
    position is < kv_len.  Returns (B, Hq, Dh).
    """
    B, Hq, Dh = q.shape
    N, P = k_arena.shape[0], k_arena.shape[3]
    k = paged_pages(k_arena, block_table, layer, k_scale)
    v = paged_pages(v_arena, block_table, layer, v_scale)
    pos = page_positions(block_table, N, P)
    Hkv = k.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k.astype(jnp.float32))
    s = s / math.sqrt(Dh)
    valid = (pos >= 0) & (pos < kv_len[:, None])
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, Dh).astype(q.dtype)
