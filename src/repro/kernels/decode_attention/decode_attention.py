"""Flash-decode TPU kernels: one query token vs a long KV cache.

Decode is HBM-bandwidth-bound (the entire KV cache is streamed once per
token), so the kernel's job is to keep the streaming dense and the
softmax state in VMEM: grid (B, Hkv, nk) with the kv dim innermost; each
step loads a (block_k, Dh) K/V tile, updates the running (m, l, acc) for
all G query heads of the kv group, and emits the normalized output on the
last step.  Length masking comes from a per-batch ``kv_len`` scalar block.

Two variants share that structure:

* ``decode_attention_bhd`` — dense per-slot caches (B, S, Hkv, Dh).
* ``paged_decode_attention_bhd`` — the NATIVE PAGED kernel.  The KV lives
  in a physical page arena (num_pages, L, Hkv, page_size, Dh) shared by
  every request; each batch row's pages are named by a block-table row.
  The block table, per-row ``kv_len`` and the arena ``layer`` index ride
  scalar prefetch (``pltpu.PrefetchScalarGridSpec``), so the K/V
  BlockSpec index maps dereference ``block_table[b, j]`` and the kernel
  walks each row's physical pages DIRECTLY in the arena — no contiguous
  per-slot KV copy is ever materialized.  Each grid step reads one
  (page_size, Dh) tile of one (page, layer, KV head), which keeps every
  block's trailing dims tile-legal for Mosaic.  Sentinel entries
  (>= num_pages) are clamped in the index map and skipped in the body,
  so unmapped pages contribute nothing.  Masking is by position: logical
  page j holds positions [j*P, j*P + P), and the serving plane writes
  every position below a row's ``kv_len`` before it is read (shared
  prefix pages are full pages), so ``j*P + i < kv_len`` is exactly the
  set of filled slots.  Int8 arenas dequantize in-kernel with
  per-(page, layer) scales gathered through the block table into SMEM.

On real hardware the page/nk dimension maps to the sequential grid walk
(``arbitrary``), giving the classic split-KV streaming pattern; splits
across the model axis are combined outside the kernel with an LSE merge
(see serve/distributed decode).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    kvlen_ref, q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, block_k: int, nk: int, G: int,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = kvlen_ref[0]
    k_start = ki * block_k

    @pl.when(k_start < kv_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                # (G, Dh)
        k = k_ref[0, :, 0].astype(jnp.float32)             # (bk, Dh)
        v = v_ref[0, :, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                          # (G, bk)
        pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        s = jnp.where(pos < kv_len, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_bhd(
    q, k_cache, v_cache, kv_len, *, block_k: int = 512, interpret: bool = True,
):
    """q: (B, Hq, Dh); k/v_cache: (B, S, Hkv, Dh); kv_len: (B,) int32."""
    B, Hq, Dh = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    block_k = min(block_k, S)
    assert S % block_k == 0
    nk = S // block_k
    qg = q.reshape(B, Hkv, G, Dh)

    kernel = functools.partial(
        _decode_kernel,
        scale=1.0 / math.sqrt(Dh), block_k=block_k, nk=nk, G=G,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, j: (b,)),
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, block_k, 1, Dh), lambda b, h, j: (b, j, h, 0)),
            pl.BlockSpec((1, block_k, 1, Dh), lambda b, h, j: (b, j, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dh), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention",
    )(kv_len, qg, k_cache, v_cache)
    return out.reshape(B, Hq, Dh)


def _paged_decode_kernel(
    bt_ref, kvlen_ref, layer_ref, *refs,  # scalar prefetch (SMEM), then blocks
    scale: float, page: int, n_log: int, num_pages: int, quant: bool,
):
    del layer_ref  # consumed by the BlockSpec index maps only
    if quant:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    page_id = bt_ref[b * n_log + j]
    kv_len = kvlen_ref[b]

    # skip unmapped pages and pages entirely past the row's valid length
    # (absolute-position layout: logical page j holds positions [j*P, j*P+P))
    @pl.when((page_id < num_pages) & (j * page < kv_len))
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                # (G, Dh)
        k = k_ref[0, 0, 0].astype(jnp.float32)             # (P, Dh)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        if quant:
            k = k * ks_ref[b * n_log + j]
            v = v * vs_ref[b * n_log + j]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                          # (G, P)
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < kv_len, s, NEG_INF)

        m_prev = m_scr[...]                                # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(j == n_log - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def page_scales(scale, block_table, layer):
    """Per-(row, logical page) int8 dequantization scales of one arena
    layer, flattened for scalar prefetch: ``scale`` (N, L) gathered
    through the block table (sentinels clamp; their pages never run)."""
    N = scale.shape[0]
    btc = jnp.minimum(block_table, N - 1)
    return jnp.take(scale, layer, axis=1)[btc].reshape(-1).astype(jnp.float32)


def paged_decode_attention_bhd(
    q, k_arena, v_arena, block_table, kv_len, layer,
    *, k_scale=None, v_scale=None, interpret: bool = True,
):
    """Paged flash-decode: q (B, Hq, Dh) vs a block-table-indirected arena.

    k/v_arena: (N, L, Hkv, P, Dh) — page-major, then layer and KV head, so
    one (page, layer, head) block is a contiguous (P, Dh) tile;
    block_table: (B, n_log) int32, entries >= N are unmapped sentinels;
    kv_len: (B,) valid count (positions [0, kv_len) are attended); layer:
    () int32 arena layer to read.  k/v_scale: (N, L) f32 per-(page, layer)
    dequantization scales for int8 arenas (None = float arena).
    Returns (B, Hq, Dh).
    """
    B, Hq, Dh = q.shape
    N, _L, Hkv, P, _ = k_arena.shape
    G = Hq // Hkv
    n_log = block_table.shape[1]
    qg = q.reshape(B, Hkv, G, Dh)
    bt_flat = block_table.reshape(-1).astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    quant = k_scale is not None
    prefetch = [bt_flat, kv_len.astype(jnp.int32), layer_arr]
    if quant:
        prefetch += [page_scales(k_scale, block_table, layer),
                     page_scales(v_scale, block_table, layer)]

    def kv_map(b, h, j, bt, kvl, lyr, *_):
        # pages past the row's length repeat the last needed block, so
        # the pipeline issues no DMA for them
        jj = jnp.minimum(j, jnp.maximum(kvl[b] - 1, 0) // P)
        return jnp.minimum(bt[b * n_log + jj], N - 1), lyr[0], h, 0, 0

    def q_map(b, h, j, *_):
        return b, h, 0, 0

    kernel = functools.partial(
        _paged_decode_kernel,
        scale=1.0 / math.sqrt(Dh), page=P, n_log=n_log, num_pages=N,
        quant=quant,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, Hkv, n_log),
        in_specs=[
            pl.BlockSpec((1, 1, G, Dh), q_map),
            pl.BlockSpec((1, 1, 1, P, Dh), kv_map),
            pl.BlockSpec((1, 1, 1, P, Dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(*prefetch, qg, k_arena, v_arena)
    return out.reshape(B, Hq, Dh)
