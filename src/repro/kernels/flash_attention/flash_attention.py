"""Flash attention TPU kernel (pl.pallas_call + BlockSpec VMEM tiling).

Layout: q (B, Hq, Sq, Dh), k/v (B, Hkv, Skv, Dh).  Grid (B, Hq, nq, nk)
with the kv dimension innermost ("arbitrary" semantics): the (m, l, acc)
running-softmax state lives in VMEM scratch and is carried across kv grid
steps; the output block is written on the last kv step.  Causal + sliding
window masking; fully-masked kv blocks are skipped with ``pl.when``.

Block sizes are chosen so the working set
(q_blk + k_blk + v_blk + acc = bq*Dh*4 + 2*bk*Dh*2 + bq*bk*4 bytes)
fits comfortably in the ~16 MiB of VMEM with MXU-aligned (128-multiple)
tile dims.

``paged_extend_attention_bhsd`` is the block-table variant for the paged
suffix-extend path (prefix-hit prefill): K/V stream straight from the
physical page arena through scalar-prefetched block-table index maps —
same calling convention as the paged decode kernel (see
kernels/decode_attention), with per-row absolute query offsets.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention.decode_attention import page_scales

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,      # blocks
    m_scr, l_scr, acc_scr,           # VMEM scratch (carried over kv steps)
    *, scale: float, causal: bool, window: Optional[int],
    block_q: int, block_k: int, nk: int, seq_off: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q + seq_off          # absolute q positions
    k_start = ki * block_k

    # skip blocks that are entirely masked
    run = True
    if causal:
        run = (q_start + block_q - 1) >= k_start
    if window is not None:
        # newest k in block must be > oldest q - window
        run = jnp.logical_and(run, (k_start + block_k - 1) > (q_start - window))

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)               # (bq, Dh)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, Dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                          # (bq, bk)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

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


def flash_attention_bhsd(
    q, k, v, *, causal: bool = True, window: Optional[int] = None,
    block_q: int = 512, block_k: int = 512, interpret: bool = True,
):
    """q: (B, Hq, Sq, Dh); k/v: (B, Hkv, Skv, Dh) -> (B, Hq, Sq, Dh)."""
    B, Hq, Sq, Dh = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, block_q, Skv, block_k)
    nq, nk = Sq // block_q, Skv // block_k
    seq_off = Skv - Sq                 # q block positions count from the end

    kernel = functools.partial(
        _flash_kernel,
        scale=1.0 / math.sqrt(Dh),
        causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk, seq_off=seq_off,
    )
    grid = (B, Hq, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


def _paged_extend_kernel(
    bt_ref, pos_ref, layer_ref, *refs,  # scalar prefetch (SMEM), then blocks
    scale: float, block_q: int, page: int, n_log: int, num_pages: int,
    quant: bool,
):
    del layer_ref  # consumed by the BlockSpec index maps only
    if quant:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    qi = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    page_id = bt_ref[b * n_log + j]
    # newest attendable position for this q block (absolute layout:
    # logical page j holds positions [j*P, j*P + P))
    q_lo = pos_ref[b] + qi * block_q
    q_hi = q_lo + block_q - 1

    @pl.when((page_id < num_pages) & (j * page <= q_hi))
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                # (bq, Dh)
        k = k_ref[0, 0, 0].astype(jnp.float32)             # (P, Dh)
        v = v_ref[0, 0, 0].astype(jnp.float32)
        if quant:
            k = k * ks_ref[b * n_log + j]
            v = v * vs_ref[b * n_log + j]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                          # (bq, P)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

        m_prev = m_scr[...]                                # (bq, 1)
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


def paged_extend_attention_bhsd(
    q, k_arena, v_arena, block_table, pos, layer,
    *, k_scale=None, v_scale=None, block_q: int = 128,
    interpret: bool = True,
):
    """Paged suffix-extend attention: q (B, Hq, Sq, Dh) vs an arena.

    The multi-query sibling of ``paged_decode_attention_bhd`` (see
    kernels/decode_attention): row b's queries sit at absolute positions
    ``pos[b] + i`` behind a prefix already resident in the block-table's
    pages; slot i of logical page j holds position ``j*P + i`` and is
    attended iff that position is <= the query position.  k/v_arena:
    (N, L, Hkv, P, Dh); block_table: (B, n_log) int32 (>= N = unmapped);
    pos: (B,) int32 per-row offsets; layer: () int32.  Returns
    (B, Hq, Sq, Dh).
    """
    B, Hq, Sq, Dh = q.shape
    N, _L, Hkv, P, _ = k_arena.shape
    G = Hq // Hkv
    n_log = block_table.shape[1]
    block_q = min(block_q, Sq)
    assert Sq % block_q == 0, (Sq, block_q)
    nq = Sq // block_q
    bt_flat = block_table.reshape(-1).astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    quant = k_scale is not None
    prefetch = [bt_flat, pos.astype(jnp.int32), layer_arr]
    if quant:
        prefetch += [page_scales(k_scale, block_table, layer),
                     page_scales(v_scale, block_table, layer)]

    def kv_map(b, h, i, j, bt, ps, lyr, *_):
        # pages past this q block's newest position repeat the last
        # needed block, so the pipeline issues no DMA for them
        jj = jnp.minimum(j, (ps[b] + (i + 1) * block_q - 1) // P)
        return jnp.minimum(bt[b * n_log + jj], N - 1), lyr[0], h // G, 0, 0

    def q_map(b, h, i, j, *_):
        return b, h, i, 0

    kernel = functools.partial(
        _paged_extend_kernel,
        scale=1.0 / math.sqrt(Dh), block_q=block_q, page=P, n_log=n_log,
        num_pages=N, quant=quant,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, Hq, nq, n_log),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh), q_map),
            pl.BlockSpec((1, 1, 1, P, Dh), kv_map),
            pl.BlockSpec((1, 1, 1, P, Dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_extend_attention",
    )(*prefetch, q, k_arena, v_arena)
