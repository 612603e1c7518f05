"""Flash-decode TPU kernels: one query token vs a long KV cache.

Decode is HBM-bandwidth-bound (the entire KV cache is streamed once per
token), so the kernel's job is to keep the streaming dense and the
softmax state in VMEM: grid (B, Hkv, nk) with the kv dim innermost; each
step loads a (block_k, Dh) K/V tile, updates the running (m, l, acc) for
all G query heads of the kv group, and emits the normalized output on the
last step.  Length masking comes from a per-batch ``kv_len`` scalar block.

Two variants:

* ``decode_attention_bhd`` — dense per-slot caches (B, S, Hkv, Dh), on
  the grid above.
* ``paged_decode_attention_bhd`` — the NATIVE PAGED kernel.  The KV lives
  in a physical page arena (num_pages, L, Hkv, page_size, Dh) shared by
  every request; each batch row's pages are named by a block-table row,
  so no contiguous per-slot KV copy is ever materialized.  The grid is
  (B, n_blk): each step takes every KV head of one row over a block of
  ``ppb`` logical pages, with the row's (Hkv, G, Dh) queries and the
  online-softmax state per (head, query) in f32 VMEM scratch.  K and V
  stay in HBM; the block table, per-row ``kv_len`` and the arena
  ``layer`` ride scalar prefetch, and each page of a block is one async
  copy of its contiguous (Hkv, P, Dh) tile at ``layer`` into a VMEM
  buffer.  Two buffer slots alternate: the next block's copies (in this
  row or the next) start before this block's compute.  Sentinel pages
  (>= num_pages) and pages at or past ``kv_len`` are never copied, and a
  block past the row's length is neither copied nor computed.  Masking
  is by position: logical page j holds positions [j*P, j*P + P), and the
  serving plane writes every position below a row's ``kv_len`` before it
  is read (shared prefix pages are full pages), so ``j*P + i < kv_len``
  of a copied page is exactly the set of filled slots.  ``ppb`` follows
  from shapes alone: the most pages, as a power of two, that fit a fixed
  VMEM budget per buffer (``BLOCK_BYTES``), capped at the table's width,
  so narrow pages (MQA, int8) come in longer blocks; a ragged last block
  is masked.  The block's pages are walked in loops and its KV heads
  taken in batched products, so the lowered kernel is the same size
  whatever the width and ``ppb``.
  Int8 arenas dequantize in-kernel with per-(page, layer) scales gathered
  through the block table into SMEM, applied to the scores and the
  probabilities of each page.
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


# VMEM bytes of one block of K pages (V takes as many) in one of the two
# buffer slots: a block holds as many whole pages as fit, so narrow pages
# (MQA, int8) come in longer blocks than wide ones.
BLOCK_BYTES = 512 * 1024


def pages_per_block(page_bytes: int, n_log: int) -> int:
    """Pages a grid step reads per row: the largest power of two whose
    pages fit ``BLOCK_BYTES``, and no more than the block table's width."""
    fit = max(1, min(BLOCK_BYTES // page_bytes, n_log))
    return 1 << (fit.bit_length() - 1)


def _paged_decode_kernel(
    bt_ref, kvlen_ref, layer_ref, *refs,  # scalar prefetch (SMEM), then the rest
    scale: float, page: int, ppb: int, n_log: int, n_blk: int, rows: int,
    num_pages: int, quant: bool,
):
    if quant:
        (ks_ref, vs_ref, q_ref, k_hbm, v_hbm, o_ref,
         kbuf, vbuf, sem, m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    width = ppb * page
    layer = layer_ref[0]

    def page_at(row, blk, i):
        """Table index and physical page of page ``i`` of block ``blk``,
        and whether it is read: inside the table, mapped, and starting
        below the row's length (logical page jj holds [jj*P, jj*P + P))."""
        jj = blk * ppb + i
        idx = row * n_log + jnp.minimum(jj, n_log - 1)
        pid = bt_ref[idx]
        ok = (jj < n_log) & (pid < num_pages) & (jj * page < kvlen_ref[row])
        return idx, pid, ok

    def copies(pid, slot, i):
        # one page's (Hkv, P, Dh) tile at this layer is contiguous
        return (pltpu.make_async_copy(k_hbm.at[pid, layer],
                                      kbuf.at[slot, :, i], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[pid, layer],
                                      vbuf.at[slot, :, i], sem.at[1, slot]))

    def fetch(row, blk, slot):
        def one(i, carry):
            _, pid, ok = page_at(row, blk, i)

            @pl.when(ok)
            def _start():
                for c in copies(pid, slot, i):
                    c.start()
            return carry
        jax.lax.fori_loop(0, ppb, one, 0)

    # The grid walks rows, then blocks, in order ("arbitrary"), so the
    # copies of the next block, in this row or the next, start before
    # this block's compute; the buffer slot alternates per grid step.
    step = b * n_blk + j
    slot = step % 2

    @pl.when(step == 0)
    def _first():
        # page slots a block does not read keep what they held: make
        # that finite once, so masked positions weigh 0, not NaN
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        fetch(0, 0, 0)

    last = j + 1 == n_blk
    nxt_b = jnp.where(last, b + 1, b)

    @pl.when(nxt_b < rows)
    def _prefetch():
        fetch(nxt_b, jnp.where(last, 0, j + 1), 1 - slot)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = kvlen_ref[b]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    ones = jnp.ones((1, width), jnp.float32)

    def page_vectors():
        """Per position of the block: 1 where its page was read, 0 where
        not, and the int8 scales of its page."""
        def one(i, carry):
            read, ks, vs = carry
            idx, _, ok = page_at(b, j, i)
            here = (col >= i * page) & (col < (i + 1) * page)
            read = jnp.where(here, jnp.where(ok, 1.0, 0.0), read)
            if quant:
                ks = jnp.where(here, ks_ref[idx], ks)
                vs = jnp.where(here, vs_ref[idx], vs)
            return read, ks, vs
        return jax.lax.fori_loop(0, ppb, one, (ones, ones, ones))

    @pl.when(j * width < kv_len)
    def _body():
        def settle(i, counts):
            # wait for page i's copies; count the pages due (inside the
            # table and below the length) and those copied (also mapped)
            due, got = counts
            _, pid, ok = page_at(b, j, i)
            jj = j * ppb + i

            @pl.when(ok)
            def _wait():
                for c in copies(pid, slot, i):
                    c.wait()
            return (due + jnp.where((jj < n_log) & (jj * page < kv_len), 1, 0),
                    got + jnp.where(ok, 1, 0))

        due, got = jax.lax.fori_loop(0, ppb, settle, (0, 0))
        if quant:
            read, ks, vs = page_vectors()
        else:
            # a sentinel inside the length leaves a hole in the block
            read = jax.lax.cond(due == got, lambda: ones,
                                lambda: page_vectors()[0])
        valid = (read > 0) & (j * width + col < jnp.minimum(kv_len, n_log * page))

        # every KV head at once: batched products over the block
        hkv, dh = kbuf.shape[1], kbuf.shape[-1]
        q = q_ref[0].astype(jnp.float32)                     # (Hkv, G, Dh)
        k = kbuf[slot].astype(jnp.float32).reshape(hkv, width, dh)
        v = vbuf[slot].astype(jnp.float32).reshape(hkv, width, dh)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                            # (Hkv, G, width)
        if quant:
            s = s * ks
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]                                  # (Hkv, G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        # a position not read weighs 0, also where the block read nothing
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=2, keepdims=True)
        if quant:
            p = p * vs
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(last)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


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
    one (page, layer) holds a contiguous (Hkv, P, Dh) tile;
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
    ppb = pages_per_block(Hkv * P * Dh * k_arena.dtype.itemsize, n_log)
    n_blk = -(-n_log // ppb)
    qg = q.reshape(B, Hkv, G, Dh)
    bt_flat = block_table.reshape(-1).astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    quant = k_scale is not None
    prefetch = [bt_flat, kv_len.astype(jnp.int32), layer_arr]
    if quant:
        prefetch += [page_scales(k_scale, block_table, layer),
                     page_scales(v_scale, block_table, layer)]

    def row_map(b, j, *_):
        return b, 0, 0, 0

    kernel = functools.partial(
        _paged_decode_kernel,
        scale=1.0 / math.sqrt(Dh), page=P, ppb=ppb, n_log=n_log,
        n_blk=n_blk, rows=B, num_pages=N, quant=quant,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, n_blk),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, Dh), row_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, Dh), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, Hkv, ppb, P, Dh), k_arena.dtype),
            pltpu.VMEM((2, Hkv, ppb, P, Dh), v_arena.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(*prefetch, qg, k_arena, v_arena)
    return out.reshape(B, Hq, Dh)
