"""Shared model layers: norms, RoPE, attention, MLPs, embeddings.

All attention paths are memory-bounded by construction: the baseline is a
chunked flash-style attention written in pure jnp (XLA-visible FLOPs so the
roofline terms from ``cost_analysis`` are exact).  The Pallas kernel path
(``cfg.use_flash_kernel``) swaps in ``repro.kernels``.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.decode_attention.ref import paged_pages
from repro.models.param import PSpec  # noqa: F401  (re-exported for layer specs)

F32 = jnp.float32
NEG_INF = -1e30


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def rms_norm(x, w, eps: float):
    x32 = x.astype(F32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * w.astype(F32)).astype(x.dtype)


def norm_spec(d: int) -> PSpec:
    return PSpec((d,), (None,), ("const", 1.0))


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=F32) / half)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                      # (Dh/2,)
    angles = positions.astype(F32)[..., None] * freqs        # (..., S, Dh/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., S, 1, Dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# chunked flash-style attention (pure jnp baseline)
# --------------------------------------------------------------------------
def _block_mask(q_pos, k_pos, causal: bool, window: Optional[int], kv_len=None):
    """(qc, kc) bool mask of VALID entries from absolute positions."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= k_pos[None, :] > q_pos[:, None] - window
    if kv_len is not None:
        m &= k_pos[None, :] < kv_len
    return m


def chunked_attention(
    q, k, v, *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    unroll: bool = False,
    kv_len: Optional[jnp.ndarray] = None,
):
    """Flash-algorithm attention in jnp (running max/sum over KV chunks).

    q: (B, Sq, Hq, Dh);  k, v: (B, Skv, Hkv, Dh);  GQA via head grouping.
    kv_len: optional (B,) per-row valid KV count — keys at positions
    >= kv_len[b] are masked out (ragged/padded memory, e.g. encdec source
    features batched to a common length).  A fully-masked q row degrades
    to a uniform average over the masked values (never NaN); callers must
    not read such rows.
    Returns (B, Sq, Hq, Dh).
    """
    B, Sq, Hq, Dh = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(Dh)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    nq, nk = Sq // q_chunk, Skv // kv_chunk
    assert Sq % q_chunk == 0 and Skv % kv_chunk == 0

    qg = q.reshape(B, nq, q_chunk, Hkv, G, Dh)
    kg = k.reshape(B, nk, kv_chunk, Hkv, Dh)
    vg = v.reshape(B, nk, kv_chunk, Hkv, Dh)

    def q_body(_, qi):
        qblk, qidx = qi                                       # (B,qc,Hkv,G,Dh)
        q_pos = q_offset + qidx * q_chunk + jnp.arange(q_chunk)

        def kv_body(carry, ki):
            m, l, acc = carry
            kblk, vblk, kidx = ki
            k_pos = kidx * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum(
                "bqhgd,bkhd->bhgqk", qblk.astype(F32), kblk.astype(F32)
            ) * scale                                         # (B,Hkv,G,qc,kc)
            mask = _block_mask(q_pos, k_pos, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            if kv_len is not None:
                row_ok = k_pos[None, :] < kv_len[:, None]     # (B, kc)
                s = jnp.where(row_ok[:, None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vblk.astype(F32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, q_chunk), NEG_INF, F32)
        l0 = jnp.zeros((B, Hkv, G, q_chunk), F32)
        a0 = jnp.zeros((B, Hkv, G, q_chunk, Dh), F32)
        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, a0),
            (kg.swapaxes(0, 1), vg.swapaxes(0, 1), jnp.arange(nk)),
            unroll=nk if unroll else 1,
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,Hkv,G,qc,Dh)
        return None, out.transpose(0, 3, 1, 2, 4)             # (B,qc,Hkv,G,Dh)

    _, outs = jax.lax.scan(q_body, None, (qg.swapaxes(0, 1), jnp.arange(nq)),
                           unroll=nq if unroll else 1)
    out = outs.swapaxes(0, 1).reshape(B, Sq, Hq, Dh)
    return out.astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, kv_len, *, window: Optional[int] = None,
                         slot_pos: Optional[jnp.ndarray] = None):
    """Single-position attention against a (possibly rolling) KV cache.

    q: (B, 1, Hq, Dh);  k/v_cache: (B, S, Hkv, Dh);  kv_len: (B,) valid count.
    slot_pos: (B, S) absolute position stored in each slot (rolling SWA
    buffers), or None meaning slot i holds position i.
    Returns (B, 1, Hq, Dh).
    """
    B, S, Hkv, Dh = k_cache.shape
    Hq = q.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, Hkv, G, Dh)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(F32), k_cache.astype(F32)) * scale
    if slot_pos is None:
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    else:
        pos = slot_pos
    valid = pos < kv_len[:, None]
    if window is not None:
        valid &= pos > (kv_len[:, None] - 1 - window)
    valid &= pos >= 0
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(F32))
    return out.reshape(B, 1, Hq, Dh).astype(q.dtype)


def extend_attention_ref(q, k_cache, v_cache, slot_pos, q_pos, *,
                         window: Optional[int] = None):
    """Multi-position attention against an absolute-position KV cache.

    The S>1 generalization of :func:`decode_attention_ref`, used by the
    suffix-extend prefill path (paged prefix sharing): ``q`` holds a
    request's suffix positions, the cache already holds its shared prefix
    (plus the just-written suffix K/V).  Masking is purely ``slot_pos``
    driven — a slot is attended iff it holds a valid position <= the
    query's absolute position — so gathered pool pages and freshly
    written slots need no separate treatment.

    q: (B, S, Hq, Dh);  k/v_cache: (B, S_c, Hkv, Dh);
    slot_pos: (B, S_c) absolute position per slot (-1 = empty);
    q_pos: (B, S) absolute position per query row.
    Returns (B, S, Hq, Dh).
    """
    B, S, Hq, Dh = q.shape
    Hkv = k_cache.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, S, Hkv, G, Dh)
    s = jnp.einsum("bshgd,bkhd->bshgk", qg.astype(F32),
                   k_cache.astype(F32)) * scale        # (B,S,Hkv,G,S_c)
    valid = (slot_pos[:, None, :] >= 0) & \
        (slot_pos[:, None, :] <= q_pos[:, :, None])    # (B,S,S_c)
    if window is not None:
        valid &= slot_pos[:, None, :] > q_pos[:, :, None] - window
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bshgk,bkhd->bshgd", p, v_cache.astype(F32))
    return out.reshape(B, S, Hq, Dh).astype(q.dtype)


# --------------------------------------------------------------------------
# attention block (QKV proj + rope + attn + out proj)
# --------------------------------------------------------------------------
def attn_specs(cfg: ArchConfig, d_in: Optional[int] = None) -> dict:
    d = d_in or cfg.d_model
    dh = cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    specs = {
        "wq": PSpec((d, hq, dh), ("embed", "heads", None), ("normal", 0)),
        "wk": PSpec((d, hkv, dh), ("embed", "kv_heads", None), ("normal", 0)),
        "wv": PSpec((d, hkv, dh), ("embed", "kv_heads", None), ("normal", 0)),
        "wo": PSpec((hq, dh, cfg.d_model), ("heads", None, "embed"), ("normal", 0)),
    }
    if cfg.qkv_bias:
        specs["bq"] = PSpec((hq, dh), ("heads", None), ("const", 0.0))
        specs["bk"] = PSpec((hkv, dh), ("kv_heads", None), ("const", 0.0))
        specs["bv"] = PSpec((hkv, dh), ("kv_heads", None), ("const", 0.0))
    if cfg.qk_norm:
        specs["q_norm"] = norm_spec(dh)
        specs["k_norm"] = norm_spec(dh)
    return specs


class KVSlice(NamedTuple):
    """Per-layer KV cache slice carried through the layer scan."""
    k: jnp.ndarray          # (B, S_cache, Hkv, Dh)
    v: jnp.ndarray
    # absolute position stored in each slot; -1 = empty (for SWA rolling)
    slot_pos: jnp.ndarray   # (B, S_cache) int32


class PagedKVCache(NamedTuple):
    """Paged KV view: the whole physical page arena + one batch's block table.

    The native-paged calling convention (see ``serve/kvpool.py``): instead
    of gathering pool pages into a dense per-slot cache, the serving layer
    hands attention the arena itself plus a ``(B, n_log)`` block table.
    Attention writes the current token(s) straight into their physical
    pages (`.at[...].set(mode="drop")` — sentinel entries ``>= N`` drop the
    write) and reads by walking the block-table row, so no contiguous KV
    copy is ever materialized.  ``layer`` selects the arena layer slice this
    view reads/writes; the layer scan rebinds it per step so one arena
    rides the scan carry (see ``Model._scan_stack``).

    Precondition: absolute-position layout only — slot ``i`` of logical
    page ``j`` holds position ``j*P + i``.  The KVPool gate guarantees it
    (``sliding_window`` is None or >= max_len), so window masking never
    binds and the paged kernels ignore it.  The kernels mask by that
    position (``slot_pos`` stays the arena's record of which slots were
    written, for export, migration and the jnp page walk).

    k/v: (N, L, Hkv, P, Dh) arena (float, or int8 with per-page scales);
    slot_pos: (N, L, P) int32 absolute position per slot (-1 = empty);
    block_table: (B, n_log) int32 physical page per logical page;
    layer: () int32 arena layer of this view;
    k_scale/v_scale: (N, L) f32 per-(page, layer) scales for int8 arenas.
    """
    k: jnp.ndarray
    v: jnp.ndarray
    slot_pos: jnp.ndarray
    block_table: jnp.ndarray
    layer: jnp.ndarray
    k_scale: Optional[jnp.ndarray] = None
    v_scale: Optional[jnp.ndarray] = None


def paged_kernels() -> bool:
    """Paged attention runs the Pallas kernels on the TPU and the jnp
    page walk (:func:`paged_gather` + dense refs) elsewhere."""
    return jax.default_backend() == "tpu"


def paged_gather(cache: "PagedKVCache"):
    """Walk a block table in pure jnp: (B, n_log*P) dense K/V/slot_pos view.

    The off-TPU half of the paged attention contract (see
    :func:`paged_kernels`): sentinel pages contribute slot_pos -1, i.e.
    masked zeros, and the dense refs get bit-identical inputs, so CPU
    serving keeps token-identical output vs the dense path.
    Int8 arenas are dequantized with their per-page scales on gather.
    """
    N = cache.k.shape[0]
    layer, bt = cache.layer, cache.block_table
    btc = jnp.minimum(bt, N - 1)                      # clamp sentinels
    k = paged_pages(cache.k, bt, layer, cache.k_scale)    # (B, Hkv, S, Dh)
    v = paged_pages(cache.v, bt, layer, cache.v_scale)
    sp_l = jnp.take(cache.slot_pos, layer, axis=1)    # (N, P)
    sp = jnp.where((bt < N)[:, :, None], sp_l[btc], -1)
    return (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            sp.reshape(bt.shape[0], -1))


def _quantize_to(arena_dtype, vals, scale):
    """Quantize (..., Hkv, Dh) floats with broadcast (...,) scales."""
    q = jnp.round(vals.astype(F32) / jnp.maximum(scale, 1e-8)[..., None, None])
    return jnp.clip(q, -127, 127).astype(arena_dtype)


def _paged_write_decode(cache: "PagedKVCache", k, v, pos):
    """Write one token per row into its physical page; returns new cache.

    k/v: (B, Hkv, Dh) values for position ``pos`` (B,).  Sentinel/unmapped
    target pages drop the write.  Int8 arenas lazily initialize the
    per-page scale on first touch (scale 0 = untouched page).
    """
    N, P = cache.k.shape[0], cache.k.shape[3]
    layer, bt = cache.layer, cache.block_table
    phys = jnp.take_along_axis(bt, (pos // P)[:, None], axis=1)[:, 0]  # (B,)
    off = pos % P
    ks, vs = cache.k_scale, cache.v_scale
    if ks is not None:
        physc = jnp.minimum(phys, N - 1)
        amax_k = jnp.max(jnp.abs(k.astype(F32)), axis=(1, 2))          # (B,)
        amax_v = jnp.max(jnp.abs(v.astype(F32)), axis=(1, 2))
        sck = jnp.where(ks[physc, layer] > 0, ks[physc, layer], amax_k / 127.0)
        scv = jnp.where(vs[physc, layer] > 0, vs[physc, layer], amax_v / 127.0)
        ks = ks.at[phys, layer].set(sck, mode="drop")
        vs = vs.at[phys, layer].set(scv, mode="drop")
        k = _quantize_to(cache.k.dtype, k, sck)
        v = _quantize_to(cache.v.dtype, v, scv)
    # one (row, head) Dh-vector per index: the scatter window stays the
    # minor dim, so XLA keeps the arena in the layout the kernels read
    h = jnp.arange(k.shape[1])[None, :]
    at = (phys[:, None], layer, h, off[:, None])
    k_a = cache.k.at[at].set(k, mode="drop")
    v_a = cache.v.at[at].set(v, mode="drop")
    sp_a = cache.slot_pos.at[phys, layer, off].set(pos, mode="drop")
    return cache._replace(k=k_a, v=v_a, slot_pos=sp_a, k_scale=ks, v_scale=vs)


def _paged_write_extend(cache: "PagedKVCache", k, v, positions):
    """Write S suffix tokens per row into their physical pages.

    k/v: (B, S, Hkv, Dh); positions: (B, S) absolute.  Positions whose
    logical page is beyond the block-table width or unmapped drop the
    write.  Int8 scales use a scatter-max per target page.
    """
    N, P = cache.k.shape[0], cache.k.shape[3]
    layer, bt = cache.layer, cache.block_table
    n_log = bt.shape[1]
    lp = positions // P
    phys = jnp.where(
        lp < n_log,
        jnp.take_along_axis(bt, jnp.minimum(lp, n_log - 1), axis=1),
        N,
    )                                                             # (B, S)
    off = positions % P
    ks, vs = cache.k_scale, cache.v_scale
    if ks is not None:
        physc = jnp.minimum(phys, N - 1)
        amax_k = jnp.max(jnp.abs(k.astype(F32)), axis=(2, 3))     # (B, S)
        amax_v = jnp.max(jnp.abs(v.astype(F32)), axis=(2, 3))
        ks = ks.at[phys, layer].max(amax_k / 127.0, mode="drop")
        vs = vs.at[phys, layer].max(amax_v / 127.0, mode="drop")
        k = _quantize_to(cache.k.dtype, k, ks[physc, layer])
        v = _quantize_to(cache.v.dtype, v, vs[physc, layer])
    h = jnp.arange(k.shape[2])[None, None, :]
    at = (phys[..., None], layer, h, off[..., None])    # see _paged_write_decode
    k_a = cache.k.at[at].set(k, mode="drop")
    v_a = cache.v.at[at].set(v, mode="drop")
    sp_a = cache.slot_pos.at[phys, layer, off].set(positions, mode="drop")
    return cache._replace(k=k_a, v=v_a, slot_pos=sp_a, k_scale=ks, v_scale=vs)


def qkv_project(p, x, cfg: ArchConfig, positions):
    """x: (B,S,D) -> q (B,S,Hq,Dh), k,v (B,S,Hkv,Dh), roped."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_block(
    p, x, cfg: ArchConfig, ctx=None, *,
    mode: str,                       # train | prefill | decode
    cache: Optional[KVSlice] = None,
    pos: Optional[jnp.ndarray] = None,   # (B,) next position (decode) or 0-base
    causal: bool = True,
    kv_len: Optional[jnp.ndarray] = None,  # (B,) ragged-memory mask (non-causal)
) -> Tuple[jnp.ndarray, Optional[KVSlice]]:
    """Full attention sublayer.  Returns (out (B,S,D), updated cache)."""
    B, S, _ = x.shape
    window = cfg.sliding_window

    # Attention parallelism: shard heads over the model axis when the head
    # count divides it (Megatron TP).  Otherwise (56/40-head archs on a
    # 16-wide axis) fall back to context parallelism: q/out sharded along
    # the sequence, KV replicated — each shard computes its q rows against
    # the full KV.  Without either, GSPMD replicates heads AND seq and the
    # score matrices blow past HBM.
    msz = ctx.model_size() if ctx is not None else 1
    heads_div = msz <= 1 or (cfg.num_heads % msz == 0)

    def head_shard(t):
        if ctx is None:
            return t
        return jax.lax.with_sharding_constraint(
            t, ctx.sharding(("batch", None, "heads", None), t.shape)
        )

    def seq_shard(t):
        if ctx is None:
            return t
        return jax.lax.with_sharding_constraint(
            t, ctx.sharding(("batch", "act_seq", None, None), t.shape)
        )

    if mode in ("train", "prefill"):
        positions = jnp.arange(S)[None, :]
        if heads_div and ctx is not None:
            # Megatron-SP all-gather placement: restore full-seq *before*
            # the QKV projection so its output lands head-sharded directly.
            # Resharding seq->heads after the fact makes GSPMD fall back to
            # "involuntary full rematerialization" (replicate + repartition).
            x = jax.lax.with_sharding_constraint(
                x, ctx.sharding(("batch", None, None), x.shape)
            )
        q, k, v = qkv_project(p, x, cfg, positions)
        G = cfg.num_heads // max(cfg.num_kv_heads, 1)
        if G > 1:
            # expand KV to full heads so the head dim (divisible by the
            # model axis) shards; the expansion is local under head sharding
            ke = jnp.repeat(k, G, axis=2)
            ve = jnp.repeat(v, G, axis=2)
        else:
            ke, ve = k, v
        if heads_div:
            q, ke, ve = head_shard(q), head_shard(ke), head_shard(ve)
            out = chunked_attention(
                q, ke, ve, causal=causal, window=window,
                q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
                unroll=cfg.unroll_attn, kv_len=kv_len,
            )
            out = head_shard(out)
        else:
            q, ke, ve = seq_shard(q), ke, ve
            # single q chunk: q stays sequence-sharded through the whole
            # attention (no per-chunk dynamic-slice resharding)
            out = chunked_attention(
                q, ke, ve, causal=causal, window=window, q_chunk=S,
                kv_chunk=cfg.attn_kv_chunk, unroll=cfg.unroll_attn,
                kv_len=kv_len,
            )
            out = seq_shard(out)
        new_cache = None
        if mode == "prefill":
            assert cache is not None
            S_c = cache.k.shape[1]
            if S_c >= S:
                kpad = jnp.zeros((B, S_c - S) + k.shape[2:], k.dtype)
                new_cache = KVSlice(
                    k=jnp.concatenate([k, kpad], axis=1),
                    v=jnp.concatenate([v, kpad], axis=1),
                    slot_pos=jnp.where(
                        jnp.arange(S_c)[None] < S,
                        jnp.arange(S_c)[None],
                        -1,
                    ) * jnp.ones((B, 1), jnp.int32),
                )
            else:
                # rolling (SWA) cache: keep the last S_c positions
                new_cache = KVSlice(
                    k=k[:, -S_c:], v=v[:, -S_c:],
                    slot_pos=(jnp.arange(S - S_c, S)[None]
                              * jnp.ones((B, 1), jnp.int32)),
                )
    elif mode == "extend":
        # Suffix continuation for paged prefix sharing: S new positions
        # appended at per-row offsets ``pos`` behind a prefix already
        # resident in the cache.  Requires an absolute-position cache
        # layout (no rolling SWA buffer — the KVPool gate guarantees it:
        # window is None or >= the cache length, so slot i holds
        # position i).  Writes beyond a row's true suffix are later
        # overwritten by decode before its position becomes attendable,
        # so no extra validity mask is needed (see serve/kvpool.py).
        assert cache is not None and pos is not None
        positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        q, k, v = qkv_project(p, x, cfg, positions)
        if isinstance(cache, PagedKVCache):
            # Native paged suffix extension: write straight into the
            # arena's physical pages, attend via the block table.
            new_cache = _paged_write_extend(cache, k, v, positions)
            if paged_kernels():
                from repro.kernels.flash_attention.ops import (
                    paged_extend_attention,
                )
                out = paged_extend_attention(
                    q, new_cache.k, new_cache.v, new_cache.block_table, pos,
                    new_cache.layer,
                    k_scale=new_cache.k_scale, v_scale=new_cache.v_scale,
                )
            else:
                k_d, v_d, sp_d = paged_gather(new_cache)
                out = extend_attention_ref(q, k_d, v_d, sp_d, positions,
                                           window=window)
        else:
            bidx = jnp.arange(B)[:, None]
            k_c = cache.k.at[bidx, positions].set(k, mode="drop")
            v_c = cache.v.at[bidx, positions].set(v, mode="drop")
            sp = cache.slot_pos.at[bidx, positions].set(positions, mode="drop")
            out = extend_attention_ref(q, k_c, v_c, sp, positions, window=window)
            new_cache = KVSlice(k=k_c, v=v_c, slot_pos=sp)
    elif mode == "decode":
        assert cache is not None and pos is not None
        positions = pos[:, None]                              # (B,1)
        q, k, v = qkv_project(p, x, cfg, positions)           # S == 1
        if isinstance(cache, PagedKVCache):
            # Native paged decode: one token per row written to its
            # physical page, attention walks the block table (no dense
            # gather/scatter around the step).  Sharded decode does not
            # apply — the arena is replicated, rows are block-table rows.
            new_cache = _paged_write_decode(cache, k[:, 0], v[:, 0], pos)
            if paged_kernels():
                from repro.kernels.decode_attention.ops import (
                    paged_decode_attention,
                )
                out = paged_decode_attention(
                    q, new_cache.k, new_cache.v, new_cache.block_table,
                    pos + 1, new_cache.layer,
                    k_scale=new_cache.k_scale, v_scale=new_cache.v_scale,
                )
            else:
                k_d, v_d, sp_d = paged_gather(new_cache)
                out = decode_attention_ref(
                    q, k_d, v_d, pos + 1, window=window, slot_pos=sp_d
                )
            y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
            return y, new_cache
        S_c = cache.k.shape[1]
        use_sharded = (
            cfg.sharded_decode and ctx is not None and cfg.decode_kv_shard_seq
            # batch must shard over the data axes, else the manual path
            # replicates per-rank work that pjit-auto handles better (B=1
            # long-context cells)
            and B % max(ctx.dp_size(), 1) == 0
        )
        if use_sharded:
            from repro.models.sharded_decode import sharded_decode_attention
            try:
                out, new_cache = sharded_decode_attention(
                    ctx, q, cache, k, v, pos, window=window
                )
            except ValueError:       # cache seq not actually sharded
                use_sharded = False
        if not use_sharded:
            if window is not None and S_c <= window:
                slot = (pos % S_c)                            # rolling buffer
            else:
                slot = jnp.minimum(pos, S_c - 1)
            bidx = jnp.arange(B)
            k_c = cache.k.at[bidx, slot].set(k[:, 0])
            v_c = cache.v.at[bidx, slot].set(v[:, 0])
            sp = cache.slot_pos.at[bidx, slot].set(pos)
            kv_len = pos + 1
            out = decode_attention_ref(
                q, k_c, v_c, kv_len, window=window, slot_pos=sp
            )
            new_cache = KVSlice(k=k_c, v=v_c, slot_pos=sp)
    else:
        raise ValueError(mode)

    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


def kv_slice_specs(cfg: ArchConfig, batch: int, max_len: int) -> KVSlice:
    """PSpec tree for one layer's KV cache slice.

    The cache sequence dim carries the ``kv_seq`` logical axis (sharded over
    data/model per the rules — distributed decode), or ``kv_heads`` when
    ``cfg.decode_kv_shard_seq`` is off.
    """
    S_c = max_len if cfg.sliding_window is None else min(max_len, cfg.sliding_window)
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    if cfg.decode_kv_shard_seq:
        axes = ("batch", "kv_seq", None, None)
    else:
        axes = ("batch", None, "kv_heads", None)
    return KVSlice(
        k=PSpec((batch, S_c, hkv, dh), axes, ("const", 0.0)),
        v=PSpec((batch, S_c, hkv, dh), axes, ("const", 0.0)),
        slot_pos=PSpec((batch, S_c), ("batch", axes[1] if axes[1] == "kv_seq" else None),
                       ("const", -1), dtype="int32"),
    )


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------
def mlp_specs(cfg: ArchConfig, d_ff: Optional[int] = None, d_in: Optional[int] = None) -> dict:
    d, f = d_in or cfg.d_model, d_ff or cfg.d_ff
    if cfg.gated_mlp:
        return {
            "w_gate": PSpec((d, f), ("embed", "ffn"), ("normal", 0)),
            "w_up": PSpec((d, f), ("embed", "ffn"), ("normal", 0)),
            "w_down": PSpec((f, d), ("ffn", "embed"), ("normal", 0)),
        }
    return {
        "w_up": PSpec((d, f), ("embed", "ffn"), ("normal", 0)),
        "w_down": PSpec((f, d), ("ffn", "embed"), ("normal", 0)),
    }


def _act(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "sq_relu":
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


def mlp_block(p, x, cfg: ArchConfig):
    act = _act(cfg.act)
    if cfg.gated_mlp:
        h = act(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = act(x @ p["w_up"])
    return h @ p["w_down"]


# --------------------------------------------------------------------------
# embeddings / logits / loss
# --------------------------------------------------------------------------
def pad_vocab(vocab: int, multiple: int) -> int:
    return ((vocab + multiple - 1) // multiple) * multiple


def embed_spec(vocab_padded: int, d: int) -> PSpec:
    return PSpec((vocab_padded, d), ("vocab", "embed"), ("normal", 1))


def out_spec(d: int, vocab_padded: int) -> PSpec:
    return PSpec((d, vocab_padded), ("embed", "vocab"), ("normal", 0))


def logits_fn(x, out_w, real_vocab: int):
    """x: (B,S,D) -> fp32 logits with padded-vocab tail masked."""
    logits = jnp.einsum("bsd,dv->bsv", x, out_w).astype(F32)
    V = logits.shape[-1]
    if V != real_vocab:
        mask = jnp.arange(V) < real_vocab
        logits = jnp.where(mask, logits, NEG_INF)
    return logits


def softmax_xent(logits, labels, z_loss: float = 0.0):
    """fp32 cross entropy; labels (B,S) int32; returns scalar mean.

    The label logit is picked with a one-hot einsum (a vocab-dim reduction)
    rather than ``take_along_axis`` — GSPMD keeps the vocab dimension
    sharded for reductions, while a sharded-dim gather forces a full
    rematerialization of the (B, S, V) logits on every device.
    """
    lse = jax.nn.logsumexp(logits, axis=-1)
    oh = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.bfloat16)
    ll = jnp.einsum("bsv,bsv->bs", logits, oh.astype(logits.dtype))
    loss = (lse - ll).mean()
    if z_loss:
        loss = loss + z_loss * jnp.square(lse).mean()
    return loss
