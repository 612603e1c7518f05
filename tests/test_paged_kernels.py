"""Parity tests for the native paged attention kernels (interpret mode).

The fixtures honour the absolute-position page layout the kernels rely
on: logical page ``j`` of a row holds positions ``[j*P, (j+1)*P)`` in a
``(N, L, Hkv, P, Dh)`` arena, the block table maps logical pages to
physical arena pages, and unmapped entries are the sentinel (``>= N``).
Slots past a row's length hold random data, so the position mask is
what keeps them out.  Model-level token identity for moe / encdec-cross layouts is
covered by ``tests/test_kvpool.py``; this file checks the kernels
directly against their pure-jnp refs and a dense oracle, across head
layouts (MHA / GQA / MQA), multi-layer arenas, ragged lengths, page
sizes that do not divide the sequence length, sentinel pages, and int8
per-(page, layer) scales.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import (
    decode_attention_ref,
    paged_decode_attention,
    paged_decode_attention_ref,
)
from repro.kernels.flash_attention import (
    attention_ref,
    paged_extend_attention,
    paged_extend_attention_ref,
)
from repro.models.cache_utils import dequantize_page, quantize_page


def _rel(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))


def _build_arena(key, B, Hkv, Dh, L, P, n_log, kv_lens):
    """Layout-consistent arena: page j of row b holds positions
    [j*P, min((j+1)*P, kv_len)); pages are mapped in a shuffled order so
    physical and logical page ids differ."""
    N = B * n_log + 2
    kk, vk, pk = jax.random.split(key, 3)
    k = jax.random.normal(kk, (N, L, Hkv, P, Dh), jnp.float32)
    v = jax.random.normal(vk, (N, L, Hkv, P, Dh), jnp.float32)
    phys = np.asarray(jax.random.permutation(pk, N))
    bt = np.full((B, n_log), N, np.int32)
    nxt = 0
    for b, kl in enumerate(kv_lens):
        for j in range(-(-kl // P)):
            bt[b, j] = phys[nxt]
            nxt += 1
    return k, v, jnp.asarray(bt)


def _dense_view(k_arena, v_arena, bt, li):
    """Gather (B, n_log*P, Hkv, Dh) dense caches; by the absolute-position
    layout, slot index == position, so kv_len masking is exact."""
    N = k_arena.shape[0]
    btc = jnp.minimum(bt, N - 1)

    def dense(a):
        x = a[:, li][btc]                              # (B, n_log, Hkv, P, Dh)
        x = x.transpose(0, 1, 3, 2, 4)
        return x.reshape(x.shape[0], -1, *x.shape[3:])
    return dense(k_arena), dense(v_arena)


DECODE_CASES = [
    # (B, Hq, Hkv, Dh, L, P, n_log, kv_lens)
    (2, 4, 4, 64, 1, 8, 4, (32, 17)),   # MHA, ragged, P does not divide len
    (3, 8, 2, 32, 3, 8, 4, (8, 29, 1)),  # GQA, multi-layer, sentinel tails
    (2, 4, 1, 16, 2, 16, 2, (5, 32)),   # MQA
]


@pytest.mark.parametrize("B,Hq,Hkv,Dh,L,P,n_log,kv_lens", DECODE_CASES)
def test_paged_decode_matches_ref(B, Hq, Hkv, Dh, L, P, n_log, kv_lens):
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    k, v, bt = _build_arena(keys[0], B, Hkv, Dh, L, P, n_log, kv_lens)
    q = jax.random.normal(keys[1], (B, 1, Hq, Dh), jnp.float32)
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    li = jnp.int32(L - 1)
    out = paged_decode_attention(q, k, v, bt, kv_len, li)
    ref = paged_decode_attention_ref(q[:, 0], k, v, bt, kv_len, li)
    assert _rel(out[:, 0], ref) < 2e-5
    # dense oracle: gather the block table into a slot-indexed cache
    kd, vd = _dense_view(k, v, bt, L - 1)
    dense = decode_attention_ref(q[:, 0], kd, vd, kv_len)
    assert _rel(ref, dense) < 1e-5


@pytest.mark.parametrize("B,Hq,Hkv,Dh,L,P,n_log,kv_lens", DECODE_CASES)
def test_paged_decode_int8_matches_ref(B, Hq, Hkv, Dh, L, P, n_log, kv_lens):
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    k, v, bt = _build_arena(keys[0], B, Hkv, Dh, L, P, n_log, kv_lens)
    kq, ks = quantize_page(k, keep_axes=(0, 1))
    vq, vs = quantize_page(v, keep_axes=(0, 1))
    q = jax.random.normal(keys[1], (B, 1, Hq, Dh), jnp.float32)
    kv_len = jnp.asarray(kv_lens, jnp.int32)
    li = jnp.int32(L - 1)
    out = paged_decode_attention(q, kq, vq, bt, kv_len, li,
                                 k_scale=ks, v_scale=vs)
    ref = paged_decode_attention_ref(q[:, 0], kq, vq, bt, kv_len, li,
                                     k_scale=ks, v_scale=vs)
    assert _rel(out[:, 0], ref) < 2e-4
    # dequantized attention stays close to the float arena's answer
    flt = paged_decode_attention_ref(q[:, 0], k, v, bt, kv_len, li)
    assert _rel(ref, flt) < 0.15


def test_paged_decode_fully_sentinel_row_is_finite():
    # A freed / width-trimmed slot maps nothing; its (discarded) output
    # must still be finite so it cannot poison the batch.
    k, v, bt = _build_arena(jax.random.PRNGKey(2), 2, 2, 16, 1, 8, 2,
                                (16, 16))
    bt = bt.at[1].set(jnp.full((2,), k.shape[0], jnp.int32))
    q = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 2, 16), jnp.float32)
    out = paged_decode_attention(q, k, v, bt,
                                 jnp.asarray([16, 1], jnp.int32), jnp.int32(0))
    assert bool(jnp.all(jnp.isfinite(out)))
    assert _rel(out[0], paged_decode_attention_ref(
        q[:, 0], k, v, bt, jnp.asarray([16, 1], jnp.int32),
        jnp.int32(0))[0]) < 2e-5


EXTEND_CASES = [
    # (B, Hq, Hkv, Dh, L, P, n_log, S, pos)
    (2, 4, 4, 32, 1, 8, 4, 8, (0, 16)),   # MHA, page-aligned offsets
    (2, 8, 2, 32, 2, 8, 4, 4, (5, 13)),   # GQA, pos off page boundaries
    (1, 4, 1, 16, 2, 16, 2, 12, (7,)),    # MQA, P does not divide pos+S
]


@pytest.mark.parametrize("B,Hq,Hkv,Dh,L,P,n_log,S,pos", EXTEND_CASES)
def test_paged_extend_matches_ref(B, Hq, Hkv, Dh, L, P, n_log, S, pos):
    # Extend attends after its own suffix is written, so the arena holds
    # positions [0, pos+S) per row.
    kv_lens = tuple(p + S for p in pos)
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    k, v, bt = _build_arena(keys[0], B, Hkv, Dh, L, P, n_log, kv_lens)
    q = jax.random.normal(keys[1], (B, S, Hq, Dh), jnp.float32)
    pos_a = jnp.asarray(pos, jnp.int32)
    li = jnp.int32(L - 1)
    out = paged_extend_attention(q, k, v, bt, pos_a, li)
    ref = paged_extend_attention_ref(q.transpose(0, 2, 1, 3), k, v, bt,
                                     pos_a, li)
    assert _rel(out, ref.transpose(0, 2, 1, 3)) < 2e-5
    # dense causal oracle per row (suffix queries against [0, pos+S))
    kd, vd = _dense_view(k, v, bt, L - 1)
    for b in range(B):
        kl = kv_lens[b]
        dense = attention_ref(
            q[b:b + 1].transpose(0, 2, 1, 3),
            kd[b:b + 1, :kl].transpose(0, 2, 1, 3),
            vd[b:b + 1, :kl].transpose(0, 2, 1, 3), causal=True)
        assert _rel(out[b], dense[0].transpose(1, 0, 2)) < 2e-5


@pytest.mark.parametrize("B,Hq,Hkv,Dh,L,P,n_log,S,pos", EXTEND_CASES)
def test_paged_extend_int8_matches_ref(B, Hq, Hkv, Dh, L, P, n_log, S, pos):
    kv_lens = tuple(p + S for p in pos)
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    k, v, bt = _build_arena(keys[0], B, Hkv, Dh, L, P, n_log, kv_lens)
    kq, ks = quantize_page(k, keep_axes=(0, 1))
    vq, vs = quantize_page(v, keep_axes=(0, 1))
    q = jax.random.normal(keys[1], (B, S, Hq, Dh), jnp.float32)
    pos_a = jnp.asarray(pos, jnp.int32)
    li = jnp.int32(L - 1)
    out = paged_extend_attention(q, kq, vq, bt, pos_a, li,
                                 k_scale=ks, v_scale=vs)
    ref = paged_extend_attention_ref(q.transpose(0, 2, 1, 3), kq, vq, bt,
                                     pos_a, li, k_scale=ks, v_scale=vs)
    assert _rel(out, ref.transpose(0, 2, 1, 3)) < 2e-4


def test_quantize_page_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(6), (6, 3, 2, 8, 16))
    x = x * jnp.arange(1, 7, dtype=jnp.float32).reshape(6, 1, 1, 1, 1)
    q, s = quantize_page(x, keep_axes=(0, 1))
    assert q.dtype == jnp.int8 and s.shape == (6, 3)
    deq = dequantize_page(q, s, keep_axes=(0, 1))
    # rounding error per element is bounded by half a quantization step
    amax = jnp.max(jnp.abs(x), axis=(2, 3, 4))
    bound = (amax / 127.0).reshape(6, 3, 1, 1, 1) * 0.5 + 1e-6
    assert bool(jnp.all(jnp.abs(deq - x) <= bound))


def test_quantize_page_zero_group():
    x = jnp.zeros((2, 1, 1, 4, 8))
    q, s = quantize_page(x, keep_axes=(0, 1))
    assert bool(jnp.all(s == 0))
    assert bool(jnp.all(dequantize_page(q, s, keep_axes=(0, 1)) == 0))
