"""Wrappers for the flash-decode kernels + distributed LSE combine."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_bhd,
    paged_decode_attention_bhd,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k_cache, v_cache, kv_len, *, block_k: int = 512):
    """q: (B, 1, Hq, Dh); caches (B, S, Hkv, Dh); kv_len (B,)."""
    out = decode_attention_bhd(
        q[:, 0], k_cache, v_cache, kv_len.astype(jnp.int32),
        block_k=block_k, interpret=not _on_tpu(),
    )
    return out[:, None]


def paged_decode_attention(q, k_arena, v_arena, block_table, kv_len, layer,
                           *, k_scale=None, v_scale=None):
    """q: (B, 1, Hq, Dh) vs a paged arena (see ``paged_decode_attention_bhd``).

    Unjitted on purpose — traced inside the caller's (model) jit so the
    arena is never copied across a jit boundary per layer.
    """
    out = paged_decode_attention_bhd(
        q[:, 0], k_arena, v_arena, block_table, kv_len.astype(jnp.int32),
        layer,
        k_scale=k_scale, v_scale=v_scale, interpret=not _on_tpu(),
    )
    return out[:, None]


def lse_combine(ms, ls, accs):
    """Merge per-split softmax partials (flash-decode split-KV combine).

    ms/ls: (n_split, ...), accs: (n_split, ..., Dh).  Used to merge kernel
    partials across sequence-sharded KV (the SP decode path).
    """
    m = jnp.max(ms, axis=0)
    w = jnp.exp(ms - m[None])
    l = jnp.sum(ls * w, axis=0)
    acc = jnp.sum(accs * w[..., None], axis=0)
    return acc / jnp.maximum(l, 1e-30)[..., None]
