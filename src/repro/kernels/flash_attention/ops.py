"""Public wrappers for the flash-attention kernels.

Accepts the model's (B, S, H, Dh) layout, dispatches to the Pallas kernel
(interpret=True on CPU — the kernel body executes for correctness; real
Mosaic lowering on TPU).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax

from repro.kernels.flash_attention.flash_attention import (
    flash_attention_bhsd,
    paged_extend_attention_bhsd,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k")
)
def flash_attention(
    q, k, v, *, causal: bool = True, window: Optional[int] = None,
    block_q: int = 512, block_k: int = 512,
):
    """q: (B, Sq, Hq, Dh); k/v: (B, Skv, Hkv, Dh) -> (B, Sq, Hq, Dh)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_bhsd(
        qt, kt, vt, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=not _on_tpu(),
    )
    return out.transpose(0, 2, 1, 3)


def paged_extend_attention(q, k_arena, v_arena, block_table, pos, layer,
                           *, k_scale=None, v_scale=None, block_q: int = 128):
    """q: (B, S, Hq, Dh) vs a paged arena (see ``paged_extend_attention_bhsd``).

    Unjitted on purpose — traced inside the caller's (model) jit so the
    arena is never copied across a jit boundary per layer.  ``block_q``
    snaps to a divisor of S so any bucketed suffix length tiles cleanly.
    """
    S = q.shape[1]
    bq = S if S <= block_q else math.gcd(S, block_q)
    out = paged_extend_attention_bhsd(
        q.transpose(0, 2, 1, 3), k_arena, v_arena, block_table, pos, layer,
        k_scale=k_scale, v_scale=v_scale, block_q=bq, interpret=not _on_tpu(),
    )
    return out.transpose(0, 2, 1, 3)
