"""Flash-decode kernels: dense (slot-indexed) and native paged variants.

The paged op consumes the serving block table directly — ``q (B, 1, Hq,
Dh)`` against a ``(num_pages, L, Hkv, page_size, Dh)`` arena, a ``(B,
n_logical)`` int32 block table (entries ``>= num_pages`` are unmapped
sentinels), per-row ``kv_len`` and a scalar ``layer`` index — so no
contiguous per-slot KV copy is ever materialized.  Optional ``k_scale``/
``v_scale (num_pages, L)`` enable int8 arenas with in-kernel dequant.
``*_ref`` are pure-jnp oracles used for interpret-mode parity tests and
as the bit-identical CPU fallback math.
"""
from repro.kernels.decode_attention.ops import (  # noqa: F401
    decode_attention,
    lse_combine,
    paged_decode_attention,
)
from repro.kernels.decode_attention.ref import (  # noqa: F401
    decode_attention_ref,
    paged_decode_attention_ref,
)
