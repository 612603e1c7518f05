"""Mamba-2 SSD per-chunk TPU kernel.

Hardware adaptation (GPU -> TPU): the original SSD kernels use warp-level
scans for the within-chunk cumulative decays.  TPUs have no warp shuffles —
instead the kernel casts *everything* as dense matmuls for the MXU:

  * the within-chunk cumsum of log-decays is a lower-triangular ones
    matmul (``tril @ dA``),
  * the decay matrix L, the (C·Bᵀ ⊙ L) score matrix, the intra-chunk
    output, and the chunk state are all (Q x Q)/(Q x N)/(Q x P) matmuls.

Grid: (Bb, H, nc) — one chunk of one head per step; B/C blocks are indexed
through the head->group map in the BlockSpec index_map (no per-head
materialization of group-shared tensors in HBM).  The inter-chunk
recurrence (tiny: nc states of (P, N)) runs outside in jnp via
``associative_scan``.

Bucket-padded prefill masking is handled entirely by the wrapper
(``ops.ssd``): masked steps are fed in with dA=0 and zero dt-weighted
input, which the matmuls below treat as identity state updates — the
kernel body stays shape-static with no divergent control flow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_chunk_kernel(x_ref, dA_ref, b_ref, c_ref,
                      y_ref, st_ref, dec_ref, *, Q: int):
    x = x_ref[0, 0, 0].astype(jnp.float32)                 # (Q, P)
    dA = dA_ref[0, 0, 0].astype(jnp.float32)               # (Q,)
    Bm = b_ref[0, 0, 0].astype(jnp.float32)                # (Q, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)                # (Q, N)

    # cumsum as a lower-triangular matmul (MXU instead of a scan)
    tril = jnp.tril(jnp.ones((Q, Q), jnp.float32))
    cs = jax.lax.dot_general(
        tril, dA[:, None], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[:, 0]                                                # (Q,)

    diff = cs[:, None] - cs[None, :]
    L = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1),
        jnp.exp(diff), 0.0,
    )
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * L                                                  # (Q, Q)
    y_ref[0, 0, 0] = jax.lax.dot_general(
        scores, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(y_ref.dtype)

    decay_states = jnp.exp(cs[-1] - cs)                    # (Q,)
    xw = x * decay_states[:, None]                         # (Q, P)
    st_ref[0, 0, 0] = jax.lax.dot_general(
        xw, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(st_ref.dtype)                                 # (P, N)
    dec_ref[0, 0, 0] = jnp.exp(cs[-1])


def ssd_chunks(x, dA, B, C, *, interpret: bool = True):
    """x: (Bb,H,nc,Q,P); dA: (Bb,H,nc,Q); B/C: (Bb,G,nc,Q,N).

    Returns (y_diag, states (Bb,H,nc,P,N), decay (Bb,H,nc)).
    """
    Bb, H, nc, Q, P = x.shape
    G, N = B.shape[1], B.shape[4]
    HG = H // G

    kernel = functools.partial(_ssd_chunk_kernel, Q=Q)
    return pl.pallas_call(
        kernel,
        grid=(Bb, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q, N), lambda b, h, c: (b, h // HG, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N), lambda b, h, c: (b, h // HG, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, P, N), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, c: (b, h, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, nc, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((Bb, H, nc, P, N), jnp.float32),
            jax.ShapeDtypeStruct((Bb, H, nc), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
        name="ssd_chunks",
    )(x, dA, B, C)
