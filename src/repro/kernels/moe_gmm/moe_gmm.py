"""Grouped expert GEMM TPU kernel with empty-block skipping.

MegaBlocks adapted to the TPU: instead of CSR block-sparse indexing (a
GPU-friendly gather), the capacity layout (E, C, D) is tiled densely and
the per-expert token count (a tiny scalar operand) gates each (bc x bf)
output tile with ``pl.when`` — tiles past an expert's token count are
skipped entirely (written zero), so compute scales with the *actual*
load per expert rather than the capacity bound.

Grid (E, C/bc, F/bf); the full D ("k") dim is kept resident per tile:
bc*D + D*bf + bc*bf floats must fit VMEM (e.g. 128x4096 tiles = ~2 MiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _gmm_kernel(cnt_ref, x_ref, w_ref, o_ref, *, block_c: int):
    ci = pl.program_id(1)
    count = cnt_ref[0]
    start = ci * block_c

    @pl.when(start < count)
    def _compute():
        x = x_ref[0].astype(jnp.float32)                   # (bc, D)
        w = w_ref[0].astype(jnp.float32)                   # (D, bf)
        acc = jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # zero partially-valid rows in the tail tile
        rows = start + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        acc = jnp.where(rows < count, acc, 0.0)
        o_ref[0] = acc.astype(o_ref.dtype)

    @pl.when(start >= count)
    def _skip():
        o_ref[0] = jnp.zeros_like(o_ref[0])


def gmm(x, w, counts, *, block_c: int = 128, block_f: int = 512,
        interpret: bool = True):
    """x: (E, C, D); w: (E, D, F); counts: (E,) int32 -> (E, C, F)."""
    E, C, D = x.shape
    F = w.shape[2]
    block_c = min(block_c, C)
    block_f = min(block_f, F)
    assert C % block_c == 0 and F % block_f == 0
    kernel = functools.partial(_gmm_kernel, block_c=block_c)
    return pl.pallas_call(
        kernel,
        grid=(E, C // block_c, F // block_f),
        in_specs=[
            pl.BlockSpec((1,), lambda e, c, f: (e,)),
            pl.BlockSpec((1, block_c, D), lambda e, c, f: (e, c, 0)),
            pl.BlockSpec((1, D, block_f), lambda e, c, f: (e, 0, f)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f), lambda e, c, f: (e, c, f)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
        name="moe_gmm",
    )(counts.astype(jnp.int32), x, w)
