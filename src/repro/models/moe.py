"""Mixture-of-experts FFN block.

Fully-manual ``jax.shard_map`` implementation so the parallel layout is
explicit and differentiable:

* **EP** (expert parallelism): when ``E % model_axis == 0`` each model rank
  owns ``E_local`` experts; activations are replicated over the model axis,
  each rank dispatches only tokens routed to its experts, and the final
  ``psum`` over the model axis sums disjoint expert contributions
  (DeepSeekMoE: 64 experts over 16 ranks).
* **TP-in-expert**: otherwise every rank holds all experts with the ffn dim
  sharded; the same ``psum`` combines partial products (Mixtral: 8 experts).
* **FSDP**: expert weights are additionally sharded over the data axis and
  explicitly ``all_gather``-ed before use; AD transposes that into the ZeRO
  gradient reduce-scatter.

Dispatch is scatter-based (capacity-bounded, GShard-style slots computed
with a cumsum over one-hots) — no O(T·E·C·D) dispatch einsum.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.param import PSpec
from repro.sharding.rules import ShardCtx

F32 = jnp.float32


def use_ep(cfg: ArchConfig, ctx: ShardCtx) -> bool:
    return cfg.moe.num_experts % max(ctx.model_size(), 1) == 0


def moe_specs(cfg: ArchConfig, ctx: ShardCtx) -> dict:
    m = cfg.moe
    d, fe = cfg.d_model, m.d_expert
    e = m.num_experts
    if use_ep(cfg, ctx):
        w_axes = {
            "w_gate": ("expert", "embed", None),
            "w_up": ("expert", "embed", None),
            "w_down": ("expert", None, "embed"),
        }
    else:
        w_axes = {
            "w_gate": (None, "embed", "expert_ffn"),
            "w_up": (None, "embed", "expert_ffn"),
            "w_down": (None, "expert_ffn", "embed"),
        }
    specs = {
        "router": PSpec((d, e), (None, None), ("normal", 0), dtype="float32"),
        "w_gate": PSpec((e, d, fe), w_axes["w_gate"], ("normal", 1)),
        "w_up": PSpec((e, d, fe), w_axes["w_up"], ("normal", 1)),
        "w_down": PSpec((e, fe, d), w_axes["w_down"], ("normal", 1)),
    }
    if m.num_shared:
        fs = m.num_shared * m.d_shared
        specs["ws_gate"] = PSpec((d, fs), ("embed", "ffn"), ("normal", 0))
        specs["ws_up"] = PSpec((d, fs), ("embed", "ffn"), ("normal", 0))
        specs["ws_down"] = PSpec((fs, d), ("ffn", "embed"), ("normal", 0))
    return specs


def _capacity(cfg: ArchConfig, t_local: int, train: bool) -> int:
    m = cfg.moe
    if not train and t_local <= 64:
        # decode / tiny prefill shards: dropless (worst case: every token
        # routes one of its k choices to the same expert).
        return t_local
    cf = m.capacity_factor if train else max(m.capacity_factor, 2.0)
    c = int(math.ceil(m.top_k * t_local * cf / m.num_experts))
    return max(min(c, t_local), 1)


def _moe_local(xf, router, w_gate, w_up, w_down, *, cfg: ArchConfig,
               ctx: ShardCtx, train: bool):
    """Per-shard MoE body (runs under fully-manual shard_map).

    xf: (T_local, D) tokens, replicated over the model axis.
    EP:  w_*: (E_local, D_local, Fe)  ->  all_gather(data) -> (E_local, D, Fe)
    TP:  w_*: (E, D_local, Fe_local)  ->  all_gather(data) -> (E, D, Fe_local)
    """
    m = cfg.moe
    ep = use_ep(cfg, ctx)
    model_ax = ctx.model_axis
    T, D = xf.shape
    E, K = m.num_experts, m.top_k

    # ---- FSDP gather of expert weights (transpose = grad reduce-scatter).
    # Weights may be sharded over ("pod","data") on the embed dim; gather
    # minor-to-major so tiles reassemble in order.
    if ctx.fsdp:
        for ax in ("data", "pod"):
            if ctx.axis_sizes.get(ax, 1) > 1 and w_gate.shape[1] < D:
                w_gate = jax.lax.all_gather(w_gate, ax, axis=1, tiled=True)
                w_up = jax.lax.all_gather(w_up, ax, axis=1, tiled=True)
                w_down = jax.lax.all_gather(w_down, ax, axis=2, tiled=True)

    # ---- routing (fp32)
    logits = xf.astype(F32) @ router.astype(F32)              # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, K)                      # (T, K)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * sum_e f_e * P_e.  f_e and P_e are
    # *global* means — average them across data shards BEFORE the product
    # (the product of local means is not linear in the sharding).
    oh_full = jax.nn.one_hot(topi, E, dtype=F32).sum(1)       # (T, E)
    f_e = jax.lax.pmean(oh_full.mean(0), ctx.batch_axes)
    p_e = jax.lax.pmean(probs.mean(0), ctx.batch_axes)
    aux = E * jnp.sum(f_e * p_e)

    # ---- capacity slots
    C = _capacity(cfg, T, train)
    flat_e = topi.reshape(-1)                                 # (T*K,)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    slot = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1          # slot within expert
    keep = slot < C
    tok = jnp.repeat(jnp.arange(T), K)
    gate = jnp.where(keep, topv.reshape(-1), 0.0)

    # ---- EP filter: this rank owns experts [r*E_local, (r+1)*E_local)
    if ep and model_ax is not None:
        e_local_n = E // ctx.model_size()
        r = jax.lax.axis_index(model_ax)
        mine = (flat_e // e_local_n) == r
        keep = keep & mine
        local_e = jnp.clip(flat_e - r * e_local_n, 0, e_local_n - 1)
    else:
        e_local_n = E
        local_e = flat_e

    safe_slot = jnp.where(keep, slot, C - 1)
    contrib = jnp.where(keep[:, None], xf[tok], 0).astype(xf.dtype)
    buf = jnp.zeros((e_local_n, C, D), xf.dtype)
    buf = buf.at[local_e, safe_slot].add(contrib, mode="drop")

    # ---- expert FFN (SwiGLU)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_up
    )
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_down)           # (E_local, C, D)

    # ---- combine.  The cross-rank sum runs as a bf16 REDUCE-SCATTER over
    # the embed dim: half the ring traffic of an all-reduce, and the output
    # lands embed-sharded — exactly the residual-stream layout, so no
    # downstream reshard.
    gathered = out_buf[local_e, safe_slot] * jnp.where(keep, gate, 0.0)[:, None].astype(xf.dtype)
    y = jax.ops.segment_sum(gathered, tok, num_segments=T)
    if model_ax is not None:
        msz = ctx.model_size()
        if msz > 1 and D % msz == 0:
            y = jax.lax.psum_scatter(
                y.astype(xf.dtype), model_ax, scatter_dimension=1, tiled=True
            )
        else:
            y = jax.lax.psum(y, model_ax)
    return y.astype(xf.dtype), aux


def moe_block(p, x, cfg: ArchConfig, ctx: ShardCtx, *, train: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) global.  Returns (y, aux_loss scalar)."""
    B, S, D = x.shape
    # divisibility-aware: decode with tiny batches replicates tokens over
    # the data axes (each instance computes identically; psum over the
    # model axis still combines expert/ffn shards correctly)
    batch_spec = ctx.pspec(("batch", None), (B * S, D))

    def wrapped(xf, router, w_gate, w_up, w_down):
        return _moe_local(
            xf, router, w_gate, w_up, w_down, cfg=cfg, ctx=ctx, train=train
        )

    wspec = lambda name, shape: ctx.pspec(moe_specs(cfg, ctx)[name].logical, shape)
    msz = ctx.model_size()
    scattered = msz > 1 and D % msz == 0 and ctx.model_axis is not None
    y_spec = (
        P(batch_spec[0], ctx.model_axis) if scattered
        else P(batch_spec[0], None)
    )
    fn = jax.shard_map(
        wrapped,
        mesh=ctx.mesh,
        in_specs=(
            batch_spec,
            P(None, None),
            wspec("w_gate", p["w_gate"].shape),
            wspec("w_up", p["w_up"].shape),
            wspec("w_down", p["w_down"].shape),
        ),
        out_specs=(y_spec, P()),
        axis_names=ctx.manual_axes,
        check_vma=False,
    )
    xf = x.reshape(B * S, D)
    y, aux = fn(xf, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    y = y.reshape(B, S, D)

    # shared experts (dense, pjit-auto part)
    if cfg.moe.num_shared:
        h = jax.nn.silu(x @ p["ws_gate"]) * (x @ p["ws_up"])
        y = y + h @ p["ws_down"]
    return y, aux
