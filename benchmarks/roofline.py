import os
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Roofline analysis per (arch x shape) on the single-pod production mesh.

``cost_analysis`` on a compiled program counts while-loop bodies ONCE, so a
scanned-layer program under-reports FLOPs by the trip count.  This harness
therefore accounts **compositionally**: each cell is decomposed into its
repeated components (layer bodies, head, optimizer), every component is
lowered+compiled standalone on the production mesh with all internal loops
unrolled (attention scans included), and totals are

    total = sum_over_components(count x per-device cost)

Train layer cost models the remat schedule explicitly: fwd + (fwd + bwd)
(the backward recomputes the forward).  Collective bytes are parsed from
each component's post-SPMD HLO.  Hardware: v5e-class — 197 TF/s bf16,
819 GB/s HBM, ~50 GB/s/link ICI.

Roofline terms (seconds, per step):
    compute    = flops_dev / 197e12
    memory     = bytes_dev / 819e9
    collective = coll_bytes_dev / 50e9
"""
__doc__ = globals().get("__doc__") or ""

import argparse
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig, shapes_for, with_opt_level
from repro.configs.registry import ARCHS, get_arch
from repro.core.accounting import collective_bytes
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh
from repro.models import encdec as encdec_mod
from repro.models import transformer as tfm
from repro.models import zamba2 as zmb
from repro.models.model import build_model
from repro.models.param import abstract_params, is_pspec
from repro.sharding.rules import make_ctx
from repro.train.optimizer import OptConfig, adamw_update, abstract_adam_state
from repro.train.train_step import resolve_microbatch


def _ns(mesh, pspec_tree):
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), pspec_tree,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )


def _cost(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, list):        # older jax returns [dict] per device
        ca = ca[0] if ca else {}
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": float(sum(coll.values())),
    }


def _lower_cost(fn, arg_sds, arg_shardings) -> Dict[str, float]:
    jitted = jax.jit(fn, in_shardings=arg_shardings)
    return _cost(jitted.lower(*arg_sds).compile())


class CellAccountant:
    """Compositional per-device cost accounting for one (arch, shape)."""

    def __init__(self, arch: ArchConfig, shape: ShapeConfig, mesh):
        kv_chunk = 4096 if shape.seq_len >= 32768 else 1024
        self.arch = arch.replace(
            unroll_attn=True,
            attn_q_chunk=kv_chunk,
            attn_kv_chunk=kv_chunk,
        )
        self.shape = shape
        self.mesh = mesh
        zero3_ok = (shape.kind == "train" and arch.train_layout == "zero3"
                    and shape.global_batch % int(mesh.devices.size) == 0)
        self.ctx = make_ctx(
            mesh,
            fsdp=True if shape.kind == "train" else arch.serve_fsdp,
            dp_over_model=zero3_ok,
        )
        self.model = build_model(self.arch, self.ctx)
        self.cfg = self.model.cfg
        self.dp = self.ctx.dp_size()
        self.n_micro = (
            resolve_microbatch(max(arch.microbatch, 1), shape.global_batch, self.dp)
            if shape.kind == "train" else 1
        )
        self.B = shape.global_batch // self.n_micro   # per-microbatch batch
        self.S = shape.seq_len

    # -- shared input makers -------------------------------------------
    def _x_sds(self, B, S):
        return jax.ShapeDtypeStruct((B, S, self.cfg.d_model), self.model.dtype)

    def _x_shard(self, B, S):
        mode = self.cfg.activation_shard
        logical = (
            ("batch", "act_seq", None) if mode == "seq"
            else ("batch", None, "act_embed") if mode == "embed"
            else ("batch", None, None)
        )
        return jax.sharding.NamedSharding(self.mesh, self.ctx.pspec(logical, (B, S, self.cfg.d_model)))

    def _layer_param_sds(self, specs):
        return abstract_params(specs, self.cfg.dtype)

    def _layer_param_shardings(self, specs):
        return _ns(self.mesh, self.ctx.params_pspecs(specs))

    # -- component cost helpers ----------------------------------------
    def _train_component(self, layer_fn, specs, B, S) -> Dict[str, float]:
        """fwd + (fwd+bwd) per the remat schedule."""
        x_sds = self._x_sds(B, S)
        lp_sds = self._layer_param_sds(specs)
        x_sh = self._x_shard(B, S)
        lp_sh = self._layer_param_shardings(specs)

        def fwd(x, lp):
            y, _, aux = layer_fn(x, lp)
            return y

        def train(x, lp):
            y, _, aux = layer_fn(x, lp)
            return y.astype(jnp.float32).sum() + aux

        c_f = _lower_cost(fwd, (x_sds, lp_sds), (x_sh, lp_sh))
        c_g = _lower_cost(
            jax.grad(train, argnums=(0, 1)), (x_sds, lp_sds), (x_sh, lp_sh)
        )
        return {k: c_f[k] + c_g[k] for k in c_f}

    def _fwd_component(self, layer_fn, specs, B, S, extra_sds=(), extra_sh=()) -> Dict[str, float]:
        x_sds = self._x_sds(B, S)
        lp_sds = self._layer_param_sds(specs)
        x_sh = self._x_shard(B, S)
        lp_sh = self._layer_param_shardings(specs)

        def fwd(x, lp, *extra):
            y, _, _ = layer_fn(x, lp, *extra)
            return y

        return _lower_cost(fwd, (x_sds, lp_sds) + tuple(extra_sds),
                           (x_sh, lp_sh) + tuple(extra_sh))

    # -- family decomposition ------------------------------------------
    def _components(self):
        """[(name, layer_fn, specs, count, decode_cache_kind)] per family."""
        cfg, ctx = self.cfg, self.ctx
        fam = cfg.family
        out = []
        if fam in ("dense", "vlm"):
            out.append(("dense", tfm.dense_layer_specs(cfg), cfg.num_layers, "kv"))
        elif fam == "moe":
            fd = cfg.moe.first_dense_layers
            if fd:
                out.append(("dense", tfm.dense_layer_specs(cfg, d_ff=cfg.moe.dense_d_ff), fd, "kv"))
            out.append(("moe", tfm.moe_layer_specs(cfg, ctx), cfg.num_layers - fd, "kv"))
        elif fam == "ssm":
            out.append(("mamba", zmb.mamba_layer_specs(cfg), cfg.num_layers, "mamba"))
        elif fam == "hybrid":
            out.append(("mamba", zmb.mamba_layer_specs(cfg), cfg.num_layers, "mamba"))
            out.append(("shared", zmb.shared_block_specs(cfg),
                        cfg.num_layers // cfg.hybrid_attn_every, "kv"))
        elif fam == "encdec":
            out.append(("enc", encdec_mod.enc_layer_specs(cfg), cfg.encoder_layers, None))
            out.append(("dec", encdec_mod.dec_layer_specs(cfg), cfg.num_layers, "dec"))
        return out

    def _layer_fn(self, name, mode, cache_sds=None, pos=None, memory_sds=None):
        cfg, ctx = self.cfg, self.ctx
        if name == "dense":
            return lambda x, lp, *e: tfm.dense_layer(
                lp, x, cfg, ctx, mode=mode,
                cache=e[0] if e else None, pos=e[1] if len(e) > 1 else None)
        if name == "moe":
            return lambda x, lp, *e: tfm.moe_layer(
                lp, x, cfg, ctx, mode=mode,
                cache=e[0] if e else None, pos=e[1] if len(e) > 1 else None)
        if name == "mamba":
            return lambda x, lp, *e: zmb.mamba_layer(
                lp, x, cfg, mode=mode, state=e[0] if e else None)
        if name == "shared":
            def f(x, lp, *e):
                y, nc = zmb.shared_block(
                    lp, x, x, cfg, ctx, mode=mode,
                    cache=e[0] if e else None, pos=e[1] if len(e) > 1 else None)
                return y, nc, jnp.float32(0.0)
            return f
        if name == "enc":
            return lambda x, lp, *e: encdec_mod.enc_layer(lp, x, cfg, ctx)
        if name == "dec":
            return lambda x, lp, *e: encdec_mod.dec_layer(
                lp, x, cfg, ctx, mode=mode,
                memory=e[0] if (e and mode == "train") else None,
                cache=e[0] if (e and mode != "train") else None,
                pos=e[1] if len(e) > 1 else None)
        raise ValueError(name)

    def _cache_slice_specs(self, kind, B, S):
        from repro.models.layers import kv_slice_specs
        if kind == "kv":
            return kv_slice_specs(self.cfg, B, S)
        if kind == "mamba":
            return self.model._mamba_state_specs(B)
        if kind == "dec":
            s_src = self.model.source_len(S)
            hkv, dh = self.cfg.num_kv_heads, self.cfg.resolved_head_dim
            from repro.models.param import PSpec
            return encdec_mod.DecCache(
                self_kv=kv_slice_specs(self.cfg, B, S),
                cross_k=PSpec((B, s_src, hkv, dh), ("batch", "kv_seq", None, None), ("const", 0.0)),
                cross_v=PSpec((B, s_src, hkv, dh), ("batch", "kv_seq", None, None), ("const", 0.0)),
            )
        raise ValueError(kind)

    # -- head & optimizer ------------------------------------------------
    def _head_cost(self, mode: str) -> Dict[str, float]:
        model, cfg = self.model, self.cfg
        B = self.B
        S = self.S if mode == "train" else (self.S if mode == "prefill" else 1)
        tok_sds = jax.ShapeDtypeStruct((B, S), jnp.int32)
        tok_sh = jax.sharding.NamedSharding(self.mesh, self.ctx.pspec(("batch", None), (B, S)))
        p_specs = {"embed": model.param_specs()["embed"],
                   "final_norm": model.param_specs()["final_norm"]}
        if "out" in model.param_specs():
            p_specs["out"] = model.param_specs()["out"]
        p_sds = abstract_params(p_specs, cfg.dtype)
        p_sh = _ns(self.mesh, self.ctx.params_pspecs(p_specs))

        from repro.models.layers import rms_norm, softmax_xent

        def head_train(p, tokens, labels):
            x = model._embed_tokens(p, tokens)
            x = rms_norm(x, p["final_norm"], cfg.rms_eps)
            logits = model._logits(p, x)
            return softmax_xent(logits, labels)

        def head_fwd(p, tokens):
            x = model._embed_tokens(p, tokens)
            x = rms_norm(x[:, -1:], p["final_norm"], cfg.rms_eps)
            return model._logits(p, x)

        if mode == "train":
            return _lower_cost(
                jax.grad(head_train), (p_sds, tok_sds, tok_sds),
                (p_sh, tok_sh, tok_sh))
        return _lower_cost(head_fwd, (p_sds, tok_sds), (p_sh, tok_sh))

    def _opt_cost(self) -> Dict[str, float]:
        opt_cfg = OptConfig(m_dtype=self.cfg.optimizer_m_dtype)
        params = self.model.abstract_params()
        state = abstract_adam_state(params, opt_cfg)
        grads = params
        p_sh = _ns(self.mesh, self.model.params_pspecs())
        from repro.train.optimizer import adam_state_pspecs
        s_sh = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(self.mesh, s),
            adam_state_pspecs(self.model.params_pspecs()))

        def step(p, g, s):
            np_, ns, _ = adamw_update(p, g, s, opt_cfg)
            return np_, ns

        return _lower_cost(step, (params, grads, state), (p_sh, p_sh, s_sh))

    # -- public -----------------------------------------------------------
    def account(self) -> Dict[str, float]:
        shape = self.shape
        total = {"flops": 0.0, "bytes": 0.0, "coll": 0.0}
        detail = {}

        def add(name, cost, count):
            detail[name] = {"count": count, **cost}
            for k in total:
                total[k] += cost[k] * count

        if shape.kind == "train":
            for name, specs, L, _ck in self._components():
                specs_only = specs
                fn = self._layer_fn(name, "train")
                S = self.S if name != "enc" else self.model.source_len(self.S)
                if name == "dec":
                    s_src = self.model.source_len(self.S)
                    mem_sds = self._x_sds(self.B, s_src)
                    mem_sh = self._x_shard(self.B, s_src)
                    fn2 = self._layer_fn("dec", "train")
                    x_sds = self._x_sds(self.B, self.S)
                    x_sh = self._x_shard(self.B, self.S)
                    lp_sds = self._layer_param_sds(specs_only)
                    lp_sh = self._layer_param_shardings(specs_only)

                    def train(x, lp, mem):
                        y, _, aux = fn2(x, lp, mem)
                        return y.astype(jnp.float32).sum() + aux

                    def fwd(x, lp, mem):
                        return fn2(x, lp, mem)[0]

                    c_f = _lower_cost(fwd, (x_sds, lp_sds, mem_sds), (x_sh, lp_sh, mem_sh))
                    c_g = _lower_cost(jax.grad(train, argnums=(0, 1, 2)),
                                      (x_sds, lp_sds, mem_sds), (x_sh, lp_sh, mem_sh))
                    cost = {k: c_f[k] + c_g[k] for k in c_f}
                else:
                    cost = self._train_component(fn, specs_only, self.B, S)
                add(f"layer:{name}", cost, L * self.n_micro)
            add("head", self._head_cost("train"), self.n_micro)
            add("optimizer", self._opt_cost(), 1)
        else:
            mode = "prefill" if shape.kind == "prefill" else "decode"
            B = shape.global_batch
            S_x = self.S if mode == "prefill" else 1
            for name, specs, L, ck in self._components():
                if name == "enc":
                    if mode == "decode":
                        continue
                    cost = self._fwd_component(
                        self._layer_fn("enc", "train"), specs,
                        B, self.model.source_len(self.S))
                    add("layer:enc", cost, L)
                    continue
                extra_sds, extra_sh = [], []
                if ck is not None:
                    cs = self._cache_slice_specs(ck, B, self.S)
                    extra_sds.append(abstract_params(cs, self.cfg.dtype))
                    extra_sh.append(_ns(self.mesh, self.ctx.params_pspecs(cs)))
                    if ck in ("kv", "dec") and mode == "decode":
                        pos_sds = jax.ShapeDtypeStruct((B,), jnp.int32)
                        pos_sh = jax.sharding.NamedSharding(
                            self.mesh, self.ctx.pspec(("batch",), (B,)))
                        extra_sds.append(pos_sds)
                        extra_sh.append(pos_sh)
                elif name == "dec" and mode == "prefill":
                    pass
                if name == "dec" and mode == "prefill":
                    # prefill dec layer consumes memory not cache
                    s_src = self.model.source_len(self.S)
                    extra_sds = [self._x_sds(B, s_src)]
                    extra_sh = [self._x_shard(B, s_src)]
                    fn = lambda x, lp, mem: encdec_mod.dec_layer(
                        lp, x, self.cfg, self.ctx, mode="prefill",
                        memory=mem,
                        cache=None, pos=None)
                    # dec prefill needs a cache arg; give it one
                    cs = self._cache_slice_specs("dec", B, self.S)
                    extra_sds.append(abstract_params(cs, self.cfg.dtype))
                    extra_sh.append(_ns(self.mesh, self.ctx.params_pspecs(cs)))
                    fn = lambda x, lp, mem, cache: encdec_mod.dec_layer(
                        lp, x, self.cfg, self.ctx, mode="prefill",
                        memory=mem, cache=cache, pos=None)
                    cost = self._fwd_component(fn, specs, B, S_x, extra_sds, extra_sh)
                else:
                    if ck == "kv" and mode == "prefill":
                        fn = self._layer_fn(name, "prefill")
                        # prefill consumes (cache,) only
                        extra_sds = extra_sds[:1]
                        extra_sh = extra_sh[:1]
                    else:
                        fn = self._layer_fn(name, mode)
                    cost = self._fwd_component(fn, specs, B, S_x, extra_sds, extra_sh)
                add(f"layer:{name}", cost, L)
            add("head", self._head_cost(mode), 1)

        return {"total": total, "detail": detail,
                "n_micro": self.n_micro}


# ---------------------------------------------------------------------------
# analytic ideal memory traffic (per device per step)
#
# ``bytes accessed`` from a CPU-backend compile systematically overestimates
# TPU HBM traffic: the CPU pipeline fuses less (every elementwise op in a
# norm/rope/softmax chain re-reads its operand) and scatter ops are counted
# as full-tensor read+write.  We therefore report BOTH the HLO-derived bound
# and this analytic lower bound assuming perfect fusion:
#   * params streamed once per pass (fwd, remat-fwd, bwd) + optimizer rw
#   * residual-stream tensors: ~12 reads+writes per layer pass
#   * flash attention streams q/k/v twice, never materializes scores
#   * decode streams the KV cache once and writes one slot
# ---------------------------------------------------------------------------
def ideal_bytes_per_device(arch: ArchConfig, shape: ShapeConfig, model, ctx,
                           n_micro: int) -> float:
    cfg = arch
    n_dev = ctx.mesh.devices.size
    dp = ctx.dp_size()
    msz = max(ctx.model_size(), 1)
    P_all = model.n_params()
    P_dev = P_all * 2 / n_dev                       # bf16 weights, fully sharded
    d, L = cfg.d_model, cfg.num_layers
    B_loc = max(shape.global_batch // max(dp, 1), 1)
    V_loc = model.vocab_padded / msz

    if shape.kind == "train":
        B_mloc = max(B_loc // n_micro, 1)
        A = B_mloc * shape.seq_len * d * 2          # residual bf16 (per dev, seq/embed-sharded dims cancel vs gathers; keep full)
        act = 24 * A * L * n_micro                  # 12 rw fwd + 12 rw bwd
        if cfg.d_ff:
            act += 6 * B_mloc * shape.seq_len * (cfg.d_ff / msz) * 2 * L * n_micro
        weights = 3 * P_dev * n_micro               # fwd + remat fwd + bwd
        opt = P_all * 28 / n_dev                    # g rw f32 + m rw + v rw + p rw
        logits = 4 * B_mloc * shape.seq_len * V_loc * 4 * n_micro
        return weights + act + opt + logits

    if shape.kind == "prefill":
        A = B_loc * shape.seq_len * d * 2
        act = 12 * A * L
        weights = P_dev
        kv_write = 0.0
        if cfg.num_kv_heads:
            s_c = min(shape.seq_len, cfg.sliding_window or shape.seq_len)
            kv_write = (2 * B_loc * s_c * cfg.num_kv_heads
                        * (cfg.resolved_head_dim or 0) * 2 * L / msz)
        return weights + act + kv_write

    # decode: weights once + KV/state streamed once + slot write
    kv = 0.0
    cs = model.cache_specs(shape.global_batch, shape.seq_len)
    kv_total = sum(
        np.prod(s.shape) * (2 if (s.dtype or "bf") != "float32" else 4)
        for s in jax.tree.leaves(cs, is_leaf=is_pspec)
    )
    kv = kv_total / n_dev
    act = 30 * shape.global_batch * d * 2 * L / max(dp, 1)
    return P_dev + kv + act


def paged_decode_bytes_per_device(arch: ArchConfig, shape: ShapeConfig, model,
                                  ctx, page_size: int = 16,
                                  kv_elt: int = 2) -> float | None:
    """Analytic HBM traffic for the native paged decode step.

    The dense decode model above streams the whole ``(B, max_len)`` cache
    allocation; the paged kernel instead walks each row's block-table
    entries and streams KV at **page granularity** — ``ceil(kv_len / P)``
    pages per row per attention layer — plus the int32 block-table row the
    kernel prefetches (it masks by position, so no per-slot metadata is
    read), plus the one slot it writes.  Weights and residual-stream activations match the dense
    model.  Returns ``None`` when the paged pool would not engage (no
    pageable KV: ssm/hybrid state, rolling-SWA slot reuse).  ``kv_elt`` is
    the arena element size — pass 1 for an int8 arena (the per-(page,
    layer) scales are counted separately).
    """
    cfg = arch
    w = cfg.sliding_window
    from repro.serve.kvpool import KVPool
    if (shape.kind != "decode" or not cfg.num_kv_heads
            or KVPool.capability(model, page_size * -(-shape.seq_len // page_size),
                                 page_size) != "paged"):
        return None
    n_dev = ctx.mesh.devices.size
    dp = max(ctx.dp_size(), 1)
    P_dev = model.n_params() * 2 / n_dev
    d, L = cfg.d_model, cfg.num_layers
    B = shape.global_batch
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim or 0
    n_attn = L
    pages = -(-shape.seq_len // page_size)
    kv_read = 2 * B * pages * page_size * hkv * dh * kv_elt * n_attn
    meta = B * pages * 4 * n_attn                    # block-table row
    if kv_elt == 1:
        meta += 2 * B * pages * 4 * n_attn           # k/v per-page scales
    kv_write = 2 * B * hkv * dh * kv_elt * n_attn
    cross = 0.0
    if cfg.family == "encdec":                       # cross memory is dense
        s_src = model.source_len(shape.seq_len)
        cross = 2 * B * s_src * hkv * dh * kv_elt * L
    act = 30 * B * d * 2 * L / dp
    return P_dev + (kv_read + meta + kv_write + cross) / n_dev + act


# ---------------------------------------------------------------------------
# analytic model flops (usefulness ratio)
# ---------------------------------------------------------------------------
def model_flops(arch: ArchConfig, shape: ShapeConfig, model) -> float:
    """6*N_active*T train / 2*N_active*T fwd, + attention context flops."""
    cfg = arch
    n_total = model.n_params()
    n_active = n_total
    if cfg.moe is not None:
        e, k = cfg.moe.num_experts, cfg.moe.top_k
        routed = (cfg.num_layers - cfg.moe.first_dense_layers) * (
            3 * cfg.d_model * cfg.moe.d_expert * e
        )
        n_active = n_total - routed + routed * (k / e)
    T = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6 if shape.kind == "train" else 2
    core = mult * n_active * T

    # attention context term
    dh = cfg.resolved_head_dim or 0
    hq = cfg.num_heads
    if cfg.family == "hybrid":
        n_attn = cfg.num_layers // cfg.hybrid_attn_every
    elif cfg.family == "ssm":
        n_attn = 0
    elif cfg.family == "encdec":
        n_attn = cfg.encoder_layers + 2 * cfg.num_layers
    else:
        n_attn = cfg.num_layers
    if n_attn and hq:
        if shape.kind == "decode":
            s_kv = min(shape.seq_len, cfg.sliding_window or shape.seq_len)
            attn = 4 * hq * dh * s_kv * shape.global_batch * n_attn
        else:
            s_kv = shape.seq_len
            w = cfg.sliding_window
            per_q = (min(w, s_kv) if w else s_kv / 2)
            attn = 4 * hq * dh * per_q * shape.global_batch * shape.seq_len * n_attn
            attn *= (3 if shape.kind == "train" else 1)
    else:
        attn = 0.0
    return core + attn


# ---------------------------------------------------------------------------
def roofline_row(arch_name: str, shape_name: str, dryrun_dir: str = "experiments/dryrun",
                 level: str = "optimized") -> dict:
    arch = with_opt_level(get_arch(arch_name), level == "optimized")
    shape = next(s for s in shapes_for(arch) if s.name == shape_name)
    mesh = make_production_mesh(multi_pod=False)
    n_dev = int(mesh.devices.size)
    acc = CellAccountant(arch, shape, mesh)
    out = acc.account()
    tot = out["total"]

    t_compute = tot["flops"] / PEAK_FLOPS_BF16
    t_memory_hlo = tot["bytes"] / HBM_BW
    ideal_b = ideal_bytes_per_device(arch, shape, acc.model, acc.ctx, out["n_micro"])
    t_memory = ideal_b / HBM_BW
    paged_b = paged_decode_bytes_per_device(arch, shape, acc.model, acc.ctx)
    paged_b_int8 = paged_decode_bytes_per_device(
        arch, shape, acc.model, acc.ctx, kv_elt=1)
    t_coll = tot["coll"] / ICI_BW
    dominant = max(("compute", t_compute), ("memory", t_memory),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]
    mf = model_flops(arch, shape, acc.model)
    mem = None
    p = os.path.join(dryrun_dir, "single", f"{arch_name}__{shape_name}.json")
    if os.path.exists(p):
        with open(p) as f:
            mem = json.load(f)["memory"]["peak_estimate_bytes"]
    row = {
        "arch": arch_name,
        "shape": shape_name,
        "flops_dev": tot["flops"],
        "bytes_dev_hlo": tot["bytes"],
        "bytes_dev_ideal": ideal_b,
        "bytes_dev_paged": paged_b,
        "bytes_dev_paged_int8": paged_b_int8,
        "t_memory_paged_s": paged_b / HBM_BW if paged_b else None,
        "coll_dev": tot["coll"],
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_memory_hlo_s": t_memory_hlo,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops_total": mf,
        "useful_ratio": mf / max(tot["flops"] * n_dev, 1.0),
        "roofline_fraction": t_compute / max(t_compute, t_memory, t_coll),
        "mem_dev_bytes": mem,
        "detail": out["detail"],
        "n_micro": out["n_micro"],
    }
    return row


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--out", default="experiments/roofline")
    p.add_argument("--level", default="baseline", choices=["baseline", "optimized"])
    args = p.parse_args(argv)
    archs = [args.arch] if args.arch else sorted(ARCHS)
    os.makedirs(args.out, exist_ok=True)
    for a in archs:
        for s in shapes_for(get_arch(a)):
            if args.shape and s.name != args.shape:
                continue
            try:
                row = roofline_row(a, s.name, level=args.level)
            except Exception as e:
                import traceback; traceback.print_exc()
                print(f"[roofline] {a} {s.name} FAILED: {e}")
                continue
            path = os.path.join(args.out, f"{a}__{s.name}__{args.level}.json")
            with open(path, "w") as f:
                json.dump(row, f, indent=1)
            paged = (
                f" Mp={row['t_memory_paged_s']*1e3:9.2f}ms"
                if row.get("t_memory_paged_s") else ""
            )
            print(
                f"[roofline] {a:24s} {s.name:12s} "
                f"C={row['t_compute_s']*1e3:9.2f}ms M={row['t_memory_s']*1e3:9.2f}ms "
                f"(hlo {row['t_memory_hlo_s']*1e3:9.2f}ms) "
                f"X={row['t_collective_s']*1e3:9.2f}ms dom={row['dominant']:10s} "
                f"frac={row['roofline_fraction']:.3f} useful={row['useful_ratio']:.2f}"
                f"{paged}"
            )


if __name__ == "__main__":
    main()
