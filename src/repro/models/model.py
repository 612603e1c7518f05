"""Model assembly: ArchConfig -> init / loss / prefill / decode programs.

One :class:`Model` per (arch, shard-ctx).  All families share the same
public surface so Cells, the dry-run, and the benchmarks treat every
architecture uniformly:

  param_specs / init / abstract_params / params_pspecs
  loss(params, batch)                                    (train shapes)
  prefill(params, batch)            -> (logits, cache)   (prefill shapes)
  decode(params, cache, batch)      -> (logits, cache)   (decode shapes)
  cache_specs(batch, max_len) / batch_specs(shape)
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import encdec as encdec_mod
from repro.models import transformer as tfm
from repro.models import zamba2 as zmb
from repro.models.layers import (
    PagedKVCache,
    embed_spec,
    kv_slice_specs,
    logits_fn,
    norm_spec,
    out_spec,
    pad_vocab,
    rms_norm,
    softmax_xent,
)
from repro.models.mamba2 import mamba_dims
from repro.models.param import (
    PSpec,
    abstract_params,
    count_params,
    init_params,
    tree_map_pspec,
)
from repro.sharding.rules import ShardCtx

F32 = jnp.float32


def stack_specs(specs, n: int):
    """Stack per-layer PSpecs along a leading 'layers' dim."""
    def bump(s: PSpec) -> PSpec:
        init = s.init
        if init[0] == "normal" and init[1] >= 0:
            init = ("normal", init[1] + 1)
        return PSpec((n,) + s.shape, ("layers",) + s.logical, init, s.dtype)
    return tree_map_pspec(bump, specs)


def _policy(name: str):
    if name == "nothing_saveable":
        return jax.checkpoint_policies.nothing_saveable
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(name)


def _scan_stack(fn, x, stacked, cache, *, remat: bool, policy: str,
                constrain=None, gather=None):
    """Scan fn(x, layer_params, cache_slice)->(x, new_slice, aux) over layers.

    Megatron-SP residual handling: the scan carry (and the remat-saved
    layer input) is kept sequence-sharded via ``constrain`` at the layer
    exit; ``gather`` all-gathers the sequence at layer ENTRY — *inside*
    the remat body so the gathered copy is recomputed in the backward
    rather than saved.  Without the entry gather, GSPMD sees seq-sharded
    activations against model-sharded weights in the dW einsums and
    replicates full weight gradients per layer step.
    """
    def wrapped(h, lp, csl):
        if gather is not None:
            h = gather(h)
        return fn(h, lp, csl)

    body_fn = jax.checkpoint(wrapped, policy=_policy(policy)) if remat else wrapped

    is_paged = lambda n: isinstance(n, PagedKVCache)
    paged_nodes = (
        [n for n in jax.tree.leaves(cache, is_leaf=is_paged) if is_paged(n)]
        if cache is not None else []
    )
    if paged_nodes:
        # Paged KV rides the scan CARRY, not the xs: arena leaves have no
        # layer-stacked leading dim (the whole (N, L, ...) arena flows
        # through every step), so slicing them per layer is impossible.
        # Instead the per-step xs carry only the layer index; the body
        # rebinds each PagedKVCache's ``layer`` field and threads the
        # updated arena through the carry.  Output ys for paged positions
        # are dummies; the real arenas are spliced back after the scan.
        L = jax.tree.leaves(stacked)[0].shape[0]
        idx = jnp.arange(L, dtype=jnp.int32)
        cache_x = jax.tree.map(lambda n: idx if is_paged(n) else n, cache,
                               is_leaf=is_paged)

        def body(carry, xs):
            h, aux, pnodes = carry
            lp, csl_x = xs
            it = iter(pnodes)
            csl = jax.tree.map(
                lambda t, sx: next(it)._replace(layer=sx) if is_paged(t) else sx,
                cache, csl_x, is_leaf=is_paged,
            )
            h, ncsl, a = body_fn(h, lp, csl)
            if constrain is not None:
                h = constrain(h)
            new_p = [n for n in jax.tree.leaves(ncsl, is_leaf=is_paged)
                     if is_paged(n)]
            ys = jax.tree.map(
                lambda n: jnp.zeros((), jnp.int32) if is_paged(n) else n,
                ncsl, is_leaf=is_paged,
            )
            return (h, aux + a, new_p), ys

        (x, aux, pnodes), ys = jax.lax.scan(
            body, (x, jnp.float32(0.0), paged_nodes), (stacked, cache_x))
        it = iter(pnodes)
        new_cache = jax.tree.map(
            lambda t, y: (next(it)._replace(layer=jnp.zeros((), jnp.int32))
                          if is_paged(t) else y),
            cache, ys, is_leaf=is_paged,
        )
        return x, new_cache, aux

    def body(carry, xs):
        h, aux = carry
        lp, csl = xs
        h, ncsl, a = body_fn(h, lp, csl)
        if constrain is not None:
            h = constrain(h)
        return (h, aux + a), ncsl

    (x, aux), new_cache = jax.lax.scan(body, (x, jnp.float32(0.0)), (stacked, cache))
    return x, new_cache, aux


class Model:
    def __init__(self, cfg: ArchConfig, ctx: ShardCtx):
        self.cfg = cfg
        self.ctx = ctx
        self.vocab_padded = pad_vocab(cfg.vocab, cfg.vocab_pad_multiple)
        self.dtype = jnp.dtype(cfg.dtype)

    # ------------------------------------------------------------------
    # parameter specs
    # ------------------------------------------------------------------
    def param_specs(self) -> Dict[str, Any]:
        cfg, ctx = self.cfg, self.ctx
        d, L = cfg.d_model, cfg.num_layers
        specs: Dict[str, Any] = {
            "embed": embed_spec(self.vocab_padded, d),
            "final_norm": norm_spec(d),
        }
        if not cfg.tie_embeddings:
            specs["out"] = out_spec(d, self.vocab_padded)

        fam = cfg.family
        if fam in ("dense", "vlm"):
            specs["layers"] = stack_specs(tfm.dense_layer_specs(cfg), L)
        elif fam == "moe":
            fd = cfg.moe.first_dense_layers
            if fd:
                specs["dense_layers"] = stack_specs(
                    tfm.dense_layer_specs(cfg, d_ff=cfg.moe.dense_d_ff), fd
                )
            specs["moe_layers"] = stack_specs(tfm.moe_layer_specs(cfg, ctx), L - fd)
        elif fam == "ssm":
            specs["mamba_layers"] = stack_specs(zmb.mamba_layer_specs(cfg), L)
        elif fam == "hybrid":
            every = cfg.hybrid_attn_every
            ngroups = L // every
            inner = stack_specs(zmb.mamba_layer_specs(cfg), every)
            specs["groups"] = stack_specs(inner, ngroups)
            specs["shared"] = zmb.shared_block_specs(cfg)
        elif fam == "encdec":
            specs["src_proj"] = PSpec((d, d), ("embed", None), ("normal", 0))
            specs["enc_layers"] = stack_specs(
                encdec_mod.enc_layer_specs(cfg), cfg.encoder_layers
            )
            specs["enc_norm"] = norm_spec(d)
            specs["dec_layers"] = stack_specs(encdec_mod.dec_layer_specs(cfg), L)
        else:
            raise ValueError(fam)
        return specs

    def init(self, rng):
        return init_params(self.param_specs(), rng, self.cfg.dtype)

    def abstract_params(self):
        return abstract_params(self.param_specs(), self.cfg.dtype)

    def params_pspecs(self):
        return self.ctx.params_pspecs(self.param_specs())

    def n_params(self) -> int:
        return count_params(self.param_specs())

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def cache_specs(self, batch: int, max_len: int):
        cfg = self.cfg
        fam = cfg.family
        L = cfg.num_layers
        if fam in ("dense", "vlm"):
            return {"layers": stack_specs(kv_slice_specs(cfg, batch, max_len), L)}
        if fam == "moe":
            fd = cfg.moe.first_dense_layers
            out = {"moe_layers": stack_specs(kv_slice_specs(cfg, batch, max_len), L - fd)}
            if fd:
                out["dense_layers"] = stack_specs(kv_slice_specs(cfg, batch, max_len), fd)
            return out
        if fam == "ssm":
            return {"mamba_layers": stack_specs(self._mamba_state_specs(batch), L)}
        if fam == "hybrid":
            every = cfg.hybrid_attn_every
            ngroups = L // every
            return {
                "groups": zmb.ZambaGroupCache(
                    mamba=stack_specs(
                        stack_specs(self._mamba_state_specs(batch), every), ngroups
                    ),
                    shared=stack_specs(
                        kv_slice_specs(cfg, batch, max_len), ngroups
                    ),
                )
            }
        if fam == "encdec":
            s_src = self.source_len(max_len)
            hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
            cross_axes = ("batch", "kv_seq", None, None)
            return {
                "dec_layers": encdec_mod.DecCache(
                    self_kv=stack_specs(kv_slice_specs(cfg, batch, max_len), L),
                    cross_k=PSpec((L, batch, s_src, hkv, dh),
                                  ("layers",) + cross_axes, ("const", 0.0)),
                    cross_v=PSpec((L, batch, s_src, hkv, dh),
                                  ("layers",) + cross_axes, ("const", 0.0)),
                    # valid source prefix per row; 0 (init) = no memory yet
                    src_len=PSpec((L, batch), ("layers", "batch"),
                                  ("const", 0), dtype="int32"),
                )
            }
        raise ValueError(fam)

    def _mamba_state_specs(self, batch: int):
        cfg = self.cfg
        d_inner, H, G, N, K = mamba_dims(cfg)
        P_ = cfg.ssm.head_dim
        from repro.models.mamba2 import MambaState
        return MambaState(
            conv=PSpec((batch, K - 1, d_inner + 2 * G * N),
                       ("batch", None, "inner"), ("const", 0.0)),
            ssm=PSpec((batch, H, P_, N),
                      ("batch", "ssm_heads", None, None), ("const", 0.0),
                      dtype="float32"),
        )

    def init_cache(self, batch: int, max_len: int):
        return init_params(self.cache_specs(batch, max_len), jax.random.PRNGKey(0), self.cfg.dtype)

    def abstract_cache(self, batch: int, max_len: int):
        return abstract_params(self.cache_specs(batch, max_len), self.cfg.dtype)

    def cache_pspecs(self, batch: int, max_len: int):
        return self.ctx.params_pspecs(self.cache_specs(batch, max_len))

    def source_len(self, seq_len: int) -> int:
        """Encoder source length for encdec shapes (audio capped at 4k frames)."""
        return int(min(seq_len, 4096) * self.cfg.source_len_ratio)

    # ------------------------------------------------------------------
    # batches
    # ------------------------------------------------------------------
    def batch_specs(self, shape: ShapeConfig):
        """(ShapeDtypeStruct tree, PartitionSpec tree) for a workload shape."""
        cfg, ctx = self.cfg, self.ctx
        B, S = shape.global_batch, shape.seq_len
        tok = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32)
        out: Dict[str, Any] = {}
        pspecs: Dict[str, Any] = {}

        def add(name, sds, logical):
            out[name] = sds
            pspecs[name] = ctx.pspec(logical, sds.shape)

        if shape.kind == "train":
            add("tokens", tok(B, S), ("batch", None))
            add("labels", tok(B, S), ("batch", None))
        elif shape.kind == "prefill":
            add("tokens", tok(B, S), ("batch", None))
        else:  # decode
            add("tokens", tok(B, 1), ("batch", None))
            add("pos", tok(B), ("batch",))
        if cfg.family == "encdec" and shape.kind != "decode":
            s_src = self.source_len(S)
            add("src", jax.ShapeDtypeStruct((B, s_src, cfg.d_model), self.dtype),
                ("batch", None, None))
        return out, pspecs

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def _embed_tokens(self, params, tokens):
        x = jnp.take(params["embed"], tokens, axis=0).astype(self.dtype)
        x = jax.lax.with_sharding_constraint(
            x, self.ctx.sharding(("batch", None, None), x.shape)
        )
        return x

    def _logits(self, params, x):
        w = params["embed"].T if self.cfg.tie_embeddings else params["out"]
        logits = logits_fn(x, w, self.cfg.vocab)
        # vocab-parallel logits (Megatron): never materialize the full vocab
        # dim on one device — the xent reductions then psum over the model
        # axis instead of all-gathering (B, S, V).
        return jax.lax.with_sharding_constraint(
            logits, self.ctx.sharding(("batch", None, "vocab"), logits.shape)
        )


    def _act_constrain(self):
        mode = self.cfg.activation_shard
        if mode is None:
            return None
        logical = (
            ("batch", "act_seq", None) if mode == "seq"
            else ("batch", None, "act_embed")
        )

        def f(h):
            return jax.lax.with_sharding_constraint(
                h, self.ctx.sharding(logical, h.shape)
            )
        return f

    def _act_gather(self):
        """Layer-entry resharding: batch-sharded only (full seq/embed)."""
        if self.cfg.activation_shard is None:
            return None

        def f(h):
            return jax.lax.with_sharding_constraint(
                h, self.ctx.sharding(("batch", None, None), h.shape)
            )
        return f

    def _backbone(self, params, x, *, mode: str, cache=None, pos=None, x0=None,
                  mask=None, ckpt_every=None):
        """Shared decoder trunk for non-encdec families.

        ``mask`` (B, S) bool marks the real tokens of bucket-padded
        prefill rows.  Recurrent families (ssm / hybrid) thread it into
        the SSD scan so pad positions make no state update; KV families
        ignore it (causality + ``mask_pad_slots`` already confine pads).

        ``ckpt_every`` (prefill, ssm/hybrid only): emit recurrent-state
        checkpoints at every interior chunk boundary — the per-layer new
        state becomes ``(state, checkpoints)`` and rides the scan ys; the
        caller splits it back apart (``prefill_ranged``).
        """
        cfg, ctx = self.cfg, self.ctx
        remat = mode == "train"
        pol = cfg.remat_policy
        aux_total = jnp.float32(0.0)
        new_cache: Dict[str, Any] = {}
        constrain = self._act_constrain()
        gather = None  # entry-gather measured WORSE (see EXPERIMENTS.md §Perf)

        fam = cfg.family
        if fam in ("dense", "vlm"):
            fn = lambda h, lp, csl: tfm.dense_layer(lp, h, cfg, ctx, mode=mode, cache=csl, pos=pos)
            x, nc, aux = _scan_stack(fn, x, params["layers"],
                                     None if cache is None else cache["layers"],
                                     remat=remat, policy=pol, constrain=constrain, gather=gather)
            new_cache["layers"] = nc
            aux_total += aux
        elif fam == "moe":
            fd = cfg.moe.first_dense_layers
            if fd:
                fn = lambda h, lp, csl: tfm.dense_layer(lp, h, cfg, ctx, mode=mode, cache=csl, pos=pos)
                x, nc, aux = _scan_stack(fn, x, params["dense_layers"],
                                         None if cache is None else cache.get("dense_layers"),
                                         remat=remat, policy=pol, constrain=constrain, gather=gather)
                new_cache["dense_layers"] = nc
                aux_total += aux
            fn = lambda h, lp, csl: tfm.moe_layer(lp, h, cfg, ctx, mode=mode, cache=csl, pos=pos)
            x, nc, aux = _scan_stack(fn, x, params["moe_layers"],
                                     None if cache is None else cache["moe_layers"],
                                     remat=remat, policy=pol, constrain=constrain, gather=gather)
            new_cache["moe_layers"] = nc
            aux_total += aux
        elif fam == "ssm":
            fn = lambda h, lp, csl: zmb.mamba_layer(lp, h, cfg, mode=mode, state=csl, mask=mask, ckpt_every=ckpt_every)
            x, nc, aux = _scan_stack(fn, x, params["mamba_layers"],
                                     None if cache is None else cache["mamba_layers"],
                                     remat=remat, policy=pol, constrain=constrain, gather=gather)
            new_cache["mamba_layers"] = nc
            aux_total += aux
        elif fam == "hybrid":
            shared = params["shared"]

            def group_fn(h, gp, gcsl):
                m_cache = None if gcsl is None else gcsl.mamba
                inner = lambda hh, lp, csl: zmb.mamba_layer(lp, hh, cfg, mode=mode, state=csl, mask=mask, ckpt_every=ckpt_every)
                h, n_m, aux = _scan_stack(inner, h, gp, m_cache, remat=False, policy=pol)
                h, n_s = zmb.shared_block(
                    shared, h, x0, cfg, self.ctx, mode=mode,
                    cache=None if gcsl is None else gcsl.shared, pos=pos,
                )
                ncache = zmb.ZambaGroupCache(mamba=n_m, shared=n_s) if gcsl is not None else None
                return h, ncache, aux

            x, nc, aux = _scan_stack(group_fn, x, params["groups"],
                                     None if cache is None else cache["groups"],
                                     remat=remat, policy=pol, constrain=constrain, gather=gather)
            new_cache["groups"] = nc
            aux_total += aux
        else:
            raise ValueError(fam)
        return x, new_cache, aux_total

    def _encode(self, params, src, *, remat: bool = False, src_len=None):
        """src (B, S_src, d_model) -> memory; ``src_len`` (B,) masks pad
        frames out of the bidirectional self-attention so each row's
        encoding is independent of the batch's common padded length."""
        cfg = self.cfg
        x = (src.astype(self.dtype) @ params["src_proj"])
        fn = lambda h, lp, _csl: encdec_mod.enc_layer(lp, h, cfg, self.ctx,
                                                      src_len=src_len)
        x, _, _ = _scan_stack(fn, x, params["enc_layers"], None,
                              remat=remat, policy=cfg.remat_policy,
                              constrain=self._act_constrain(),
                              gather=self._act_gather())
        return rms_norm(x, params["enc_norm"], cfg.rms_eps)

    def _decode_stack(self, params, x, *, mode, memory=None, cache=None, pos=None,
                      src_len=None):
        cfg = self.cfg
        fn = lambda h, lp, csl: encdec_mod.dec_layer(
            lp, h, cfg, self.ctx, mode=mode, memory=memory, cache=csl, pos=pos,
            src_len=src_len,
        )
        remat = mode == "train"
        x, nc, aux = _scan_stack(fn, x, params["dec_layers"],
                                 None if cache is None else cache["dec_layers"],
                                 remat=remat, policy=cfg.remat_policy,
                                 constrain=self._act_constrain(),
                                 gather=self._act_gather())
        return x, ({"dec_layers": nc} if cache is not None else {}), aux

    # ------------------------------------------------------------------
    # public programs
    # ------------------------------------------------------------------
    def loss(self, params, batch) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        if cfg.family == "encdec":
            src_len = batch.get("src_len")
            memory = self._encode(params, batch["src"], remat=True,
                                  src_len=src_len)
            x = self._embed_tokens(params, batch["tokens"])
            x, _, aux = self._decode_stack(params, x, mode="train",
                                           memory=memory, src_len=src_len)
        else:
            x = self._embed_tokens(params, batch["tokens"])
            x0 = x
            x, _, aux = self._backbone(params, x, mode="train", x0=x0)
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        if self.ctx.dp_over_model and x.shape[1] >= 1024:
            # ZeRO-3 layout: the vocab dim can't shard (the model axis backs
            # the batch), so never materialize full-seq logits — scan the
            # head over sequence chunks with remat
            xent = self._chunked_xent(params, x, batch["labels"])
        else:
            logits = self._logits(params, x)
            xent = softmax_xent(logits, batch["labels"])
        loss = xent + 0.01 * aux
        return loss, {"loss": loss, "xent": xent, "aux": aux}

    def _chunked_xent(self, params, x, labels, chunk: int = 512):
        B, S, D = x.shape
        n = S // chunk
        assert S % chunk == 0
        xs = (
            x.reshape(B, n, chunk, D).swapaxes(0, 1),
            labels.reshape(B, n, chunk).swapaxes(0, 1),
        )

        def body(tot, xs_c):
            xc, lc = xs_c
            logits = self._logits(params, xc)
            lse = jax.nn.logsumexp(logits, axis=-1)
            oh = jax.nn.one_hot(lc, logits.shape[-1], dtype=jnp.bfloat16)
            ll = jnp.einsum("bsv,bsv->bs", logits, oh.astype(logits.dtype))
            return tot + (lse - ll).sum(), None

        body = jax.checkpoint(body, policy=_policy(self.cfg.remat_policy))
        total, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
        return total / (B * S)

    def prefill(self, params, batch, cache):
        cfg = self.cfg
        if cfg.family == "encdec":
            src_len = batch.get("src_len")
            memory = self._encode(params, batch["src"], src_len=src_len)
            x = self._embed_tokens(params, batch["tokens"])
            x, new_cache, _ = self._decode_stack(
                params, x, mode="prefill", memory=memory, cache=cache,
                src_len=src_len,
            )
        else:
            x = self._embed_tokens(params, batch["tokens"])
            x0 = x
            x, new_cache, _ = self._backbone(
                params, x, mode="prefill", cache=cache, x0=x0
            )
        x = rms_norm(x[:, -1:], params["final_norm"], cfg.rms_eps)
        logits = self._logits(params, x)[:, 0]
        return logits, new_cache

    @property
    def chunked_prefill_exact(self) -> bool:
        """True when :meth:`prefill_ranged` is EXACT for this family on
        bucket-padded prompt batches.

        The single source of truth for the serving capability:
        ``serve_step.supports_chunked_prefill`` consults this (plus the
        cache-layout condition on ``sliding_window``) and
        :meth:`prefill_ranged` raises ``NotImplementedError`` exactly when
        this is False — the two can never drift.

        Every registered family qualifies: KV families (dense/vlm/moe) via
        causal attention + ``mask_pad_slots``; recurrent families
        (ssm/hybrid) via the pad-token validity mask threaded into the SSD
        scan (zero ``dt`` at pads, conv state snapshotted at the last real
        token); encdec via per-request source features with ``src_len``
        masked encoder/cross attention.
        """
        return self.cfg.family in ("dense", "vlm", "moe", "ssm", "hybrid",
                                   "encdec")

    @property
    def supports_paged_kv(self) -> bool:
        """True when this family's serve cache can live in a paged KV
        arena with radix-tree prefix sharing (``repro.serve.kvpool``).

        Requires every *positional* cache leaf to be a ``KVSlice`` whose
        per-position values depend only on the token prefix up to that
        position (causal KV) — then interned prefix pages written by one
        request are bit-identical to what any other request with the
        same prompt prefix (and, for encdec, the same source features)
        would compute, so they can be mapped read-only.  Recurrent state
        (ssm / hybrid) folds the whole history into one non-positional
        state and cannot be page-shared — those families share state
        SNAPSHOTS at chunk boundaries instead
        (:attr:`supports_snapshot_state`); the pool-level three-way
        capability is ``repro.serve.kvpool.KVPool.capability``.
        encdec qualifies: its decoder self-KV pages, while the cross
        memory rides the dense *resident* remainder of the cache.
        """
        return self.cfg.family in ("dense", "vlm", "moe", "encdec")

    @property
    def supports_snapshot_state(self) -> bool:
        """True when this family's serve cache is a recurrent state that
        can be SNAPSHOTTED at token-chunk boundaries and restored to seed
        a suffix-only prefill (``repro.serve.kvpool`` snapshot pools).

        Requires the state after token ``i`` to depend only on tokens
        ``<= i`` (plus, for hybrid, the shared-attention KV up to ``i``,
        which is causal and travels with the snapshot as page stacks), so
        an interned checkpoint written by one request is bit-identical to
        what any request with the same prefix would compute —
        :meth:`prefill_ranged` with ``checkpoint_every`` emits the
        checkpoints, :meth:`restore_state_row` +
        :meth:`prefill_extend` replay from the deepest one.
        """
        return self.cfg.family in ("ssm", "hybrid")

    def prefill_extend(self, params, batch, cache):
        """Suffix-only prefill behind a resident prefix (prefix sharing).

        ``batch`` = {tokens (B, S_ext) int32, pos (B,) int32, length (B,)
        int32}: row b's suffix ``tokens[b, :length[b]]`` continues a
        prompt whose first ``pos[b]`` positions are ALREADY present in
        ``cache`` (gathered from interned pool pages).  Positions are
        absolute (``pos[b] + i``), attention masks purely by the cache's
        ``slot_pos``, and K/V for the suffix is written in place — the
        per-layer work is ``attention_block(mode="extend")``.  encdec
        reads its cross memory from the cache (installed by the caller),
        exactly like decode.  Recurrent families (ssm/hybrid) continue
        from the cache's restored snapshot state instead of resident
        pages: the suffix validity mask keeps pad tokens out of the SSD
        scan (a ``length`` 0 row is a pure no-op: identity state update,
        and its attention writes land out of range when the caller sets
        ``pos`` past the cache length).  Returns (logits at each row's
        LAST REAL suffix token, updated cache).
        """
        cfg = self.cfg
        if not (self.supports_paged_kv or self.supports_snapshot_state):
            raise NotImplementedError(
                f"no suffix prefill for family {cfg.family!r}"
            )
        tokens, pos, length = batch["tokens"], batch["pos"], batch["length"]
        mask = jnp.arange(tokens.shape[1])[None, :] < length[:, None]
        x = self._embed_tokens(params, tokens)
        if cfg.family == "encdec":
            x, new_cache, _ = self._decode_stack(
                params, x, mode="extend", cache=cache, pos=pos
            )
        else:
            x, new_cache, _ = self._backbone(
                params, x, mode="extend", cache=cache, pos=pos, x0=x,
                mask=mask,
            )
        last = jnp.clip(length - 1, 0, x.shape[1] - 1)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
        x_last = rms_norm(x_last, params["final_norm"], cfg.rms_eps)
        logits = self._logits(params, x_last)[:, 0]
        return logits, new_cache

    @property
    def decode_state_positional(self) -> bool:
        """True when every per-slot serve-cache leaf is position-masked
        (pure KV with ``slot_pos``), so stale rows left by a slot's
        previous occupant are invisible to decode attention.  Recurrent
        state (ssm/hybrid) and encdec cross memory are NOT positional —
        a reused slot must be reset to init values before a
        token-at-a-time admit (the batcher consults this)."""
        return self.cfg.family in ("dense", "vlm", "moe")

    def prefill_ranged(self, params, batch, cache, *, checkpoint_every=None):
        """Chunked prefill: whole padded prompts in a single invocation.

        ``batch`` = {tokens (B, S_pad) int32, length (B,) int32} where row b
        holds a real prompt in ``tokens[b, :length[b]]`` and padding after
        (``length`` 0 marks a dummy batch-padding row).  encdec batches add
        {src (B, S_src, d_model), src_len (B,)} — see
        :meth:`ranged_batch_extras`.

        ``checkpoint_every`` (ssm/hybrid only; must divide S_pad): also
        return the stacked per-boundary recurrent-state checkpoints the
        snapshot cache plane interns — return becomes ``(logits, cache,
        ckpts)`` with ``ckpts`` sliceable via :meth:`slice_checkpoint`.
        Checkpoints at boundaries past a row's true length are garbage
        (identity updates over pad conv windows) and must not be read —
        consumers only intern full-chunk boundaries ``<= length - 1``.

        Returns (logits (B, V) taken at each
        row's LAST REAL token, cache exact at each row's true length:

        * KV families: pad slots' ``slot_pos`` masked to -1 so decode
          attention never sees the padding K/V;
        * ssm / hybrid: pad tokens contribute ZERO state update (``dt``
          masked inside the SSD scan) and the causal-conv state is
          snapshotted at each row's last real token;
        * encdec: cross-attention memory encoded under a ``src_len`` mask
          and carried in the cache (with the mask) for decode).
        """
        cfg = self.cfg
        if not self.chunked_prefill_exact:
            raise NotImplementedError(
                f"no exact chunked prefill for family {cfg.family!r}"
            )
        tokens, length = batch["tokens"], batch["length"]
        if checkpoint_every is not None:
            if not self.supports_snapshot_state:
                raise NotImplementedError(
                    f"no state checkpoints for family {cfg.family!r}")
            if tokens.shape[1] % checkpoint_every:
                raise ValueError(
                    f"S_pad={tokens.shape[1]} not a multiple of "
                    f"checkpoint_every={checkpoint_every}")
        mask = jnp.arange(tokens.shape[1])[None, :] < length[:, None]
        if cfg.family == "encdec":
            src_len = batch.get("src_len")
            if src_len is None:
                src_len = jnp.full((tokens.shape[0],), batch["src"].shape[1],
                                   jnp.int32)
            memory = self._encode(params, batch["src"], src_len=src_len)
            x = self._embed_tokens(params, tokens)
            x, new_cache, _ = self._decode_stack(
                params, x, mode="prefill", memory=memory, cache=cache,
                src_len=src_len,
            )
        else:
            x = self._embed_tokens(params, tokens)
            x, new_cache, _ = self._backbone(
                params, x, mode="prefill", cache=cache, x0=x, mask=mask,
                ckpt_every=checkpoint_every,
            )
        ckpts = None
        if checkpoint_every is not None:
            new_cache, ckpts = self._split_checkpoints(new_cache)
        last = jnp.clip(length - 1, 0, x.shape[1] - 1)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)  # (B,1,D)
        x_last = rms_norm(x_last, params["final_norm"], cfg.rms_eps)
        logits = self._logits(params, x_last)[:, 0]
        from repro.models.cache_utils import mask_pad_slots
        new_cache = mask_pad_slots(new_cache, length)
        if checkpoint_every is not None:
            return logits, new_cache, ckpts
        return logits, new_cache

    # ------------------------------------------------------------------
    # recurrent-state snapshots (the ssm/hybrid cache-plane payload)
    # ------------------------------------------------------------------
    def _split_checkpoints(self, new_cache):
        """Split the ``(state, checkpoints)`` tuples the checkpointing
        backbone threads through the layer scan back into (cache, ckpts).
        ``ckpts`` leaves carry the chunk axis right after batch: ssm
        (L, B, nb, ...), hybrid (G, E, B, nb, ...)."""
        if self.cfg.family == "ssm":
            states, ck = new_cache["mamba_layers"]
            return {**new_cache, "mamba_layers": states}, ck
        g = new_cache["groups"]
        states, ck = g.mamba
        return {**new_cache, "groups": g._replace(mamba=states)}, ck

    @property
    def _state_batch_axis(self) -> int:
        """Batch-axis index of the stacked recurrent-state leaves: ssm
        stacks (L,) in front, hybrid (G, E)."""
        return 1 if self.cfg.family == "ssm" else 2

    def slice_checkpoint(self, ckpts, row: int, chunk_idx: int):
        """One row's recurrent state at interior chunk boundary
        ``chunk_idx`` (state AFTER chunk ``chunk_idx``), as a 1-row state
        tree shaped exactly like the recurrent part of a dense cache row
        — the snapshot payload :meth:`restore_state_row` writes back."""
        ax = self._state_batch_axis
        idx = (slice(None),) * ax + (slice(row, row + 1), chunk_idx)
        return jax.tree.map(lambda a: a[idx], ckpts)

    def restore_state_row(self, cache, state, row: int):
        """Write a 1-row snapshot ``state`` (from :meth:`slice_checkpoint`
        or a final prefill state row) over slot ``row``'s recurrent cache
        leaves; KV leaves (hybrid shared attention) are untouched — the
        caller restores those from the snapshot's page stacks."""
        ax = self._state_batch_axis
        idx = (slice(None),) * ax + (slice(row, row + 1),)

        def put(c, s):
            return c.at[idx].set(s.astype(c.dtype))

        if self.cfg.family == "ssm":
            return {**cache,
                    "mamba_layers": jax.tree.map(put, cache["mamba_layers"],
                                                 state)}
        g = cache["groups"]
        return {**cache, "groups": g._replace(
            mamba=jax.tree.map(put, g.mamba, state))}

    # ------------------------------------------------------------------
    # chunked-prefill batch helpers (family-specific knowledge lives HERE
    # so the serve layer stays free of family branches)
    # ------------------------------------------------------------------
    def ranged_batch_extras(self, srcs, max_len: int):
        """Extra ``prefill_ranged`` batch keys for ``len(srcs)`` rows.

        ``srcs``: per-row source feature arrays (S_src_i, d_model) or None
        (no source -> zero features, ``src_len`` 0).  Families without
        side inputs return {}; encdec returns {src, src_len} padded to the
        cache's source length so every bucket compiles one program shape.
        """
        if self.cfg.family != "encdec":
            return {}
        import numpy as np
        B = len(srcs)
        s_src = self.source_len(max_len)
        src = np.zeros((B, s_src, self.cfg.d_model), np.float32)
        src_len = np.zeros((B,), np.int32)
        for i, s in enumerate(srcs):
            if s is None:
                continue
            s = np.asarray(s, np.float32)
            L = min(len(s), s_src)
            src[i, :L] = s[:L]
            src_len[i] = L
        return {"src": jnp.asarray(src, self.dtype),
                "src_len": jnp.asarray(src_len)}

    def encode_cross_rows(self, params, srcs, max_len: int):
        """Cross-attention memory rows for token-at-a-time prompt paths.

        Returns (cross_k (L,B,S_src,Hkv,Dh), cross_v, src_len (B,)) ready
        for :func:`repro.models.cache_utils.install_cross_memory`, or None
        when this family has no cross memory (or no row carries source
        features) — callers need no family branch.
        """
        if self.cfg.family != "encdec" or all(s is None for s in srcs):
            return None
        extras = self.ranged_batch_extras(srcs, max_len)
        if not hasattr(self, "_encode_cross_jit"):
            def _encode_cross(params, src, src_len):
                memory = self._encode(params, src, src_len=src_len)
                # the SAME projection dec_layer uses in prefill, vmapped
                # over the stacked layer dim — one definition, two paths
                return jax.vmap(encdec_mod.cross_kv, in_axes=(0, None))(
                    params["dec_layers"]["cross"], memory)
            self._encode_cross_jit = jax.jit(_encode_cross)
        ck, cv = self._encode_cross_jit(params, extras["src"], extras["src_len"])
        return ck, cv, extras["src_len"]

    def decode(self, params, cache, batch):
        cfg = self.cfg
        x = self._embed_tokens(params, batch["tokens"])     # (B,1,D)
        pos = batch["pos"]
        if cfg.family == "encdec":
            x, new_cache, _ = self._decode_stack(
                params, x, mode="decode", cache=cache, pos=pos
            )
        else:
            x0 = x
            x, new_cache, _ = self._backbone(
                params, x, mode="decode", cache=cache, pos=pos, x0=x0
            )
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        logits = self._logits(params, x)[:, 0]
        return logits, new_cache


def build_model(cfg: ArchConfig, ctx: ShardCtx) -> Model:
    return Model(cfg, ctx)
