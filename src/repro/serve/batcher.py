"""Continuous batcher: slot-based request scheduling for decode.

A fixed-width decode batch (B slots) over a shared-shape KV cache; requests
join free slots, run until EOS/max_tokens, and free their slot.  Per-slot
positions (``pos`` is a vector) let slots be at different depths — the
model's decode path masks per-slot.  This is the serving front used by the
serving cells and the tail-latency benchmarks.

Prompt consumption is CHUNKED-PREFILL by default: an admitted request's
whole prompt runs through one bucket-padded prefill program invocation that
writes its KV rows straight into the slot (O(1) invocations per prompt),
and the first output token is sampled from the same invocation.  Requests
admitted in the same scheduler tick whose prompts land in the SAME pad
bucket share one (B, S_pad) prefill invocation — under bursty arrivals the
prompt phase costs O(buckets) invocations per tick, not O(requests).
Every registered family chunks exactly (``Model.chunked_prefill_exact``);
only rolling-SWA cache layouts (``sliding_window < max_len``) fall back to
the token-at-a-time decode loop (see ``supports_chunked_prefill``).

Slots can also be filled from OUTSIDE via :meth:`install_prefilled` — the
disaggregated serving path (``repro.serve.disagg``) prefills on a separate
cell and streams the KV rows over an ArrayChannel into a free slot here.

KV STORAGE IS PAGED by default for the families that support it
(``Model.supports_paged_kv`` + an absolute-position cache layout): the
batcher owns a :class:`~repro.serve.kvpool.KVPool` — a page-granular
arena + block table + radix-tree prefix cache — instead of a dense
per-slot cache.  Admission consults the tree first: a request whose
prompt shares an interned prefix maps those pages read-only, skips their
prefill chunks entirely (only the suffix runs, one ``prefill_extend``
invocation per pad bucket), and admission BLOCKS (requests stay queued)
when the pool is exhausted instead of over-committing memory.

RECURRENT FAMILIES (ssm/hybrid) get the SAME prefix-cache plane through
snapshot payloads (``KVPool.capability`` == "snapshot"): decode stays on
the dense per-slot cache (recurrent state is O(1) per slot), but cold
prefills emit per-chunk boundary-state checkpoints that intern into the
pool's radix tree, and a warm prompt restores the deepest checkpoint
into its slot row and suffix-extends only the divergence tail
(:meth:`ContinuousBatcher._restore_group`).  Only rolling-SWA layouts
keep the plain dense cache with no prefix sharing.

ADMISSION IS TENANT-AWARE (``repro.serve.tenancy``): requests carry a
``tenant`` tag, a persistent deficit-round-robin scheduler shares free
slots by tenant weight, token buckets rate-limit each tenant's own FIFO,
and a request blocked on pool pages is scanned PAST instead of stalling
the whole queue.  With no tenants configured all of this degenerates to
the old FIFO behavior (minus the head-of-line block).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import (
    finish_request,
    mark_admitted,
    migrate_decode,
    open_decode,
    open_request,
    recorder_of,
    span_group,
)
from repro.serve.tenancy import (
    DEFAULT_TENANT,
    TenantRegistry,
    TenantScheduler,
)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (S,) int32
    max_new_tokens: int = 16
    submitted_at: float = 0.0
    started_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    output: List[int] = dataclasses.field(default_factory=list)
    # per-request source features (S_src, d_model) for encdec models;
    # None = no source (zero cross memory).  Ignored by other families.
    src: Optional[np.ndarray] = None
    # QoS attribution: which tenant's bucket/weight/page-pocket this
    # request bills.  ``public=True`` puts its prompt in the shared
    # prefix namespace any granted tenant may hit read-only.
    tenant: str = DEFAULT_TENANT
    public: bool = False

    @property
    def latency(self) -> Optional[float]:
        if self.finished_at is None:
            return None
        return self.finished_at - self.submitted_at

    @property
    def ttft(self) -> Optional[float]:
        """Time to first token (submission -> first output token)."""
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def tpot(self) -> Optional[float]:
        """Time per output token over the decode phase; None when fewer
        than two tokens were produced (there was no decode phase to
        measure — a 0.0 would drag the percentiles toward zero)."""
        if self.finished_at is None or self.first_token_at is None:
            return None
        n = len(self.output) - 1
        if n < 1:
            return None
        return (self.finished_at - self.first_token_at) / n


class ContinuousBatcher:
    """Slot-based continuous batching over prefill + decode programs."""

    def __init__(self, model, params, *, batch_slots: int, max_len: int,
                 temperature: float = 0.0, eos_token: Optional[int] = None,
                 prefill_chunk: Optional[int] = 32, accounting=None,
                 kv_pool: Any = "auto", page_size: int = 16,
                 pool_pages: Optional[int] = None, tenants: Any = None,
                 tenant_buckets: bool = True, quantum: int = 256,
                 kv_dtype: Optional[str] = None):
        from repro.models.cache_utils import cache_batch_axes, strip_kv_nodes
        from repro.serve.kvpool import KVPool, build_paged_serve_step
        from repro.serve.serve_step import (
            build_prefill_step,
            build_serve_step,
            supports_chunked_prefill,
        )
        self.model = model
        self.params = params
        self.B = batch_slots
        self.max_len = max_len
        self.eos = eos_token
        self.temperature = temperature
        self.accounting = accounting
        # the owning cell's flight recorder (a shared no-op when the
        # batcher runs standalone with accounting=None)
        self.rec = recorder_of(accounting)
        self.pos = np.zeros(batch_slots, np.int32)
        self.cur_tok = np.zeros(batch_slots, np.int32)
        self.slot_req: List[Optional[Request]] = [None] * batch_slots
        self.queue: deque = deque()
        self.done: List[Request] = []
        # tenant QoS plane: weights + token buckets drive _admit through
        # a persistent DRR scheduler; page quotas (if any tenant declares
        # one) partition the pool's arena into bulkheaded pockets.  With
        # no tenants declared the scheduler degenerates to FIFO-with-
        # scan-past and the pool stays unpartitioned — the single-tenant
        # cold path is byte-identical to the pre-tenancy batcher.
        self.tenants: TenantRegistry = (
            tenants if isinstance(tenants, TenantRegistry)
            else TenantRegistry(tenants or (), buckets=tenant_buckets))
        self.scheduler = TenantScheduler(self.tenants, quantum=quantum)
        quota_fn = (self.tenants.page_quotas
                    if any(t.page_quota is not None
                           for t in self.tenants.specs.values()) else None)
        # cache payload plane: "auto" -> pool iff the family/cache layout
        # supports one (``KVPool.capability``: "paged" arenas for KV
        # families, "snapshot" state checkpoints for ssm/hybrid); None ->
        # legacy dense per-slot cache; or inject a prebuilt KVPool
        if kv_pool == "auto":
            kv_pool = (KVPool(model, max_len=max_len, page_size=page_size,
                              slots=batch_slots, num_pages=pool_pages,
                              accounting=accounting, quotas=quota_fn,
                              kv_dtype=kv_dtype)
                       if KVPool.capability(model, max_len, page_size)
                       != "none" else None)
        self.pool: Optional[KVPool] = kv_pool
        self._paged = (self.pool is not None
                       and self.pool.payload_kind == "page")
        self._snapshot = (self.pool is not None
                          and self.pool.payload_kind == "snapshot")
        if self._paged:
            self.cache = None
            self.resident = strip_kv_nodes(model.init_cache(batch_slots, max_len))
            # native paged decode: the arena + block table flow straight
            # into Model.decode (no gather/scatter); arena, scales and
            # resident are donated so the jitted step mutates in place
            self._step = jax.jit(
                build_paged_serve_step(
                    model, temperature, template=self.pool.template,
                ),
                donate_argnums=(1, 2, 3),
            )
        else:
            # dense per-slot cache — also the decode plane for snapshot
            # pools (recurrent state is O(1) per slot; the pool only
            # holds the shareable checkpoint chains, not the hot state)
            self.cache = model.init_cache(batch_slots, max_len)
            self.resident = None
            self._step = jax.jit(build_serve_step(model, temperature),
                                 donate_argnums=(1,))
        self._rng = jax.random.PRNGKey(0)
        self._cache_axes = cache_batch_axes(model, batch_slots, max_len)
        self._resident_axes = strip_kv_nodes(self._cache_axes)
        self.prefill_chunk = prefill_chunk
        self.chunked = (
            prefill_chunk is not None
            and supports_chunked_prefill(model, max_len)
        )
        if self.chunked and self._snapshot:
            # checkpoint boundaries live at page_size multiples, so every
            # prefill bucket must be page-aligned: coarsen the bucket
            # quantum to lcm(chunk, page_size) (the max_len cap stays
            # aligned — snapshot pools require page-divisible max_len)
            self.prefill_chunk = int(np.lcm(prefill_chunk, page_size))
        self._prefill = (
            jax.jit(build_prefill_step(
                model, temperature,
                checkpoint_every=page_size if self._snapshot else None))
            if self.chunked else None
        )
        self._extend = None                        # lazy; first prefix hit
        self._scratch_caches: Dict[int, Any] = {}  # B -> B-row prefill cache
        self._slot_init_cache = None               # lazy; see _slot_init()
        self.prefill_invocations = 0
        self.prefill_batch_sizes: List[int] = []   # prompts per invocation
        self.decode_invocations = 0

    # -- request management --------------------------------------------
    def submit(self, req: Request):
        req.submitted_at = req.submitted_at or time.monotonic()
        # colocated front door: the root "request" span opens here (the
        # disagg server opens it earlier, on the prefill cell — then
        # this is a no-op returning the existing root)
        open_request(self.rec, req)
        self.queue.append(req)

    def free_slots(self) -> List[int]:
        return [s for s in range(self.B) if self.slot_req[s] is None]

    def _finish(self, req: Request, now: float, slot: Optional[int] = None):
        req.finished_at = now
        finish_request(req, ts=now)
        self.done.append(req)
        if slot is not None:
            self.slot_req[slot] = None
            if self.pool is not None:
                # private + pocket pages return to the free list; shared
                # prefix pages decref (and stay interned as reclaimable
                # cache for the next request with this prefix)
                self.pool.release_slot(slot)
        if self.accounting is not None:
            self.accounting.record_request(
                req.rid, ttft=req.ttft, tpot=req.tpot,
                prompt_len=len(req.prompt), new_tokens=len(req.output),
                tenant=getattr(req, "tenant", None),
            )

    # -- chunked prefill ------------------------------------------------
    def _scratch(self, batch: int):
        """B-row prefill scratch cache, reused across invocations."""
        if batch not in self._scratch_caches:
            self._scratch_caches[batch] = self.model.init_cache(batch, self.max_len)
        return self._scratch_caches[batch]

    def _slot_init(self):
        """Pristine 1-row cache for resetting a slot at token-at-a-time
        admit: KV families mask stale rows by position, but recurrent
        state (ssm/hybrid) is NOT positional — a request admitted into a
        reused slot would integrate its predecessor's state.  Allocated
        on first fallback admit; purely-chunked batchers never pay for it."""
        if self._slot_init_cache is None:
            self._slot_init_cache = self.model.init_cache(1, self.max_len)
        return self._slot_init_cache

    def _prefill_group(self, group):
        """ONE prefill invocation over same-bucket (slot, request, lease)
        triples (cold path — empty leases).

        Power-of-two batch padding (dummy rows discarded) keeps compiled
        prefill variants O(log slots) per bucket and scratch caches O(2B)
        rows total — see ``run_prefill_group``.
        """
        from repro.models.cache_utils import slice_cache_slots
        from repro.serve.serve_step import run_prefill_group
        B = len(group)
        reqs = [r for _, r, _ in group]
        with self.rec.phase("prefill:cold") as ph:
            toks, rows_cache, self._rng, b_pad = run_prefill_group(
                self._prefill, self.params, self._scratch, reqs,
                chunk=self.prefill_chunk, max_len=self.max_len,
                rng=self._rng, model=self.model, accounting=self.accounting,
            )
        span_group(self.rec, "prefill", reqs, ph.ts, ph.ts + ph.dur,
                   kind="cold", batch=B)
        self.rec.record("prefill_s", ph.dur)
        ckpts = None
        if self._snapshot:
            rows_cache, ckpts = rows_cache
        self.prefill_invocations += 1
        self.prefill_batch_sizes.append(B)
        slots = [s for s, _, _ in group]
        if self._paged:
            self._install_pool_rows(group, rows_cache, toks[:B])
        else:
            if ckpts is not None:
                self._intern_snapshot_chains(group, rows_cache, ckpts)
            if b_pad != B:
                rows_cache = slice_cache_slots(rows_cache, self._cache_axes,
                                               list(range(B)))
            self._install_rows(slots, reqs, rows_cache, toks[:B])

    def _extend_group(self, group):
        """ONE suffix-extend invocation over prefix-hit (slot, request,
        lease) triples whose suffixes share a pad bucket — the shared
        prefix pages are already mapped, so only the divergence tail is
        computed (mixed hit depths batch fine: each row carries its own
        offset).

        NATIVE paged: each row's block-table row IS its slot's row, so
        the suffix K/V lands directly in the slot's arena pages — no
        dense rows cache, no post-install page copy.  Afterwards the
        freshly written full prompt pages are interned by ownership
        transfer (``promote_slot_pages``)."""
        from repro.serve.kvpool import (
            build_paged_extend_step,
            request_ctx_key,
            run_extend_group,
        )
        if self._extend is None:
            self._extend = jax.jit(
                build_paged_extend_step(self.model, self.temperature,
                                        template=self.pool.template),
                donate_argnums=(1, 2, 3),
            )
        slots = [s for s, _, _ in group]
        reqs = [r for _, r, _ in group]
        leases = [le for _, _, le in group]
        for slot, req in zip(slots, reqs):
            self.pool.map_suffix_pages(slot, len(req.prompt))
        bt_rows = np.asarray(self.pool.block_table[slots], np.int32)
        with self.rec.phase("prefill:warm") as ph:
            toks, resident_rows, self._rng, _b_pad = run_extend_group(
                self._extend, self.params, self._scratch, self.pool, reqs,
                leases, bt_rows, chunk=self.prefill_chunk,
                max_len=self.max_len, rng=self._rng, model=self.model,
                accounting=self.accounting,
            )
        span_group(self.rec, "prefill", reqs, ph.ts, ph.ts + ph.dur,
                   kind="warm", batch=len(group),
                   hit_tokens=sum(le.tokens for le in leases))
        self.rec.record("prefill_s", ph.dur)
        self.prefill_invocations += 1
        self.prefill_batch_sizes.append(len(group))
        for slot, req in zip(slots, reqs):
            self.pool.promote_slot_pages(slot, req.prompt,
                                         request_ctx_key(req))
            self.pool.ensure_decode_page(slot, len(req.prompt))
        self._merge_resident_rows(resident_rows, list(range(len(group))),
                                  slots)
        self._post_install(slots, reqs, toks[:len(group)])

    def _intern_snapshot_chains(self, group, rows_cache, ckpts):
        """Intern each cold request's per-chunk snapshot chain (snapshot
        pools): chunk ``lp``'s payload is the boundary recurrent state
        AFTER position ``(lp+1)*P`` (sliced from the prefill's stacked
        checkpoints) plus, for hybrid, the chunk's shared-attention KV
        page.  Checkpoints at boundaries past a row's true length are
        never read — only ``len(prompt) // P`` chunks intern."""
        from repro.serve.kvpool import (
            build_snapshot_payloads,
            request_ctx_key,
        )
        for i, (_slot, req, _lease) in enumerate(group):
            payloads = build_snapshot_payloads(
                self.model, self.pool.axes, self.pool.page_size,
                req.prompt, rows_cache, ckpts, i)
            if payloads:
                self.pool.intern_snapshots(
                    req.prompt, request_ctx_key(req), payloads,
                    tenant=getattr(req, "tenant", None))

    def _restore_group(self, group):
        """Warm-path twin of ``_extend_group`` for SNAPSHOT pools: seed
        each slot's dense cache row from its leased chain (deepest
        boundary state + the chain's shared-attention pages), then run
        ONE dense suffix-extend over the full slot cache — only the
        divergence tail is computed; the shared prefix is replayed in
        O(1) by the state restore.

        Rows outside the group ride along untouched: their batch rows
        carry ``length`` 0 (every SSD step dt-masked to identity, so
        recurrent state is preserved bit-exactly) and ``pos`` = max_len
        (every KV write lands out of bounds and drops)."""
        from repro.models.cache_utils import clear_kv_row, load_pages_into_row
        from repro.serve.serve_step import bucket_len, build_extend_step
        if self._extend is None:
            self._extend = jax.jit(
                build_extend_step(self.model, self.temperature))
        P = self.pool.page_size
        for slot, _req, lease in group:
            state, stacks = self.pool.snapshot_chain(lease)
            if self.pool.axes:
                self.cache = clear_kv_row(self.cache, self.pool.axes, slot)
            if state is not None:
                self.cache = self.model.restore_state_row(self.cache, state,
                                                          slot)
            if stacks:
                self.cache = load_pages_into_row(
                    self.cache, self.cache, self.pool.axes, slot, stacks,
                    0, P)
        s_pad = bucket_len(
            max(len(r.prompt) - le.tokens for _, r, le in group),
            self.prefill_chunk, self.max_len)
        tokens = np.zeros((self.B, s_pad), np.int32)
        length = np.zeros((self.B,), np.int32)
        pos = np.full((self.B,), self.max_len, np.int32)
        for slot, req, lease in group:
            suf = req.prompt[lease.tokens:]
            tokens[slot, :len(suf)] = suf
            length[slot] = len(suf)
            pos[slot] = lease.tokens
        batch = {
            "tokens": jnp.asarray(tokens),
            "pos": jnp.asarray(pos),
            "length": jnp.asarray(length),
        }
        self._rng, sub = jax.random.split(self._rng)
        with self.rec.phase("prefill:warm_snapshot") as ph:
            toks, _logits, self.cache = self._extend(self.params, self.cache,
                                                     batch, sub)
            with self.rec.phase("prefill.wait", wait=True):
                toks = np.asarray(toks)
        span_group(self.rec, "prefill", [r for _, r, _ in group], ph.ts,
                   ph.ts + ph.dur, kind="warm_snapshot", batch=len(group),
                   hit_tokens=sum(le.tokens for _, _, le in group))
        self.rec.record("prefill_s", ph.dur)
        self.prefill_invocations += 1
        self.prefill_batch_sizes.append(len(group))
        self._post_install([s for s, _, _ in group],
                           [r for _, r, _ in group],
                           [int(toks[s]) for s, _, _ in group])

    def _install_pool_rows(self, group, rows_cache, first_tokens):
        """Map each request's computed pages out of a dense rows cache
        into its slot (interning full prompt pages for future sharing),
        copy the resident remainder, then run the shared bookkeeping."""
        from repro.serve.kvpool import request_ctx_key
        rows = list(range(len(group)))
        for i, (slot, req, lease) in enumerate(group):
            self.pool.install_rows(slot, req.prompt, request_ctx_key(req),
                                   rows_cache, i, lease.pages)
        self._merge_resident_rows(rows_cache, rows,
                                  [s for s, _, _ in group])
        self._post_install([s for s, _, _ in group],
                           [r for _, r, _ in group], first_tokens)

    def _install_rows(self, slots, reqs, rows_cache, first_tokens):
        """Write prefilled KV rows + first tokens into free slots.

        ``rows_cache`` has batch dim == len(slots); one scatter merges all
        rows, then per-request bookkeeping runs on the host."""
        from repro.models.cache_utils import merge_cache_slots
        self.cache = merge_cache_slots(self.cache, rows_cache,
                                       self._cache_axes, slots)
        self._post_install(slots, reqs, first_tokens)

    def _merge_resident_rows(self, rows_cache, rows, slots):
        """Copy the non-paged cache remainder (encdec cross memory) of
        the given prefill rows into the batcher's resident tree."""
        from repro.models.cache_utils import (
            merge_cache_slots,
            slice_cache_slots,
            strip_kv_nodes,
        )
        res = strip_kv_nodes(rows_cache)
        if not jax.tree.leaves(res):
            return
        res = slice_cache_slots(res, self._resident_axes, rows)
        self.resident = merge_cache_slots(self.resident, res,
                                          self._resident_axes, slots)

    def _post_install(self, slots, reqs, first_tokens):
        """Per-request bookkeeping after KV rows landed in slots."""
        now = time.monotonic()
        for slot, req, tok in zip(slots, reqs, first_tokens):
            req.started_at = req.started_at or now
            req.first_token_at = req.first_token_at or now
            L = len(req.prompt)
            self.pos[slot] = L
            self.cur_tok[slot] = tok
            req.output.append(tok)
            finished = (
                len(req.output) >= req.max_new_tokens
                or (self.eos is not None and tok == self.eos)
                or L >= self.max_len - 1
            )
            if finished:
                self._finish(req, now, slot=slot)
            else:
                self.slot_req[slot] = req
                open_decode(self.rec, req, ts=now)

    def install_prefilled(self, req: Request, row_cache, first_token: int) -> bool:
        """Adopt an EXTERNALLY prefilled request (disaggregated serving):
        ``row_cache`` is a 1-row cache already on this batcher's devices.
        Returns False when no slot is free — or, under a paged pool, when
        page admission would exhaust the arena (caller retries later)."""
        from repro.serve.kvpool import (
            PoolExhausted,
            public_ctx_key,
            request_ctx_key,
        )
        free = self.free_slots()
        if not free:
            return False
        slot = free[0]
        if self.pool is None:
            self._install_rows([slot], [req], row_cache, [first_token])
            return True
        if not self._paged:
            return self.install_snapshot(req, row_cache, first_token)
        ctx = request_ctx_key(req)
        alt = (public_ctx_key(req) if self.tenants.share_public(
            getattr(req, "tenant", DEFAULT_TENANT)) else None)
        lease = self.pool.lease(req.prompt, ctx, alt)
        try:
            self.pool.admit(slot, lease, len(req.prompt), req.max_new_tokens,
                            tenant=getattr(req, "tenant", None))
        except PoolExhausted:
            self.pool.release_lease(lease)
            return False
        self.pool.install_rows(slot, req.prompt, ctx, row_cache, 0,
                               lease.pages)
        self._merge_resident_rows(row_cache, [0], [slot])
        self._post_install([slot], [req], [first_token])
        return True

    def install_snapshot(self, req: Request, row_cache, first_token: int,
                         lease=None, chain=None) -> bool:
        """Adopt an externally prefilled request on a SNAPSHOT pool: the
        dense 1-row install of :meth:`install_prefilled` plus the prefix
        bookkeeping — the lease (router-acquired, or taken fresh here)
        transfers to the slot via ``admit`` (recording the hit/saved
        counters), and a cold handoff's snapshot chain (per-chunk payload
        dicts) interns so the NEXT request with this prefix stays warm.
        Returns False (lease released) when no slot is free."""
        from repro.serve.kvpool import (
            PoolExhausted,
            public_ctx_key,
            request_ctx_key,
        )
        free = self.free_slots()
        if not free:
            if lease is not None:
                self.pool.release_lease(lease)
            return False
        slot = free[0]
        ctx = request_ctx_key(req)
        if lease is None:
            alt = (public_ctx_key(req) if self.tenants.share_public(
                getattr(req, "tenant", DEFAULT_TENANT)) else None)
            lease = self.pool.lease(req.prompt, ctx, alt)
        try:
            self.pool.admit(slot, lease, len(req.prompt),
                            req.max_new_tokens,
                            tenant=getattr(req, "tenant", None))
        except PoolExhausted:            # snapshot admit reserves nothing,
            self.pool.release_lease(lease)   # but keep the contract
            return False
        if chain:
            self.pool.intern_snapshots(req.prompt, ctx, chain,
                                       tenant=getattr(req, "tenant", None))
        self._install_rows([slot], [req], row_cache, [first_token])
        return True

    def install_paged(self, req: Request, stacks, resident_row,
                      start_page: int, first_token: int, lease) -> bool:
        """Adopt an externally prefilled request from PAGE STACKS — the
        disaggregated handoff when both sides run the paged cache plane:
        only the non-shared page suffix crossed the channel; pages
        ``[0, start_page)`` map read-only from this pool's own interned
        prefix (held by ``lease``, whose ownership transfers to the slot
        on success).  Returns False (lease untouched) when no slot is
        free or the pool is exhausted — the caller requeues."""
        from repro.serve.kvpool import PoolExhausted, request_ctx_key
        free = self.free_slots()
        if not free:
            return False
        slot = free[0]
        try:
            self.pool.admit(slot, lease, len(req.prompt), req.max_new_tokens,
                            tenant=getattr(req, "tenant", None))
        except PoolExhausted:
            return False
        self.pool.install_stacks(slot, req.prompt, request_ctx_key(req),
                                 stacks, start_page)
        if resident_row is not None and jax.tree.leaves(resident_row):
            from repro.models.cache_utils import merge_cache_slots
            self.resident = merge_cache_slots(
                self.resident, resident_row, self._resident_axes, [slot])
        self._post_install([slot], [req], [first_token])
        return True

    def export_slot(self, slot: int) -> dict:
        """Snapshot a busy slot's full mid-decode state for migration
        (the cluster cache plane's drain-before-detach): the request, its
        decode cursor (``pos``/``cur_tok``), every WRITTEN page's data
        (positions ``[0, pos)`` — all mapped by the decode invariant) and
        the slot's resident cache row.  Read-only: the caller drops the
        slot only after a successful adopt on the destination."""
        from repro.models.cache_utils import slice_cache_slots
        req = self.slot_req[slot]
        assert req is not None and self._paged, \
            "slot export is page-granular (snapshot/dense slots requeue)"
        pos = int(self.pos[slot])
        P = self.pool.page_size
        n_pages = -(-pos // P)
        page_ids = np.asarray(self.pool.block_table[slot, :n_pages],
                              np.int32)
        assert not (page_ids == self.pool.sentinel).any(), \
            "written page unmapped — block-table invariant broken"
        resident_row = None
        if jax.tree.leaves(self.resident):
            resident_row = slice_cache_slots(self.resident,
                                             self._resident_axes, [slot])
        return {
            "req": req, "pos": pos, "cur_tok": int(self.cur_tok[slot]),
            "stacks": self.pool.read_pages(jnp.asarray(page_ids)),
            "resident": resident_row,
        }

    def adopt_slot(self, req: Request, stacks, resident_row, pos: int,
                   cur_tok: int) -> bool:
        """Adopt a MIGRATED in-flight request mid-decode (the other half
        of :meth:`export_slot`): admit it into a free slot, map its
        written pages (interning full prompt pages — the migrated prefix
        becomes shareable cache here too) and resume the decode cursor
        exactly where the source replica left it.  The request's token
        bookkeeping (``output``, TTFT stamps) is NOT re-run — decode
        continues, it does not restart.  Returns False (nothing changed)
        when no slot is free or page admission would exhaust the pool —
        the caller requeues for an ordinary cold restart instead."""
        from repro.serve.kvpool import (
            PoolExhausted,
            public_ctx_key,
            request_ctx_key,
        )
        free = self.free_slots()
        if not free or not self._paged:
            return False
        slot = free[0]
        ctx = request_ctx_key(req)
        alt = (public_ctx_key(req) if self.tenants.share_public(
            getattr(req, "tenant", DEFAULT_TENANT)) else None)
        lease = self.pool.lease(req.prompt, ctx, alt)
        try:
            self.pool.admit(slot, lease, len(req.prompt),
                            req.max_new_tokens,
                            tenant=getattr(req, "tenant", None))
        except PoolExhausted:
            self.pool.release_lease(lease)
            return False
        # the locally shared prefix maps from this pool's own interned
        # pages; only the remainder of the migrated stacks installs (and
        # its full prompt pages re-intern here — prefix migration rides
        # along with the slot)
        start = lease.pages
        if start:
            rows = jnp.arange(start, stacks[0].k.shape[0])
            stacks = [type(s)(k=s.k[rows], v=s.v[rows],
                              slot_pos=s.slot_pos[rows]) for s in stacks]
        self.pool.install_stacks(slot, req.prompt, ctx, stacks, start)
        if resident_row is not None and jax.tree.leaves(resident_row):
            from repro.models.cache_utils import merge_cache_slots
            self.resident = merge_cache_slots(
                self.resident, resident_row, self._resident_axes, [slot])
        self.slot_req[slot] = req
        self.pos[slot] = pos
        self.cur_tok[slot] = cur_tok
        migrate_decode(req, self.rec)
        return True

    def _admit_fallback(self, slot: int, req: Request):
        """Token-at-a-time admission: the prompt is consumed through the
        decode path (shared cache keeps slot shapes uniform).
        Non-positional slot state (recurrent ssm/hybrid state, encdec
        cross memory) must go back to init values first — unlike stale
        KV it is not masked by position."""
        if not self.model.decode_state_positional:
            from repro.models.cache_utils import (
                merge_cache_slots,
                strip_kv_nodes,
            )
            if self._paged:
                self.resident = merge_cache_slots(
                    self.resident, strip_kv_nodes(self._slot_init()),
                    self._resident_axes, [slot])
            else:
                self.cache = merge_cache_slots(
                    self.cache, self._slot_init(),
                    self._cache_axes, [slot])
        # request-scoped side state (encdec cross memory) still has to
        # land in the slot up front — the model says what, if anything
        mem = self.model.encode_cross_rows(
            self.params, [getattr(req, "src", None)], self.max_len)
        if mem is not None:
            from repro.models.cache_utils import install_cross_memory
            if self._paged:
                self.resident = install_cross_memory(self.resident, mem,
                                                     [slot])
            else:
                self.cache = install_cross_memory(self.cache, mem, [slot])
        self.slot_req[slot] = req
        self.pos[slot] = 0
        self.cur_tok[slot] = int(req.prompt[0]) if len(req.prompt) else 0
        req._prompt_cursor = 1  # type: ignore[attr-defined]

    def _admit(self):
        from repro.serve.kvpool import (
            PoolExhausted,
            public_ctx_key,
            request_ctx_key,
        )
        from repro.serve.serve_step import bucket_len
        free = self.free_slots()
        staged: List[tuple] = []        # chunked-eligible (slot, req, lease)
        taken = [0]                     # free-slot cursor
        blocked = [0]                   # attempts refused by the pool

        def try_admit(req: Request) -> bool:
            # the scheduler's resource gate: bind the next free slot and
            # reserve pool pages.  False = blocked (pool/quota) — the
            # scheduler scans PAST this request, so a huge blocked prompt
            # no longer head-of-line-blocks a small one that would fit
            slot = free[taken[0]]
            chunkable = (self.chunked
                         and 0 < len(req.prompt) <= self.max_len - 1)
            lease = None
            if self.pool is not None:
                ctx = request_ctx_key(req)
                alt = (public_ctx_key(req)
                       if chunkable and self.tenants.share_public(
                           getattr(req, "tenant", DEFAULT_TENANT))
                       else None)
                lease = (self.pool.lease(req.prompt, ctx, alt) if chunkable
                         else self.pool.empty_lease())
                try:
                    self.pool.admit(slot, lease, len(req.prompt),
                                    req.max_new_tokens,
                                    tenant=getattr(req, "tenant", None))
                except PoolExhausted:
                    self.pool.release_lease(lease)
                    blocked[0] += 1
                    return False
            taken[0] += 1
            req.started_at = req.started_at or time.monotonic()
            mark_admitted(req, slot=slot,
                          prefix_hit=lease.tokens if lease else 0)
            if chunkable:
                staged.append((slot, req, lease))
            else:
                self._admit_fallback(slot, req)
                open_decode(self.rec, req)
            return True

        if free and self.queue:
            self.scheduler.select(self.queue, try_admit, budget=len(free))
        # same-bucket prompts admitted this tick share one invocation;
        # prefix hits group by their SUFFIX bucket (their shared pages are
        # already mapped — only the divergent tail runs), cold prompts by
        # their full bucket through the ordinary prefill program
        cold: Dict[int, List[tuple]] = {}
        warm: Dict[int, List[tuple]] = {}
        for slot, req, lease in staged:
            hit = lease.tokens if lease is not None else 0
            if hit:
                b = bucket_len(len(req.prompt) - hit, self.prefill_chunk,
                               self.max_len)
                warm.setdefault(b, []).append((slot, req, lease))
            else:
                b = bucket_len(len(req.prompt), self.prefill_chunk,
                               self.max_len)
                cold.setdefault(b, []).append((slot, req, lease))
        for _, group in sorted(cold.items()):
            self._prefill_group(group)
        for _, group in sorted(warm.items()):
            if self._paged:
                self._extend_group(group)
            else:
                self._restore_group(group)
        return taken[0], blocked[0]

    # -- one decode step over all busy slots -----------------------------
    def step(self) -> int:
        """Admit what fits, then one decode step over the busy slots.

        Profiler annotations (phases) nest as ``step`` > ``admit`` >
        ``prefill:cold|warm`` > ``prefill.wait``, then ``step`` >
        ``decode_step`` > ``decode.prep``, ``decode.wait``, then
        ``step`` > ``decode.post``.  The recorder's ring gets one
        ``step`` span per call that found work, with ``busy`` (rows
        decoded), ``admitted``, ``blocked`` (admissions the pool
        refused) and ``wait_s`` (seconds in the wait phases)."""
        rec = self.rec
        wait0 = rec.wait_s
        with rec.phase("step") as ph:
            with rec.phase("admit"):
                admitted, blocked = self._admit()
            busy = [s for s in range(self.B) if self.slot_req[s] is not None]
            if busy:
                with rec.phase("decode_step") as dec:
                    with rec.phase("decode.prep"):
                        toks = self._dispatch_decode(busy)
                    with rec.phase("decode.wait", wait=True):
                        toks = np.asarray(toks)   # device step complete
                rec.add_complete("decode_step", dec.ts, dec.dur,
                                 busy=len(busy))
                rec.record("decode_step_s", dec.dur)
                with rec.phase("decode.post"):
                    self._advance_slots(busy, toks)
        if busy or blocked:
            rec.add_complete("step", ph.ts, ph.dur, busy=len(busy),
                             admitted=admitted, blocked=blocked,
                             wait_s=rec.wait_s - wait0)
        return len(busy)

    def _dispatch_decode(self, busy: List[int]):
        """Upload the step's inputs and dispatch the jitted decode step;
        returns its tokens, still on the device."""
        batch = {
            "tokens": jnp.asarray(self.cur_tok[:, None]),
            "pos": jnp.asarray(self.pos),
        }
        self._rng, sub = jax.random.split(self._rng)
        if self._paged:
            # map the page each busy slot is about to write (drawn from
            # the pocket its admission reserved — cannot fail mid-decode)
            for s in busy:
                self.pool.ensure_decode_page(s, int(self.pos[s]))
            # width-trim the block table to the pow2 page bucket covering
            # the deepest busy slot: the paged kernel's page walk then
            # scales with occupancy, not max_len (compiled variants stay
            # O(log n_logical))
            n_act = max(int(self.pos[s]) // self.pool.page_size + 1
                        for s in busy)
            width = min(1 << (n_act - 1).bit_length(), self.pool.n_logical)
            toks, self.pool.arena, self.pool.kv_scales, self.resident = \
                self._step(
                    self.params, self.pool.arena, self.pool.kv_scales,
                    self.resident,
                    jnp.asarray(self.pool.block_table[:, :width]),
                    batch, sub,
                )
        else:
            toks, _logits, self.cache = self._step(self.params, self.cache,
                                                   batch, sub)
        self.decode_invocations += 1
        return toks

    def _advance_slots(self, busy: List[int], toks: np.ndarray):
        """Hand each busy slot its token (or its next prompt token) and
        finish the requests that are done."""
        now = time.monotonic()
        for s in busy:
            req = self.slot_req[s]
            self.pos[s] += 1
            cursor = getattr(req, "_prompt_cursor", len(req.prompt))
            if cursor < len(req.prompt):
                if self.pos[s] >= self.max_len - 1:
                    # prompt overran the cache: fail fast instead of
                    # spinning forever past the last writable slot
                    self._finish(req, now, slot=s)
                    continue
                # still consuming the prompt: feed next prompt token
                self.cur_tok[s] = int(req.prompt[cursor])
                req._prompt_cursor = cursor + 1  # type: ignore[attr-defined]
                continue
            tok = int(toks[s])
            if not req.output:
                req.first_token_at = now
            req.output.append(tok)
            self.cur_tok[s] = tok
            finished = (
                len(req.output) >= req.max_new_tokens
                or (self.eos is not None and tok == self.eos)
                or self.pos[s] >= self.max_len - 1
            )
            if finished:
                self._finish(req, now, slot=s)

    def drop_slot(self, slot: int) -> Optional[Request]:
        """Evict a slot's request WITHOUT finishing it (detach/requeue
        path): clears the slot and releases its pool pages; the caller
        owns the request's re-homing."""
        req = self.slot_req[slot]
        self.slot_req[slot] = None
        if self.pool is not None:
            self.pool.release_slot(slot)
        return req

    def run_until_drained(self, max_steps: int = 100_000) -> List[Request]:
        steps = 0
        while (self.queue or any(r is not None for r in self.slot_req)) and steps < max_steps:
            self.step()
            steps += 1
        return self.done
