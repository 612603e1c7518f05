"""Disaggregated serving: a prefill cell feeding decode-cell replicas.

The paper's "isolate first, then share on demand" applied to inference::

                                    +--kv channel-->  [ decode cell 0 ]
    requests ->  [ prefill cell ]---+                    continuous
                 whole prompts,     +--kv channel-->  [ decode cell 1 ]
                 batched bucket        per-request KV    batching
                 invocations           rows + meta

Each cell is a subOS: it owns its zone/mesh outright and compiles its own
programs.  The ONLY coupling is the on-demand KV channels opened through
the supervisor — prefill never touches a decode cell's devices except
through ``send_kv`` (device_put onto that decode mesh), mirroring RFcom's
explicit resource-sharing surface.

Why disaggregate: prefill is compute-bound over whole prompts, decode is
latency-bound per token.  Co-scheduling them on one cell head-of-line
blocks decode steps behind prompt processing; isolating prefill keeps TPOT
flat while TTFT scales with prefill-cell capacity.  Decode capacity scales
out *declaratively*: a decode :class:`~repro.core.spec.CellSpec` with
``replicas=N`` materializes N uniform decode cells and the server routes
each request to the replica with the most free slots (per-request routing,
round-robin on ties).  Same-bucket prompts waiting together are prefilled
in ONE batched program invocation (see ``run_prefill_prompts``).

Weight placement: every cell needs the same parameters.  Cells that have
none sync them over on-demand array channels at construction time — decode
replica 0 is the source of truth, further replicas and the prefill cell
pull from it (share-on-demand for weights, too).

The elastic :class:`~repro.core.elastic.ReconcilePolicy` can rebalance
columns between the prefill and decode specs from live TTFT/TPOT
accounting (see ``benchmarks/disagg_serving.py``) AND autoscale the
decode spec's ``replicas`` from queue depth + TPOT tail;
:meth:`DisaggServer.sync` then live-attaches/detaches replicas so the
serving surface follows the spec while traffic flows — the
:class:`~repro.core.daemon.SupervisorDaemon` closes that loop on a
timer with zero manual primitive calls.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Union

import jax
import numpy as np

from repro.core.telemetry import (
    HistogramSketch,
    chrome_trace,
    collect_traces,
    finish_request,
    mark_admitted,
    open_request,
    recorder_of,
    requeue_request,
    span_group,
    write_trace,
)
from repro.serve.batcher import ContinuousBatcher, Request
from repro.serve.serve_step import (
    build_prefill_step,
    bucket_len,
    run_prefill_group,
    supports_chunked_prefill,
)


class PrefillWorker:
    """Runs bucket-padded prefill programs on a (prefill) cell.

    When the model's cache plane is pageable the worker also keeps a
    slot-less :class:`~repro.serve.kvpool.KVPool` as a prefix CACHE: a
    prompt whose leading chunks match an interned prefix runs only its
    suffix through one NATIVE paged ``prefill_extend`` invocation — the
    lease's pages plus freshly allocated temp pages form the row's block
    table, the suffix K/V lands directly in the arena (no dense prefix
    gather), and full pages intern afterwards by ownership transfer
    (``intern_arena_pages``).  The shared chunks' prefill compute is
    skipped entirely (``prefix_hit_tokens`` on the prefill cell's
    accounting), independent of what the decode side has cached.
    """

    def __init__(self, cell, *, max_len: int, chunk: int = 32,
                 temperature: float = 0.0, pool_pages: Optional[int] = None,
                 page_size: int = 16, tenants=None):
        from repro.serve.kvpool import KVPool
        from repro.serve.tenancy import TenantRegistry
        if not supports_chunked_prefill(cell.model, max_len):
            # every family chunks exactly now; only a rolling SWA cache
            # layout (sliding_window < max_len) lands here.  DisaggServer
            # checks the same capability first and degrades to its
            # token-at-a-time fallback instead of constructing a worker.
            raise ValueError(
                f"config {cell.model.cfg.name!r} has no exact chunked "
                f"prefill at max_len={max_len} (rolling sliding-window "
                "cache would shift real tokens out behind the pad tail)"
            )
        if cell.serve_params is None:
            cell.init_serve()
        self.cell = cell
        self.model = cell.model
        self.max_len = max_len
        self.chunk = chunk
        self.temperature = temperature
        self.tenants = (tenants if isinstance(tenants, TenantRegistry)
                        else TenantRegistry(tenants or ()))
        quota_fn = (self.tenants.page_quotas
                    if any(t.page_quota is not None
                           for t in self.tenants.specs.values()) else None)
        cap = KVPool.capability(self.model, max_len, page_size)
        self.pool = (KVPool(self.model, max_len=max_len, num_pages=pool_pages,
                            page_size=page_size, accounting=cell.accounting,
                            quotas=quota_fn)
                     if cap != "none" else None)
        self._snapshot = cap == "snapshot"
        if self._snapshot:
            # checkpoint boundaries live at page_size multiples, so every
            # prefill bucket must be page-aligned: coarsen the bucket
            # quantum to lcm(chunk, page_size) (the max_len cap stays
            # aligned — snapshot pools require page-divisible max_len)
            self.chunk = int(np.lcm(chunk, page_size))
        # snapshot families prefill with per-chunk boundary checkpoints
        # enabled so cold prompts feed both the worker's prefix cache and
        # the handoff chain the decode pool interns
        self._step = jax.jit(build_prefill_step(
            self.model, temperature,
            checkpoint_every=page_size if self._snapshot else None))
        self._extend = None
        self._scratch_caches: Dict[int, object] = {}
        self._axes = None
        self._rng = jax.random.PRNGKey(0)
        self.invocations = 0
        self.rec = recorder_of(cell.accounting)

    def _scratch(self, batch: int):
        if batch not in self._scratch_caches:
            self._scratch_caches[batch] = self.model.init_cache(batch, self.max_len)
        return self._scratch_caches[batch]

    def _cold_group(self, group, out):
        """ONE cold prefill invocation over same-bucket requests, interned
        into the prefix cache and emitted through :meth:`_payload`.

        Snapshot families additionally slice the invocation's boundary
        checkpoints into per-chunk chain payloads: the chain interns into
        THIS worker's tree (the next same-prefix prompt prefills warm)
        and rides the handoff so the decode replica's pool can intern it
        too (the next same-prefix REQUEST routes warm cluster-wide)."""
        from repro.serve.kvpool import (
            build_snapshot_payloads,
            request_ctx_key,
        )
        with self.rec.phase("prefill:cold") as ph:
            toks, cache, self._rng, _b_pad = run_prefill_group(
                self._step, self.cell.serve_params, self._scratch, group,
                chunk=self.chunk, max_len=self.max_len, rng=self._rng,
                model=self.model, accounting=self.cell.accounting,
            )
        span_group(self.rec, "prefill", group, ph.ts, ph.ts + ph.dur,
                   kind="cold", batch=len(group))
        self.rec.record("prefill_s", ph.dur)
        ckpts = None
        if self._snapshot:
            cache, ckpts = cache
        self.invocations += 1
        for i, (req, tok) in enumerate(zip(group, toks)):
            if self._snapshot:
                chain = build_snapshot_payloads(
                    self.model, self.pool.axes, self.pool.page_size,
                    req.prompt, cache, ckpts, i)
                if chain:
                    self.pool.intern_snapshots(
                        req.prompt, request_ctx_key(req), chain,
                        tenant=getattr(req, "tenant", None))
                out[req.rid] = (req, tok,
                                {"row": self._dense_row(cache, i),
                                 "chain": chain})
                continue
            if self.pool is not None:
                self.pool.intern_rows(req.prompt, request_ctx_key(req),
                                      cache, i,
                                      tenant=getattr(req, "tenant", None))
            out[req.rid] = (req, tok, self._payload(cache, i, req))

    def _dense_row(self, cache, row: int):
        from repro.models.cache_utils import slice_cache_slots
        return slice_cache_slots(cache, self._axes, [row])

    def _warm_snapshot_group(self, group, out):
        """Warm snapshot prefill: restore each request's deepest interned
        boundary state into a scratch row (plus the chain's
        shared-attention pages for hybrid), then ONE dense suffix-extend
        over the group — the shared prefix replays in O(1) instead of
        re-running its chunks.  The handoff payload is the 1-row cache
        WITHOUT a chain (nothing new was computed below the boundary), so
        a warm handoff ships strictly fewer bytes than a cold one."""
        from repro.models.cache_utils import (
            cache_batch_axes,
            clear_kv_row,
            load_pages_into_row,
        )
        from repro.serve.serve_step import build_extend_step
        if self._axes is None:
            self._axes = cache_batch_axes(self.model, 1, self.max_len)
        if self._extend is None:
            self._extend = jax.jit(
                build_extend_step(self.model, self.temperature))
        P = self.pool.page_size
        B = len(group)
        b_pad = 1 << (B - 1).bit_length()
        cache = self._scratch(b_pad)
        for i, (req, lease) in enumerate(group):
            state, stacks = self.pool.snapshot_chain(lease)
            if self.pool.axes:
                cache = clear_kv_row(cache, self.pool.axes, i)
            if state is not None:
                cache = self.model.restore_state_row(cache, state, i)
            if stacks:
                cache = load_pages_into_row(cache, cache, self.pool.axes,
                                            i, stacks, 0, P)
        s_pad = bucket_len(
            max(len(r.prompt) - le.tokens for r, le in group),
            self.chunk, self.max_len)
        tokens = np.zeros((b_pad, s_pad), np.int32)
        length = np.zeros((b_pad,), np.int32)
        pos = np.full((b_pad,), self.max_len, np.int32)
        for i, (req, lease) in enumerate(group):
            suf = req.prompt[lease.tokens:]
            tokens[i, :len(suf)] = suf
            length[i] = len(suf)
            pos[i] = lease.tokens
        import jax.numpy as jnp
        batch = {
            "tokens": jnp.asarray(tokens),
            "pos": jnp.asarray(pos),
            "length": jnp.asarray(length),
        }
        self._rng, sub = jax.random.split(self._rng)
        t0 = self.rec.clock()
        toks, _logits, cache = self._extend(self.cell.serve_params, cache,
                                            batch, sub)
        toks = np.asarray(toks)
        t1 = self.rec.clock()
        span_group(self.rec, "prefill", [r for r, _ in group], t0, t1,
                   kind="warm_snapshot", batch=len(group),
                   hit_tokens=sum(le.tokens for _, le in group))
        self.rec.record("prefill_s", t1 - t0)
        self.invocations += 1
        for i, (req, lease) in enumerate(group):
            out[req.rid] = (req, int(toks[i]),
                            {"row": self._dense_row(cache, i),
                             "chain": None})
            self.pool.release_lease(lease)

    def _payload(self, cache, row: int, req: Request):
        """The per-request handoff artifact: with a pool, a dict of FULL-
        prompt canonical page stacks (floats — an int8 arena dequantizes
        on read) plus the 1-row resident remainder, so ``pump`` can slice
        from any replica's shared-prefix depth without a dense row; with
        no pool, the legacy dense 1-row cache."""
        from repro.models.cache_utils import (
            extract_row_pages,
            slice_cache_slots,
            strip_kv_nodes,
        )
        if self.pool is None:
            return slice_cache_slots(cache, self._axes, [row])
        P = self.pool.page_size
        n_total = -(-len(req.prompt) // P)
        res = strip_kv_nodes(cache)
        if jax.tree.leaves(res):
            res = slice_cache_slots(res, strip_kv_nodes(self._axes), [row])
        return {
            "stacks": extract_row_pages(cache, self.pool.axes, row, 0,
                                        n_total, P),
            "resident": res,
        }

    def prefill_many(self, reqs: Sequence[Request]):
        """Prefill a batch of requests, ONE invocation per pad bucket.

        Batch dims are padded to the next power of two (dummy rows masked
        and discarded, their waste accounted) — see ``run_prefill_group``.
        Prefix-cache hits group by their SUFFIX bucket (mixed hit depths
        share one NATIVE paged extend: prefix pages + temp pages form
        each row's block table, suffix K/V lands in the arena directly)
        and every computed full page is interned for the next prompt by
        ownership transfer.  Returns ``[(req, first_token, payload),
        ...]`` in input order — ``payload`` covers the FULL prompt KV
        (see :meth:`_payload`)."""
        from repro.models.cache_utils import cache_batch_axes, slice_cache_slots
        from repro.serve.kvpool import (
            PoolExhausted,
            build_paged_extend_step,
            public_ctx_key,
            request_ctx_key,
            run_extend_group,
        )
        from repro.serve.tenancy import DEFAULT_TENANT
        if self._axes is None:
            self._axes = cache_batch_axes(self.model, 1, self.max_len)
        cold: Dict[int, List[Request]] = {}
        warm: Dict[int, List[tuple]] = {}
        for req in reqs:
            L = len(req.prompt)
            if not 0 < L <= self.max_len - 1:
                raise ValueError(
                    f"prompt length {L} does not fit max_len={self.max_len}")
            alt = (public_ctx_key(req) if self.tenants.share_public(
                getattr(req, "tenant", DEFAULT_TENANT)) else None)
            lease = (self.pool.lease(req.prompt, request_ctx_key(req), alt)
                     if self.pool is not None else None)
            if self.pool is not None:
                # prefill-side hits are skipped COMPUTE (the bytes-saved
                # ledger belongs to the decode plane's pools)
                self.pool.note_lookup(L, lease.tokens,
                                      accounting=self.cell.accounting,
                                      saved_bytes=False)
            if lease is not None and lease.pages:
                b = bucket_len(L - lease.tokens, self.chunk, self.max_len)
                warm.setdefault(b, []).append((req, lease))
            else:
                if lease is not None:
                    self.pool.release_lease(lease)
                cold.setdefault(bucket_len(L, self.chunk, self.max_len), []
                                ).append(req)
        out = {}
        for _, group in sorted(cold.items()):
            self._cold_group(group, out)
        for _, group in sorted(warm.items()):
            if self._snapshot:
                self._warm_snapshot_group(group, out)
                continue
            if self._extend is None:
                self._extend = jax.jit(
                    build_paged_extend_step(self.model, self.temperature,
                                            template=self.pool.template),
                    donate_argnums=(1, 2, 3),
                )
            greqs = [r for r, _ in group]
            leases = [le for _, le in group]
            P = self.pool.page_size
            # temp pages back the suffix writes (lease depth through the
            # prompt's last page); exhaustion demotes the whole group to
            # the cold path — nothing is held on the failure
            temps: List[List[int]] = []
            try:
                for req, lease in group:
                    n_t = -(-len(req.prompt) // P) - lease.pages
                    temps.append(self.pool.alloc_temp_pages(
                        n_t, tenant=getattr(req, "tenant", None)))
            except PoolExhausted:
                for t, (req, _le) in zip(temps, group):
                    self.pool.free_temp_pages(
                        t, tenant=getattr(req, "tenant", None))
                for _, lease in group:
                    self.pool.release_lease(lease)
                regroup: Dict[int, List[Request]] = {}
                for req, _le in group:
                    regroup.setdefault(
                        bucket_len(len(req.prompt), self.chunk,
                                   self.max_len), []).append(req)
                for _, g in sorted(regroup.items()):
                    self._cold_group(g, out)
                continue
            bt_rows = np.full((len(group), self.pool.n_logical),
                              self.pool.sentinel, np.int32)
            for i, (req, lease) in enumerate(group):
                for lp, node in enumerate(lease.nodes):
                    bt_rows[i, lp] = node.page
                for j, pg in enumerate(temps[i]):
                    bt_rows[i, lease.pages + j] = pg
            with self.rec.phase("prefill:warm") as ph:
                toks, rows, self._rng, _b_pad = run_extend_group(
                    self._extend, self.cell.serve_params, self._scratch,
                    self.pool, greqs, leases, bt_rows, chunk=self.chunk,
                    max_len=self.max_len, rng=self._rng, model=self.model,
                    accounting=self.cell.accounting,
                )
            span_group(self.rec, "prefill", greqs, ph.ts, ph.ts + ph.dur,
                       kind="warm", batch=len(group),
                       hit_tokens=sum(le.tokens for le in leases))
            self.rec.record("prefill_s", ph.dur)
            self.invocations += 1
            from repro.models.cache_utils import strip_kv_nodes
            for i, (req, tok) in enumerate(zip(greqs, toks)):
                # snapshot the FULL prompt pages (prefix + fresh suffix)
                # BEFORE interning may free/recycle the temp pages
                page_ids = ([n.page for n in leases[i].nodes] + temps[i])
                stacks = self.pool.read_pages(np.asarray(page_ids, np.int32))
                res = rows
                if jax.tree.leaves(res):
                    res = slice_cache_slots(
                        res, strip_kv_nodes(self._axes), [i])
                payload = {"stacks": stacks, "resident": res}
                # intern the freshly written suffix pages by ownership
                # transfer, THEN drop the lease (the pinned prefix keeps
                # the walk safe).  A FOREIGN (public-grant) hit never
                # interns — intern_arena_pages frees every temp instead
                self.pool.intern_arena_pages(
                    req.prompt, request_ctx_key(req), leases[i], temps[i],
                    tenant=getattr(req, "tenant", None))
                self.pool.release_lease(leases[i])
                out[req.rid] = (req, tok, payload)
        self.cell.heartbeat()
        return [out[r.rid] for r in reqs]

    def prefill(self, req: Request):
        """One request -> (first_token, 1-row KV cache)."""
        (_, tok, row_cache), = self.prefill_many([req])
        return tok, row_cache


class _DecodeReplica:
    """One decode cell's serving surface: batcher + KV channel + shardings."""

    def __init__(self, cell, channel, batcher, kv_shardings):
        self.cell = cell
        self.channel = channel
        self.batcher = batcher
        self.kv_shardings = kv_shardings
        self.inflight: Dict[int, Request] = {}   # rid -> sent, not installed
        # rid -> PrefixLease on THIS replica's pool, acquired when the
        # suffix was routed (pins the shared pages against eviction until
        # install transfers them to the slot)
        self.leases: Dict[int, object] = {}
        self.drained = False            # state already handed to survivors

    @property
    def pool(self):
        return self.batcher.pool

    def free_capacity(self) -> int:
        # queued-but-unslotted requests (token-at-a-time fallback) hold
        # capacity just like in-flight KV rows do
        return (len(self.batcher.free_slots()) - len(self.inflight)
                - len(self.batcher.queue))

    def pool_admittable(self, req: Request, lease) -> bool:
        """Can this replica's pool cover ``req``'s worst case right now
        (counting reclaimable refcount-0 prefixes as available)?  Under
        quotas the answer is scoped to the REQUEST's tenant pocket: an
        adversary having drained its own pocket never makes a victim's
        admission look blocked."""
        if self.pool is None:
            return True
        need = self.pool.required_pages(
            len(req.prompt), req.max_new_tokens,
            lease.pages if lease is not None else 0)
        return need <= self.pool.available_pages(
            getattr(req, "tenant", None))


class DisaggServer:
    """Prefill cell -> KV channels -> decode replica(s), one submit() front.

    ``decode_cells`` is a cell name or a list of replica cell names (e.g.
    ``spec.cell("decode").instances()``).  Each replica's batcher runs
    with ``prefill_chunk=None`` — it NEVER chunk-prefills on its own;
    requests normally arrive as KV rows over its channel.  TTFT is the
    (possibly batched) prefill invocation + one channel transfer; TPOT is
    pure decode.  Configs with no exact chunked prefill at this
    ``max_len`` (rolling SWA caches — see ``supports_chunked_prefill``)
    DEGRADE instead of crashing: ``pump`` routes their prompts straight
    onto replica queues for token-at-a-time consumption and the prefill
    cell's accounting records ``prefill_fallback_requests``.

    The replica set is LIVE: after a reconcile changes the decode spec's
    ``replicas`` or recovers a failed instance, :meth:`sync` converges
    the serving surface to the spec — attach opens the KV channel, fans
    the weights out on demand and builds a fresh batcher; detach drains
    the replica's slots, requeues its in-flight requests onto ``pending``
    (no request is ever lost to a scale-down or a dead cell) and closes
    its channel.  :meth:`pump` reaps dead replicas the same way, so a
    mid-traffic column failure degrades to the surviving replicas
    instead of leaking the victim's requests.
    """

    def __init__(self, supervisor, prefill_cell: str,
                 decode_cells: Union[str, Sequence[str]], *,
                 batch_slots: int, max_len: int, chunk: int = 32,
                 temperature: float = 0.0, eos_token: Optional[int] = None,
                 page_size: int = 16, pool_pages: Optional[int] = None,
                 tenants=None, shed_queue: Optional[int] = None,
                 quantum: int = 256, migrate: bool = False):
        from repro.serve.cacheplane import CachePlane
        from repro.serve.tenancy import TenantRegistry, TenantScheduler
        if isinstance(decode_cells, str):
            decode_cells = [decode_cells]
        if not decode_cells:
            raise ValueError("need at least one decode cell")
        self.sup = supervisor
        self.prefill_cell = supervisor.cells[prefill_cell]
        self.max_len = max_len
        self.batch_slots = batch_slots
        self.chunk = chunk
        self.temperature = temperature
        self.eos_token = eos_token
        self.page_size = page_size
        self.pool_pages = pool_pages
        # spec name the decode instances materialize from ("dec/0" -> "dec")
        self._decode_base = decode_cells[0].split("/")[0]
        # tenant QoS: default to the decode spec's declared contract (the
        # supervisor-validated source of truth); token buckets + DRR run
        # HERE at the front door — replica batchers get the same registry
        # minus buckets, so one request is never rate-charged twice
        if tenants is None and supervisor.desired is not None \
                and supervisor.desired.has_cell(self._decode_base):
            tenants = supervisor.desired.cell(self._decode_base).tenants
        self.tenants: TenantRegistry = (
            tenants if isinstance(tenants, TenantRegistry)
            else TenantRegistry(tenants or ()))
        self.scheduler = TenantScheduler(self.tenants, quantum=quantum)
        self.shed_queue = shed_queue    # pending cap; None = never shed
        self.shed_requests = 0
        self.pending: deque = deque()
        self.rejected: List[Request] = []   # unservable, never routed
        self.requeued = 0               # requests re-homed off a detached replica
        self.blocked_on_pool = 0        # admissions deferred: pool exhausted
        self.blocked_by_tenant: Dict[str, int] = {}
        self.fallback_requests = 0      # served token-at-a-time (no worker);
                                        # server-owned so a prefill-cell
                                        # recovery can't zero the ledger
        self._done_detached: List[Request] = []  # served by since-gone replicas
        self._detached_stats = {"requests": 0, "decode_invocations": 0,
                                "kv_bytes": 0, "kv_transfers": 0,
                                "kv_seconds": 0.0,
                                "prefix_hit_tokens": 0,
                                "prefix_miss_tokens": 0,
                                "pages_evicted": 0, "kv_bytes_saved": 0,
                                "snapshots_interned": 0,
                                "snapshot_hit_tokens": 0,
                                "snapshot_bytes_saved": 0}
        # detached replicas' telemetry survives the same way their
        # counters do: the recorder's ring drains into an archive of
        # dumps (for trace_export) and its sketches merge into
        # _detached_hists (for stats()["telemetry"])
        self._detached_dumps: List[dict] = []
        self._detached_hists: Dict[str, HistogramSketch] = {}
        # cluster cache plane: a supervisor-held prefix index routes warm
        # prompts to the replica already holding their deepest prefix.
        # Live page/slot migration (drain-before-detach) is OPT-IN via
        # ``migrate=True``: it changes detach semantics (a victim's
        # slotted requests finish on survivors instead of requeueing) and
        # opens replica-to-replica "pages" channels on demand
        self.cacheplane = CachePlane(supervisor, page_size=page_size)
        self.migrate = migrate
        self.routed_warm = 0            # index-directed warm routings
        self.routed_cold = 0            # capacity-routed (no usable index hit)
        self.pages_migrated = 0         # prefix pages re-interned on survivors
        self.drain_handoffs = 0         # in-flight slots adopted by survivors
        if migrate:
            supervisor.add_drain_hook(self._drain_hook)

        primary = supervisor.cells[decode_cells[0]]
        if primary.serve_params is None:
            primary.init_serve()
        # share-on-demand weight sync: the prefill cell pulls params from
        # the primary decode cell over an array channel (replicas sync
        # the same way inside _attach)
        if self.prefill_cell.serve_params is None:
            self._sync_weights(prefill_cell, decode_cells[0])
        if supports_chunked_prefill(self.prefill_cell.model, max_len):
            self.worker: Optional[PrefillWorker] = PrefillWorker(
                self.prefill_cell, max_len=max_len, chunk=chunk,
                temperature=temperature, page_size=page_size,
                pool_pages=pool_pages, tenants=self.tenants,
            )
        else:
            # degraded-but-serving: configs the batcher would silently run
            # token-at-a-time (rolling SWA cache) used to CRASH here via
            # the PrefillWorker guard.  Route their prompts straight onto
            # the decode replicas' queues instead, and say so loudly in
            # the prefill cell's accounting.
            self.worker = None
            self.prefill_cell.accounting.record_counter("prefill_fallback")
        self.replicas: List[_DecodeReplica] = []
        for name in decode_cells:
            self._attach(name)
        self._refresh_index()

    # -- replica lifecycle ---------------------------------------------
    def _sync_weights(self, dst_name: str, src_name: str):
        """On-demand weight fan-out: ``dst`` pulls params from ``src``
        over a supervisor array channel (opened if not already there)."""
        dst = self.sup.cells[dst_name]
        src = self.sup.cells[src_name]
        if src.serve_params is None:
            # fanning out None would mark dst "running" while unservable
            raise ValueError(
                f"weight source {src_name!r} holds no params to fan out")
        wch = (self.sup.find_channel(src_name, dst_name, "array")
               or self.sup.open_channel(src_name, dst_name, kind="array"))
        shardings = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(dst.mesh, s),
            dst.model.params_pspecs(),
        )
        wch.send(src.serve_params, shardings)
        dst.serve_params = wch.recv()
        if dst.status == "created":     # params in hand: it is serving now
            dst.status = "running"

    def _weight_source(self) -> Optional[str]:
        """First live replica holding params, else the prefill cell if it
        holds any — None when nothing can be fanned out yet."""
        for rep in self.replicas:
            if rep.cell.serve_params is not None and rep.cell.status == "running":
                return rep.cell.name
        if self.prefill_cell.serve_params is not None:
            return self.prefill_cell.name
        return None

    def _attach(self, name: str) -> Optional[_DecodeReplica]:
        """Bring a decode cell into the serving surface: weight fan-out
        (if it has no params yet), KV channel, fresh batcher.  Returns
        None when no weight source exists yet (a later sync retries)."""
        cell = self.sup.cells[name]
        if cell.serve_params is None:
            src = self._weight_source()
            if src is None:
                return None
            self._sync_weights(name, src)
        ch = (self.sup.find_channel(self.prefill_cell.name, name, "kv")
              or self.sup.open_channel(self.prefill_cell.name, name, kind="kv"))
        batcher = cell.make_batcher(
            batch_slots=self.batch_slots, max_len=self.max_len,
            temperature=self.temperature, eos_token=self.eos_token,
            prefill_chunk=None, page_size=self.page_size,
            pool_pages=self.pool_pages,
            # replica-local admission reuses the tenant contract (page
            # quotas partition each replica's pool; the fallback queue
            # schedules fairly) but never re-charges the server-level
            # token buckets
            tenants=self.tenants.specs.values(), tenant_buckets=False,
        )
        kv_shardings = jax.tree.map(
            lambda s, m=cell.mesh: jax.sharding.NamedSharding(m, s),
            cell.model.cache_pspecs(1, self.max_len),
        )
        rep = _DecodeReplica(cell, ch, batcher, kv_shardings)
        self.replicas.append(rep)
        return rep

    def _requeue(self, req: Request):
        """Reset a request's serving state and put it back at the front
        of ``pending`` — it will be prefilled again from scratch on
        another replica.  ``submitted_at`` is kept, so its eventual TTFT
        honestly includes the disruption."""
        req.output.clear()
        req.started_at = None
        req.first_token_at = None
        req.finished_at = None
        if hasattr(req, "_prompt_cursor"):
            del req._prompt_cursor
        requeue_request(recorder_of(self.prefill_cell.accounting), req,
                        "requeued")
        self.pending.appendleft(req)
        self.requeued += 1

    def _detach(self, rep: _DecodeReplica) -> int:
        """Remove a replica from the serving surface, requeueing every
        request it held (in-flight on the channel or sitting in a slot).
        Returns the number of requests requeued."""
        self.replicas.remove(rep)
        # the replica's served history and counters must survive the
        # detach — ``done``/``stats`` are the front door's ledger, not
        # the batcher's (the re-attach channel is always a fresh one, so
        # nothing here is counted twice)
        self._done_detached.extend(rep.batcher.done)
        self._detached_stats["requests"] += len(rep.batcher.done)
        self._detached_stats["decode_invocations"] += rep.batcher.decode_invocations
        self._detached_stats["kv_bytes"] += rep.channel.bytes_sent
        self._detached_stats["kv_transfers"] += rep.channel.transfers
        self._detached_stats["kv_seconds"] += rep.channel.seconds
        if rep.pool is not None:
            ps = rep.pool.stats()
            for k in ("prefix_hit_tokens", "prefix_miss_tokens",
                      "pages_evicted", "kv_bytes_saved",
                      "snapshots_interned", "snapshot_hit_tokens",
                      "snapshot_bytes_saved"):
                self._detached_stats[k] += ps[k]
        n = 0
        for rid, req in list(rep.inflight.items()):
            # an in-flight suffix's shared-prefix lease pins pool pages;
            # drop it with the request so the pool ends the detach with
            # every refcount back at zero
            if rid in rep.leases and rep.pool is not None:
                rep.pool.release_lease(rep.leases.pop(rid))
            self._requeue(req)
            n += 1
        rep.inflight.clear()
        rep.leases.clear()
        for slot, req in enumerate(rep.batcher.slot_req):
            if req is not None:
                rep.batcher.drop_slot(slot)    # releases the slot's pages
                self._requeue(req)
                n += 1
        while rep.batcher.queue:            # token-at-a-time fallback queue
            self._requeue(rep.batcher.queue.pop())
            n += 1
        if rep.channel.open:
            rep.channel.close()
        # archive the victim's telemetry AFTER the requeues above closed
        # its open decode spans — the drained ring is this replica's
        # complete, final record
        dump = recorder_of(rep.cell.accounting).dump(reset=True)
        self._detached_dumps.append(dump)
        for k, hd in dump["hists"].items():
            h = HistogramSketch.from_dict(hd)
            if k in self._detached_hists:
                self._detached_hists[k].merge(h)
            else:
                self._detached_hists[k] = h
        return n

    # -- cluster cache plane -------------------------------------------
    def _refresh_index(self):
        """One advert round: live replicas advertise their interned roots
        to the supervisor-held prefix index (control-plane messages —
        metadata only, no pages move)."""
        self.cacheplane.refresh(
            {rep.cell.name: rep.pool for rep in self.replicas})

    def _pages_channel(self, src: _DecodeReplica, dst: _DecodeReplica):
        """Replica-to-replica page-migration channel, opened through the
        supervisor on first use (on-demand inter-subOS communication)."""
        s, d = src.cell.name, dst.cell.name
        return (self.sup.find_channel(s, d, "pages")
                or self.sup.open_channel(s, d, kind="pages"))

    def _drain_hook(self, cell_name: str):
        """Supervisor drain hook (``migrate=True``): runs from the
        reconciler's destroy branch, while the doomed cell and its
        channels are still live — the only window where a policy-driven
        scale-down can still move state off the victim."""
        for rep in self.replicas:
            if rep.cell.name == cell_name:
                self._drain(rep)
                return

    def _drain(self, rep: _DecodeReplica) -> int:
        """Live subOS resize: hand a doomed replica's hot state to the
        survivors BEFORE it detaches — interned prefix subtrees migrate
        over a "pages" channel to the survivor with the most free pool
        pages, and every slotted in-flight request's written pages +
        decode cursor are adopted by a survivor with a free slot, so the
        request keeps decoding instead of cold-restarting (no TTFT
        cliff).  Best-effort and idempotent: what cannot be placed is
        left for ``_detach`` to requeue the ordinary way.  Returns the
        number of requests handed off."""
        from repro.serve.cacheplane import migrate_prefixes
        if rep.drained or rep.pool is None:
            return 0
        rep.drained = True
        survivors = [r for r in self.replicas
                     if r is not rep and self._alive(r)
                     and r.pool is not None]
        if not survivors:
            return 0
        # hot prefixes -> the survivor with the most free pages (stable
        # replica order breaks ties, so migration is deterministic)
        dst = survivors[0]
        for r in survivors[1:]:
            if len(r.pool.free) > len(dst.pool.free):
                dst = r
        self.pages_migrated += migrate_prefixes(
            rep.pool, dst.pool, self._pages_channel(rep, dst))
        # in-flight slotted requests -> any survivor with a free slot.
        # Slot export is page-granular; snapshot-plane slots (dense rows,
        # no mid-decode boundary checkpoint) requeue via _detach instead
        # — their interned prefix chains DID just migrate above, so the
        # cold restart still prefills warm on the survivor
        if rep.pool.payload_kind != "page":
            return 0
        handoffs = 0
        for slot, req in enumerate(rep.batcher.slot_req):
            if req is None:
                continue
            if getattr(req, "_prompt_cursor",
                       len(req.prompt)) < len(req.prompt):
                continue        # mid-prompt fallback slot: requeue instead
            snap = rep.batcher.export_slot(slot)
            for r in survivors:
                if not r.batcher.free_slots():
                    continue
                ch = self._pages_channel(rep, r)
                ch.send_pages({"stacks": snap["stacks"],
                               "resident": snap["resident"]},
                              meta={"rid": req.rid, "pos": snap["pos"],
                                    "cur_tok": snap["cur_tok"]})
                env = ch.poll_pages()
                if r.batcher.adopt_slot(req, env.cache["stacks"],
                                        env.cache["resident"],
                                        env.meta["pos"],
                                        env.meta["cur_tok"]):
                    rep.batcher.drop_slot(slot)
                    handoffs += 1
                    break
        self.drain_handoffs += handoffs
        vrec = recorder_of(rep.cell.accounting)
        if vrec.enabled:
            t = vrec.clock()
            vrec.add_complete("drain", t, 0.0, handoffs=handoffs,
                              pages_migrated=self.pages_migrated)
        return handoffs

    def _refresh_prefill(self) -> bool:
        """Rebind to a prefill cell the supervisor replaced under us.

        A recover/recreate leaves ``self.prefill_cell`` pointing at the
        dead object: the worker would keep computing on the released
        zone, the NEW cell would never heartbeat (and be re-marked
        failed forever), and every KV channel would stay closed.  When
        the supervisor holds a different live cell under the same name,
        fan the weights back out to it and rebuild the worker.  The
        replicas' channels (bound to the old cell, closed by the
        recover) are reaped right after, and sync re-attaches them over
        the reconcile-opened fresh channels.
        """
        live = self.sup.cells.get(self.prefill_cell.name)
        if (live is self.prefill_cell or live is None
                or live.status in ("failed", "destroyed")):
            return False
        if live.serve_params is None:
            src = next((rep.cell.name for rep in self.replicas
                        if rep.cell.serve_params is not None
                        and rep.cell.status == "running"), None)
            if src is None:
                return False        # no weight source yet; retry later
            self._sync_weights(live.name, src)
        self.prefill_cell = live
        if self.worker is not None:
            self.worker = PrefillWorker(
                live, max_len=self.max_len, chunk=self.chunk,
                temperature=self.temperature, page_size=self.page_size,
                pool_pages=self.pool_pages, tenants=self.tenants,
            )
        return True

    def _reap_failed(self) -> int:
        """Detach replicas whose cell died under us (failed / destroyed /
        replaced by a recover) — their orphaned requests go back onto
        ``pending`` instead of leaking while ``_busy()`` spins forever."""
        self._refresh_prefill()
        n = 0
        for rep in list(self.replicas):
            if not self._alive(rep):
                n += self._detach(rep)
        return n

    def _alive(self, rep: _DecodeReplica) -> bool:
        return (self.sup.cells.get(rep.cell.name) is rep.cell
                and rep.cell.status not in ("failed", "destroyed")
                and rep.channel.open)

    def sync(self, spec, decode_spec: Optional[str] = None) -> dict:
        """Converge the replica set to ``spec`` (live attach/detach).

        Call after any reconcile that may have changed the decode spec's
        ``replicas`` or recovered a failed instance.  Replicas the spec
        no longer names (or whose cell object went stale) are detached —
        their requests requeue onto ``pending`` — and spec instances
        that exist as running cells but are not yet serving are attached
        (KV channel + weight fan-out + fresh batcher).  Cells the
        reconciler has not materialized yet are picked up by a later
        sync.  Returns ``{"attached": [...], "detached": [...],
        "requeued": n}``.
        """
        base = decode_spec or self._decode_base
        self._refresh_prefill()
        desired: List[str] = []
        if spec is not None and spec.has_cell(base):
            desired = spec.cell(base).instances()
        attached, detached, requeued = [], [], 0
        for rep in list(self.replicas):
            name = rep.cell.name
            if name in desired and self._alive(rep):
                continue
            if self.migrate and self._alive(rep):
                # spec-driven scale-down with the victim still live: hand
                # its hot prefixes and slotted requests to survivors so
                # the detach below finds (mostly) nothing to requeue.
                # Idempotent — the reconciler's drain hook may already
                # have run during apply().
                self._drain(rep)
            requeued += self._detach(rep)
            detached.append(name)
        current = {rep.cell.name for rep in self.replicas}
        for name in desired:
            cell = self.sup.cells.get(name)
            if (name in current or cell is None
                    or cell.status in ("failed", "destroyed")):
                continue
            if self._attach(name) is not None:
                attached.append(name)
        # the surface changed (or may have): re-advertise so the prefix
        # index never routes to a detached replica or misses a fresh one
        self._refresh_index()
        return {"attached": attached, "detached": detached,
                "requeued": requeued}

    # -- legacy single-replica surface ---------------------------------
    @property
    def decode_cell(self):
        return self.replicas[0].cell

    @property
    def batcher(self) -> ContinuousBatcher:
        return self.replicas[0].batcher

    @property
    def channel(self):
        return self.replicas[0].channel

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        req.submitted_at = req.submitted_at or time.monotonic()
        # disagg front door: the root "request" span opens on the PREFILL
        # cell (the first cell to touch the request); the handle rides
        # with the request across cells, its storage stays here
        open_request(recorder_of(self.prefill_cell.accounting), req)
        self.pending.append(req)

    def _route(self, capacity: Dict[int, int]) -> Optional[int]:
        """Pick the replica with the most free capacity; the LOWEST index
        wins ties (stable replica order), so routing is a pure function
        of observable state — no hidden round-robin cursor — and the same
        queue state always routes the same way.  Load still spreads:
        every placement debits ``capacity``, which re-ranks the next
        pick."""
        best, best_cap = None, 0
        for i in range(len(self.replicas)):
            if capacity[i] > best_cap:
                best, best_cap = i, capacity[i]
        return best

    def _route_paged(self, capacity: Dict[int, int], req: Request):
        """Cache-aware slot routing + page admission.

        Warm first: the supervisor-held prefix index names the replica
        already holding the request's deepest interned prefix; when that
        replica has a free slot and its pool admits the request, it wins
        — the lease re-maps the prefix pages instead of re-computing and
        re-streaming them, so with N replicas the aggregate hit rate
        stays at the single-replica level instead of ~1/N of it.  When
        no candidate advertises a chunk (or the warm pick is saturated)
        the request falls back to most-free-slots placement
        (:meth:`_route`), leasing wherever it lands.  Replicas that fail
        the pool check are skipped for THIS request only.  Returns
        (index, lease) or (None, None) when every replica is slot- or
        page-saturated (the caller blocks)."""
        from repro.serve.kvpool import public_ctx_key, request_ctx_key
        from repro.serve.tenancy import DEFAULT_TENANT
        ctx = request_ctx_key(req)
        alt = (public_ctx_key(req) if self.tenants.share_public(
            getattr(req, "tenant", DEFAULT_TENANT)) else None)
        # routing decision breadcrumbs for the request's "route" span
        self._last_route = {"warm": False, "depth": 0, "replica": None}

        def try_lease(i: int):
            rep = self.replicas[i]
            le = (rep.pool.lease(req.prompt, ctx, alt)
                  if rep.pool is not None else None)
            if rep.pool_admittable(req, le):
                capacity[i] -= 1
                return True, le
            if le is not None:
                rep.pool.release_lease(le)
            return False, None

        # warm path: deepest advertised prefix among replicas with slots
        cand = {r.cell.name: i for i, r in enumerate(self.replicas)
                if capacity[i] > 0 and r.pool is not None}
        if cand:
            keys = [ctx] + ([alt] if alt is not None else [])
            name, depth = self.cacheplane.best_replica(
                req.prompt, keys, list(cand))
            if name is not None and depth > 0:
                ok, le = try_lease(cand[name])
                if ok and le is not None and le.tokens > 0:
                    self.routed_warm += 1
                    self._last_route = {"warm": True, "depth": depth,
                                        "replica": name}
                    return cand[name], le
                if ok:   # admitted but the advert was stale (no hit):
                    self.routed_cold += 1
                    self._last_route = {"warm": False, "depth": 0,
                                        "replica": name}
                    return cand[name], le
        # cold path: most-free-slots, deterministic tie-break
        skipped: Dict[int, int] = {}
        pick, lease = None, None
        while True:
            i = self._route(capacity)
            if i is None:
                break
            ok, le = try_lease(i)
            if ok:
                pick, lease = i, le
                break
            skipped[i] = capacity[i]
            capacity[i] = 0
        capacity.update(skipped)
        if pick is not None:
            self.routed_cold += 1
            self._last_route = {"warm": False, "depth": 0,
                                "replica": self.replicas[pick].cell.name}
        return pick, lease

    def _block_on_pool(self, req: Request, deferred: List[Request]):
        """Defer a request whose page admission cannot be covered yet
        (blocking, never dropping); ``pump`` re-queues the whole deferred
        batch at the front of ``pending`` in ORIGINAL order, so blocked
        requests never lose their place to each other."""
        req.started_at = None
        requeue_request(recorder_of(self.prefill_cell.accounting), req,
                        "pool_blocked")
        deferred.append(req)
        self.blocked_on_pool += 1
        tenant = getattr(req, "tenant", None)
        if tenant is not None:
            self.blocked_by_tenant[tenant] = (
                self.blocked_by_tenant.get(tenant, 0) + 1)
            self.prefill_cell.accounting.record_counter(
                "blocked_on_pool", tenant=tenant)

    def pump(self) -> int:
        """Prefill waiting requests (up to the replicas' free capacity,
        batching same-bucket prompts into one invocation), stream their KV
        over the per-replica channels, and install arrivals into free
        slots.  Returns the number of requests installed.

        Unservable prompts (empty, or longer than the decode cache) are
        finished immediately with empty output rather than poisoning the
        loop — one bad request must not stall every other request."""
        from repro.serve.kvpool import public_ctx_key, request_ctx_key
        from repro.serve.tenancy import DEFAULT_TENANT
        self._reap_failed()
        deferred: List[Request] = []    # pool-blocked this tick, FIFO
        # unservable prompts (empty / overlong) are finished immediately
        # with empty output so per-replica stats only count routed traffic
        servable: List[Request] = []
        for req in self.pending:
            if 0 < len(req.prompt) <= self.max_len - 1:
                servable.append(req)
            else:
                req.started_at = req.started_at or time.monotonic()
                req.finished_at = time.monotonic()
                finish_request(req, ts=req.finished_at, outcome="rejected")
                self.rejected.append(req)
        if len(servable) != len(self.pending):
            self.pending = deque(servable)
        # overload shedding: past the pending cap, the LOW-weight tier
        # loses first (newest first within a tier) — the paying tenant's
        # backlog survives a free-tier flood
        if self.shed_queue is not None and len(self.pending) > self.shed_queue:
            victims = self.scheduler.shed_victims(
                self.pending, len(self.pending) - self.shed_queue)
            vids = {id(v) for v in victims}
            self.pending = deque(r for r in self.pending
                                 if id(r) not in vids)
            now = time.monotonic()
            for req in victims:
                req.finished_at = now
                finish_request(req, ts=now, outcome="shed")
                self.rejected.append(req)
                self.shed_requests += 1
                self.prefill_cell.accounting.record_counter(
                    "shed_requests", tenant=getattr(req, "tenant", None))
        capacity = {i: r.free_capacity() for i, r in enumerate(self.replicas)}
        budget = sum(c for c in capacity.values() if c > 0)

        def can_place(req: Request) -> bool:
            """Cheap admission pre-check for the fair scheduler: some
            replica has a free slot AND (on the paged plane) its pool can
            cover the request's hit-aware worst case within the request
            tenant's quota.  No pages are reserved here — the real lease
            and admission happen at routing — so a False just means the
            scheduler scans past this request this tick."""
            ctx = request_ctx_key(req)
            alt = (public_ctx_key(req)
                   if self.tenants.share_public(
                       getattr(req, "tenant", DEFAULT_TENANT))
                   else None)
            for i, rep in enumerate(self.replicas):
                if capacity[i] <= 0:
                    continue
                if rep.pool is None:
                    return True
                hit = len(rep.pool.tree.match(req.prompt, ctx))
                if alt is not None:
                    hit = max(hit, len(rep.pool.tree.match(req.prompt, alt)))
                need = rep.pool.required_pages(
                    len(req.prompt), req.max_new_tokens, hit)
                if need <= rep.pool.available_pages(
                        getattr(req, "tenant", None)):
                    return True
            return False

        taking: List[Request] = []

        def take(req: Request) -> bool:
            if not can_place(req):
                return False
            req.started_at = req.started_at or time.monotonic()
            taking.append(req)
            return True

        if budget > 0 and self.pending:
            # weighted-fair intake: DRR over tenants + per-tenant token
            # buckets, scanning past requests no replica can place yet
            self.scheduler.select(self.pending, take, budget=budget)
        if taking and self.worker is None:
            # token-at-a-time fallback: no chunked prefill program exists
            # for this config — hand each prompt to a replica's own queue,
            # where the decode loop consumes it one token per invocation
            for req in taking:
                i = self._route(capacity)
                assert i is not None, "capacity budget guarantees a replica"
                capacity[i] -= 1
                self.replicas[i].batcher.submit(req)
            self.fallback_requests += len(taking)
            self.prefill_cell.accounting.record_counter(
                "prefill_fallback_requests", len(taking))
        elif taking:
            import jax.numpy as jnp
            prec = recorder_of(self.prefill_cell.accounting)
            for req in taking:
                mark_admitted(req)      # queue wait ends: prefill begins
            # fresh adverts before routing: what each replica interned
            # since the last pump is exactly what warm routing needs
            self._refresh_index()
            for req, tok, row_cache in self.worker.prefill_many(taking):
                root = getattr(req, "_tspans", {}).get("request")
                rspan = prec.start_span("route", trace_id=req.rid,
                                        parent=root.ctx if root else None)
                i, lease = self._route_paged(capacity, req)
                rspan.end(**self._last_route,
                          blocked=(i is None))
                if i is None:
                    # every replica is slot- or page-saturated right now:
                    # block (prefix pages the prefill cell just interned
                    # make the retry cheap) instead of overrunning a pool
                    self._block_on_pool(req, deferred)
                    continue
                rep = self.replicas[i]
                if rep.pool is None:
                    st = rep.channel.send_kv(
                        row_cache, rep.kv_shardings,
                        meta={"rid": req.rid, "first_token": tok,
                              "prompt_len": len(req.prompt)},
                    )
                elif rep.pool.payload_kind == "snapshot":
                    # snapshot handoff: one dense row (the state IS the
                    # prefix) plus, cold only, the intern-able chain — a
                    # warm worker payload carries no chain, so the warm
                    # channel bytes are strictly below the cold ones.
                    # The replica-side lease (acquired by routing) pins
                    # the replica's own chain until install transfers it
                    st = rep.channel.send_kv(
                        row_cache, None,
                        meta={"rid": req.rid, "first_token": tok,
                              "prompt_len": len(req.prompt)},
                    )
                    if lease is not None:
                        rep.leases[req.rid] = lease
                else:
                    # paged handoff: ONLY the page suffix the decode pool
                    # does not already hold crosses the channel — the
                    # worker's payload carries FULL-prompt page stacks, so
                    # slicing from THIS replica's shared-prefix depth is a
                    # row slice, not a dense-cache extraction (the prefix
                    # is re-mapped from its interned pages, pinned by
                    # ``lease`` until install)
                    stacks = row_cache["stacks"]
                    if lease.pages:
                        rows = jnp.arange(lease.pages, stacks[0].k.shape[0])
                        stacks = [type(s)(k=s.k[rows], v=s.v[rows],
                                          slot_pos=s.slot_pos[rows])
                                  for s in stacks]
                    payload = {
                        "stacks": stacks,
                        "resident": row_cache["resident"],
                    }
                    st = rep.channel.send_kv(
                        payload, None,
                        meta={"rid": req.rid, "first_token": tok,
                              "prompt_len": len(req.prompt),
                              "start_page": lease.pages},
                    )
                    rep.leases[req.rid] = lease
                if prec.enabled:
                    # the KV handoff as a traced child of the request's
                    # tree (the channel also self-records an untraced
                    # xfer:kv span on this cell)
                    t1 = prec.clock()
                    prec.add_complete(
                        "channel", t1 - st["seconds"], st["seconds"],
                        trace_id=req.rid,
                        parent=root.ctx if root else None,
                        bytes=st["bytes"], dst=rep.cell.name)
                rep.inflight[req.rid] = req
        installed = 0
        for rep in self.replicas:
            while True:
                env = rep.channel.poll_kv()
                if env is None:
                    break
                req = rep.inflight.pop(env.meta["rid"])
                if rep.pool is None:
                    ok = rep.batcher.install_prefilled(
                        req, env.cache, env.meta["first_token"]
                    )
                    # the capacity budget reserves a slot for every send
                    # on the legacy plane — a failure here is a real
                    # accounting bug, not back-pressure
                    assert ok, \
                        "pump() never sends more KV than there are free slots"
                elif rep.pool.payload_kind == "snapshot":
                    lease = rep.leases.pop(env.meta["rid"], None)
                    ok = rep.batcher.install_snapshot(
                        req, env.cache["row"], env.meta["first_token"],
                        lease=lease, chain=env.cache["chain"],
                    )
                    # snapshot admission reserves no pages, so like the
                    # legacy plane only slot capacity gates the install
                    assert ok, \
                        "pump() never sends more KV than there are free slots"
                else:
                    lease = rep.leases.pop(env.meta["rid"])
                    ok = rep.batcher.install_paged(
                        req, env.cache["stacks"], env.cache["resident"],
                        env.meta["start_page"], env.meta["first_token"],
                        lease,
                    )
                    if not ok:
                        # pages vanished between send and install (e.g. a
                        # lease elsewhere pinned the evictable cache this
                        # admission counted on): re-home, never drop
                        rep.pool.release_lease(lease)
                        self._block_on_pool(req, deferred)
                        continue
                installed += 1
        self.pending.extendleft(reversed(deferred))
        return installed

    def step(self) -> int:
        """One scheduler tick: pump the handoff, then one decode step on
        every replica with busy slots."""
        self.pump()
        # the prefill cell is alive as long as this loop drives it — it
        # must not go heartbeat-stale (and get spuriously recovered by a
        # daemon) just because a long decode phase has nothing to prefill
        self.prefill_cell.heartbeat()
        n = 0
        for rep in self.replicas:
            n += rep.batcher.step()
            rep.cell.heartbeat()
        return n

    def _busy(self) -> bool:
        return bool(
            self.pending
            or any(rep.inflight for rep in self.replicas)
            or any(rep.batcher.queue for rep in self.replicas)
            or any(r is not None for rep in self.replicas
                   for r in rep.batcher.slot_req)
        )

    def run_until_drained(self, max_steps: int = 100_000,
                          on_step=None) -> List[Request]:
        """Step until no request is pending, in flight, or slotted.

        ``on_step`` (e.g. ``SupervisorDaemon.tick``) runs after every
        scheduler tick — the hook that lets health checks, reconcile and
        replica re-attach interleave with live traffic."""
        steps = 0
        while self._busy() and steps < max_steps:
            self.step()
            if on_step is not None:
                on_step()
            steps += 1
        return self.done

    @property
    def done(self) -> List[Request]:
        out: List[Request] = list(self.rejected) + list(self._done_detached)
        for rep in self.replicas:
            out.extend(rep.batcher.done)
        return out

    def pool_occupancy(self) -> float:
        """Worst committed-page pressure across live replica pools (the
        third autoscale signal beside queue depth and the TPOT tail);
        0.0 when the cache plane is not paged."""
        occ = [rep.pool.occupancy() for rep in self.replicas
               if rep.pool is not None]
        return max(occ) if occ else 0.0

    def tenant_stats(self) -> dict:
        """Per-tenant serving rollups over every finished request —
        live replicas, detached replicas, and rejected/shed alike."""
        from repro.core.accounting import summarize_requests
        from repro.serve.tenancy import DEFAULT_TENANT
        by: Dict[str, List[Request]] = {}
        for r in self.done:
            by.setdefault(getattr(r, "tenant", DEFAULT_TENANT) or
                          DEFAULT_TENANT, []).append(r)
        return {
            tenant: summarize_requests(reqs)
            for tenant, reqs in sorted(by.items())
        }

    # -- telemetry plane ------------------------------------------------
    def _recorders(self) -> Dict[str, object]:
        """name -> FlightRecorder of every live serving cell."""
        recs = {self.prefill_cell.name:
                recorder_of(self.prefill_cell.accounting)}
        for rep in self.replicas:
            recs[rep.cell.name] = recorder_of(rep.cell.accounting)
        return recs

    def trace_export(self, path: Optional[str] = None, *,
                     daemon=None) -> dict:
        """Export the cluster's flight-recorder state as Chrome
        trace-event JSON (Perfetto-loadable).

        One collection round over the supervisor's control plane (each
        live cell unicasts its dump — metadata only, mirroring the cache
        plane's advert round), plus the archived dumps of since-detached
        replicas.  ``daemon=`` folds a :class:`SupervisorDaemon`'s
        decision audit in as instant events on a ``daemon`` pseudo-pid
        and under ``otherData.decision_audit``.  Writes JSON to ``path``
        when given; returns the trace dict either way."""
        dumps = collect_traces(self.sup, self._recorders())
        dumps += [d for d in self._detached_dumps
                  if d.get("events") or d.get("open_spans")]
        audit = getattr(daemon, "audit", None) if daemon is not None \
            else None
        trace = chrome_trace(dumps, audit=audit)
        if path is not None:
            write_trace(path, trace)
        return trace

    def telemetry_summary(self) -> Dict[str, dict]:
        """Merged histogram summaries (p50/p99/p99.9) across every live
        cell's sketches plus the detached archive — O(buckets), no
        request-list re-scan."""
        merged: Dict[str, HistogramSketch] = {
            k: HistogramSketch.from_dict(h.to_dict())
            for k, h in self._detached_hists.items()}
        for rec in self._recorders().values():
            for k, h in rec.hists.items():
                if k in merged:
                    merged[k].merge(h)
                else:
                    merged[k] = HistogramSketch.from_dict(h.to_dict())
        return {k: h.summary() for k, h in sorted(merged.items())}

    def stats(self) -> dict:
        from repro.core.accounting import summarize_requests
        ds = self._detached_stats

        pools = [rep.pool.stats() for rep in self.replicas
                 if rep.pool is not None]

        def pool_sum(key):
            return ds[key] + sum(p[key] for p in pools)

        def hit_rate(hit, miss):
            return hit / max(hit + miss, 1)

        return {
            "paged_kv": bool(pools),
            "prefix_hit_tokens": pool_sum("prefix_hit_tokens"),
            "prefix_miss_tokens": pool_sum("prefix_miss_tokens"),
            # aggregate + per-replica warm fraction of looked-up tokens;
            # the aggregate folds detached replicas in, so a scale-down
            # never flatters the cluster-wide rate
            "prefix_hit_rate": hit_rate(pool_sum("prefix_hit_tokens"),
                                        pool_sum("prefix_miss_tokens")),
            "per_replica_prefix_hit_rate": [
                hit_rate(p["prefix_hit_tokens"], p["prefix_miss_tokens"])
                for p in pools],
            "routed_warm": self.routed_warm,
            "routed_cold": self.routed_cold,
            "pages_migrated": self.pages_migrated,
            "drain_handoffs": self.drain_handoffs,
            "cache_index_entries": len(self.cacheplane.index),
            "pages_evicted": pool_sum("pages_evicted"),
            "kv_bytes_saved": pool_sum("kv_bytes_saved"),
            # snapshot cache plane (ssm/hybrid): zero on page pools, so
            # the keys are uniform across payload kinds
            "snapshots_interned": pool_sum("snapshots_interned"),
            "snapshot_hit_tokens": pool_sum("snapshot_hit_tokens"),
            "snapshot_bytes_saved": pool_sum("snapshot_bytes_saved"),
            "pages_in_use": sum(p["pages_in_use"] for p in pools),
            "pool_occupancy": max((p["occupancy"] for p in pools),
                                  default=0.0),
            "blocked_on_pool": self.blocked_on_pool,
            "decode_serving": summarize_requests(self.done),
            "prefill_chunked": self.worker is not None,
            "prefill_invocations": (
                self.worker.invocations if self.worker is not None else 0),
            "prefill_fallback_requests": self.fallback_requests,
            "decode_invocations": ds["decode_invocations"] + sum(
                r.batcher.decode_invocations for r in self.replicas),
            "kv_bytes": ds["kv_bytes"] + sum(
                r.channel.bytes_sent for r in self.replicas),
            "kv_transfers": ds["kv_transfers"] + sum(
                r.channel.transfers for r in self.replicas),
            "kv_seconds": ds["kv_seconds"] + sum(
                r.channel.seconds for r in self.replicas),
            "replicas": len(self.replicas),
            "per_replica_requests": [len(r.batcher.done) for r in self.replicas],
            "requests_detached": ds["requests"],
            "pending": len(self.pending),
            "requeued": self.requeued,
            "per_tenant": self.tenant_stats(),
            "shed_requests": self.shed_requests,
            "blocked_by_tenant": dict(self.blocked_by_tenant),
            "throttled_by_tenant": dict(self.scheduler.throttled),
            "served_cost_by_tenant": dict(self.scheduler.served_cost),
            "telemetry": self.telemetry_summary(),
        }
