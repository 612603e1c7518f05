"""KVPool — payload-polymorphic cache memory with radix-tree prefix sharing.

The paper's memory model applied to the serving cache plane: each subOS
(here: each decode cell) owns an *isolated* arena of physical memory, and
the supervisor-of-the-cache (the pool) grants *shared* read-only mappings
only on demand.  Concretely:

* **Isolate first** — every request's KV lives in page-granular private
  allocations (pages of ``page_size`` positions spanning all layers); a
  block table maps ``(slot, logical_page) -> physical_page``, and a slot
  only ever holds the pages its request actually reached — no more dense
  ``max_len`` slabs committed to 12-token prompts.
* **Then share** — immutable, fully-written prompt pages are *interned*
  into a :class:`PrefixTree` (a radix tree over ``page_size``-token
  chunks) with refcounts.  A new request whose prompt shares a cached
  prefix maps those pages read-only (copy-free), skips their prefill
  compute entirely (only the suffix runs, via ``Model.prefill_extend``),
  and allocates private pages from its divergence point.  The partial
  boundary page is the copy-on-write edge: it is always private, so
  decode writes can never touch a shared page.
* **Revoke on pressure** — admission *blocks* (requests stay queued) when
  the pool is exhausted, and interned pages whose refcount has dropped to
  zero are LRU-evicted to make room, exactly like the paper's
  supervisor-mediated reclamation of granted-but-idle resources (and in
  the spirit of XOS's application-defined memory mapping and OSmosis'
  explicit sharing-set semantics — see PAPERS.md).

Exactness: for causal-KV families the K/V at position ``i`` depends only
on tokens ``<= i`` (plus, for encdec, the request's source features — the
tree roots are keyed by a source digest), so an interned page written by
one request is bit-identical to what any other request with the same
prefix would have computed; chunk-granular matching means partial matches
are misses.

**The payload protocol.**  The unit of sharing is a typed *payload*, not
hard-coded pages — the OSmosis argument (arXiv:2309.09291) that
isolation/sharing policy should be expressed over a uniform resource
abstraction.  A :class:`PrefixTree` node's ``page`` field is an integer
HANDLE whose meaning is the pool's ``payload_kind``:

* ``"page"`` — a physical page id in the KV arena (causal-KV families:
  dense/vlm/moe/encdec), the classic paged plane above;
* ``"snapshot"`` — a key into the pool's snapshot store holding
  ``{"state": <1-row recurrent-state tree at the chunk boundary>,
  "pages": [<this chunk's shared-attention KV page stacks>]}`` for
  recurrent families (ssm/hybrid).  Node ``d-1``'s state is the FULL
  state after the depth-``d`` prefix (Mamba state folds history, so each
  node stores one boundary checkpoint, not a delta); ``"pages"`` carries
  only chunk ``d-1``'s KV positions (empty for pure ssm), so a chain's
  KV grows linearly with depth.  A warm prompt restores the deepest
  node's state (plus the chain's concatenated KV pages) into a dense
  cache row and prefill-extends only the suffix.

Every mechanism above the handle — refcounts, LRU eviction, tenant quota
pockets, COW admission, export/import migration — is payload-agnostic
and identical for both kinds.  The three-way capability predicate is
:meth:`KVPool.capability` (``"paged" | "snapshot" | "none"``): the ONLY
place family reach into the cache plane is decided.  Digest
compatibility: both kinds key tree nodes by the same ``page_size``-token
chunks, so ``serve.cacheplane.chunk_digests`` / ``advertise`` /
``PrefixIndex`` routing and ``migrate_prefixes`` work unchanged over
snapshot pools — the cluster plane never looks inside a payload.

Tenancy applies the same subOS model one level up, to *users* of one
pool.  Each tenant is a little subOS of the cache plane:

* its **page quota** is a physical-resource partition — ``quotas``
  splits the arena into per-tenant pockets (plus a shared commons for
  quota-less tenants), every allocated page is charged to exactly one
  pocket, and a tenant over its pocket can only reclaim its *own*
  refcount-0 cache, so it can exhaust its quota but never the pool;
* its **prefix namespace** is an address space — tree roots are keyed
  per tenant (:func:`request_ctx_key`), so one tenant's prompts never
  match another's pages;
* the **public namespace** is the supervisor-mediated memory grant — a
  prompt marked public interns under the shared ``__public__`` root
  (charged to the commons), and any granted tenant may map those pages
  read-only (:func:`public_ctx_key` fallback in :meth:`KVPool.lease`).
  A foreign (public) hit never lets the tenant intern *into* the public
  namespace: its suffix pages stay private, so the grant is strictly
  read-only — sharing is something the spec grants, never ambient.

The decode/extend hot path is NATIVELY paged — the block table reaches
the kernels instead of being flattened away above them.  The calling
convention (``build_paged_serve_step`` / ``build_paged_extend_step``):
the step function takes ``(params, arena, scales, resident, block_table,
batch, rng)``; ``cache_utils.paged_view`` wraps each positional arena
node in a :class:`~repro.models.layers.PagedKVCache` carrying the whole
``(num_pages, L, Hkv, page_size, Dh)`` arena plus the batch's
``(B, n_logical)`` block table, and ``Model.decode`` /
``Model.prefill_extend`` thread that view into every attention layer
(the arena rides the layer-scan carry; each step rebinds the ``layer``
index).  Attention writes the current token(s) straight into their
physical pages — sentinel entries drop the write — and the paged Pallas
kernels (``kernels/decode_attention``, ``kernels/flash_attention``) walk
each row's pages directly in the arena via scalar-prefetched block-table
index maps; on CPU an equivalent jnp page gather feeds the dense
reference attention, bit-identical to the pre-paged path.  No contiguous
per-slot KV copy is ever materialized in steady state: only the
export/import/migration and cold-install paths move whole pages.  With
``kv_dtype="int8"`` the arena stores int8 pages with per-(page, layer)
scales — quantize on page write, dequantize in-kernel — doubling pool
capacity at documented (small, non-exact) accuracy cost.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.cache_utils import (
    clean_arena_pages,
    dequantize_page,
    extract_paged,
    extract_row_pages,
    install_cross_memory,
    kv_node_axes,
    kv_position_bytes,
    page_arena,
    paged_view,
    quantize_page,
    read_arena_pages,
    recurrent_state_bytes,
    strip_kv_nodes,
    write_arena_pages,
)
from repro.models.layers import KVSlice
from repro.serve.serve_step import bucket_len, sample_tokens
from repro.serve.tenancy import COMMONS, DEFAULT_TENANT, PUBLIC


class PoolExhausted(RuntimeError):
    """No free or evictable page is left — the caller must requeue."""


def _src_part(req) -> Optional[tuple]:
    """Source-feature digest component of a ctx key (encdec decoder KV
    depends on the request's source features as well as its tokens, so
    prompts may only share pages when the sources are byte-identical)."""
    src = getattr(req, "src", None)
    if src is None:
        return None
    a = np.ascontiguousarray(np.asarray(src))
    return ("src", a.shape, hashlib.sha1(a.tobytes()).hexdigest())


def request_ctx_key(req) -> Optional[tuple]:
    """Prefix-tree root key for a request: its tenant namespace plus any
    non-token context.

    The default tenant's private namespace keeps the pre-tenancy keys
    (None, or the bare source digest) so a single-tenant deployment is
    byte-identical to the old stack; a request marked ``public`` lives
    under the shared ``__public__`` root; any other tenant gets a
    private ``("tenant", name)`` root no other tenant's lookups can
    reach."""
    src = _src_part(req)
    if getattr(req, "public", False):
        return ("public",) if src is None else ("public", src)
    tenant = getattr(req, "tenant", DEFAULT_TENANT)
    if tenant != DEFAULT_TENANT:
        return (("tenant", tenant) if src is None
                else ("tenant", tenant, src))
    return src


def public_ctx_key(req) -> Optional[tuple]:
    """The public-namespace variant of a request's ctx key — the root a
    granted tenant may additionally match READ-ONLY (the supervisor
    grant).  None when the request already lives there."""
    if getattr(req, "public", False):
        return None
    src = _src_part(req)
    return ("public",) if src is None else ("public", src)


class _Node:
    """One interned page: a ``page_size``-token chunk under its parent."""

    __slots__ = ("parent", "key", "children", "page", "refs", "last_used",
                 "owner")

    def __init__(self, parent, key, page, owner=None):
        self.parent = parent
        self.key = key                  # tuple of page_size token ids
        self.children: Dict[tuple, "_Node"] = {}
        self.page = page                # physical page id (None for roots)
        self.refs = 0
        self.last_used = 0
        self.owner = owner              # tenant / PUBLIC the page bills to


class PrefixTree:
    """Radix tree over ``page_size``-token chunks with refcounted pages.

    Nodes are interned *full* pages only — a prompt's partial tail chunk
    never enters the tree, so every match is exact by construction.
    Refcounts track live users (slots holding the page mapped, or
    in-flight leases); refcount-0 nodes are cache, reclaimable LRU."""

    def __init__(self, page_size: int):
        self.page_size = page_size
        self._roots: Dict[Optional[tuple], _Node] = {}
        self._clock = 0
        self.interned = 0               # live interned (non-root) nodes

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def root(self, ctx_key) -> _Node:
        if ctx_key not in self._roots:
            self._roots[ctx_key] = _Node(None, None, None)
        return self._roots[ctx_key]

    def match(self, prompt, ctx_key) -> List[_Node]:
        """Longest chain of interned full-chunk nodes matching ``prompt``
        — capped so at least one suffix token is left to compute (the
        extend invocation must produce the first output token)."""
        P = self.page_size
        L = len(prompt)
        node = self._roots.get(ctx_key)
        out: List[_Node] = []
        if node is None:
            return out
        for lp in range(max(L - 1, 0) // P):
            child = node.children.get(tuple(int(t) for t in
                                            prompt[lp * P:(lp + 1) * P]))
            if child is None:
                break
            out.append(child)
            node = child
        return out

    def acquire(self, nodes: List[_Node]):
        now = self._tick()
        for n in nodes:
            n.refs += 1
            n.last_used = now

    def release(self, nodes: List[_Node]):
        now = self._tick()
        for n in nodes:
            assert n.refs > 0, "refcount underflow on an interned page"
            n.refs -= 1
            n.last_used = now

    def insert(self, parent: _Node, key: tuple, page: int,
               owner=None) -> _Node:
        assert key not in parent.children
        node = _Node(parent, key, page, owner)
        node.last_used = self._tick()
        parent.children[key] = node
        self.interned += 1
        return node

    def _walk(self):
        stack = list(self._roots.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.page is not None:
                yield n

    def evictable_pages(self, visible=None) -> int:
        """Pages reclaimable right now: interned nodes whose whole
        subtree is refcount-0 (evicting leaf-upward never strands a
        live descendant's prefix).  One ITERATIVE bottom-up pass — each
        node's pinned flag is computed once, children before parents;
        no recursion, so page chains as deep as max_len/page_size (long
        shared prompts) can never blow the interpreter stack.  With
        ``visible`` (a node predicate), count only nodes the caller may
        reclaim — the per-tenant quota view."""
        total = 0
        pinned: Dict[int, bool] = {}
        for root in self._roots.values():
            stack = [(root, False)]
            while stack:
                n, seen = stack.pop()
                if not seen:
                    stack.append((n, True))
                    stack.extend((c, False) for c in n.children.values())
                    continue
                p = n.refs > 0 or any(pinned[id(c)]
                                      for c in n.children.values())
                pinned[id(n)] = p
                if (n.page is not None and not p
                        and (visible is None or visible(n))):
                    total += 1
        return total

    def evict_lru(self, visible=None) -> Optional[Tuple[_Node, int]]:
        """Detach the least-recently-used evictable LEAF node; returns
        (node, freed page id) or None when nothing is evictable.  A
        childless node's subtree is itself, so evictability is just its
        own refcount.  ``visible`` restricts candidates to nodes the
        requester may reclaim (its own pocket's cache)."""
        best: Optional[_Node] = None
        for n in self._walk():
            if (n.refs == 0 and not n.children
                    and (visible is None or visible(n))
                    and (best is None or n.last_used < best.last_used)):
                best = n
        if best is None:
            return None
        del best.parent.children[best.key]
        self.interned -= 1
        return best, best.page


@dataclasses.dataclass
class PrefixLease:
    """An acquired (incref'd) chain of shared prefix nodes.

    Held from lookup until the pages are mapped into a slot (ownership
    transfers to the slot) or the request is abandoned (release).
    ``foreign`` marks a chain matched in a namespace the request does
    not own (the public grant): its pages map read-only and the slot's
    suffix never interns under them."""

    nodes: List[_Node]
    page_size: int
    released: bool = False
    foreign: bool = False

    @property
    def pages(self) -> int:
        return len(self.nodes)

    @property
    def tokens(self) -> int:
        return len(self.nodes) * self.page_size


def _write_pages_q(arena: list, scales: list, page_ids, stacks: list):
    """``write_arena_pages`` for an int8 arena: quantize each float page
    stack per (page, layer) and update the scale tables alongside."""
    idx = jnp.asarray(page_ids, jnp.int32)
    new_arena, new_scales = [], []
    for a, (ks, vs), s in zip(arena, scales, stacks):
        kq, ksc = quantize_page(s.k, keep_axes=(0, 1))
        vq, vsc = quantize_page(s.v, keep_axes=(0, 1))
        new_arena.append(KVSlice(
            k=a.k.at[idx].set(kq), v=a.v.at[idx].set(vq),
            slot_pos=a.slot_pos.at[idx].set(s.slot_pos)))
        new_scales.append((ks.at[idx].set(ksc), vs.at[idx].set(vsc)))
    return new_arena, new_scales


def _clean_pages_q(arena: list, scales: list, page_ids):
    """``clean_arena_pages`` for an int8 arena: also zero the recycled
    pages' scales so the lazy in-place scale init sees them untouched."""
    idx = jnp.asarray(page_ids, jnp.int32)
    arena = clean_arena_pages(arena, idx)
    scales = [(ks.at[idx].set(0.0), vs.at[idx].set(0.0))
              for ks, vs in scales]
    return arena, scales


class KVPool:
    """Page-granular KV arena + block table + prefix tree for one cell.

    Two deployment shapes share this class:

    * a *decode* pool (``slots`` > 0) backs a ``ContinuousBatcher``: the
      block table is the storage plane its jitted decode step reads
      through, and slot admission reserves a private-page *pocket* up
      front (worst case ``ceil((prompt + max_new) / page_size)`` minus
      the shared prefix) so mid-decode page-boundary growth can never
      fail — admission is the single choke point that blocks on
      exhaustion;
    * a *prefill* pool (``slots`` == 0) backs a ``PrefillWorker``: no
      block table traffic, just the tree + arena as a prefix cache that
      lets warm prompts skip their shared chunks' prefill compute.
    """

    def __init__(self, model, *, max_len: int, page_size: int = 16,
                 slots: int = 0, num_pages: Optional[int] = None,
                 accounting=None, quotas: Any = None,
                 kv_dtype: Optional[str] = None):
        if model.supports_paged_kv:
            self.payload_kind = "page"
        elif getattr(model, "supports_snapshot_state", False):
            self.payload_kind = "snapshot"
        else:
            raise ValueError(
                f"family {model.cfg.family!r} has no shareable cache "
                f"payload (neither paged KV nor state snapshots)")
        if max_len % page_size:
            raise ValueError(f"max_len={max_len} not a multiple of "
                             f"page_size={page_size}")
        self.model = model
        self.max_len = max_len
        self.page_size = page_size
        self.slots = slots
        self.n_logical = max_len // page_size
        self.num_pages = int(num_pages if num_pages is not None else
                             (slots + 2) * self.n_logical if slots
                             else 8 * self.n_logical)
        if self.num_pages < self.n_logical:
            raise ValueError("pool smaller than one request's worst case")
        self.template = model.cache_specs(1, max_len)
        self.axes = kv_node_axes(model, 1, max_len)
        # a warm hit skips BOTH the prefix KV bytes (hybrid shared
        # attention; zero for pure ssm) and, amortized per position, the
        # boundary state checkpoints the handoff no longer ships
        self.position_bytes = kv_position_bytes(model, max_len)
        if self.payload_kind == "snapshot":
            self.position_bytes += (
                recurrent_state_bytes(model, max_len) // page_size)
        # snapshot store: handle -> interned payload pytree.  Handles are
        # drawn from the same free list / quota / eviction machinery as
        # physical page ids — only the backing storage differs.
        self._snaps: Dict[int, Any] = {}
        if self.payload_kind == "snapshot":
            if kv_dtype is not None:
                raise ValueError(
                    "snapshot pools hold float state payloads; kv_dtype "
                    "quantization applies to page arenas only")
            self.arena = []
            self.kv_scales = None
        else:
            self.arena = page_arena(model, self.num_pages, page_size)
            if kv_dtype is None:
                self.kv_scales = None
            elif kv_dtype == "int8":
                # int8 page scaffolding: k/v store int8 with one f32 scale
                # per (page, layer) per tensor — quantized on page write,
                # dequantized in-kernel on the paged hot path (and on
                # read_pages / export, so migration round-trips via floats)
                self.arena = [KVSlice(k=jnp.zeros(a.k.shape, jnp.int8),
                                      v=jnp.zeros(a.v.shape, jnp.int8),
                                      slot_pos=a.slot_pos)
                              for a in self.arena]
                self.kv_scales = [
                    (jnp.zeros((self.num_pages, a.k.shape[1]), jnp.float32),
                     jnp.zeros((self.num_pages, a.k.shape[1]), jnp.float32))
                    for a in self.arena]
            else:
                raise ValueError(f"unsupported kv_dtype {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        self.sentinel = self.num_pages          # unmapped block-table entry
        self.block_table = np.full((max(slots, 1), self.n_logical),
                                   self.sentinel, np.int32)
        self.tree = PrefixTree(page_size)
        self.free: deque = deque(range(self.num_pages))
        self.accounting = accounting
        # per-slot ownership: shared tree nodes (refcounted), private
        # pages (this request's divergent/boundary/decode pages), and the
        # pre-reserved pocket future boundary crossings draw from
        self._shared: List[List[_Node]] = [[] for _ in range(max(slots, 1))]
        self._private: List[List[int]] = [[] for _ in range(max(slots, 1))]
        self._pocket: List[List[int]] = [[] for _ in range(max(slots, 1))]
        # tenant bulkheads: quotas maps pocket name -> page budget (the
        # COMMONS pocket is the unreserved remainder); every allocated
        # page is charged to exactly one pocket in ``used``.  A callable
        # gets the resolved page count (TenantRegistry.page_quotas)
        if callable(quotas):
            quotas = quotas(self.num_pages)
        if quotas is not None:
            if sum(quotas.values()) > self.num_pages:
                raise ValueError(
                    f"quota pockets sum to {sum(quotas.values())}, "
                    f"pool has only {self.num_pages} pages")
            if any(q < 0 for q in quotas.values()):
                raise ValueError("negative page quota pocket")
        self.quotas = dict(quotas) if quotas is not None else None
        self.used: Dict[str, int] = ({p: 0 for p in quotas}
                                     if quotas is not None else {})
        self._slot_tenant: List[Optional[str]] = [None] * max(slots, 1)
        self._slot_foreign: List[bool] = [False] * max(slots, 1)
        self.pages_evicted = 0
        self.prefix_hit_tokens = 0
        self.prefix_miss_tokens = 0
        self.kv_bytes_saved = 0
        # snapshot-payload counters — present (zero) on page pools too so
        # aggregators can fold stats() dicts without key checks
        self.snapshots_interned = 0
        self.snapshot_hit_tokens = 0
        self.snapshot_bytes_saved = 0
        # arena mutators run jitted with the arena DONATED so updates are
        # in-place buffer writes, not whole-arena functional copies — the
        # admission path must not pay O(arena) per request (compiled
        # variants are bounded by the <= n_logical distinct page counts)
        if self.payload_kind == "snapshot":
            self._clean_fn = self._write_fn = None
        elif self.kv_scales is None:
            self._clean_fn = jax.jit(clean_arena_pages, donate_argnums=(0,))
            self._write_fn = jax.jit(write_arena_pages, donate_argnums=(0,))
        else:
            self._clean_fn = jax.jit(_clean_pages_q, donate_argnums=(0, 1))
            self._write_fn = jax.jit(_write_pages_q, donate_argnums=(0, 1))

    def _clean_pages(self, page_ids):
        """In-place (donated) page clean; also resets int8 scales."""
        if self.kv_scales is None:
            self.arena = self._clean_fn(self.arena, page_ids)
        else:
            self.arena, self.kv_scales = self._clean_fn(
                self.arena, self.kv_scales, page_ids)

    def _write_pages(self, page_ids, stacks):
        """In-place (donated) page write from FLOAT canonical stacks;
        quantizes into an int8 arena (updating the scale tables)."""
        if self.kv_scales is None:
            self.arena = self._write_fn(self.arena, page_ids, stacks)
        else:
            self.arena, self.kv_scales = self._write_fn(
                self.arena, self.kv_scales, page_ids, stacks)

    # -- capability ----------------------------------------------------
    @staticmethod
    def capability(model, max_len: int, page_size: int) -> str:
        """Pool gate, three-way: what cache payload can this config share?

        * ``"paged"`` — attention KV lives in a pageable absolute-position
          layout: full page-granular prefix sharing.
        * ``"snapshot"`` — no paged KV, but the family carries compact
          recurrent state (ssm/hybrid): prefix sharing via interned
          boundary-state checkpoints.
        * ``"none"`` — neither (page-misaligned cache, or a rolling SWA
          buffer that keeps only a window of *slots*, so neither page ids
          nor chunk-boundary states are stable).

        This predicate is the ONLY place payload capability is decided;
        callers branch on its result, never on ``supports_paged_kv``."""
        w = model.cfg.sliding_window
        if max_len % page_size or not (w is None or w >= max_len):
            return "none"
        if model.supports_paged_kv:
            return "paged"
        if getattr(model, "supports_snapshot_state", False):
            return "snapshot"
        return "none"

    # -- occupancy -----------------------------------------------------
    @property
    def pages_in_use(self) -> int:
        """Allocated pages (slot-held, pocketed, or interned cache)."""
        return self.num_pages - len(self.free)

    def evictable_pages(self) -> int:
        return self.tree.evictable_pages()

    def _pocket_of(self, tenant: Optional[str]) -> Optional[str]:
        """Charge pocket for a tenant / namespace owner: an explicitly
        quota'd tenant bills its own pocket; everyone else (quota-less
        tenants, unknown tenants, the public namespace) shares the
        commons remainder."""
        if self.quotas is None:
            return None
        if tenant is not None and tenant in self.quotas:
            return tenant
        return COMMONS

    def _pocket_visible(self, pocket: str):
        """Eviction-candidate predicate for a requester charged to
        ``pocket``: only refcount-0 cache chargeable to the same pocket
        may be reclaimed — a tenant reclaims its own idle cache (or, in
        the commons, anyone's commons cache incl. public pages), never a
        bulkheaded co-tenant's."""
        return lambda n: self._pocket_of(n.owner) == pocket

    def available_pages(self, tenant: Optional[str] = None) -> int:
        """Pages an admission could obtain right now (free + reclaimable
        refcount-0 interned cache).

        With quotas, the answer is scoped to the pocket the admission
        would charge (``_pocket_of``: the tenant's own, or the commons
        for untagged/unknown tenants): quota headroom plus that pocket's
        evictable cache.  The bulkhead invariant (pockets sum <= pool,
        every page charged) guarantees headroom is always physically
        backed by free pages, so this never overstates — which is the
        whole point: a True pre-check here means ``admit`` succeeds."""
        if self.quotas is None:
            return len(self.free) + self.evictable_pages()
        pocket = self._pocket_of(tenant)
        headroom = self.quotas[pocket] - self.used.get(pocket, 0)
        return headroom + self.tree.evictable_pages(
            self._pocket_visible(pocket))

    def occupancy(self) -> float:
        """Committed (non-reclaimable) fraction of the arena — the
        autoscale pressure signal: 1.0 means even evicting every cached
        prefix frees nothing.  Always the GLOBAL view — quota pockets
        partition who may allocate, not how full the arena is."""
        free = len(self.free) + self.evictable_pages()
        return 1.0 - free / self.num_pages

    def stats(self) -> dict:
        out = {
            "num_pages": self.num_pages,
            "pages_in_use": self.pages_in_use,
            "pages_evicted": self.pages_evicted,
            "interned_pages": self.tree.interned,
            "occupancy": self.occupancy(),
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_miss_tokens": self.prefix_miss_tokens,
            "kv_bytes_saved": self.kv_bytes_saved,
            "snapshots_interned": self.snapshots_interned,
            "snapshot_hit_tokens": self.snapshot_hit_tokens,
            "snapshot_bytes_saved": self.snapshot_bytes_saved,
        }
        if self.quotas is not None:
            out["quota_pages"] = dict(self.quotas)
            out["tenant_pages"] = dict(self.used)
        return out

    def _gauge(self):
        if self.accounting is not None:
            self.accounting.record_gauge("pages_in_use", self.pages_in_use)

    def _reap(self, handle: int):
        """Drop the payload behind an evicted/freed handle.  Physical
        pages have nothing to drop (the arena slab is recycled in place);
        snapshot handles release their interned state pytree."""
        self._snaps.pop(handle, None)

    # -- page supply ---------------------------------------------------
    def _alloc_raw(self, tenant: Optional[str] = None) -> Optional[int]:
        """One page, charged to ``tenant``'s pocket (when quotas are on).

        Postcondition on success: the returned page is charged to
        ``_pocket_of(tenant)``.  Quota path: a full pocket may only
        reclaim refcount-0 cache chargeable to the SAME pocket (charge
        unchanged — the page moves from tree cache to slot use), so a
        tenant can exhaust its quota but never another tenant's; an
        under-quota pocket always finds a free page because pockets sum
        to at most the pool and every allocated page is charged."""
        if self.quotas is None:
            if self.free:
                return self.free.popleft()
            evicted = self.tree.evict_lru()
            if evicted is None:
                return None
            _, page = evicted
            self._reap(page)
            self.pages_evicted += 1
            if self.accounting is not None:
                self.accounting.record_counter("pages_evicted")
            return page
        pocket = self._pocket_of(tenant)
        if self.used[pocket] >= self.quotas[pocket]:
            evicted = self.tree.evict_lru(self._pocket_visible(pocket))
            if evicted is None:
                return None             # quota exhausted, pool untouched
            _, page = evicted
            self._reap(page)
            self.pages_evicted += 1
            if self.accounting is not None:
                self.accounting.record_counter("pages_evicted",
                                               tenant=tenant)
            return page
        assert self.free, "bulkhead invariant broken: headroom w/o free"
        self.used[pocket] += 1
        return self.free.popleft()

    def _uncharge(self, tenant: Optional[str], n: int):
        """Return ``n`` pages' worth of charge from ``tenant``'s pocket
        (the pages themselves go back on ``self.free`` at the caller)."""
        if self.quotas is None or n == 0:
            return
        pocket = self._pocket_of(tenant)
        self.used[pocket] -= n
        assert self.used[pocket] >= 0, f"pocket {pocket} charge underflow"

    def _take_pocket(self, slot: int) -> int:
        assert self._pocket[slot], (
            "pocket underflow: admission reserved too few pages")
        return self._pocket[slot].pop()

    # -- prefix lookup -------------------------------------------------
    def lease(self, prompt, ctx_key=None, alt_key=None) -> PrefixLease:
        """Match + acquire the longest interned prefix for ``prompt``.

        The acquired nodes are pinned (non-evictable) until the lease is
        released or its ownership transfers to a slot via ``admit``.

        ``alt_key`` is the read-only fallback namespace (the public
        grant): both roots are matched and the longer chain wins, the
        request's own namespace on ties.  A winning ``alt_key`` chain is
        marked ``foreign`` — its pages map read-only and the suffix will
        not intern under them."""
        nodes = self.tree.match(prompt, ctx_key)
        foreign = False
        if alt_key is not None:
            alt = self.tree.match(prompt, alt_key)
            if len(alt) > len(nodes):
                nodes, foreign = alt, True
        self.tree.acquire(nodes)
        return PrefixLease(nodes=nodes, page_size=self.page_size,
                           foreign=foreign)

    def empty_lease(self) -> PrefixLease:
        """A zero-page lease (cold request / token-at-a-time admit)."""
        return PrefixLease(nodes=[], page_size=self.page_size)

    def release_lease(self, lease: PrefixLease):
        if lease is None or lease.released:
            return
        self.tree.release(lease.nodes)
        lease.released = True

    def note_lookup(self, prompt_len: int, hit_tokens: int,
                    accounting=None, saved_bytes: bool = True):
        """Record a prefix lookup's hit/miss token split (and the KV
        bytes the hit avoided recomputing/duplicating).

        Counted per ADMISSION ATTEMPT, matching the rest of the serving
        ledger (``kv_transfers`` also counts a requeued request's
        re-send): a request re-admitted after a replica detach really
        did skip its prefix work twice."""
        acc = accounting if accounting is not None else self.accounting
        self.prefix_hit_tokens += hit_tokens
        self.prefix_miss_tokens += prompt_len - hit_tokens
        saved = hit_tokens * self.position_bytes if saved_bytes else 0
        self.kv_bytes_saved += saved
        if self.payload_kind == "snapshot":
            self.snapshot_hit_tokens += hit_tokens
            self.snapshot_bytes_saved += saved
        if acc is not None:
            acc.record_counter("prefix_hit_tokens", hit_tokens)
            acc.record_counter("prefix_miss_tokens", prompt_len - hit_tokens)
            if saved:
                acc.record_counter("kv_bytes_saved", saved)

    # -- slot lifecycle ------------------------------------------------
    def required_pages(self, prompt_len: int, max_new: int,
                       shared_pages: int = 0) -> int:
        """Worst-case private pages a request can touch: every page up to
        its last writable position, minus the shared prefix.  At least
        one post-prompt position is counted — install always maps the
        page holding position ``prompt_len`` for the first decode write.

        Snapshot pools reserve nothing per slot: the request's state
        lives in its dense cache row, and handle supply is consumed only
        when a finished prefix interns new checkpoints."""
        if self.payload_kind == "snapshot":
            return 0
        last = min(prompt_len + max(max_new, 1), self.max_len)
        return -(-last // self.page_size) - shared_pages

    def admit(self, slot: int, lease: PrefixLease, prompt_len: int,
              max_new: int, tenant: Optional[str] = None):
        """Commit a slot to a request: map the lease's shared pages into
        the block table (ownership of the lease transfers to the slot)
        and materialize the full private-page pocket, evicting LRU
        refcount-0 prefixes as needed — all charged to ``tenant``'s
        quota pocket.  Raises :class:`PoolExhausted` (with the lease
        still held by the CALLER to release) when the arena — or the
        tenant's pocket — cannot cover the worst case: the admission
        choke point that makes exhaustion a queueing event, not an OOM,
        and the bulkhead that keeps one tenant's exhaustion out of
        everyone else's admission."""
        assert not self._shared[slot] and not self._private[slot] \
            and not self._pocket[slot], f"slot {slot} not released"
        need = self.required_pages(prompt_len, max_new, lease.pages)
        got: List[int] = []
        for _ in range(need):
            page = self._alloc_raw(tenant)
            if page is None:
                self._uncharge(tenant, len(got))
                self.free.extend(got)
                if self.accounting is not None and self.quotas is not None:
                    self.accounting.record_counter("quota_blocked",
                                                   tenant=tenant)
                raise PoolExhausted(
                    f"need {need} pages, got {len(got)} "
                    f"(free={len(self.free)}, "
                    f"evictable={self.evictable_pages()}, "
                    f"tenant={tenant!r})")
            got.append(page)
        self._slot_tenant[slot] = tenant
        self._slot_foreign[slot] = lease.foreign
        if got:
            self._clean_pages(jnp.asarray(got, jnp.int32))
        self._pocket[slot] = got
        if self.payload_kind == "page":
            for lp, node in enumerate(lease.nodes):
                self.block_table[slot, lp] = node.page
        self._shared[slot] = list(lease.nodes)
        lease.released = True            # ownership moved to the slot
        self.note_lookup(prompt_len, lease.tokens)
        self._gauge()

    def _transfer_charge(self, tenant: Optional[str], owner) -> bool:
        """Move one page's charge from ``tenant``'s pocket to
        ``owner``'s — interning a slot-billed page into a namespace
        billed elsewhere (a public prompt's pages move to the commons).
        Returns False (leave the page private) when the destination
        pocket cannot absorb the charge even after reclaiming its own
        idle cache."""
        if self.quotas is None:
            return True
        src = self._pocket_of(tenant)
        dst = self._pocket_of(owner)
        if src == dst:
            return True
        if self.used[dst] >= self.quotas[dst]:
            evicted = self.tree.evict_lru(self._pocket_visible(dst))
            if evicted is None:
                return False
            _, page = evicted
            self._reap(page)
            self.pages_evicted += 1
            self.free.append(page)
            self.used[dst] -= 1
        self.used[src] -= 1
        self.used[dst] += 1
        return True

    def map_private(self, slot: int, logical_page: int) -> int:
        """Map a pocket page at ``logical_page`` (decode growth / the
        copy-on-write boundary page)."""
        page = self._take_pocket(slot)
        self.block_table[slot, logical_page] = page
        self._private[slot].append(page)
        return page

    def ensure_decode_page(self, slot: int, pos: int):
        """Called before a decode step: make sure the page holding
        ``pos`` is mapped (drawn from the slot's reserved pocket, so it
        cannot fail)."""
        lp = pos // self.page_size
        if self.block_table[slot, lp] == self.sentinel:
            self.map_private(slot, lp)

    def map_suffix_pages(self, slot: int, prompt_len: int):
        """Map pocket pages under every logical page a suffix extend
        will write (lease depth through the prompt's last page).  The
        native paged extend writes K/V straight into the slot's arena
        pages, so they must be mapped BEFORE the kernel runs — a
        sentinel block-table entry silently drops the write.  Pocket-
        backed, so it cannot fail; decode growth past the prompt keeps
        drawing pages per step via ``ensure_decode_page``."""
        for lp in range(-(-prompt_len // self.page_size)):
            if self.block_table[slot, lp] == self.sentinel:
                self.map_private(slot, lp)

    def promote_slot_pages(self, slot: int, prompt, ctx_key):
        """Intern a warm-extended slot's full prompt pages by OWNERSHIP
        TRANSFER — the paged extend already wrote the suffix KV in place,
        so no page data moves: each full-page chunk either joins the
        tree as-is (the slot's private page becomes the interned node,
        refcount 1 held by this slot) or, when the chunk is already
        interned, the slot remaps to the existing node and frees its
        now-redundant private copy (bit-identical by the exactness
        invariant).  The partial boundary page stays private (the
        copy-on-write edge); a foreign-prefix slot never interns
        (read-only public grant)."""
        if self._slot_foreign[slot]:
            return
        P = self.page_size
        L = len(prompt)
        tenant = self._slot_tenant[slot]
        owner = (PUBLIC if (ctx_key is not None and ctx_key
                            and ctx_key[0] == "public")
                 else (tenant if tenant is not None else DEFAULT_TENANT))
        parent = (self._shared[slot][-1] if self._shared[slot]
                  else self.tree.root(ctx_key))
        for lp in range(len(self._shared[slot]), L // P):
            page = int(self.block_table[slot, lp])
            key = tuple(int(t) for t in prompt[lp * P:(lp + 1) * P])
            node = parent.children.get(key)
            if node is not None:
                # chunk already interned: share it, free our copy
                self.block_table[slot, lp] = node.page
                self._private[slot].remove(page)
                self.free.append(page)
                self._uncharge(tenant, 1)
            elif self._transfer_charge(tenant, owner):
                node = self.tree.insert(parent, key, page, owner)
                self._private[slot].remove(page)
            else:
                break                   # owner pocket full: stay private
            node.refs += 1
            node.last_used = self.tree._tick()
            self._shared[slot].append(node)
            parent = node
        self._gauge()

    def install_stacks(self, slot: int, prompt, ctx_key,
                       stacks: List[KVSlice], start_page: int):
        """Map a request's computed suffix pages into ``slot``.

        ``stacks``: canonical page stacks covering logical pages
        ``start_page ..`` up to the prompt's last page.  Full prompt
        pages are INTERNED (copied into pool pages owned by the tree,
        refcount 1 held by this slot) so the next request with this
        prefix shares them; the partial boundary page stays private
        (copy-on-write edge).  Finally the page holding position
        ``len(prompt)`` is mapped so the first decode write lands."""
        P = self.page_size
        L = len(prompt)
        n = stacks[0].k.shape[0] if stacks else 0
        tenant = self._slot_tenant[slot]
        owner = (PUBLIC if (ctx_key is not None and ctx_key
                            and ctx_key[0] == "public")
                 else (tenant if tenant is not None else DEFAULT_TENANT))
        # a foreign (public-grant) prefix is read-only: the suffix may
        # never intern under it, so every suffix page stays private —
        # one tenant's data can't leak into a namespace it doesn't own
        can_intern = not self._slot_foreign[slot]
        parent = (self._shared[slot][-1] if self._shared[slot]
                  else self.tree.root(ctx_key))
        new_ids: List[int] = []         # pages needing a data write,
        new_rows: List[int] = []        # batched into ONE arena scatter
        for j in range(n):
            lp = start_page + j
            node = None
            if can_intern and (lp + 1) * P <= L:
                key = tuple(int(t) for t in prompt[lp * P:(lp + 1) * P])
                node = parent.children.get(key)
                if node is None:
                    if self._transfer_charge(tenant, owner):
                        page = self._take_pocket(slot)
                        node = self.tree.insert(parent, key, page, owner)
                        new_ids.append(page)
                        new_rows.append(j)
                    else:
                        # owner pocket full: the rest of the chain stays
                        # private (a child without its parent interned
                        # would be unreachable anyway)
                        can_intern = False
            if node is not None:
                node.refs += 1
                node.last_used = self.tree._tick()
                self._shared[slot].append(node)
                self.block_table[slot, lp] = node.page
                parent = node
            else:
                page = self._take_pocket(slot)
                new_ids.append(page)
                new_rows.append(j)
                self._private[slot].append(page)
                self.block_table[slot, lp] = page
        if new_ids:
            rows = jnp.asarray(new_rows, jnp.int32)
            sub = [KVSlice(k=s.k[rows], v=s.v[rows],
                           slot_pos=s.slot_pos[rows]) for s in stacks]
            self._write_pages(jnp.asarray(new_ids, jnp.int32), sub)
        self.ensure_decode_page(slot, L)
        self._gauge()

    def install_rows(self, slot: int, prompt, ctx_key, rows_cache,
                     row: int, start_page: int):
        """``install_stacks`` fed straight from a dense prefill/extend
        rows cache (the colocated batcher path)."""
        P = self.page_size
        n_total = -(-len(prompt) // P)
        stacks = extract_row_pages(rows_cache, self.axes, row, start_page,
                                   n_total - start_page, P)
        self.install_stacks(slot, prompt, ctx_key, stacks, start_page)

    def release_slot(self, slot: int):
        """Free a slot's pages: decref shared prefixes (they stay
        interned as reclaimable cache, still charged to their owner's
        pocket), return private + pocket pages to the free list
        (uncharging the slot tenant's pocket), unmap the block-table
        row."""
        self.tree.release(self._shared[slot])
        self._shared[slot] = []
        self._uncharge(self._slot_tenant[slot],
                       len(self._private[slot]) + len(self._pocket[slot]))
        self.free.extend(self._private[slot])
        self._private[slot] = []
        self.free.extend(self._pocket[slot])
        self._pocket[slot] = []
        self._slot_tenant[slot] = None
        self._slot_foreign[slot] = False
        self.block_table[slot, :] = self.sentinel
        self._gauge()

    def release_all(self):
        for slot in range(len(self._shared)):
            self.release_slot(slot)

    # -- prefill-side prefix cache (slot-less) -------------------------
    def intern_rows(self, prompt, ctx_key, rows_cache, row: int,
                    tenant: Optional[str] = None):
        """Best-effort intern of a prompt's full pages from a dense rows
        cache (the PrefillWorker's cache-fill path — refcounts stay 0,
        pages are pure reclaimable cache).  Stops silently when no page
        can be obtained.  Pages bill the namespace they land in: the
        public root charges the commons, a tenant root charges that
        tenant's pocket."""
        P = self.page_size
        L = len(prompt)
        owner = (PUBLIC if (ctx_key is not None and ctx_key
                            and ctx_key[0] == "public")
                 else (tenant if tenant is not None else DEFAULT_TENANT))
        parent = self.tree.root(ctx_key)
        path: List[_Node] = []          # pinned so eviction inside
        new_ids: List[int] = []         # _alloc_raw can't detach our walk
        new_lps: List[int] = []
        try:
            for lp in range(L // P):
                key = tuple(int(t) for t in prompt[lp * P:(lp + 1) * P])
                node = parent.children.get(key)
                if node is None:
                    # a fresh node's children can't pre-exist, so from
                    # the first miss on every page is new — the data
                    # writes batch into one scatter below
                    page = self._alloc_raw(owner)
                    if page is None:
                        break
                    node = self.tree.insert(parent, key, page, owner)
                    new_ids.append(page)
                    new_lps.append(lp)
                self.tree.acquire([node])
                path.append(node)
                parent = node
            if new_ids:
                stacks = extract_row_pages(rows_cache, self.axes, row,
                                           new_lps[0], len(new_lps), P)
                self._write_pages(jnp.asarray(new_ids, jnp.int32), stacks)
        finally:
            self.tree.release(path)
            self._gauge()

    def intern_snapshots(self, prompt, ctx_key, payloads,
                         tenant: Optional[str] = None):
        """Best-effort intern of a prompt's per-chunk state snapshots —
        the snapshot-pool twin of ``intern_rows`` (refcounts stay 0, the
        chain is pure reclaimable cache).  ``payloads[lp]`` is chunk
        ``lp``'s payload dict: ``{"state": the 1-row recurrent state
        AFTER position ``(lp+1)*page_size``, "pages": per-KV-node 1-page
        canonical stacks for the chunk's shared-attention positions
        ([] for pure ssm)}``.  Handles bill the landing namespace's
        pocket exactly like pages; the walked chain is pinned so an
        eviction inside ``_alloc_raw`` can't detach it mid-walk."""
        assert self.payload_kind == "snapshot", "page pools intern rows"
        P = self.page_size
        L = len(prompt)
        owner = (PUBLIC if (ctx_key is not None and ctx_key
                            and ctx_key[0] == "public")
                 else (tenant if tenant is not None else DEFAULT_TENANT))
        parent = self.tree.root(ctx_key)
        path: List[_Node] = []
        try:
            for lp in range(min(L // P, len(payloads))):
                key = tuple(int(t) for t in prompt[lp * P:(lp + 1) * P])
                node = parent.children.get(key)
                if node is None:
                    handle = self._alloc_raw(owner)
                    if handle is None:
                        break
                    node = self.tree.insert(parent, key, handle, owner)
                    self._snaps[handle] = payloads[lp]
                    self.snapshots_interned += 1
                    if self.accounting is not None:
                        self.accounting.record_counter("snapshots_interned")
                self.tree.acquire([node])
                path.append(node)
                parent = node
        finally:
            self.tree.release(path)
            self._gauge()

    def snapshot_chain(self, lease: PrefixLease) -> tuple:
        """Materialize a warm lease's restore payload.

        Returns ``(state, page_stacks)``: ``state`` is the DEEPEST
        node's boundary recurrent state (the scan state after
        ``lease.tokens`` positions — restoring it replays the whole
        prefix in O(1)); ``page_stacks`` is, per KV node, the
        concatenation of every chain chunk's shared-attention pages
        (logical pages ``[0, lease.pages)``, [] for pure ssm).
        ``(None, [])`` for an empty lease.  Read-only — the lease keeps
        its pins."""
        if not lease.nodes:
            return None, []
        payloads = [self._snaps[n.page] for n in lease.nodes]
        state = payloads[-1]["state"]
        per_chunk = [p["pages"] for p in payloads]
        if not per_chunk[0]:
            return state, []
        stacks = [
            jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                         *(pc[i] for pc in per_chunk))
            for i in range(len(per_chunk[0]))
        ]
        return state, stacks

    def alloc_temp_pages(self, n: int,
                         tenant: Optional[str] = None) -> List[int]:
        """``n`` cleaned scratch pages for a slot-less paged extend (the
        prefill worker's warm path writes suffix KV straight into them).
        Charged to ``tenant``'s pocket; raises :class:`PoolExhausted`
        (holding nothing) when the pocket/pool cannot cover them — the
        caller falls back to the cold dense-prefill path."""
        got: List[int] = []
        for _ in range(n):
            page = self._alloc_raw(tenant)
            if page is None:
                self._uncharge(tenant, len(got))
                self.free.extend(got)
                raise PoolExhausted(
                    f"need {n} temp pages, got {len(got)} "
                    f"(free={len(self.free)}, "
                    f"evictable={self.evictable_pages()})")
            got.append(page)
        if got:
            self._clean_pages(jnp.asarray(got, jnp.int32))
        return got

    def free_temp_pages(self, pages: List[int],
                        tenant: Optional[str] = None):
        """Return temp pages that did not transfer into the tree."""
        self._uncharge(tenant, len(pages))
        self.free.extend(pages)

    def intern_arena_pages(self, prompt, ctx_key, lease: PrefixLease,
                           temp_pages: List[int],
                           tenant: Optional[str] = None):
        """Ownership-transfer intern for the slot-less warm path:
        ``temp_pages[i]`` holds logical page ``lease.pages + i`` of
        ``prompt``, already written IN PLACE by the paged extend — the
        native-paged twin of ``intern_rows`` with zero data movement.
        Full pages enter the tree as refs-0 reclaimable cache (or are
        freed when the chunk is already interned); the partial tail
        page is always freed.  A foreign lease never interns (read-only
        public grant): every temp page is freed.  The walked chain is
        pinned so an eviction inside ``_transfer_charge`` can't reap a
        just-inserted leaf mid-walk."""
        P = self.page_size
        L = len(prompt)
        owner = (PUBLIC if (ctx_key is not None and ctx_key
                            and ctx_key[0] == "public")
                 else (tenant if tenant is not None else DEFAULT_TENANT))
        can_intern = not lease.foreign
        parent = (lease.nodes[-1] if lease.nodes
                  else self.tree.root(ctx_key))
        path: List[_Node] = []
        leftover: List[int] = []
        try:
            for i, page in enumerate(temp_pages):
                lp = lease.pages + i
                node = None
                if can_intern and (lp + 1) * P <= L:
                    key = tuple(int(t) for t in prompt[lp * P:(lp + 1) * P])
                    node = parent.children.get(key)
                    if node is None:
                        if self._transfer_charge(tenant, owner):
                            node = self.tree.insert(parent, key, page, owner)
                            page = None     # consumed: the tree owns it
                        else:
                            can_intern = False
                if page is not None:
                    leftover.append(page)
                if node is not None:
                    self.tree.acquire([node])
                    path.append(node)
                    parent = node
        finally:
            self.tree.release(path)
            if leftover:
                self._uncharge(tenant, len(leftover))
                self.free.extend(leftover)
            self._gauge()

    def read_pages(self, page_ids) -> list:
        """Canonical page stacks for ``page_ids`` (test / audit surface:
        the copy-on-write suite snapshots interned pages through this).
        An int8 arena dequantizes to f32 — export/migration round-trips
        through floats, so int8 pools make no bit-exactness claims."""
        stacks = read_arena_pages(self.arena, page_ids)
        if self.kv_scales is None:
            return stacks
        idx = jnp.asarray(page_ids, jnp.int32)
        return [KVSlice(k=dequantize_page(s.k, ks[idx], keep_axes=(0, 1)),
                        v=dequantize_page(s.v, vs[idx], keep_axes=(0, 1)),
                        slot_pos=s.slot_pos)
                for s, (ks, vs) in zip(stacks, self.kv_scales)]

    # -- replica-to-replica migration (the cluster cache plane) --------
    def export_subtree(self, ctx_key=None,
                       max_pages: Optional[int] = None) -> tuple:
        """Serialize one namespace's interned prefix tree for migration.

        Returns ``(records, stacks)``: ``records[i]`` is ``{"key":
        chunk-token tuple, "owner": billing owner, "parent": j}`` with
        ``j`` the index of the node's parent record (``-1`` = root), in
        pre-order so every parent precedes its children; ``stacks`` is
        the canonical page data aligned row-for-row with ``records``
        (``read_pages`` over the nodes' arena pages).  ``max_pages``
        caps the export — children of an unexported node are dropped
        with it (a child without its parent would be unreachable).
        Read-only: refcounts and the tree are untouched."""
        root = self.tree._roots.get(ctx_key)
        records: List[dict] = []
        pages: List[int] = []
        if root is None:
            return records, []
        stack: List[tuple] = [(root, -1)]
        while stack and (max_pages is None or len(records) < max_pages):
            node, pidx = stack.pop()
            if node.page is not None:
                idx = len(records)
                records.append({"key": node.key, "owner": node.owner,
                                "parent": pidx})
                pages.append(node.page)
            else:
                idx = pidx
            stack.extend((c, idx) for c in node.children.values())
        if self.payload_kind == "snapshot":
            # stacks row i is record i's interned payload dict verbatim
            # (ArrayChannel._transfer device-puts any pytree)
            return records, [self._snaps[p] for p in pages]
        stacks = (self.read_pages(jnp.asarray(pages, jnp.int32))
                  if pages else [])
        return records, stacks

    def import_subtree(self, ctx_key, records, stacks) -> int:
        """Best-effort re-intern of an exported subtree into this pool.

        Refcount-correct: imported nodes arrive as refs-0 reclaimable
        cache (no phantom pins survive the migration), each page is
        charged to its record's ORIGINAL owner's pocket, and nodes this
        tree already holds are skipped (the interned page is
        bit-identical by the exactness invariant).  A record whose page
        cannot be allocated — or whose parent was skipped — is dropped
        with its descendants, never partially linked.  The walked chain
        is pinned during the import so an eviction triggered by
        ``_alloc_raw`` can never reap a just-imported leaf mid-walk.
        Returns the number of NEW pages interned."""
        root = self.tree.root(ctx_key)
        nodes: List[Optional[_Node]] = [None] * len(records)
        pinned: List[_Node] = []
        new_ids: List[int] = []
        new_rows: List[int] = []
        try:
            for i, rec in enumerate(records):
                parent = (root if rec["parent"] < 0
                          else nodes[rec["parent"]])
                if parent is None:      # parent dropped -> drop subtree
                    continue
                key = tuple(rec["key"])
                node = parent.children.get(key)
                if node is None:
                    page = self._alloc_raw(rec["owner"])
                    if page is None:
                        continue        # exhausted: siblings may still fit
                    node = self.tree.insert(parent, key, page, rec["owner"])
                    if self.payload_kind == "snapshot":
                        self._snaps[page] = stacks[i]
                        self.snapshots_interned += 1
                    new_ids.append(page)
                    new_rows.append(i)
                self.tree.acquire([node])
                pinned.append(node)
                nodes[i] = node
            if new_ids and self.payload_kind == "page":
                rows = jnp.asarray(new_rows, jnp.int32)
                sub = [KVSlice(k=s.k[rows], v=s.v[rows],
                               slot_pos=s.slot_pos[rows]) for s in stacks]
                self._write_pages(jnp.asarray(new_ids, jnp.int32), sub)
        finally:
            self.tree.release(pinned)
            self._gauge()
        return len(new_ids)


# --------------------------------------------------------------------------
# jitted programs over the paged cache
# --------------------------------------------------------------------------
def build_paged_serve_step(model, temperature, *, template):
    """paged_step(params, arena, scales, resident, block_table, batch,
    rng) -> (next_tokens, arena, scales, resident).

    NATIVE paged decode: ``paged_view`` hands ``Model.decode`` the arena
    itself behind each row's block table — attention writes the new
    token's K/V straight into its physical page (sentinel entries drop
    the write) and the paged decode kernel walks the row's pages in
    place.  No gather, no scatter, no dense per-slot KV is ever
    materialized.  ``resident`` carries the non-positional cache
    remainder (encdec cross memory) dense per slot; ``scales`` is the
    per-(page, layer) int8 scale list (None for float arenas).  Callers
    jit with the arena/scales/resident donated and may width-trim the
    block table to the live page bucket — paged cost then scales with
    occupancy, not ``max_len``."""
    def paged_step(params, arena, scales, resident, block_table, batch, rng):
        cache = paged_view(template, resident, arena, block_table, scales)
        logits, new_cache = model.decode(params, cache, batch)
        arena, scales, resident = extract_paged(new_cache)
        toks = sample_tokens(logits, rng, temperature)
        return toks, arena, scales, resident
    return paged_step


def build_paged_extend_step(model, temperature, *, template):
    """paged_extend(params, arena, scales, resident, block_table, batch,
    rng) -> (first_tokens, arena, scales, resident).

    The suffix-extend twin of ``build_paged_serve_step``:
    ``Model.prefill_extend`` runs over the paged view, writing each
    row's suffix K/V directly into its mapped arena pages — no dense
    prefix gather in front, no page scatter behind.  Each row's block
    table must already map every page its suffix touches
    (``KVPool.map_suffix_pages`` / ``alloc_temp_pages``); unmapped rows
    and pages drop their writes and read fully masked."""
    def paged_extend(params, arena, scales, resident, block_table, batch,
                     rng):
        cache = paged_view(template, resident, arena, block_table, scales)
        logits, new_cache = model.prefill_extend(params, batch, cache)
        arena, scales, resident = extract_paged(new_cache)
        toks = sample_tokens(logits, rng, temperature)
        return toks, arena, scales, resident
    return paged_extend


def build_snapshot_payloads(model, axes, page_size: int, prompt,
                            rows_cache, ckpts, row: int) -> list:
    """Per-chunk snapshot payload dicts for one cold-prefilled row — the
    intern/handoff artifact of the snapshot cache plane.

    ``payloads[lp]`` covers prompt chunk ``lp``: ``state`` is the 1-row
    recurrent state AFTER position ``(lp+1)*page_size`` (sliced from the
    checkpoint-emitting prefill's stacked ``ckpts``) and ``pages`` holds
    the chunk's shared-attention KV as per-node 1-page canonical stacks
    ([] for pure ssm — ``axes`` empty).  Only ``len(prompt) //
    page_size`` chunks are built: checkpoints at boundaries past a row's
    true length are bucket-pad garbage and must never be read."""
    from repro.models.cache_utils import extract_row_pages
    n_chunks = len(prompt) // page_size
    if n_chunks == 0:
        return []
    all_stacks = (extract_row_pages(rows_cache, axes, row, 0, n_chunks,
                                    page_size)
                  if axes else None)
    payloads = []
    for lp in range(n_chunks):
        pages = ([jax.tree.map(lambda a, lp=lp: a[lp:lp + 1], s)
                  for s in all_stacks] if all_stacks else [])
        payloads.append({
            "state": model.slice_checkpoint(ckpts, row, lp),
            "pages": pages,
        })
    return payloads


def run_extend_group(extend_fn, params, scratch, pool: KVPool, reqs,
                     leases: List[PrefixLease], bt_rows, *, chunk: int,
                     max_len: int, rng, model, accounting=None):
    """ONE native-paged suffix-extend invocation over prefix-hit rows.

    Mirrors ``run_prefill_group``: the batch dim pads to the next power
    of two with dummy rows and all suffixes share one pad bucket, but
    each row carries its own prefix offset (``pos``), so requests with
    DIFFERENT hit depths batch together.  ``bt_rows`` (B, n_logical)
    gives each row's block table — slot rows in the batcher, lease +
    temp-page rows in the prefill worker — with every page the suffix
    writes already mapped; pad rows are all-sentinel (writes drop,
    reads mask, outputs are discarded).  The table is width-trimmed to
    the pow2 page bucket covering the longest prompt, so extend cost
    scales with occupancy, not ``max_len``.  The suffix K/V lands
    directly in the arena pages (``extend_fn`` is a — typically
    jitted — ``build_paged_extend_step`` step; the pool's arena/scales
    are updated in place here).  ``scratch`` is a ``batch -> cache``
    factory (callers memoize theirs; only its resident structure is
    used).  Returns (first_tokens, b_pad-row resident tree, advanced
    rng, b_pad)."""
    B = len(reqs)
    b_pad = 1 << (B - 1).bit_length()
    P = pool.page_size
    prefix = [lease.tokens for lease in leases] + [0] * (b_pad - B)
    suffixes = [np.asarray(r.prompt[h:], np.int32)
                for r, h in zip(reqs, prefix)]
    s_pad = bucket_len(max(len(s) for s in suffixes), chunk, max_len)
    tokens = np.zeros((b_pad, s_pad), np.int32)
    lengths = np.zeros((b_pad,), np.int32)
    for i, s in enumerate(suffixes):
        tokens[i, :len(s)] = s
        lengths[i] = len(s)
    width = max(-(-len(r.prompt) // P) for r in reqs)
    width = min(1 << (width - 1).bit_length(), pool.n_logical)
    bt = np.full((b_pad, width), pool.sentinel, np.int32)
    bt[:B] = np.asarray(bt_rows, np.int32)[:, :width]
    resident = jax.tree.map(jnp.zeros_like, strip_kv_nodes(scratch(b_pad)))
    srcs = [getattr(r, "src", None) for r in reqs] + [None] * (b_pad - B)
    mem = model.encode_cross_rows(params, srcs, max_len)
    if mem is not None:
        resident = install_cross_memory(resident, mem, list(range(b_pad)))
    batch = {
        "tokens": jnp.asarray(tokens),
        "pos": jnp.asarray(prefix, jnp.int32),
        "length": jnp.asarray(lengths),
    }
    rng, sub = jax.random.split(rng)
    toks, arena, scales, rows = extend_fn(
        params, pool.arena, pool.kv_scales, resident, jnp.asarray(bt),
        batch, sub)
    pool.arena = arena
    pool.kv_scales = scales
    if accounting is not None and b_pad != B:
        accounting.record_counter("prefill_dummy_rows", b_pad - B)
    return [int(t) for t in np.asarray(toks)], rows, rng, b_pad
