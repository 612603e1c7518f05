"""KVPool — paged KV memory + radix-tree prefix sharing.

Covers the cache-plane tentpole (hypothesis property tests live in
``test_kvpool_properties.py`` so this module runs without the dep):

  * block-table gather == the dense rows the pages came from (unmapped
    entries read empty) for dense / moe / encdec cache layouts;
  * the copy-on-write invariant: interned (shared) pages are never
    written by serving traffic;
  * EXACTNESS — prefix-hit serving is token-for-token identical to cold
    serving for dense + moe + encdec, colocated and disaggregated;
  * hardening regressions — pool exhaustion REQUEUES (blocks) instead of
    dropping, and a replica detach releases every page / refcount;
  * pool occupancy as the third replica-autoscale signal.
"""
import numpy as np
import pytest

import jax

from repro.configs.base import smoke_config
from repro.configs.registry import get_arch
from repro.models.cache_utils import (
    extract_row_pages,
    gather_pages,
    kv_cache_nodes,
    kv_node_axes,
    page_arena,
    read_arena_pages,
    write_arena_pages,
)
from repro.models.model import build_model
from repro.serve.batcher import ContinuousBatcher, Request
from repro.sharding.rules import single_device_ctx

MAX_LEN = 32
CHUNK = 8
PAGE = 8
N_LOG = MAX_LEN // PAGE

# moe stays DROPLESS (expert capacity never binds) as long as every
# prefill/extend invocation sees <= 64 tokens — the sizes here guarantee
# it, so interned pages are bit-identical across batch compositions and
# the exactness assertions below are deterministic.
FAMILY_ARCHS = ["qwen3-4b", "mixtral-8x7b", "seamless-m4t-large-v2"]

_CACHE = {}


def _model(name):
    if name not in _CACHE:
        cfg = smoke_config(get_arch(name))
        if cfg.sliding_window is not None and cfg.sliding_window < MAX_LEN:
            cfg = cfg.replace(sliding_window=64)
        model = build_model(cfg, single_device_ctx())
        _CACHE[name] = (model, model.init(jax.random.PRNGKey(0)))
    return _CACHE[name]


def _requests(cfg, lens, *, shared=0, max_new=4, seed=0, rid0=0, src_seed=None):
    """Prompts sharing a ``shared``-token prefix (seeded separately)."""
    srng = np.random.RandomState(1234)
    sysp = srng.randint(1, cfg.vocab, size=shared).astype(np.int32)
    rng = np.random.RandomState(seed)
    out = []
    for i, L in enumerate(lens):
        tail = rng.randint(1, cfg.vocab, size=L).astype(np.int32)
        src = None
        if cfg.family == "encdec":
            sr = np.random.RandomState(src_seed if src_seed is not None
                                       else 99)
            src = sr.randn(9, cfg.d_model).astype(np.float32)
        out.append(Request(rid=rid0 + i, prompt=np.concatenate([sysp, tail]),
                           max_new_tokens=max_new, src=src))
    return out


# ---------------------------------------------------------------------------
# property-based: page-indexed gather/scatter roundtrips per cache layout
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_block_table_gather_matches_dense(arch):
    """gather_pages through a block table == the dense rows the pages
    came from; unmapped entries read as empty (slot_pos -1)."""
    model, _ = _model(arch)
    num_pages = 2 * N_LOG + 1
    arena = page_arena(model, num_pages, PAGE)
    axes = kv_node_axes(model, 1, MAX_LEN)
    rng = np.random.RandomState(0)
    cache = jax.tree.map(
        lambda x: jax.numpy.asarray(
            rng.standard_normal(x.shape).astype(np.float32)).astype(x.dtype),
        model.init_cache(2, MAX_LEN))
    bt = np.full((2, N_LOG), num_pages, np.int32)      # all unmapped
    for row in range(2):
        stacks = extract_row_pages(cache, axes, row, 0, N_LOG, PAGE)
        ids = list(range(row * N_LOG, (row + 1) * N_LOG))
        arena = write_arena_pages(arena, ids, stacks)
        bt[row, :] = ids
    bt[1, -1] = num_pages                              # hole in row 1
    dense = gather_pages(arena, axes, jax.numpy.asarray(bt), PAGE)
    src = kv_cache_nodes(cache)
    for node, got, a in zip(src, dense, axes):
        ref_sp = np.moveaxis(np.asarray(node.slot_pos), a, 0).copy()
        got_sp = np.moveaxis(np.asarray(got.slot_pos), a, 0)
        ref_k = np.moveaxis(np.asarray(node.k, np.float32), a, 0).copy()
        got_k = np.moveaxis(np.asarray(got.k, np.float32), a, 0)
        # row 1's last page is unmapped: reads empty (slot_pos -1); row
        # 0 is exact everywhere (k checked on its full row)
        ref_sp[1, ..., -PAGE:] = -1
        assert np.array_equal(got_sp, ref_sp)
        assert np.array_equal(got_k[0], ref_k[0])


# ---------------------------------------------------------------------------
# copy-on-write: shared pages are never written
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_shared_pages_never_written(arch):
    """After a warm wave decodes THROUGH shared pages, the interned page
    bytes are bit-identical to their post-intern snapshot — decode only
    ever writes each slot's private current page."""
    model, params = _model(arch)
    cfg = model.cfg
    bat = ContinuousBatcher(model, params, batch_slots=2, max_len=MAX_LEN,
                            prefill_chunk=CHUNK, page_size=PAGE)
    assert bat.pool is not None
    for r in _requests(cfg, [3, 5], shared=18):
        bat.submit(r)
    bat.run_until_drained()
    pool = bat.pool
    interned = [n.page for n in pool.tree._walk()]
    assert interned, "shared prefix must have been interned"
    before = [np.asarray(leaf).copy()
              for s in read_arena_pages(pool.arena, interned) for leaf in s]
    for r in _requests(cfg, [2, 6], shared=18, seed=7, rid0=10):
        bat.submit(r)
    bat.run_until_drained()
    assert pool.prefix_hit_tokens > 0
    after = [np.asarray(leaf)
             for s in read_arena_pages(pool.arena, interned) for leaf in s]
    for b, a in zip(before, after):
        assert np.array_equal(b, a), "a shared page was written"


# ---------------------------------------------------------------------------
# EXACTNESS: prefix-hit serving == cold serving, token for token
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_prefix_hit_exact_colocated(arch):
    """A warm batcher (tree already holding the shared prefix) must serve
    bit-identical token streams to a cold batcher, for dense + moe +
    encdec — the whole point of chunk-exact interning."""
    model, params = _model(arch)
    cfg = model.cfg

    def fresh():
        return ContinuousBatcher(model, params, batch_slots=2,
                                 max_len=MAX_LEN, prefill_chunk=CHUNK,
                                 page_size=PAGE)

    warm = fresh()
    for r in _requests(cfg, [3, 5, 2], shared=18):       # seeds the tree
        warm.submit(r)
    warm.run_until_drained()
    probe = _requests(cfg, [4, 7], shared=18, seed=5, rid0=10)
    for r in probe:
        warm.submit(r)
    got = {r.rid: r.output for r in warm.run_until_drained()
           if r.rid >= 10}
    assert warm.pool.prefix_hit_tokens >= 2 * 16        # 2 pages x 2 reqs

    cold = fresh()
    for r in _requests(cfg, [4, 7], shared=18, seed=5, rid0=10):
        cold.submit(r)
    ref = {r.rid: r.output for r in cold.run_until_drained()}
    assert cold.pool.prefix_hit_tokens == 0
    assert got == ref, (arch, got, ref)


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_paged_kernels_serve_like_page_walk(arch, monkeypatch):
    """Serving through the Pallas paged kernels (interpret mode here, the
    path the chip takes) emits the same tokens as the jnp page walk, cold
    and warm: the kernels' position mask and the arena's ``slot_pos``
    agree on every page the serving plane maps, shared prefixes
    included."""
    import repro.models.layers as layers
    model, params = _model(arch)
    cfg = model.cfg

    def run():
        bat = ContinuousBatcher(model, params, batch_slots=2,
                                max_len=MAX_LEN, prefill_chunk=CHUNK,
                                page_size=PAGE)
        for r in _requests(cfg, [3, 5], shared=18):
            bat.submit(r)
        bat.run_until_drained()
        for r in _requests(cfg, [4, 7], shared=18, seed=5, rid0=10):
            bat.submit(r)
        out = {r.rid: r.output for r in bat.run_until_drained()}
        assert bat.pool.prefix_hit_tokens > 0
        return out

    ref = run()
    monkeypatch.setattr(layers, "paged_kernels", lambda: True)
    assert run() == ref, arch


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_paged_matches_legacy_dense_cache(arch):
    """The paged cache plane (block-table indirection + paged installs)
    serves the same outputs as the legacy dense per-slot cache on a cold
    ragged batch, chunked AND token-at-a-time."""
    model, params = _model(arch)
    cfg = model.cfg
    lens = [3, 17, 1, 20, 9]

    def run(chunk, pool):
        bat = ContinuousBatcher(model, params, batch_slots=2,
                                max_len=MAX_LEN, prefill_chunk=chunk,
                                page_size=PAGE, kv_pool=pool)
        assert (bat.pool is not None) == (pool == "auto")
        for r in _requests(cfg, lens, shared=0):
            bat.submit(r)
        return {r.rid: r.output for r in bat.run_until_drained()}

    assert run(CHUNK, "auto") == run(CHUNK, None), arch
    assert run(None, "auto") == run(None, None), arch


def test_prefix_hit_exact_disagg():
    """Disaggregated: a warm server (both prefill-side and decode-side
    trees populated, only the page suffix crossing the channel) serves
    the same tokens as a cold server, and the savings are visible in
    stats() — hit tokens, kv_bytes_saved, and fewer channel bytes."""
    from repro.core import DeviceGrid, Supervisor
    from repro.serve.disagg import DisaggServer

    model, params = _model("qwen3-4b")
    cfg = model.cfg

    def fresh_server():
        grid = DeviceGrid.from_flat(jax.devices()[:1], pods=1, rows=1,
                                    cols=3, allow_reuse=True)
        sup = Supervisor(grid)
        sup.create_cell("prefill", cfg, "serve", ncols=1)
        dec = sup.create_cell("dec0", cfg, "serve", ncols=1)
        dec.init_serve(rng=jax.random.PRNGKey(0))
        sup.create_cell("dec1", cfg, "serve", ncols=1)
        return sup, DisaggServer(sup, "prefill", ["dec0", "dec1"],
                                 batch_slots=2, max_len=MAX_LEN,
                                 chunk=CHUNK, page_size=PAGE)

    sup, srv = fresh_server()
    assert srv.worker.pool is not None
    for r in _requests(cfg, [3, 5, 2, 4], shared=18):
        srv.submit(r)
    srv.run_until_drained(max_steps=2_000)
    cold_bytes_wave1 = srv.stats()["kv_bytes"]
    probe = _requests(cfg, [4, 7, 3], shared=18, seed=5, rid0=10)
    for r in probe:
        srv.submit(r)
    got = {r.rid: r.output
           for r in srv.run_until_drained(max_steps=2_000) if r.rid >= 10}
    st = srv.stats()
    assert st["paged_kv"]
    assert st["prefix_hit_tokens"] > 0 and st["kv_bytes_saved"] > 0
    # the warm wave's suffixes crossed the channel, not the shared prefix
    warm_bytes = st["kv_bytes"] - cold_bytes_wave1
    assert warm_bytes < cold_bytes_wave1
    # prefill cell skipped the shared chunks' compute
    pc = sup.cells["prefill"].accounting.counters
    assert pc["prefix_hit_tokens"] > 0

    sup2, srv2 = fresh_server()
    for r in _requests(cfg, [4, 7, 3], shared=18, seed=5, rid0=10):
        srv2.submit(r)
    ref = {r.rid: r.output for r in srv2.run_until_drained(max_steps=2_000)}
    assert got == ref


# ---------------------------------------------------------------------------
# hardening regressions
# ---------------------------------------------------------------------------
def test_pool_exhaustion_requeues_not_drops():
    """Regression: a request whose page allocation fails mid-admission
    must go BACK to the queue head (admission blocks) — not be dropped —
    and must serve once pages free up."""
    model, params = _model("qwen3-4b")
    cfg = model.cfg
    # pool of exactly one request's worst case: the second admit blocks
    bat = ContinuousBatcher(model, params, batch_slots=2, max_len=MAX_LEN,
                            prefill_chunk=CHUNK, page_size=PAGE,
                            pool_pages=N_LOG)
    reqs = _requests(cfg, [20, 20, 20], shared=0, max_new=4)
    for r in reqs:
        bat.submit(r)
    bat.step()
    # only one slot admitted; the others are QUEUED, not dropped
    need = bat.pool.required_pages(20, 4)
    assert sum(1 for s in bat.slot_req if s is not None) == 1
    assert len(bat.queue) == 2 and bat.pool.pages_in_use == need
    done = bat.run_until_drained(max_steps=5_000)
    assert {r.rid for r in done} == {0, 1, 2}            # nothing lost
    assert all(len(r.output) == 4 for r in done)


def test_install_prefilled_blocks_on_exhausted_pool():
    """The disaggregated install path returns False (caller retries)
    instead of overrunning the arena."""
    model, params = _model("qwen3-4b")
    cfg = model.cfg
    bat = ContinuousBatcher(model, params, batch_slots=2, max_len=MAX_LEN,
                            prefill_chunk=CHUNK, page_size=PAGE,
                            pool_pages=N_LOG)
    (r0, r1) = _requests(cfg, [20, 20], shared=0)
    bat.submit(r0)
    bat.step()                                           # r0 owns the arena
    row = model.init_cache(1, MAX_LEN)
    before = bat.pool.pages_in_use
    assert bat.install_prefilled(r1, row, 7) is False
    assert bat.pool.pages_in_use == before               # nothing leaked


def test_pump_blocks_on_replica_pool_pressure():
    """Disagg admission control: when every replica's pool is committed,
    pump defers the overflow to pending (``blocked_on_pool``) and serves
    it once pages free — no request lost, no pool overrun."""
    from repro.core import DeviceGrid, Supervisor
    from repro.serve.disagg import DisaggServer

    model, _ = _model("qwen3-4b")
    cfg = model.cfg
    grid = DeviceGrid.from_flat(jax.devices()[:1], pods=1, rows=1, cols=3,
                                allow_reuse=True)
    sup = Supervisor(grid)
    sup.create_cell("prefill", cfg, "serve", ncols=1)
    sup.create_cell("dec0", cfg, "serve", ncols=1).init_serve(
        rng=jax.random.PRNGKey(0))
    sup.create_cell("dec1", cfg, "serve", ncols=1)
    # each replica's pool covers exactly ONE in-flight request
    srv = DisaggServer(sup, "prefill", ["dec0", "dec1"], batch_slots=2,
                       max_len=MAX_LEN, chunk=CHUNK, page_size=PAGE,
                       pool_pages=N_LOG)
    for r in _requests(cfg, [20, 20, 20, 20, 20], shared=0, max_new=4):
        srv.submit(r)
    srv.step()
    assert srv.blocked_on_pool >= 1          # overflow deferred, not sent
    assert len(srv.pending) >= 1
    done = {r.rid for r in srv.run_until_drained(max_steps=5_000)}
    assert done == {0, 1, 2, 3, 4}           # every request served
    for rep in srv.replicas:
        assert rep.pool.pages_in_use == rep.pool.tree.interned


def test_detach_releases_pages_and_decrefs():
    """Regression: detaching a replica mid-flight must release its pool
    pages and decref its interned prefixes — every refcount back to 0,
    no page owned by a vanished slot — while its requests requeue."""
    from repro.core import DeviceGrid, Supervisor
    from repro.serve.disagg import DisaggServer

    model, _ = _model("qwen3-4b")
    cfg = model.cfg
    grid = DeviceGrid.from_flat(jax.devices()[:1], pods=1, rows=1, cols=3,
                                allow_reuse=True)
    sup = Supervisor(grid)
    sup.create_cell("prefill", cfg, "serve", ncols=1)
    sup.create_cell("dec0", cfg, "serve", ncols=1).init_serve(
        rng=jax.random.PRNGKey(0))
    sup.create_cell("dec1", cfg, "serve", ncols=1)
    srv = DisaggServer(sup, "prefill", ["dec0", "dec1"], batch_slots=2,
                       max_len=MAX_LEN, chunk=CHUNK, page_size=PAGE)
    for r in _requests(cfg, [3, 5, 2, 4], shared=18, max_new=6):
        srv.submit(r)
    srv.step()
    victim = srv.replicas[1]
    pool = victim.pool
    held = sum(1 for s in victim.batcher.slot_req if s is not None)
    infl = len(victim.inflight)
    assert held + infl >= 1 and pool.pages_in_use > 0
    hit_before = srv.stats()["prefix_hit_tokens"]
    n = srv._detach(victim)
    assert n == held + infl
    # every slot page released; interned cache pages all refcount-0
    assert all(n_.refs == 0 for n_ in pool.tree._walk())
    assert pool.pages_in_use == pool.tree.interned
    assert not any(pool._private) and not any(pool._pocket)
    # detached-replica rollup keeps the pool counters in stats()
    assert srv.stats()["prefix_hit_tokens"] >= hit_before
    done = {r.rid for r in srv.run_until_drained(max_steps=2_000)}
    assert done == {0, 1, 2, 3}                          # nothing lost


def test_pool_occupancy_is_third_autoscale_signal():
    """ReconcilePolicy grows replicas on KV-pool pressure alone, and
    refuses to shrink into a memory squeeze."""
    from benchmarks.simlib import SimSupervisor
    from repro.core import CellSpec, ClusterSpec
    from repro.core.elastic import ElasticPolicy, ReconcilePolicy

    sup = SimSupervisor()
    sup.apply(ClusterSpec(cells=(
        CellSpec("dec", None, "serve", ncols=1, replicas=1, max_replicas=3),)))
    occ = {"v": 0.0}
    pol = ReconcilePolicy(
        sup, "dec",
        replica_policy=ElasticPolicy(lt=0.05, ut=0.2, window=10,
                                     metric="tpot"),
        queue_depth=lambda: 0,
        pool_occupancy=lambda: occ["v"], occupancy_high=0.9)
    # a nearly-full pool grows even with an empty queue and no samples
    occ["v"] = 0.95
    act = pol.maybe_act(now=0.0)
    assert act and act["kind"] == "grow_replicas"
    assert act["pool_occupancy"] == 0.95
    assert sup.desired.cell("dec").replicas == 2
    # comfortably-low tail would shrink — but not while memory is tight
    for i in range(10):
        sup.cells["dec/0"].accounting.record_request(i, tpot=0.01)
    occ["v"] = 0.6
    assert pol.maybe_act(now=1.0) is None
    assert sup.desired.cell("dec").replicas == 2
    # memory relaxed: the shrink goes through
    occ["v"] = 0.1
    act = pol.maybe_act(now=2.0)
    assert act and act["kind"] == "shrink_replicas"
    assert sup.desired.cell("dec").replicas == 1
