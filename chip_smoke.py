#!/usr/bin/env python3
"""Chip smoke: the serving main path at qwen3-4b's published width.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # replicas on four chips vs one chip

One chip: the paged Pallas kernels are checked against their jnp
references on the chip, then ``repro.launch.serve`` builds a serving cell
(weights from ``PRNGKey(--seed)``, 8 slots, ``max_len`` 2048, paged KV
pool) and serves 12 requests of 64-1024 prompt tokens, five of them
opening with one 320-token prefix, one arrival per batcher step, 32
greedy tokens each.  Every phase prints its facts; every request must
finish with its token count, the pool must report prefix-hit tokens, and
the compiled decode step must hold the Pallas paged kernel.

``--chips 4``: the same requests served colocated on device 0 (the
reference, then destroyed), then by one prefill cell and three decode
replicas on four distinct chips behind the cache-plane router
(``DisaggServer``).  Every request must complete with no requeues and
with the reference's first token.

The last line of standard output is one JSON object naming the device.
Without a TPU the script exits non-zero and prints no result.  Latencies
printed here are a first reading on the chip, not a benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-4b"
SLOTS = 8
MAX_LEN = 2048
CHUNK = 256
MAX_NEW = 32
N_REQUESTS = 12
SHARED_PREFIX = 320
SHARED = (0, 3, 6, 9, 11)
# 8 slots x ceil((1024 + 32) / 16) pages, plus room for interned prefixes
POOL_PAGES = 640


def require(ok: bool, what: str):
    """A failed check ends the run (asserts vanish under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class CompileLog:
    """Backend compile seconds per jitted program, from JAX's own
    compile-duration events (a persistent-cache hit reports its load)."""

    def __init__(self):
        import jax
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((kw.get("fun_name", "?"), duration))

    def report(self, phase: str, since: int = 0):
        big = [(n, s) for n, s in self.events[since:] if s >= 0.5]
        small = [s for _, s in self.events[since:] if s < 0.5]
        for n, s in big:
            print(f"[{phase}] compile {n}: {s:.2f} s")
        print(f"[{phase}] compile total {sum(s for _, s in self.events[since:]):.2f} s "
              f"over {len(self.events) - since} programs "
              f"({len(small)} under 0.5 s)")
        return len(self.events)


def make_prompts(vocab: int, seed: int):
    from repro.launch.serve import make_requests
    import numpy as np
    rng = np.random.default_rng(seed)
    lengths = rng.integers(64, 1025, size=N_REQUESTS)
    lengths[list(SHARED)] = rng.integers(SHARED_PREFIX + 64, 1025,
                                         size=len(SHARED))
    return make_requests(vocab, [int(n) for n in lengths], seed=seed,
                         max_new=MAX_NEW, shared_prefix=SHARED_PREFIX,
                         shared=SHARED)


def check_kernels(seed: int, *, tol: float = 2e-2):
    """Both paged kernels vs their jnp references at qwen3-4b's head
    widths (Hq 32, Hkv 8, Dh 128, 16-token pages), bf16 and int8."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attention import (
        paged_decode_attention, paged_decode_attention_ref)
    from repro.kernels.flash_attention import (
        paged_extend_attention, paged_extend_attention_ref)
    from repro.models.cache_utils import quantize_page

    B, Hq, Hkv, Dh, L, P, n_log, S = 2, 32, 8, 128, 2, 16, 8, 64
    N = B * n_log + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    k = jax.random.normal(ks[0], (N, L, Hkv, P, Dh), jnp.bfloat16)
    v = jax.random.normal(ks[1], (N, L, Hkv, P, Dh), jnp.bfloat16)
    bt = jax.random.permutation(ks[2], N)[:B * n_log].reshape(B, n_log)
    bt = bt.at[0, -1].set(N)                 # one unmapped (sentinel) page
    kv_len = jnp.asarray([n_log * P - P - 3, 37], jnp.int32)
    pos = jnp.asarray([n_log * P - P - S, 5], jnp.int32)
    q1 = jax.random.normal(ks[3], (B, 1, Hq, Dh), jnp.bfloat16)
    qs = jax.random.normal(ks[4], (B, S, Hq, Dh), jnp.bfloat16)
    lyr = jnp.int32(1)

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    kq, ksc = quantize_page(k, keep_axes=(0, 1))
    vq, vsc = quantize_page(v, keep_axes=(0, 1))
    for name, kk, vv, sc in (("bf16", k, v, {}),
                             ("int8", kq, vq, {"k_scale": ksc,
                                               "v_scale": vsc})):
        with jax.default_matmul_precision("highest"):
            d_ref = paged_decode_attention_ref(q1[:, 0], kk, vv, bt, kv_len,
                                               lyr, **sc)
            e_ref = paged_extend_attention_ref(qs.transpose(0, 2, 1, 3), kk,
                                               vv, bt, pos, lyr, **sc)
        d = rel(paged_decode_attention(q1, kk, vv, bt, kv_len, lyr, **sc)[:, 0],
                d_ref)
        e = rel(paged_extend_attention(qs, kk, vv, bt, pos, lyr, **sc),
                e_ref.transpose(0, 2, 1, 3))
        print(f"[kernels] {name}: paged decode rel err {d:.3e}, "
              f"paged extend rel err {e:.3e} (limit {tol:g})")
        require(d < tol and e < tol, f"{name} kernel parity {d}, {e}")


def param_bytes(params) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(params))


def check_served(done, reqs):
    require(len(done) == len(reqs), f"{len(done)}/{len(reqs)} requests served")
    for r in done:
        require(len(r.output) == r.max_new_tokens,
                f"request {r.rid}: {len(r.output)} tokens")


def serve_one_chip(arch, *, seed: int, log: CompileLog):
    """The colocated paged serving path, end to end.  Returns (cell,
    batcher, finished requests)."""
    import jax
    from repro.launch.serve import build_server, serve_arrivals

    t0 = time.time()
    _sup, cell, bat = build_server(arch, slots=SLOTS, max_len=MAX_LEN,
                                   prefill_chunk=CHUNK, pool_pages=POOL_PAGES,
                                   seed=seed)
    jax.block_until_ready(cell.serve_params)
    require(bat.pool is not None and bat.pool.payload_kind == "page",
            "paged KV pool")
    print(f"[init] {arch.name}: {cell.model.n_params():,} params, "
          f"{param_bytes(cell.serve_params):,} param bytes, "
          f"{time.time() - t0:.2f} s; paged KV pool of "
          f"{bat.pool.num_pages} pages x {bat.pool.page_size} tokens")
    reqs = make_prompts(arch.vocab, seed)
    n0 = len(log.events)
    t0 = time.time()
    done = serve_arrivals(bat, reqs)
    dt = time.time() - t0
    log.report("serve", n0)
    check_served(done, reqs)
    toks = sum(len(r.output) for r in done)
    stats = bat.pool.stats()
    print(f"[serve] {len(done)} requests served, {toks} tokens out, "
          f"{sum(len(r.prompt) for r in reqs)} prompt tokens, {dt:.2f} s "
          f"wall (compiles included)")
    print(f"[serve] prefix-hit tokens {stats['prefix_hit_tokens']}, "
          f"miss tokens {stats['prefix_miss_tokens']}, "
          f"prefill invocations {bat.prefill_invocations}, "
          f"decode invocations {bat.decode_invocations}")
    require(stats["prefix_hit_tokens"] > 0, f"prefix hits: {stats}")
    summ = cell.accounting.serving_summary()
    print(f"[serve] first chip reading, not a benchmark: TTFT p50 "
          f"{summ['ttft_p50']:.4f} s, TPOT p50 {summ['tpot_p50']:.4f} s "
          f"(CellAccounting, compiles included)")
    return cell, bat, done


def decode_step_holds_kernel(bat) -> bool:
    """Does the batcher's jitted paged decode step, compiled over the
    full block-table width, call the Pallas paged decode kernel?"""
    import jax
    import jax.numpy as jnp
    pool = bat.pool
    batch = {"tokens": jnp.asarray(bat.cur_tok[:, None]),
             "pos": jnp.asarray(bat.pos)}
    text = bat._step.lower(
        bat.params, pool.arena, pool.kv_scales, bat.resident,
        jnp.asarray(pool.block_table), batch, jax.random.PRNGKey(0),
    ).compile().as_text()
    return any('custom_call_target="tpu_custom_call"' in line
               and "paged_decode_attention" in line
               for line in text.splitlines())


def run_one(args, log: CompileLog) -> int:
    import jax
    from repro.launch.serve import resolve_arch

    check_kernels(args.seed)
    log.report("kernels")
    cell, bat, _done = serve_one_chip(resolve_arch(ARCH, smoke=False),
                                      seed=args.seed, log=log)
    has = decode_step_holds_kernel(bat)
    print(f"[decode] compiled decode step holds the Pallas paged kernel "
          f"(tpu_custom_call paged_decode_attention): {has}")
    require(has, "paged decode kernel in the compiled decode step")
    dev = jax.devices()[0]
    peak = dev.memory_stats()["peak_bytes_in_use"]
    print(f"[memory] {dev.device_kind} peak_bytes_in_use {peak:,}")
    log.report("total")
    return 1


def run_four(args, log: CompileLog) -> int:
    import jax
    import numpy as np
    from repro.core import (CellSpec, ChannelSpec, ClusterSpec, DeviceGrid,
                            Supervisor)
    from repro.launch.serve import resolve_arch
    from repro.serve.disagg import DisaggServer

    devs = jax.devices()
    require(len(devs) >= 4, f"--chips 4 needs four devices, found {len(devs)}")
    arch = resolve_arch(ARCH, smoke=False)

    # reference: the same requests colocated on device 0
    cell, bat, done = serve_one_chip(arch, seed=args.seed, log=log)
    ref = {r.rid: list(r.output) for r in done}
    cell.destroy()
    del cell, bat, done
    gc.collect()

    grid = DeviceGrid.from_flat(devs[:4], pods=1, rows=1, cols=4)
    sup = Supervisor(grid)
    spec = ClusterSpec(
        cells=(CellSpec("prefill", arch, "serve", ncols=1),
               CellSpec("decode", arch, "serve", ncols=1, replicas=3)),
        channels=(ChannelSpec("prefill", "decode", kind="kv"),),
    )
    sup.apply(spec)
    names = spec.cell("decode").instances()
    t0 = time.time()
    sup.cells[names[0]].init_serve(rng=jax.random.PRNGKey(args.seed))
    srv = DisaggServer(sup, "prefill", names, batch_slots=SLOTS,
                       max_len=MAX_LEN, chunk=CHUNK, pool_pages=POOL_PAGES)
    print(f"[disagg] cells up with weight fan-out over array channels in "
          f"{time.time() - t0:.2f} s")
    where = {}
    for name in ("prefill", *names):
        leaf = jax.tree.leaves(sup.cells[name].serve_params)[0]
        where[name] = sorted(str(d) for d in leaf.devices())
        print(f"[disagg] {name} params on {where[name]}")
    distinct = {d for ds in where.values() for d in ds}
    require(len(distinct) == 4, f"cells on 4 distinct devices: {where}")

    reqs = make_prompts(arch.vocab, args.seed)
    n0 = len(log.events)
    t0 = time.time()
    for req in reqs:
        srv.submit(req)
        srv.step()
    srv.run_until_drained()
    dt = time.time() - t0
    log.report("disagg", n0)
    done = srv.done
    check_served(done, reqs)
    st = srv.stats()
    require(srv.requeued == 0, f"{srv.requeued} requests requeued")
    first = sum(r.output[0] == ref[r.rid][0] for r in done)
    agree = sum(int(np.sum(np.asarray(r.output) == np.asarray(ref[r.rid])))
                for r in done)
    total = sum(len(r.output) for r in done)
    print(f"[disagg] {len(done)} requests served in {dt:.2f} s wall, "
          f"requeued {srv.requeued}, routed_warm {st['routed_warm']}, "
          f"routed_cold {st['routed_cold']}")
    print(f"[disagg] first tokens equal to the one-chip reference: "
          f"{first}/{len(done)}; greedy token agreement {agree}/{total} "
          f"({agree / total:.4f})")
    require(first == len(done), "first tokens equal to the reference")
    return 4


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    print(f"[cache] compilation cache at {use_compile_cache()}")
    log = CompileLog()
    count = run_four(args, log) if args.chips == 4 else run_one(args, log)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
