"""Serving driver: spawn a serving cell and run batched requests.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
        --requests 32 --slots 8 --max-new 16

``--smoke`` serves the reduced same-family config (CPU-friendly); without
it the published widths are served.  Weights are random, drawn from
``--seed``.  Prompt lengths are drawn up to a quarter of ``--max-len``,
and every other request opens with one shared prefix, so both the cold
prefill and the warm (prefix-hit) extend paths run.
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional, Sequence

import jax
import numpy as np

from repro.configs.base import ArchConfig, smoke_config, with_opt_level
from repro.configs.registry import get_arch
from repro.core import CellSpec, ClusterSpec, Supervisor, single_device_grid
from repro.launch.compile_cache import use_compile_cache
from repro.serve.batcher import Request


def resolve_arch(name: str, smoke: bool) -> ArchConfig:
    """The registered config, reduced when ``smoke``, with the serving
    flags ``with_opt_level`` sets."""
    arch = get_arch(name)
    if smoke:
        arch = smoke_config(arch)
    return with_opt_level(arch, True)


def build_server(arch: ArchConfig, *, slots: int, max_len: int,
                 temperature: float = 0.0, prefill_chunk: Optional[int] = 32,
                 pool_pages: Optional[int] = None, seed: int = 0):
    """One serving cell on the first device, declared through the
    supervisor, with weights from ``PRNGKey(seed)`` and a continuous
    batcher over the family's cache plane (a paged KV pool for KV
    families).  Returns (supervisor, cell, batcher)."""
    sup = Supervisor(single_device_grid())
    sup.apply(ClusterSpec(cells=(CellSpec(arch.name, arch, "serve", ncols=1),)))
    cell = sup.cells[arch.name]
    cell.init_serve(rng=jax.random.PRNGKey(seed))
    bat = cell.make_batcher(batch_slots=slots, max_len=max_len,
                            temperature=temperature,
                            prefill_chunk=prefill_chunk or None,
                            pool_pages=pool_pages)
    return sup, cell, bat


def make_requests(vocab: int, lengths: Sequence[int], *, seed: int,
                  max_new: int, shared_prefix: int = 0,
                  shared: Sequence[int] = ()) -> List[Request]:
    """Requests with random prompts of the given lengths; the requests
    indexed by ``shared`` open with one common ``shared_prefix``-token
    prefix (their total length stays ``lengths[i]``)."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, vocab, size=shared_prefix).astype(np.int32)
    out = []
    for rid, n in enumerate(lengths):
        prompt = rng.integers(1, vocab, size=n).astype(np.int32)
        if rid in shared:
            if n <= shared_prefix:
                raise ValueError(f"request {rid}: length {n} does not "
                                 f"exceed the shared prefix {shared_prefix}")
            prompt[:shared_prefix] = prefix
        out.append(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
    return out


def serve_arrivals(bat, requests: Sequence[Request]) -> List[Request]:
    """Submit one request per batcher step (a steady arrival stream),
    then run until every request has finished."""
    for req in requests:
        bat.submit(req)
        bat.step()
    return bat.run_until_drained()


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen3-4b")
    p.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                   default=False)
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-len", type=int, default=128)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--prefill-chunk", type=int, default=32,
                   help="chunked-prefill bucket size; 0 = token-at-a-time")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    use_compile_cache()
    arch = resolve_arch(args.arch, args.smoke)
    _sup, cell, bat = build_server(
        arch, slots=args.slots, max_len=args.max_len,
        temperature=args.temperature, prefill_chunk=args.prefill_chunk,
        seed=args.seed)

    rng = np.random.default_rng(args.seed)
    prefix = max(args.max_len // 8, 1)
    hi = max(args.max_len // 4, prefix + 2)
    lengths = rng.integers(prefix + 1, hi, size=args.requests)
    reqs = make_requests(arch.vocab, lengths, seed=args.seed,
                         max_new=args.max_new, shared_prefix=prefix,
                         shared=range(0, args.requests, 2))
    t0 = time.time()
    done = serve_arrivals(bat, reqs)
    dt = time.time() - t0

    lats = sorted(r.latency for r in done)
    toks = sum(len(r.output) for r in done)
    hit = bat.pool.prefix_hit_tokens if bat.pool is not None else 0
    print(f"[serve] {arch.name} on {jax.devices()[0].device_kind}: "
          f"{len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s), {hit} prefix-hit tokens")
    print(f"[serve] latency p50={lats[len(lats)//2]*1e3:.1f}ms "
          f"p99={lats[int(len(lats)*0.99)-1]*1e3:.1f}ms")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
