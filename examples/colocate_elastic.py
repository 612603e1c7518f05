"""Co-located serving + training under the declarative elastic loop.

The paper's headline scenario (Figs 10/11): a latency-critical serving
cell shares a machine with a batch training cell.  Desired state is a
ClusterSpec — server bounded to [1, 3] columns, trainer taking the rest
— and a :class:`ReconcilePolicy` closes the loop: the serving cell's
batcher records per-request TTFT into its ``CellAccounting``, the policy
pulls those live samples, and on a threshold crossing it rewrites the
spec's desired ``ncols`` and re-applies it.  The reconciler turns every
+1/-1 into a real column transfer with live resharding on both cells —
this file never touches a resize/transfer primitive.

Run:  PYTHONPATH=src python examples/colocate_elastic.py
(a CPU demo on 8 virtual host devices)
"""
import os
# a CPU virtual-device demo: eight host devices stand in for the 2x4
# column grid, on any machine (a one-chip host has too few devices)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses
import itertools

import numpy as np
import jax

from repro.configs.base import ShapeConfig, smoke_config
from repro.configs.registry import get_arch
from repro.core import (
    CellSpec,
    ClusterSpec,
    DeviceGrid,
    ElasticPolicy,
    ReconcilePolicy,
    SLOTarget,
    Supervisor,
)
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.serve.batcher import Request
from repro.train.optimizer import OptConfig

MAX_LEN, SLOTS, PROMPT_LEN, MAX_NEW = 48, 4, 12, 4


def main():
    if len(jax.devices()) < 8:
        raise SystemExit(
            f"{__file__} is a CPU virtual-device demo and needs 8 host "
            f"devices; XLA_FLAGS={os.environ.get('XLA_FLAGS')!r} gave "
            f"{len(jax.devices())}")
    grid = DeviceGrid.from_flat(jax.devices(), pods=1, rows=2, cols=4)
    sup = Supervisor(grid)
    arch = smoke_config(get_arch("qwen3-4b"))

    # -- desired state: bounded serving cell + batch trainer on the rest
    spec = ClusterSpec(cells=(
        CellSpec("server", arch, "serve", ncols=1, min_ncols=1, max_ncols=3),
        CellSpec("batch", arch, "train", ncols=3, min_ncols=1,
                 opt_cfg=OptConfig(lr=1e-3)),
    ))
    sup.apply(spec)
    server, trainer = sup.cells["server"], sup.cells["batch"]
    server.init_serve()
    pipe = SyntheticPipeline(DataConfig(kind="bigram", vocab=256), arch,
                             ShapeConfig("t", "train", 32, 24))

    # the batcher is rebuilt after any topology change (resize rebuilds the
    # cell's model -> fresh compile, a real cost the elastic loop pays)
    state = {"epoch": None, "bat": None}

    def batcher():
        if state["epoch"] != server.zone_epoch:
            state["epoch"] = server.zone_epoch
            state["bat"] = server.make_batcher(batch_slots=SLOTS, max_len=MAX_LEN)
        return state["bat"]

    rng = np.random.default_rng(0)
    rid = itertools.count()

    def serve_tick(load: int):
        """Submit `load` requests and drain them; TTFT/TPOT land in the
        server cell's CellAccounting (what the policy reads)."""
        bat = batcher()
        for _ in range(load):
            prompt = rng.integers(1, arch.vocab, size=PROMPT_LEN).astype(np.int32)
            bat.submit(Request(rid=next(rid), prompt=prompt,
                               max_new_tokens=MAX_NEW))
        bat.run_until_drained()

    # calibrate the SLO band to this machine: lt/ut around the idle TTFT
    for _ in range(3):
        serve_tick(2)
    idle = float(np.median([r.ttft for r in server.accounting.requests
                            if r.ttft is not None]))
    slo = SLOTarget(ttft_p99=idle * 2.0)
    spec = spec.with_cell(
        dataclasses.replace(spec.cell("server"), slo=slo))
    sup.apply(spec)
    policy = ReconcilePolicy(
        sup, "server", "batch",
        ElasticPolicy(lt=idle * 1.3, ut=slo.ttft_p99, window=8,
                      percentile=99.0, cooldown=0.0, metric="ttft"),
    )
    print(f"idle TTFT {idle*1e3:.1f} ms -> band "
          f"({policy.policy.lt*1e3:.1f}, {policy.policy.ut*1e3:.1f}) ms")
    # the calibration's first tick paid program compiles; keep those
    # TTFT samples out of the policy window
    policy.pull()
    policy.samples.clear()

    phases = [("calm", 2), ("burst", 14), ("calm", 2)]
    for phase, load in phases:
        for _tick in range(4):
            serve_tick(load)
            act = policy.maybe_act()
            if act:
                # warm the rebuilt batcher (fresh mesh -> fresh compile)
                # and drop the compile-tainted samples from the window
                serve_tick(2)
                policy.pull()
                policy.samples.clear()
            trainer.train_steps(pipe.get_batch, 1)
            note = f" -> {act['kind']} [{act['plan']}]" if act else ""
            print(f"[{phase:5s}] server={sup.cells['server'].zone.ncols}col "
                  f"batch={sup.cells['batch'].zone.ncols}col{note}")
    print(f"actions: {[a['kind'] for a in policy.actions]}")
    print(f"served {len(server.accounting.requests)} requests; "
          f"trainer reached step {trainer.step}; epoch {sup.table.epoch}")
    print(f"reconcile converged: {sup.reconcile().empty}")


if __name__ == "__main__":
    main()
