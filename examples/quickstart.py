"""Quickstart: the IFTS runtime in ~60 lines, declaratively.

Boots a supervisor over the local device grid, applies a ClusterSpec
(the desired state: one training cell), trains a tiny model, *rescales
the spec* to grow the cell on the fly, adds a serving cell + weight-sync
channel to the spec, and serves a request.  Every topology change goes
through ``Supervisor.apply`` — the reconciler turns the spec diff into
create/resize/channel primitives.

Run:  PYTHONPATH=src python examples/quickstart.py
(a CPU demo: 8 virtual host devices, so resize/transfer are real)
"""
import os
# a CPU virtual-device demo: eight host devices stand in for the 2x4
# column grid, on any machine (a one-chip host has too few devices)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import jax

from repro.configs.base import ShapeConfig, smoke_config
from repro.configs.registry import get_arch
from repro.core import CellSpec, ChannelSpec, ClusterSpec, DeviceGrid, Supervisor
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.serve.batcher import Request
from repro.train.optimizer import OptConfig


def main():
    # -- supervisor boots first (paper: the firstly-booted instance)
    if len(jax.devices()) < 8:
        raise SystemExit(
            f"{__file__} is a CPU virtual-device demo and needs 8 host "
            f"devices; XLA_FLAGS={os.environ.get('XLA_FLAGS')!r} gave "
            f"{len(jax.devices())}")
    grid = DeviceGrid.from_flat(jax.devices(), pods=1, rows=2, cols=4)
    sup = Supervisor(grid)
    print(f"supervisor up: grid={grid.shape}, epoch={sup.table.epoch}")

    # -- desired state: one training cell (a subOS) on 2 columns
    arch = smoke_config(get_arch("qwen3-4b"))
    spec = ClusterSpec(cells=(
        CellSpec("trainer", arch, "train", ncols=2, min_ncols=1, max_ncols=3,
                 opt_cfg=OptConfig(lr=1e-3, warmup_steps=20, total_steps=400)),
    ))
    plan = sup.apply(spec)
    print(f"applied spec -> plan [{plan.summary()}], epoch={sup.table.epoch}")
    trainer = sup.cells["trainer"]
    pipe = SyntheticPipeline(DataConfig(kind="bigram", vocab=256), arch,
                             ShapeConfig("t", "train", 32, 32))
    m = trainer.train_steps(pipe.get_batch, 20)
    print(f"trained 20 steps on {trainer.zone.ncols} cols: xent={m['xent']:.3f}")

    # -- elastic grow: rewrite the DESIRED width; reconcile does the resize
    spec = spec.scale("trainer", 3)
    plan = sup.apply(spec)
    grow = plan.by_verb("grow")[0]
    print(f"rescaled 2->3 cols [{grow.status}] "
          f"({grow.result['bytes']/1e6:.1f} MB resharded)")
    m = trainer.train_steps(pipe.get_batch, 10)
    print(f"10 more steps on 3 cols: xent={m['xent']:.3f}")

    # -- add a serving cell + an on-demand weight channel to the spec
    spec = spec.with_cell(CellSpec("server", arch, "serve", ncols=1)) \
               .with_channel(ChannelSpec("trainer", "server"))
    plan = sup.apply(spec)
    print(f"applied serving spec -> plan [{plan.summary()}]")
    server = sup.cells["server"]
    server.init_serve()
    ch = sup.find_channel("trainer", "server")
    shardings = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(server.mesh, s),
        server.model.params_pspecs())
    st = ch.send(trainer.state.params, shardings)
    server.serve_params = ch.recv()
    print(f"weight sync: {st['bytes']/1e6:.1f} MB in {st['seconds']*1e3:.1f} ms")

    # -- serve
    bat = server.make_batcher(batch_slots=4, max_len=64)
    bat.submit(Request(rid=0, prompt=np.array([5, 7, 11], np.int32), max_new_tokens=8))
    done = bat.run_until_drained()
    print(f"served request -> tokens {done[0].output}")

    # -- converged: reconcile again is a no-op
    print(f"reconcile converged: {sup.reconcile().empty}")
    print(f"events: {[e['op'] for e in sup.events]}")

    # -- empty spec tears everything down
    sup.apply(ClusterSpec())
    print(f"final epoch: {sup.table.epoch}, cells: {list(sup.cells)}")
    print("done.")


if __name__ == "__main__":
    main()
