"""Training driver: boot a supervisor, spawn a training cell, run.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b \
        --smoke --steps 50 [--ckpt-dir /tmp/ckpt] [--resume]

``--smoke`` uses the reduced same-family config (CPU-friendly); without it
the published widths are trained.  The cell checkpoints periodically
and ``--resume`` continues from the latest checkpoint (the data pipeline
is step-deterministic, so restarts don't skew batches).
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint import checkpoint as ckpt
from repro.configs.base import ShapeConfig, smoke_config, with_opt_level
from repro.configs.registry import get_arch
from repro.core import Supervisor, single_device_grid
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.launch.compile_cache import use_compile_cache
from repro.train.optimizer import OptConfig
from repro.train.train_step import abstract_train_state, train_state_pspecs


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen3-4b")
    p.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                   default=False)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=25)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--compress-grads", action="store_true")
    args = p.parse_args(argv)

    use_compile_cache()
    arch = get_arch(args.arch)
    if args.smoke:
        arch = smoke_config(arch)
    arch = with_opt_level(arch, True)

    sup = Supervisor(single_device_grid())
    cell = sup.create_cell(
        arch.name, arch, "train", ncols=1,
        opt_cfg=OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps,
                          m_dtype=arch.optimizer_m_dtype),
    )
    print(f"[train] {arch.name}: {cell.model.n_params()/1e6:.1f}M params on "
          f"{cell.n_devices} device(s)")
    shape = ShapeConfig("train", "train", args.seq, args.batch)
    pipe = SyntheticPipeline(DataConfig(kind="bigram"), arch, shape)

    if args.resume and args.ckpt_dir:
        step = ckpt.latest_step(args.ckpt_dir)
        if step is not None:
            target = abstract_train_state(cell.model, cell.opt_cfg)
            shardings = jax.tree.map(
                lambda s: jax.sharding.NamedSharding(cell.mesh, s),
                train_state_pspecs(cell.model))
            cell.state = ckpt.restore(args.ckpt_dir, step, target, shardings)
            cell.step = step
            print(f"[train] resumed from step {step}")

    t0 = time.time()
    while cell.step < args.steps:
        n = min(10, args.steps - cell.step)
        m = cell.train_steps(pipe.get_batch, n)
        if args.ckpt_dir and cell.step % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, cell.step, cell.state, blocking=False)
        tput = args.batch * args.seq * cell.step / (time.time() - t0)
        print(f"[{cell.step:5d}] xent={m['xent']:.3f} lr={m['lr']:.2e} "
              f"({tput:,.0f} tok/s)")
    print(f"[train] done; floor={pipe.bigram_entropy():.3f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
