"""Paper Table 4 — elasticity overheads (MEASURED).

Creates/destroys/resizes real cells on 8 virtual CPU devices in a child
process pinned to ``JAX_PLATFORMS=cpu`` (this process must keep seeing a
single device, and on a machine with a TPU the parent holds the chip, so
the child must never ask for it) and reports CPU wall times —
the analogue of the paper's create/destroy/online/offline measurements.
Every lifecycle change goes through the declarative path
(``Supervisor.apply`` of a rescaled ClusterSpec -> reconcile -> primitive),
so the timings include the spec-diff overhead applications actually pay.
Paper reference points (seconds): LXC create 2.1 / cpu 0.002; Xen create
14.2 / cpu 0.126; RainForest create 6.1 / cpu-online 0.066 / offline 0.054.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import List

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time, sys
sys.path.insert(0, "src")
import jax
from repro.configs.base import smoke_config, ShapeConfig
from repro.configs.registry import get_arch
from repro.core import CellSpec, ClusterSpec, DeviceGrid, Supervisor
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.train.optimizer import OptConfig

grid = DeviceGrid.from_flat(jax.devices(), pods=1, rows=2, cols=4)
sup = Supervisor(grid)
cfg = smoke_config(get_arch("qwen3-4b"))
pipe = SyntheticPipeline(DataConfig(kind="uniform", vocab=256), cfg,
                         ShapeConfig("t", "train", 32, 8))
out = {}
spec = ClusterSpec(cells=(
    CellSpec("c", cfg, "train", ncols=2, min_ncols=1, max_ncols=3,
             opt_cfg=OptConfig()),
))

t0 = time.monotonic()
sup.apply(spec)                                    # create via reconcile
cell = sup.cells["c"]
cell.train_steps(lambda s: pipe.get_batch(s), 1)   # includes first compile
out["create_and_first_step_s"] = time.monotonic() - t0

t0 = time.monotonic()
cell.train_steps(lambda s: pipe.get_batch(s), 1)
out["steady_step_s"] = time.monotonic() - t0

t0 = time.monotonic()
plan = sup.apply(spec.scale("c", 3))               # grow: "cpu online"
out["grow_1col_s"] = time.monotonic() - t0
out["grow_reshard_bytes"] = plan.by_verb("grow")[0].result["bytes"]

t0 = time.monotonic()
cell.train_steps(lambda s: pipe.get_batch(s), 1)   # recompile on new mesh
out["post_resize_step_s"] = time.monotonic() - t0

t0 = time.monotonic()
sup.apply(spec.scale("c", 2))                      # shrink: "cpu offline"
out["shrink_1col_s"] = time.monotonic() - t0

t0 = time.monotonic()
sup.apply(ClusterSpec())                           # empty spec: destroy
out["destroy_s"] = time.monotonic() - t0
assert not sup.cells and sup.reconcile().empty

print(json.dumps(out))
"""


def run(rows: List[dict]):
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=560,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"elasticity child failed: {proc.stderr.strip()[-400:]}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    paper = {
        "create_and_first_step_s": "paper rf=6.1s lxc=2.1s xen=14.2s",
        "grow_1col_s": "paper rf cpu-online=0.066s xen=0.126s",
        "shrink_1col_s": "paper rf cpu-offline=0.054s",
        "destroy_s": "paper rf=0s (async)",
    }
    for k, v in out.items():
        if k.endswith("_bytes"):
            continue
        rows.append({
            "name": f"table4_elasticity/{k}",
            "us_per_call": v * 1e6,
            "derived": f"{paper.get(k, '')} MEASURED on 8 CPU virtual "
                       "devices".strip(),
        })
