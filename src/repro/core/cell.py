"""Cell — the subOS abstraction for TPU computing.

A cell *directly manages* its resources: it owns a mesh over its zone,
compiles its own programs for that mesh, holds its train/serve state, and
runs steps without any supervisor involvement on the step path.  The
supervisor only creates/destroys/resizes it — and applications do not
call even those verbs directly: they declare a
:class:`~repro.core.spec.CellSpec` (arch, role, ``[min,max]`` column
bounds, replicas, SLO targets) inside a ClusterSpec, and the reconciler
(``Supervisor.apply``/``reconcile``) drives the primitives that keep
this cell converged to it.

Paper §4.3 properties implemented here:
  1. management facility      -> CellSpec desired state; the reconciler
                                 executes create/destroy/resize_cell as
                                 its plan-executor layer
  2. exact accounting         -> CellAccounting per compiled program +
                                 per-request TTFT/TPOT (what elastic
                                 ReconcilePolicies read)
  3. IPC-like channels        -> ArrayChannel / ControlPlane endpoints
                                 (declared via ChannelSpec or opened on
                                 demand)
  4. fork-like spawn          -> Supervisor.spawn_child (sub-zone carved
                                 from the parent; lineage() walks it)
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.accounting import CellAccounting
from repro.core.partition import DeviceGrid, Zone
from repro.core.resharding import reshard_tree
from repro.models.model import build_model
from repro.sharding.rules import make_ctx
from repro.train.optimizer import OptConfig
from repro.train.train_step import (
    TrainState,
    build_train_step,
    init_train_state,
    train_state_pspecs,
)


class CellError(Exception):
    pass


class Cell:
    def __init__(
        self,
        name: str,
        zone: Zone,
        grid: DeviceGrid,
        arch: ArchConfig,
        role: str,                       # "train" | "serve"
        *,
        epoch: int,
        opt_cfg: Optional[OptConfig] = None,
        parent: Optional[str] = None,
    ):
        self.name = name
        self.arch = arch
        self.role = role
        self.parent = parent
        self.grid = grid
        self.opt_cfg = opt_cfg or OptConfig()
        self.accounting = CellAccounting(name)
        self.status = "created"
        self.step = 0
        self.last_heartbeat = time.monotonic()
        self.state: Optional[TrainState] = None
        self.serve_params = None
        self.serve_cache = None
        self._programs: Dict[str, Any] = {}
        self._bind_zone(zone, epoch)

    # ------------------------------------------------------------------
    # zone binding / resize
    # ------------------------------------------------------------------
    def _bind_zone(self, zone: Zone, epoch: int):
        self.zone = zone
        self.mesh = self.grid.zone_mesh(zone)
        self.ctx = make_ctx(self.mesh)
        self.model = build_model(self.arch, self.ctx)
        self.bound_epoch = epoch      # epoch programs are compiled under
        self.zone_epoch = epoch       # epoch of the last zone change
        self._programs.clear()

    @property
    def n_devices(self) -> int:
        return int(np.prod(self.mesh.devices.shape))

    def default_sharding(self, ndim: int):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(*([None] * ndim)))

    def heartbeat(self):
        self.last_heartbeat = time.monotonic()

    # ------------------------------------------------------------------
    # training role
    # ------------------------------------------------------------------
    def init_train(self, rng=None, *, compress: bool = False):
        assert self.role == "train"
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        state = init_train_state(self.model, rng, self.opt_cfg, compress=compress)
        shardings = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(self.mesh, s),
            train_state_pspecs(self.model, compress=compress),
        )
        self.state, _ = reshard_tree(state, shardings, donate=True)
        self._compress = compress
        self.status = "running"
        return self.state

    def _get_train_step(self) -> Callable:
        key = "train_step"
        if key not in self._programs:
            if self.bound_epoch != self.zone_epoch:
                self.bound_epoch = self.zone_epoch
            pspecs = train_state_pspecs(self.model, compress=getattr(self, "_compress", False))
            shardings = jax.tree.map(
                lambda s: jax.sharding.NamedSharding(self.mesh, s), pspecs
            )
            fn = jax.jit(
                build_train_step(self.model, self.opt_cfg,
                                 compress=getattr(self, "_compress", False)),
                in_shardings=(shardings, None),
                out_shardings=(shardings, None),
                donate_argnums=(0,),
            )
            self._programs[key] = fn
        return self._programs[key]

    def train_steps(self, batches, n: int) -> dict:
        """Run n steps; batches: callable step -> batch.

        The first call AOT-compiles the step for this zone's mesh, runs the
        BoundaryGuard over the executable (device confinement + epoch
        binding — the Security-guard analogue) and registers its exact cost
        with the cell's accounting.
        """
        if self.state is None:
            self.init_train()
        fn = self._get_train_step()
        metrics = {}
        for _ in range(n):
            batch = batches(self.step)
            key = "train_step_compiled"
            if key not in self._programs:
                compiled = fn.lower(self.state, batch).compile()
                from repro.core.guard import BoundaryGuard
                BoundaryGuard(lambda: None).validate(self, compiled)
                self.accounting.register_program("train_step", compiled)
                self._programs[key] = compiled
            self.state, metrics = self._programs[key](self.state, batch)
            self.step += 1
            self.heartbeat()
        self.accounting.record_invocation("train_step", n)
        return {k: float(v) for k, v in metrics.items()}

    # ------------------------------------------------------------------
    # serving role
    # ------------------------------------------------------------------
    def init_serve(self, params=None, rng=None):
        assert self.role == "serve"
        shardings = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(self.mesh, s),
            self.model.params_pspecs(),
        )
        if params is None:
            # one jitted program draws every leaf in place, already in
            # the model dtype and sharding — no host-side float32 copies
            rng = rng if rng is not None else jax.random.PRNGKey(0)
            self.serve_params = jax.jit(
                self.model.init, out_shardings=shardings)(rng)
        else:
            self.serve_params = jax.device_put(params, shardings)
        self.status = "running"
        return self.serve_params

    def make_batcher(self, *, batch_slots: int, max_len: int, **kw):
        from repro.serve.batcher import ContinuousBatcher
        if self.serve_params is None:
            self.init_serve()
        kw.setdefault("accounting", self.accounting)
        return ContinuousBatcher(
            self.model, self.serve_params,
            batch_slots=batch_slots, max_len=max_len, **kw,
        )

    # ------------------------------------------------------------------
    # resize: live reshard onto the new zone
    # ------------------------------------------------------------------
    def resize_to(self, zone: Zone, epoch: int) -> dict:
        old = self.zone
        state = self.state if self.role == "train" else self.serve_params
        self._bind_zone(zone, epoch)
        stats = {"bytes": 0, "seconds": 0.0}
        if state is not None:
            if self.role == "train":
                pspecs = train_state_pspecs(self.model, compress=getattr(self, "_compress", False))
            else:
                pspecs = self.model.params_pspecs()
            shardings = jax.tree.map(
                lambda s: jax.sharding.NamedSharding(self.mesh, s), pspecs
            )
            new_state, stats = reshard_tree(state, shardings, donate=True)
            if self.role == "train":
                self.state = new_state
            else:
                self.serve_params = new_state
        stats.update(old=f"{old.ncols}cols", new=f"{zone.ncols}cols")
        return stats

    # ------------------------------------------------------------------
    def snapshot_state(self):
        return self.state if self.role == "train" else self.serve_params

    def destroy(self):
        self.status = "destroyed"
        self.state = None
        self.serve_params = None
        self.serve_cache = None
        self._programs.clear()
