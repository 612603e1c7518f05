"""Logical-axis -> mesh-axis sharding rules.

Every parameter / activation dim carries a *logical* axis name; the rules
below map it to (tuples of) mesh axes.  Resolution is divisibility-aware:
an axis that does not divide the dim is dropped (safe fallback to
replication) and the drop is recorded so the dry-run can report it.

Baseline rule set (paper-faithful cell layout):
  batch     -> ("pod", "data")      DP over pods and the data axis
  vocab     -> "model"              vocab-parallel embedding / logits
  heads     -> "model"              Megatron TP for attention
  kv_heads  -> "model"              (dropped when n_kv < model-axis size)
  ffn       -> "model"              Megatron TP for MLPs
  expert    -> "model"              EP when E divides the model axis
  expert_ffn-> "model"              TP-in-expert when EP not divisible
  inner/ssm_heads -> "model"        Mamba d_inner / SSD head parallelism
  embed     -> "data"               ZeRO-3/FSDP weight sharding
  kv_seq    -> ("data", "model")    decode KV cache sequence sharding (SP)
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.param import tree_map_pspec


Axes = Tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Per-cell sharding context: the cell's mesh + axis roles.

    ``dp_over_model``: ZeRO-3 layout — the model axis joins the batch axes
    (256-way DP), weights keep FSDP sharding, and only the vocab head stays
    model-parallel.  Right for archs whose per-layer TP activation
    collectives dwarf their weight traffic (small dense models).
    """

    mesh: Mesh
    batch_axes: Axes = ("data",)
    model_axis: Optional[str] = "model"
    fsdp: bool = True
    dp_over_model: bool = False

    @property
    def axis_sizes(self) -> Dict[str, int]:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    @property
    def all_axes(self) -> Axes:
        return tuple(self.mesh.axis_names)

    def dp_size(self) -> int:
        sizes = self.axis_sizes
        axes = self.rules()["batch"]
        return int(np.prod([sizes[a] for a in axes]))

    def model_size(self) -> int:
        if self.model_axis is None:
            return 1
        return self.axis_sizes[self.model_axis]

    # ---- rules ------------------------------------------------------------
    def rules(self) -> Dict[str, Axes]:
        m = (self.model_axis,) if self.model_axis else ()
        fsdp_axes: Axes = (
            tuple(a for a in ("pod", "data") if a in self.axis_sizes)
            if self.fsdp else ()
        )
        if self.dp_over_model:
            # ZeRO-3: no per-layer tensor parallelism; all device axes do
            # data parallelism.  The head keeps vocab parallelism with the
            # batch dim backing off to the data axes ("batch_head") so the
            # (B, S, V) logits never materialize a full vocab per device.
            return {
                "batch": self.batch_axes + m,
                "batch_head": self.batch_axes,
                "vocab": m,
                "heads": (), "kv_heads": (), "ffn": (),
                "expert": (), "expert_ffn": (), "inner": (), "ssm_heads": (),
                "embed": fsdp_axes,
                "kv_seq": (),
                "act_seq": (), "act_embed": (),
            }
        return {
            "batch": self.batch_axes,
            "batch_head": self.batch_axes,
            "vocab": m,
            "heads": m,
            "kv_heads": m,
            "ffn": m,
            "expert": m,
            "expert_ffn": m,
            "inner": m,
            "ssm_heads": m,
            # embed: FSDP when on; in serve mode (fsdp off) fall back to the
            # model axis so weights whose TP dim doesn't divide it (56/40
            # heads on a 16-axis) don't end up fully replicated.  "embed"
            # resolves LAST (see pspec priority), so TP dims keep the model
            # axis whenever they can use it.
            "embed": fsdp_axes if self.fsdp else m,
            "kv_seq": tuple(a for a in ("data",) + m if a in self.axis_sizes),
            "act_seq": m,       # sequence dim of the residual stream
            "act_embed": m,     # d_model dim of the residual stream
        }

    # ---- resolution -------------------------------------------------------
    # resolution priority: batch dims bind first (the decode cache's batch
    # dim must win the data axis over kv_seq), then TP dims, then "embed"
    # (so its model-axis serve fallback never steals from a TP dim)
    _PRIORITY = {"batch": 0, "batch_head": 0, "embed": 2}

    def pspec(self, logical: Sequence[Optional[str]], shape: Sequence[int]) -> P:
        """Resolve logical axes to a PartitionSpec, divisibility-aware."""
        rules = self.rules()
        sizes = self.axis_sizes
        used: set = set()
        parts: list = [None] * len(shape)
        order = sorted(
            range(len(shape)),
            key=lambda i: (self._PRIORITY.get(logical[i], 1), i),
        )
        for i in order:
            dim, name = shape[i], logical[i]
            if name is None or name not in rules:
                continue
            cand = rules[name]
            chosen = []
            prod = 1
            for ax in cand:
                if ax in used or ax not in sizes:
                    continue
                if dim % (prod * sizes[ax]) == 0:
                    chosen.append(ax)
                    prod *= sizes[ax]
            if not chosen:
                continue
            parts[i] = chosen[0] if len(chosen) == 1 else tuple(chosen)
            used.update(chosen)
        return P(*parts)

    def sharding(self, logical: Sequence[Optional[str]], shape: Sequence[int]) -> NamedSharding:
        return NamedSharding(self.mesh, self.pspec(logical, shape))

    def params_pspecs(self, spec_tree):
        """PartitionSpec tree for a PSpec tree."""
        return tree_map_pspec(lambda s: self.pspec(s.logical, s.shape), spec_tree)

    def params_shardings(self, spec_tree):
        return tree_map_pspec(
            lambda s: NamedSharding(self.mesh, self.pspec(s.logical, s.shape)),
            spec_tree,
        )

    def activation_pspec(self, logical: Sequence[Optional[str]], shape: Sequence[int]) -> P:
        return self.pspec(logical, shape)

    # manual shard_map axis bookkeeping
    @property
    def manual_axes(self) -> frozenset:
        return frozenset(a for a in self.all_axes)


def single_device_ctx() -> ShardCtx:
    """A trivial ctx for single-device tests (same code paths)."""
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(dev, ("data", "model"))
    return ShardCtx(mesh=mesh, batch_axes=("data",), model_axis="model")


def make_ctx(mesh: Mesh, fsdp: bool = True, dp_over_model: bool = False) -> ShardCtx:
    """Infer axis roles from mesh axis names (pod/data/model conventions)."""
    names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    model_axis = "model" if "model" in names else None
    return ShardCtx(mesh=mesh, batch_axes=batch_axes or (names[0],),
                    model_axis=model_axis, fsdp=fsdp,
                    dp_over_model=dp_over_model)
