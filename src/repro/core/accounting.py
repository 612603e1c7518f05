"""Per-cell resource accounting.

The paper argues the subOS abstraction makes accounting *exact*: a subOS
owns its resources, so consumption attribution is unambiguous.  The same
holds here — each cell's compiled programs yield per-device FLOPs/bytes
(``cost_analysis``) and collective traffic (parsed from HLO), all of which
belong to that cell alone because nothing is shared.
"""
from __future__ import annotations

import dataclasses
import itertools
import re
from collections import defaultdict
from typing import Dict, List, Optional

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}\s]+?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
    re.M,
)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum per-device output bytes of every collective op in an HLO module.

    ``-start/-done`` pairs are counted once (on the ``-start``).
    """
    out: Dict[str, int] = defaultdict(int)
    for m in _OP_RE.finditer(hlo_text):
        shape_str, op = m.group(1), m.group(2)
        line = m.group(0)
        if "-done(" in line:
            continue
        out[op] += _shape_bytes(shape_str)
    return dict(out)


@dataclasses.dataclass
class RequestMetrics:
    """Per-request serving latencies (seconds), attributed to one cell."""
    rid: int
    ttft: Optional[float] = None     # submission -> first output token
    tpot: Optional[float] = None     # per-token decode latency after that
    prompt_len: int = 0
    new_tokens: int = 0
    tenant: Optional[str] = None     # QoS attribution (None = untagged)


def summarize_requests(requests) -> dict:
    """p50/p99/p99.9 TTFT/TPOT over any collection carrying .ttft/.tpot
    (the per-cell request log, or a merged multi-replica one)."""
    import numpy as np
    ttfts = [r.ttft for r in requests if r.ttft is not None]
    tpots = [r.tpot for r in requests if r.tpot is not None]
    out = {"requests": len(requests)}
    for key, xs in (("ttft", ttfts), ("tpot", tpots)):
        if xs:
            out[f"{key}_p50"] = float(np.percentile(xs, 50))
            out[f"{key}_p99"] = float(np.percentile(xs, 99))
            out[f"{key}_p999"] = float(np.percentile(xs, 99.9))
    return out


def tenant_percentile(requests, metric: str, q: float,
                      tenant: Optional[str] = None) -> Optional[float]:
    """Percentile ``q`` of ``metric`` (``"ttft"``/``"tpot"``) over the
    subset of ``requests`` attributed to ``tenant`` (None = all).  The
    per-tenant SLO probe: ``tenant_percentile(acct.requests, "ttft", 99,
    "paid")`` is the number a tenant's SLOTarget is judged against."""
    import numpy as np
    xs = [getattr(r, metric) for r in requests
          if getattr(r, metric, None) is not None
          and (tenant is None or getattr(r, "tenant", None) == tenant)]
    return float(np.percentile(xs, q)) if xs else None


@dataclasses.dataclass
class ProgramCost:
    name: str
    flops_per_device: float = 0.0
    bytes_per_device: float = 0.0
    collective_per_device: Dict[str, int] = dataclasses.field(default_factory=dict)
    arg_bytes: int = 0
    temp_bytes: int = 0
    invocations: int = 0

    @property
    def total_collective_bytes(self) -> int:
        return sum(self.collective_per_device.values())


class CellAccounting:
    """Exact per-cell attribution of compiled-program costs."""

    _ids = itertools.count()

    def __init__(self, cell_name: str):
        self.cell = cell_name
        # process-unique, never reused (unlike id()): readers that cursor
        # into ``requests`` key on this to detect a recovered cell's
        # fresh log (see ReconcilePolicy.pull)
        self.uid = next(CellAccounting._ids)
        self.programs: Dict[str, ProgramCost] = {}
        self.requests: List[RequestMetrics] = []
        # named event counters (serving-path waste/degradation signals:
        # prefill_dummy_rows, prefill_fallback_requests, ...)
        self.counters: Dict[str, int] = {}
        # the same counters broken down by tenant label:
        # tenant -> name -> value
        self.tenant_counters: Dict[str, Dict[str, int]] = {}
        # the cell's private flight recorder (spans + latency sketches);
        # same ownership rule as every field above — strictly per-cell
        from .telemetry import FlightRecorder
        self.recorder = FlightRecorder(cell_name)

    def register_program(self, name: str, compiled, hlo_text: Optional[str] = None):
        ca = compiled.cost_analysis() or {}
        ma = compiled.memory_analysis()
        text = hlo_text if hlo_text is not None else compiled.as_text()
        pc = ProgramCost(
            name=name,
            flops_per_device=float(ca.get("flops", 0.0)),
            bytes_per_device=float(ca.get("bytes accessed", 0.0)),
            collective_per_device=collective_bytes(text),
            arg_bytes=getattr(ma, "argument_size_in_bytes", 0),
            temp_bytes=getattr(ma, "temp_size_in_bytes", 0),
        )
        self.programs[name] = pc
        return pc

    def record_request(self, rid: int, *, ttft: Optional[float] = None,
                       tpot: Optional[float] = None, prompt_len: int = 0,
                       new_tokens: int = 0,
                       tenant: Optional[str] = None) -> RequestMetrics:
        rm = RequestMetrics(rid=rid, ttft=ttft, tpot=tpot,
                            prompt_len=prompt_len, new_tokens=new_tokens,
                            tenant=tenant)
        self.requests.append(rm)
        return rm

    def serving_summary(self) -> dict:
        """p50/p99 TTFT and TPOT over every request this cell served."""
        return summarize_requests(self.requests)

    def tenant_summary(self) -> Dict[str, dict]:
        """:func:`summarize_requests` broken down by tenant label.
        Untagged requests roll up under ``None``."""
        by: Dict[Optional[str], List[RequestMetrics]] = defaultdict(list)
        for r in self.requests:
            by[r.tenant].append(r)
        return {t: summarize_requests(rs) for t, rs in by.items()}

    def tenant_percentile(self, metric: str, q: float,
                          tenant: Optional[str] = None) -> Optional[float]:
        """Per-tenant tail probe over this cell's request log."""
        return tenant_percentile(self.requests, metric, q, tenant)

    def record_counter(self, name: str, n: int = 1,
                       tenant: Optional[str] = None):
        """Bump a named event counter (e.g. batch-padding dummy rows, or
        requests served over a degraded path) — cheap, exact attribution
        of serving overheads that program costs alone can't show.  With
        ``tenant=`` the bump is additionally recorded under that label
        in :attr:`tenant_counters` (the global counter still moves, so
        unlabeled readers see totals)."""
        self.counters[name] = self.counters.get(name, 0) + n
        if tenant is not None:
            tc = self.tenant_counters.setdefault(tenant, {})
            tc[name] = tc.get(name, 0) + n

    def record_gauge(self, name: str, value: int,
                     tenant: Optional[str] = None):
        """Set a point-in-time counter (e.g. ``pages_in_use`` of the
        cell's KV pool) — unlike :meth:`record_counter` it overwrites,
        reflecting current state rather than a cumulative total.  Like
        :meth:`record_counter`, the global entry always moves; with
        ``tenant=`` the value is additionally mirrored under that
        label, so unlabeled readers see the latest state either way."""
        self.counters[name] = value
        if tenant is not None:
            self.tenant_counters.setdefault(tenant, {})[name] = value

    def record_invocation(self, name: str, n: int = 1):
        if name in self.programs:
            self.programs[name].invocations += n

    def totals(self) -> dict:
        t = {"flops": 0.0, "bytes": 0.0, "collective_bytes": 0.0}
        for pc in self.programs.values():
            t["flops"] += pc.flops_per_device * pc.invocations
            t["bytes"] += pc.bytes_per_device * pc.invocations
            t["collective_bytes"] += pc.total_collective_bytes * pc.invocations
        return t
