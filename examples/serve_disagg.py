"""Disaggregated serving under the supervisor daemon: autoscale + self-heal.

The paper's "isolate first, then share on demand" applied to inference,
with the management loop CLOSED: a ClusterSpec names one prefill cell, a
decode cell with ``replicas=2`` (bounded ``[2, 3]``), a ``kv``
ChannelSpec per replica, a ``tpot_p99`` SLOTarget and a ``ckpt_dir``.
One ``Supervisor.apply`` materializes all of it; from then on a
:class:`SupervisorDaemon` tick — interleaved with traffic via
``run_until_drained(on_step=daemon.tick)`` — does everything the old
imperative demos sequenced by hand:

* **autoscale**: when the request queue backs up past the band derived
  from the declared SLO, the policy rewrites ``replicas`` and reconcile
  materializes a third decode cell, which ``DisaggServer.sync``
  live-attaches (KV channel + weight fan-out + fresh batcher);
* **self-heal**: killing a decode replica's column mid-traffic marks the
  cell failed; its in-flight requests requeue, reconcile re-carves the
  cell once the column is repaired, the declared ``ckpt_dir`` restores
  its params (no re-init, no fan-out), and sync re-attaches it — zero
  requests lost, zero manual primitive calls.

Run:  PYTHONPATH=src python examples/serve_disagg.py [--trace-out FILE]
(a CPU demo: 8 virtual host devices, so the cells sit on disjoint zones;
``--trace-out`` exports the whole run — request span trees + the
daemon's decision audit — as Chrome trace-event JSON, openable in
Perfetto / chrome://tracing: ``make trace-demo``)
"""
import os
# a CPU virtual-device demo: eight host devices stand in for the 2x4
# column grid, on any machine (a one-chip host has too few devices)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import tempfile

import numpy as np
import jax

from repro.checkpoint import checkpoint as ckpt
from repro.configs.base import smoke_config
from repro.configs.registry import get_arch
from repro.core import (
    CellSpec,
    ChannelSpec,
    ClusterSpec,
    DeviceGrid,
    SLOTarget,
    Supervisor,
    SupervisorDaemon,
)
from repro.serve.batcher import Request
from repro.serve.disagg import DisaggServer


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="export the run as Chrome trace-event JSON "
                         "(Perfetto-loadable), incl. the decision audit")
    args = ap.parse_args(argv)
    if len(jax.devices()) < 8:
        raise SystemExit(
            f"{__file__} is a CPU virtual-device demo and needs 8 host "
            f"devices; XLA_FLAGS={os.environ.get('XLA_FLAGS')!r} gave "
            f"{len(jax.devices())}")
    grid = DeviceGrid.from_flat(jax.devices(), pods=1, rows=2, cols=4)
    sup = Supervisor(grid)
    arch = smoke_config(get_arch("qwen3-4b"))
    ckpt_dir = tempfile.mkdtemp(prefix="decode-ckpt-")

    # -- desired state: prompts vs tokens; decode bounded [2,3] replicas,
    #    latency objective + checkpoint location declared, not scripted
    spec = ClusterSpec(
        cells=(CellSpec("prefill", arch, "serve", ncols=1),
               CellSpec("decode", arch, "serve", ncols=1, replicas=2,
                        min_replicas=2, max_replicas=3,
                        slo=SLOTarget(tpot_p99=0.25), ckpt_dir=ckpt_dir)),
        channels=(ChannelSpec("prefill", "decode", kind="kv"),),
    )
    plan = sup.apply(spec)
    print(f"applied spec -> plan [{plan.summary()}], epoch={sup.table.epoch}")
    decode_names = spec.cell("decode").instances()
    sup.cells[decode_names[0]].init_serve(rng=jax.random.PRNGKey(0))

    # -- share on demand: weight fan-out + per-replica KV handoff channels
    srv = DisaggServer(sup, "prefill", decode_names,
                       batch_slots=2, max_len=64, chunk=16)
    print(f"channels: {[(c.kind, c.src.name, '->', c.dst.name) for c in sup.channels]}")
    # checkpoint the params so recovery restores STATE, not just a zone
    ckpt.save(ckpt_dir, 0, sup.cells[decode_names[0]].serve_params)

    # -- the closed loop: health + reconcile + SLO autoscale + replica sync
    daemon = SupervisorDaemon(sup)
    daemon.attach_server(srv)
    daemon.add_slo_policy("decode", autoscale_replicas=True,
                          queue_depth=lambda: len(srv.pending),
                          queue_high=4, window=16, cooldown=0.0)

    rng = np.random.RandomState(0)

    def burst(n, rid0):
        for rid in range(rid0, rid0 + n):
            L = int(rng.randint(28, 52))
            srv.submit(Request(
                rid=rid, prompt=rng.randint(1, arch.vocab, size=L).astype(np.int32),
                max_new_tokens=8))
        return rid0 + n

    # -- burst 1: the backlog crosses the SLO-derived band -> autoscale
    next_rid = burst(12, 0)
    srv.run_until_drained(on_step=daemon.tick)
    print(f"burst 1 drained: {len(srv.done)}/12 served, "
          f"replicas={len(srv.replicas)}, "
          f"actions={[a['kind'] for p in daemon.policies for a in p.actions]}")

    # -- burst 2: kill a decode replica's column mid-traffic
    next_rid = burst(6, next_rid)
    for _ in range(2):
        srv.step()
        daemon.tick()
    victim = srv.replicas[1].cell
    pod, col = victim.zone.pods[0], victim.zone.c0
    affected = sup.fail_column(pod, col)
    print(f"killed column ({pod},{col}) -> affected={affected}")
    for _ in range(3):                     # daemon reaps + requeues; recover
        srv.step()                         # stays blocked while the column
        daemon.tick()                      # is quarantined
    sup.restore_column(pod, col)           # the repair arrives
    srv.run_until_drained(on_step=daemon.tick)
    done = {r.rid for r in srv.done}
    restored = [e for e in sup.events if e["op"] == "restore_ckpt"]
    print(f"burst 2 drained: all {next_rid} requests done={done == set(range(next_rid))}, "
          f"requeued={srv.requeued}, replicas={len(srv.replicas)}")
    print(f"recovery restored from checkpoint: "
          f"{[(e['cell'], 'step ' + str(e['step'])) for e in restored]}")

    # -- the handoff in numbers: invocations, routing, channel traffic
    st = srv.stats()
    print(f"prefill invocations: {st['prefill_invocations']} (same-bucket "
          f"prompts batched)")
    print(f"decode invocations:  {st['decode_invocations']} across "
          f"{st['replicas']} replicas (requests per replica: "
          f"{st['per_replica_requests']})")
    print(f"kv channels: {st['kv_bytes'] / 1e6:.2f} MB over {st['kv_transfers']} "
          f"transfers in {st['kv_seconds'] * 1e3:.1f} ms")
    if st["paged_kv"]:
        print(f"kv pool: prefix hits {st['prefix_hit_tokens']} tok / misses "
              f"{st['prefix_miss_tokens']} tok, saved "
              f"{st['kv_bytes_saved'] / 1e6:.2f} MB, pages in use "
              f"{st['pages_in_use']} (evicted {st['pages_evicted']}, "
              f"occupancy {st['pool_occupancy']:.2f})")
    print(f"serving summary: {st['decode_serving']}")
    print(f"daemon: {daemon.ticks} ticks, "
          f"{sum(1 for r in daemon.history if r['plan'] != 'noop')} non-noop plans")
    print(f"tail telemetry: { {k: round(v.get('p99', 0), 4) for k, v in st['telemetry'].items() if 'p99' in v} }")

    # -- the decision audit: WHY the daemon scaled / recovered / synced
    print("decision audit (scale/recover/sync):")
    for hit in daemon.audit.query():
        if any(k in hit["kind"] for k in
               ("grow", "shrink", "scale", "recover", "sync",
                "mark_failed", "destroy", "drain")):
            print(f"  tick {hit['tick']:3d}  {hit['kind']:<16} "
                  f"{hit.get('cell') or '-':<10} {hit.get('reason', '')}")

    # -- flight-recorder export: one span tree per request, audit folded
    #    in as instant events (must run BEFORE teardown drops the cells)
    if args.trace_out:
        trace = srv.trace_export(args.trace_out, daemon=daemon)
        print(f"trace: {len(trace['traceEvents'])} events "
              f"-> {args.trace_out} (open in Perfetto / chrome://tracing)")

    # -- empty spec tears everything down
    sup.apply(ClusterSpec())
    print(f"cells after teardown: {list(sup.cells)}")
    print("done.")


if __name__ == "__main__":
    main()
