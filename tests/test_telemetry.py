"""Flight recorder: span lifecycle, histogram sketches, decision audit,
and the Chrome trace export — plus the end-to-end guarantee that one
request yields exactly ONE closed span tree in both colocated and
disaggregated serving.
"""
import glob
import itertools
import json
import os

import numpy as np
import pytest

import jax

from repro.configs.base import smoke_config
from repro.configs.registry import get_arch
from repro.core import DeviceGrid, Supervisor
from repro.core.accounting import CellAccounting, summarize_requests
from repro.core import telemetry
from repro.core.telemetry import (
    DISABLED,
    DecisionAudit,
    EventLog,
    FlightRecorder,
    HistogramSketch,
    chrome_trace,
    finish_request,
    mark_admitted,
    open_request,
    recorder_of,
)
from repro.models.model import build_model
from repro.serve.batcher import ContinuousBatcher, Request
from repro.sharding.rules import single_device_ctx

MAX_LEN = 48
SLOTS = 3


@pytest.fixture(scope="module")
def model_and_params():
    cfg = smoke_config(get_arch("qwen3-4b"))
    model = build_model(cfg, single_device_ctx())
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _requests(vocab, lens, max_new=4, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(rid=i, prompt=rng.randint(1, vocab, size=L).astype(np.int32),
                    max_new_tokens=max_new)
            for i, L in enumerate(lens)]


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------
def test_span_lifecycle_with_fake_clock():
    t = [0.0]
    rec = FlightRecorder("cellA", clock=lambda: t[0])
    root = rec.start_span("request", trace_id=7, prompt_len=12)
    assert root.open and rec.open_spans == [root]
    t[0] = 0.5
    child = rec.start_span("queue", trace_id=7, parent=root.ctx)
    t[0] = 1.25
    child.end(outcome="admitted")
    root.end()
    assert not root.open and rec.open_spans == []
    evs = {e["name"]: e for e in rec.log}
    assert evs["queue"]["parent_id"] == root.span_id
    assert evs["queue"]["dur"] == pytest.approx(0.75)
    assert evs["request"]["dur"] == pytest.approx(1.25)
    assert evs["request"]["attrs"]["prompt_len"] == 12
    # end() is idempotent: a second close must not double-log
    root.end()
    assert sum(1 for e in rec.log if e["name"] == "request") == 1


def test_disabled_recorder_is_total_noop():
    rec = FlightRecorder("off", enabled=False)
    s = rec.start_span("x", trace_id=1)
    s.end()
    rec.add_complete("y", 0.0, 1.0)
    rec.record("lat", 0.5)
    assert len(rec.log) == 0 and rec.hists == {} and rec.open_spans == []
    # accounting=None resolves to the shared disabled recorder
    assert recorder_of(None).enabled is False


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs what opens and
    closes, in order."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return _Ann()


def test_phase_annotates_and_times_on_enabled_recorder(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(telemetry, "TraceAnnotation", ann)
    t = [10.0]
    rec = FlightRecorder("cellA", clock=lambda: t[0])
    with rec.phase("step") as ph:
        t[0] = 10.5
        with rec.phase("decode.wait", wait=True) as w:
            t[0] = 10.75
        t[0] = 11.0
    assert ann.log == [("enter", "step"), ("enter", "decode.wait"),
                       ("exit", "decode.wait"), ("exit", "step")]
    assert (ph.ts, ph.dur) == (10.0, 1.0)
    assert (w.ts, w.dur) == (10.5, 0.25)
    assert rec.wait_s == 0.25                      # only the wait phase
    assert len(rec.log) == 0                       # a phase alone is no span
    # a site that keeps the phase records it with its times
    rec.add_complete("step", ph.ts, ph.dur, busy=2, wait_s=w.dur)
    (ev,) = rec.log
    assert (ev["name"], ev["ts"], ev["dur"]) == ("step", 10.0, 1.0)
    assert ev["attrs"] == {"busy": 2, "wait_s": 0.25}


def test_disabled_recorder_phase_annotates_and_records_nothing(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(telemetry, "TraceAnnotation", ann)
    waited = DISABLED.wait_s
    with DISABLED.phase("decode.wait", wait=True) as ph:
        pass
    assert ann.log == [("enter", "decode.wait"), ("exit", "decode.wait")]
    assert (ph.ts, ph.dur) == (0.0, 0.0)
    DISABLED.add_complete("step", ph.ts, ph.dur, busy=1)
    assert len(DISABLED.log) == 0 and DISABLED.wait_s == waited


def test_event_log_ring_is_bounded_and_counts_drops():
    log = EventLog(capacity=4)
    for i in range(10):
        log.append({"i": i})
    assert len(log) == 4
    assert log.dropped == 6
    assert [e["i"] for e in log] == [6, 7, 8, 9]
    assert [e["i"] for e in log.drain()] == [6, 7, 8, 9]
    assert len(log) == 0


def test_histogram_sketch_tracks_numpy_percentiles():
    rng = np.random.RandomState(0)
    xs = rng.lognormal(mean=-3.0, sigma=1.0, size=20_000)
    h = HistogramSketch(rel_err=0.01)
    for x in xs:
        h.record(x)
    for q, pct in ((0.5, 50), (0.99, 99), (0.999, 99.9)):
        got, want = h.quantile(q), float(np.percentile(xs, pct))
        assert abs(got - want) / want < 0.05, (q, got, want)
    s = h.summary()
    assert s["count"] == len(xs)
    assert s["min"] == pytest.approx(xs.min())
    assert s["max"] == pytest.approx(xs.max())


def test_histogram_sketch_merge_and_roundtrip():
    a, b = HistogramSketch(), HistogramSketch()
    xs = np.linspace(0.001, 1.0, 500)
    for x in xs:
        a.record(x)
        b.record(x)
    b = HistogramSketch.from_dict(json.loads(json.dumps(b.to_dict())))
    a.merge(b)
    assert a.count == 2 * len(xs)
    assert a.quantile(0.5) == pytest.approx(float(np.percentile(xs, 50)),
                                            rel=0.05)
    # zeros bin + empty sketch edges
    z = HistogramSketch()
    assert z.quantile(0.5) is None and z.summary() == {"count": 0}
    z.record(0.0)
    z.record(-1.0)
    # non-positive values collapse into the zeros bin (estimate 0.0);
    # the true minimum survives in the summary
    assert z.quantile(0.5) == 0.0
    assert z.summary()["min"] == -1.0


def test_decision_audit_query_filters_kind_and_cell():
    audit = DecisionAudit()
    audit.record(0, 1.0, {"decode": {"queue_depth": 7}},
                 [{"kind": "scale_replicas", "cell": "decode",
                   "reason": "scale replicas 2->3: queue_depth 7 > 4"}])
    audit.record(1, 2.0, {}, [{"kind": "plan:recover", "cell": "decode/1",
                               "reason": "reconcile: recover decode/1 [failed]"}])
    hits = audit.query(kind="scale")
    assert len(hits) == 1 and "2->3" in hits[0]["reason"]
    assert hits[0]["signals"]["decode"]["queue_depth"] == 7
    assert audit.query(cell="decode/1")[0]["kind"] == "plan:recover"
    assert audit.query(kind="nope") == []


# ---------------------------------------------------------------------------
# accounting satellites
# ---------------------------------------------------------------------------
def test_record_gauge_always_sets_global_entry():
    """Regression: a gauge recorded WITH a tenant label must still move
    the global counter — unlabeled readers (pool occupancy, stats())
    would otherwise read a stale global while the per-tenant mirror
    advanced."""
    acc = CellAccounting("c")
    acc.record_gauge("pages_in_use", 5)
    assert acc.counters["pages_in_use"] == 5
    acc.record_gauge("pages_in_use", 9, tenant="t0")
    assert acc.counters["pages_in_use"] == 9
    assert acc.tenant_counters["t0"]["pages_in_use"] == 9
    acc.record_gauge("pages_in_use", 2)
    assert acc.counters["pages_in_use"] == 2


def test_summarize_requests_reports_p999():
    reqs = [Request(rid=i, prompt=np.ones(4, np.int32), max_new_tokens=1)
            for i in range(100)]
    for i, r in enumerate(reqs):
        r.submitted_at = 0.0
        r.first_token_at = 0.001 * (i + 1)
        r.finished_at = r.first_token_at + 0.01
        r.output = [1, 2]
    s = summarize_requests(reqs)
    assert {"ttft_p50", "ttft_p99", "ttft_p999", "tpot_p999"} <= set(s)
    assert s["ttft_p50"] <= s["ttft_p99"] <= s["ttft_p999"] <= 0.1


# ---------------------------------------------------------------------------
# request helpers
# ---------------------------------------------------------------------------
def test_request_helpers_build_one_closed_tree():
    t = [0.0]
    rec = FlightRecorder("front", clock=lambda: t[0])
    req = Request(rid=3, prompt=np.ones(8, np.int32), max_new_tokens=2)
    req.submitted_at = 0.0
    open_request(rec, req)
    assert open_request(rec, req) is req._tspans["request"]  # idempotent
    t[0] = 0.2
    mark_admitted(req, slot=1)
    t[0] = 1.0
    req.first_token_at, req.finished_at, req.output = 0.3, 1.0, [5, 6]
    finish_request(req, ts=1.0)
    finish_request(req, ts=2.0)                              # idempotent
    assert rec.open_spans == []
    names = [e["name"] for e in rec.log]
    assert names.count("request") == 1 and names.count("finish") == 1
    fin = next(e for e in rec.log if e["name"] == "finish")
    assert fin["attrs"]["outcome"] == "ok"
    assert fin["attrs"]["new_tokens"] == 2
    assert "ttft_s" in rec.hists and "tpot_s" in rec.hists


# ---------------------------------------------------------------------------
# colocated end-to-end
# ---------------------------------------------------------------------------
def test_colocated_requests_yield_closed_span_trees(model_and_params):
    model, params = model_and_params
    acc = CellAccounting("solo")
    bat = ContinuousBatcher(model, params, batch_slots=SLOTS, max_len=MAX_LEN,
                            prefill_chunk=16, accounting=acc)
    reqs = _requests(model.cfg.vocab, [3, 33, 17, 40])
    for r in reqs:
        bat.submit(r)
    done = bat.run_until_drained()
    assert len(done) == len(reqs)

    rec = acc.recorder
    assert rec.open_spans == [], [s.name for s in rec.open_spans]
    roots = [e for e in rec.log if e["name"] == "request"]
    assert sorted(e["trace_id"] for e in roots) == [0, 1, 2, 3]
    assert all(e["dur"] is not None for e in roots)
    # per-request phases all parent back to that request's root
    by_rid = {e["trace_id"]: e for e in roots}
    for name in ("queue", "prefill", "decode", "finish"):
        evs = [e for e in rec.log if e["name"] == name]
        assert len(evs) == len(reqs), name
        for e in evs:
            assert e["parent_id"] == by_rid[e["trace_id"]]["span_id"], name
    assert any(e["name"] == "decode_step" for e in rec.log)
    assert {"ttft_s", "tpot_s", "prefill_s", "decode_step_s"} <= set(rec.hists)


# ---------------------------------------------------------------------------
# disaggregated end-to-end + export
# ---------------------------------------------------------------------------
def test_disagg_span_tree_and_chrome_export(model_and_params, tmp_path):
    from repro.serve.disagg import DisaggServer

    model, params = model_and_params
    cfg = model.cfg
    grid = DeviceGrid.from_flat(jax.devices()[:1], pods=1, rows=1, cols=2,
                                allow_reuse=True)
    sup = Supervisor(grid)
    sup.create_cell("prefill", cfg, "serve", ncols=1)
    dec = sup.create_cell("decode", cfg, "serve", ncols=1)
    dec.init_serve(rng=jax.random.PRNGKey(0))
    srv = DisaggServer(sup, "prefill", "decode", batch_slots=SLOTS,
                       max_len=MAX_LEN, chunk=16)
    reqs = _requests(cfg.vocab, [3, 33, 17, 40])
    for r in reqs:
        srv.submit(r)
    done = srv.run_until_drained()
    assert len(done) == len(reqs)

    # zero leaked spans on ANY cell after drain
    for name, rec in srv._recorders().items():
        assert rec.open_spans == [], (name, [s.name for s in rec.open_spans])

    # one closed root per request, on the front-door (prefill) cell
    prec = recorder_of(srv.prefill_cell.accounting)
    roots = {e["trace_id"]: e for e in prec.log if e["name"] == "request"}
    assert sorted(roots) == [0, 1, 2, 3]
    assert all(e["dur"] is not None for e in roots.values())

    # the full disagg phase chain, each phase parented to its root:
    # queue -> route -> prefill (prefill cell) -> channel -> decode (decode
    # cell) -> finish
    all_events = [e for _, rec in srv._recorders().items() for e in rec.log]
    for name in ("queue", "route", "prefill", "channel", "decode", "finish"):
        evs = [e for e in all_events if e["name"] == name
               and e.get("trace_id") is not None]
        assert len(evs) >= len(reqs), name
        for e in evs:
            assert e["parent_id"] == roots[e["trace_id"]]["span_id"], name
    drec = recorder_of(dec.accounting)
    assert any(e["name"] == "decode" for e in drec.log)
    # per-transfer spans land on the SENDING cell (exact attribution)
    assert any(e["name"] == "xfer:kv" for e in prec.log)

    # export: valid JSON, Perfetto-shaped, round-trips through json.loads
    path = tmp_path / "trace.json"
    trace = srv.trace_export(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["traceEvents"] and loaded["displayTimeUnit"] == "ms"
    assert len(loaded["traceEvents"]) == len(trace["traceEvents"])
    for ev in loaded["traceEvents"]:
        assert {"ph", "ts", "pid", "tid"} <= set(ev), ev
        assert ev["ph"] in ("X", "M", "i")
        if ev["ph"] == "X":
            assert ev["ts"] >= 0.0 and ev["dur"] >= 0.0
    # one pid per cell, tid = request id on request-scoped events
    names = {ev["args"]["name"] for ev in loaded["traceEvents"]
             if ev["ph"] == "M"}
    assert {"cell:prefill", "cell:decode"} <= names
    tids = {ev["tid"] for ev in loaded["traceEvents"]
            if ev["ph"] == "X" and ev["name"] == "request"}
    assert tids == {0, 1, 2, 3}

    # histogram summaries fold into stats()
    st = srv.stats()
    tel = st["telemetry"]
    assert tel["ttft_s"]["count"] == len(reqs)
    assert {"p50", "p99", "p999"} <= set(tel["ttft_s"])
    assert "xfer_kv_bytes" in tel


def test_daemon_audit_explains_actions():
    """A daemon tick records observed signals + audited actions; the
    Chrome export folds them in as instant events on a daemon pid."""

    class _FakePlan:
        ops = ()

        def summary(self):
            return "noop"

    class _FakeSup:
        cells: dict = {}
        desired = None

        def check_health(self):
            return ["decode/1"]

        def reconcile(self):
            return _FakePlan()

    d = None
    from repro.core.daemon import SupervisorDaemon
    d = SupervisorDaemon(_FakeSup())
    d.tick(now=1.0)
    hits = d.audit.query(kind="mark_failed")
    assert len(hits) == 1 and hits[0]["cell"] == "decode/1"
    assert "heartbeat" in hits[0]["reason"]

    trace = chrome_trace([], audit=d.audit)
    inst = [e for e in trace["traceEvents"] if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["name"] == "mark_failed"
    assert trace["otherData"]["decision_audit"][0]["tick"] == 0


# ---------------------------------------------------------------------------
# the serving loop's phases
# ---------------------------------------------------------------------------
PAGE = 8


def _count_refusals(bat, refused):
    """Wrap the pool's admit so each PoolExhausted it raises is counted."""
    from repro.serve.kvpool import PoolExhausted
    admit = bat.pool.admit

    def counting(*a, **kw):
        try:
            return admit(*a, **kw)
        except PoolExhausted:
            refused[0] += 1
            raise

    bat.pool.admit = counting


def test_step_span_per_call_with_busy_admitted_blocked_wait(model_and_params):
    """One ``step`` ring span per call, its attrs read against what the
    call did.  The recorder's clock ticks once per read, so a wait phase
    (one sync, no clock read inside) lasts exactly 1: ``wait_s`` is the
    step's prefill groups plus its decode sync."""
    model, params = model_and_params
    acc = CellAccounting("solo")
    tick = itertools.count()
    acc.recorder.clock = lambda: float(next(tick))
    # two slots, but pages for one request's worst case: the others block
    bat = ContinuousBatcher(model, params, batch_slots=2, max_len=MAX_LEN,
                            prefill_chunk=16, page_size=PAGE,
                            pool_pages=MAX_LEN // PAGE, accounting=acc)
    refused = [0]
    _count_refusals(bat, refused)
    reqs = _requests(model.cfg.vocab, [30, 30, 9], max_new=3)
    for r in reqs:
        bat.submit(r)
    rec = acc.recorder
    calls = blocked = 0
    while bat.queue or any(r is not None for r in bat.slot_req):
        before = {r.rid: (len(r.output), r.started_at) for r in reqs}
        refused[0] = 0
        rec.log.drain()
        bat.step()
        calls += 1
        evs = rec.log.drain()
        (st,) = [e for e in evs if e["name"] == "step"]
        a = st["attrs"]
        admitted = sum(1 for r in reqs if before[r.rid][1] is None
                       and r.started_at is not None)
        assert a["admitted"] == admitted
        # each admission's prefill gives a first token, each decoded row one
        assert a["busy"] == sum(len(r.output) - before[r.rid][0]
                                for r in reqs) - admitted
        assert a["blocked"] == refused[0]
        groups = {(e["ts"], e["dur"]) for e in evs if e["name"] == "prefill"}
        assert a["wait_s"] == len(groups) + (1 if a["busy"] else 0)
        for e in evs:
            if e["name"] in ("prefill", "decode_step"):
                assert st["ts"] <= e["ts"]
                assert e["ts"] + e["dur"] <= st["ts"] + st["dur"]
        blocked += a["blocked"]
    assert calls > 3 and blocked > 0
    assert sum(r.started_at is not None for r in reqs) == len(reqs)


def _served_with_phases(model, params, accounting):
    """Three requests, the third sharing the first's prefix (a warm
    extend), on two slots; the first step runs before the others come."""
    bat = ContinuousBatcher(model, params, batch_slots=2, max_len=MAX_LEN,
                            prefill_chunk=16, page_size=PAGE,
                            accounting=accounting)
    rng = np.random.RandomState(1)
    vocab = model.cfg.vocab
    pre = rng.randint(1, vocab, size=16).astype(np.int32)
    tail = [rng.randint(1, vocab, size=n).astype(np.int32) for n in (3, 5)]
    reqs = [Request(rid=0, prompt=np.concatenate([pre, tail[0]]),
                    max_new_tokens=3),
            Request(rid=1, prompt=rng.randint(1, vocab, size=11)
                    .astype(np.int32), max_new_tokens=2),
            Request(rid=2, prompt=np.concatenate([pre, tail[1]]),
                    max_new_tokens=3)]
    bat.submit(reqs[0])
    bat.step()
    bat.submit(reqs[1])
    bat.submit(reqs[2])
    return bat, reqs


def test_phases_keep_request_and_decode_spans(model_and_params):
    """The ``prefill``, ``decode_step`` and ``queue`` spans read as they
    did before the phases: one ``prefill`` and one ``queue`` per request,
    one ``decode_step`` per step that decoded, the same attrs, each
    inside its ``step``."""
    model, params = model_and_params
    acc = CellAccounting("solo")
    bat, reqs = _served_with_phases(model, params, acc)
    bat.run_until_drained()
    evs = list(acc.recorder.log)
    steps = [e for e in evs if e["name"] == "step"]
    decodes = [e for e in evs if e["name"] == "decode_step"]
    prefills = [e for e in evs if e["name"] == "prefill"]
    queues = [e for e in evs if e["name"] == "queue"]
    assert len(steps) == bat.decode_invocations
    assert len(decodes) == bat.decode_invocations
    assert [d["attrs"] for d in decodes] == [{"busy": s["attrs"]["busy"]}
                                             for s in steps]
    assert sorted(e["trace_id"] for e in prefills) == [0, 1, 2]
    assert sorted(e["trace_id"] for e in queues) == [0, 1, 2]
    kinds = {e["trace_id"]: e["attrs"] for e in prefills}
    assert kinds[0] == {"kind": "cold", "batch": 1}
    assert kinds[1] == {"kind": "cold", "batch": 1}
    assert kinds[2] == {"kind": "warm", "batch": 1, "hit_tokens": 16}
    assert {tuple(sorted(e["attrs"])) for e in queues} == {
        ("prefix_hit", "slot")}
    for e in decodes + prefills:
        assert any(s["ts"] <= e["ts"] and e["ts"] + e["dur"]
                   <= s["ts"] + s["dur"] for s in steps), e["name"]
    assert sum(s["attrs"]["admitted"] for s in steps) == len(reqs)


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_profiler_trace_nests_the_step_phases(model_and_params, tmp_path):
    """A CPU profiler trace of a run: every phase on one host line, nested
    step > admit > prefill:* > prefill.wait and step > decode_step >
    decode.prep, decode.wait, with decode.post after decode_step."""
    from jax.profiler import ProfileData

    model, params = model_and_params
    bat, _ = _served_with_phases(model, params, None)
    untraced = bat.decode_invocations
    jax.profiler.start_trace(str(tmp_path))
    try:
        bat.run_until_drained()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    names = {"step", "admit", "prefill:cold", "prefill:warm",
             "prefill.wait", "decode_step", "decode.prep", "decode.wait",
             "decode.post"}
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.end_ns) for e in line.events
                   if e.name in names]
            if evs:
                lines.append(evs)
    (evs,) = lines                                  # one host line
    by = {n: [(a, b) for m, a, b in evs if m == n] for n in names}
    assert len(by["step"]) == bat.decode_invocations - untraced
    prefills = by["prefill:cold"] + by["prefill:warm"]
    assert len(by["prefill:cold"]) == 1 and len(by["prefill:warm"]) == 1
    for n, parents in (("admit", by["step"]), ("decode_step", by["step"]),
                       ("decode.post", by["step"]),
                       ("prefill:cold", by["admit"]),
                       ("prefill:warm", by["admit"]),
                       ("prefill.wait", prefills),
                       ("decode.prep", by["decode_step"]),
                       ("decode.wait", by["decode_step"])):
        for iv in by[n]:
            assert any(_inside(iv, p) for p in parents), (n, iv)
    assert len(by["prefill.wait"]) == len(prefills)
    for step in by["step"]:
        (dec,) = [iv for iv in by["decode_step"] if _inside(iv, step)]
        (prep,) = [iv for iv in by["decode.prep"] if _inside(iv, dec)]
        (wait,) = [iv for iv in by["decode.wait"] if _inside(iv, dec)]
        (post,) = [iv for iv in by["decode.post"] if _inside(iv, step)]
        assert prep[1] <= wait[0] and dec[1] <= post[0]
