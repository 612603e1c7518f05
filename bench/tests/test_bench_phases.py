"""The readers of the batcher's phases: ``step_host_ms`` and
``queue_wait_p95_s.code`` on synthetic span lists, ``host_bound_idle_share``
on a synthetic trace whose window holds the program's annotations, and a
tiny traced run that reports the new metrics with no edit to the harness."""
import json

import numpy as np
import pytest
from jax.profiler import ProfileData

from benchtiny import REPO, make_tree, run_tiny
from bench import trace_reduce
from bench.harness import Context, Served, reader
from bench.traffic import Planned

METRICS = REPO / "bench"


def read(name, ctx):
    return reader(METRICS, name)(ctx)


def xspace(device_ops, host_events):
    """A one-chip XSpace: ``device_ops`` and ``host_events`` are
    (name, start_ns, end_ns) on one ``XLA Ops`` line and one host line."""
    def plane(pid, name, line, events):
        names = sorted({n for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {a * 1000} "
            f"duration_ps: {(b - a) * 1000} }}\n" for n, a, b in events)
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }}\n' for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}"\n'
                f'lines {{ id: 1 name: "{line}" timestamp_ns: 0\n{evs}}}\n'
                f'{meta}}}\n')
    return ProfileData.from_text_proto(
        plane(1, "/device:TPU:0", "XLA Ops", device_ops)
        + plane(2, "/host:CPU", "python", host_events))


# window 0..20000 ns; the device runs 2000-4000, 6000-9000, 12000-13000
OPS = [("fusion.1", 2000, 4000), ("paged_decode_attention.3", 6000, 9000),
       ("fusion.1", 12000, 13000)]
HARNESS = [("bench.window", 0, 20000), ("bench.submit", 0, 600),
           ("bench.step", 600, 11500), ("bench.stamp", 11500, 12500)]
# one step: idle 0-2000 (middle 1000 in admit), 4000-6000 (middle 5000 in
# a JAX call of decode.prep), 9000-12000 (middle 10500 in decode.post);
# 13000-20000 (middle 16500) has nothing open: the loop asleep
PHASES = [("step", 700, 11400), ("admit", 700, 1200),
          ("decode_step", 1200, 10000), ("decode.prep", 1200, 5500),
          ("PjitFunction(_threefry_split)", 4500, 5500),
          ("decode.wait", 5500, 10000), ("np.asarray(jax.Array)", 9500, 10000),
          ("decode.post", 10000, 11400)]


def ctx_of(trace=None, spans=(), served=(), open_loop=True, t0=0.0, t1=40.0):
    return Context(open_loop=open_loop, chips=1, setup_s=1.0, t0=t0, t1=t1,
                   served=list(served), spans=list(spans), pool_delta={},
                   trace=trace, kernel_least_s={}, model_flops=0.0,
                   peaks=None)


def step_span(ts, dur, busy, wait_s):
    return {"name": "step", "trace_id": None, "ts": ts, "dur": dur,
            "attrs": {"busy": busy, "admitted": 0, "blocked": 0,
                      "wait_s": wait_s}}


def test_program_phases_name_the_gaps_and_change_no_other_number():
    with_phases = trace_reduce.reduce(xspace(OPS, HARNESS + PHASES))
    without = trace_reduce.reduce(xspace(OPS, HARNESS))
    for s in (with_phases, without):
        assert s.window_s == pytest.approx(20e-6)
        assert s.busy_s == pytest.approx(6e-6)
        assert s.kernel_s("paged_decode_attention") == pytest.approx(3e-6)
    assert with_phases.op_s == without.op_s
    assert (with_phases.breakdown()["device_ops"]
            == without.breakdown()["device_ops"])
    steps = [step_span(0.0, 0.0114, 4, 0.0045)]
    assert read("device_idle_share", ctx_of(with_phases, steps)) == (
        read("device_idle_share", ctx_of(without, steps)))
    gaps = dict(with_phases.breakdown()["idle_gaps"])
    assert gaps == pytest.approx({
        "admit": 2e-6, "PjitFunction(_threefry_split)": 2e-6,
        "decode.post": 3e-6, "none": 7e-6})


def test_host_bound_idle_share_counts_gaps_inside_step():
    s = trace_reduce.reduce(xspace(OPS, HARNESS + PHASES))
    steps = [step_span(0.0, 0.0114, 4, 0.0045)]
    # 2000 + 2000 + 3000 ns of 20000 idle inside step; 7000 asleep
    assert read("host_bound_idle_share", ctx_of(s, steps)) == pytest.approx(35.0)
    assert read("device_idle_share", ctx_of(s, steps)) == pytest.approx(70.0)
    # a gap whose middle lies in the harness's own phase is not the step's:
    # here the step ends at 10200, before the middle of 9000-12000
    short = [("step", 700, 10200)] + [
        (n, a, b) for n, a, b in PHASES if n not in ("step", "decode.post")]
    s2 = trace_reduce.reduce(xspace(OPS, HARNESS + short))
    assert dict(s2.breakdown()["idle_gaps"])["bench.step"] == pytest.approx(3e-6)
    assert read("host_bound_idle_share", ctx_of(s2, steps)) == pytest.approx(20.0)


def test_host_bound_idle_share_needs_the_programs_steps_and_a_device():
    s = trace_reduce.reduce(xspace(OPS, HARNESS + PHASES))
    # a program without ``step`` spans (the parent of this metric) reads None
    decode = {"name": "decode_step", "ts": 0.0, "dur": 0.01,
              "attrs": {"busy": 4}}
    assert read("host_bound_idle_share", ctx_of(s, [decode])) is None
    assert read("host_bound_idle_share", ctx_of(None, [step_span(0, 1, 1, 0)])) is None
    # a trace with no device plane (a CPU run) makes up no device number
    host_only = ProfileData.from_text_proto(
        'planes { id: 2 name: "/host:CPU" lines { id: 1 name: "python" '
        'timestamp_ns: 0 events { metadata_id: 1 offset_ps: 0 '
        'duration_ps: 1000000 } } event_metadata { key: 1 value { id: 1 '
        'name: "bench.window" } } }')
    cpu = trace_reduce.reduce(host_only)
    assert read("host_bound_idle_share", ctx_of(cpu, [step_span(0, 1, 1, 0)])) is None


def test_step_host_ms_is_the_median_step_less_its_waits():
    spans = [step_span(1.0, 0.050, 4, 0.044),     # 6 ms of host
             step_span(2.0, 0.060, 4, 0.052),     # 8 ms
             step_span(3.0, 0.045, 2, 0.041),     # 4 ms
             step_span(4.0, 0.200, 0, 0.0),       # decoded nothing: left out
             step_span(50.0, 0.010, 4, 0.0),      # after the window
             {"name": "decode_step", "ts": 1.0, "dur": 0.04,
              "attrs": {"busy": 4}}]
    assert read("step_host_ms", ctx_of(spans=spans)) == pytest.approx(6.0)
    # without ``step`` spans there is nothing to read
    assert read("step_host_ms", ctx_of(spans=spans[-1:])) is None


def served(rid, due):
    plan = Planned(rid=rid, prompt=np.zeros(4, np.int32), max_new=4, due=due)
    return Served(plan=plan, req=None, due=due)


def queue_span(rid, ts, dur):
    return {"name": "queue", "trace_id": rid, "ts": ts, "dur": dur,
            "attrs": {"slot": 0, "prefix_hit": 0}}


def test_queue_wait_p95_counts_a_request_never_admitted_as_infinite():
    reqs = [served(i, 1.0 + i) for i in range(20)]
    reqs.append(served(99, 50.0))                 # due after the window
    spans = [step_span(1.0 + i, 0.04, 1, 0.03) for i in range(39)]
    spans += [queue_span(i, 1.0 + i, 0.1 * i) for i in range(19)]
    spans.append(queue_span(99, 50.0, 7.0))
    # request 19 never got a slot: the 95th percentile of 20 waits
    # interpolates between the 19th (1.8 s) and infinity
    assert read("queue_wait_p95_s.code", ctx_of(spans=spans, served=reqs)) is None
    spans.append(queue_span(19, 20.0, 1.9))
    want = float(np.percentile([0.1 * i for i in range(20)], 95))
    got = read("queue_wait_p95_s.code", ctx_of(spans=spans, served=reqs))
    assert got == pytest.approx(want)
    # one never admitted among 40 stays beyond the 95th percentile
    more = reqs + [served(100 + i, 21.0 + i / 2) for i in range(20)]
    spans += [queue_span(100 + i, 21.0 + i / 2, 0.5) for i in range(19)]
    got = read("queue_wait_p95_s.code", ctx_of(spans=spans, served=more))
    waits = [0.1 * i for i in range(20)] + [0.5] * 19 + [float("inf")]
    assert got == pytest.approx(float(np.percentile(waits, 95)))
    assert np.isfinite(got)
    # a program without ``step`` spans (the parent of this metric) reads None
    queues = [sp for sp in spans if sp["name"] == "queue"]
    assert read("queue_wait_p95_s.code", ctx_of(spans=queues, served=more)) is None
    # a backlog has no due times to wait from
    assert read("queue_wait_p95_s.code",
                ctx_of(spans=spans, served=reqs, open_loop=False)) is None


def test_tiny_traced_run_reports_the_phase_metrics(tmp_path):
    """The new metrics are files beside the others: a tree that lists
    them reports them from a traced run, the harness unchanged."""
    root = make_tree(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    both = ["tiny-a.code", "tiny-b.batch"]
    for name, unit, source, cells in (
            ("step_host_ms", "ms", "program_span", both),
            ("queue_wait_p95_s.code", "s", "program_span", ["tiny-a.code"]),
            ("host_bound_idle_share", "%", "device_trace", both)):
        spec["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                                  "source": source, "layer": "batcher",
                                  "moves": "itl_p95_s", "workloads": cells})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    r = run_tiny(root, "tiny-a.code", trace=True)
    m = r["metrics"]
    assert r["correct"] is True
    assert m["step_host_ms"]["value"] > 0
    assert m["queue_wait_p95_s.code"]["value"] >= 0
    # the CPU trace has no device plane: no device share is made up
    assert "host_bound_idle_share" not in m


def test_queue_wait_leaves_out_the_pause_after_the_close():
    """Requests queued at the close wait through the harness's stop of the
    profiler (here 70 s) before the loop steps again; that stretch is not
    the program's."""
    reqs = [served(i, 1.0 + i) for i in range(39)] + [served(39, 39.5)]
    spans = [step_span(1.0 + i, 0.04, 1, 0.03) for i in range(39)]
    spans += [queue_span(i, 1.0 + i, 0.5) for i in range(39)]
    spans += [step_span(110.0, 0.04, 1, 0.03),         # the loop resumes
              queue_span(39, 39.5, 110.01 - 39.5)]      # admitted at 110.01
    got = read("queue_wait_p95_s.code", ctx_of(spans=spans, served=reqs))
    want = float(np.percentile([0.5] * 39 + [110.01 - 39.5 - 70.0], 95))
    assert got == pytest.approx(want)
    # with no pause (an untraced loop steps on at once) nothing is taken out
    spans[-2] = step_span(40.0, 0.04, 1, 0.03)
    spans[-1] = queue_span(39, 39.5, 1.0)
    got = read("queue_wait_p95_s.code", ctx_of(spans=spans, served=reqs))
    assert got == pytest.approx(float(np.percentile([0.5] * 39 + [1.0], 95)))
