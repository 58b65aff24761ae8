"""The serving path's spans and always-on phase counters (`llm` marker, CPU
tier-1, a tiny `decoder_tiny_lm` engine behind ModelServer).

- every span of the table in PERF.md lands in the xplane's ``/host:CPU``
  plane when a ``jax.profiler`` session records, nested as the table says,
  and one request's ``rid`` ties its HTTP span to its engine spans;
- a finished request's three phases add up to its total, a step's parts to
  its wall, preemptions included;
- the scheduler's counters count what they say and restart at ``reset()``;
- a span costs next to nothing while no session records.

No number here is a measurement of the chip."""
from __future__ import annotations

import glob
import json
import math
import os
import re
import threading
import time
import urllib.request

import pytest

import jax

from mxnet_tpu import profiler, serving
from mxnet_tpu.models import decoder

pytestmark = pytest.mark.llm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK = 8
PROMPTS = [list(range(1, 20)), list(range(3, 12)), list(range(5, 30))]

#: span -> the span it must lie inside on its thread (None: top level)
SPANS = {
    "engine.wait_for_work": None,
    "engine.step": None,
    "engine.ops": "engine.step",
    "engine.expire": "engine.step",
    "engine.admit": "engine.step",
    "request.admit": "engine.admit",
    "engine.prefill": "engine.step",
    "engine.prefill_launch": "engine.prefill",
    "engine.first_token_read": "engine.prefill",
    "engine.decode": "engine.step",
    "engine.decode_launch": "engine.decode",
    "engine.retire": "engine.decode",
    "engine.device_wait": "engine.retire",
    "engine.account": "engine.step",
    "request.finish": "engine.step",
    "http.generate": None,
    "http.wait_engine": "http.generate",
}


@pytest.fixture(scope="module")
def lm():
    return decoder.decoder_tiny_lm(seed=0, vocab_size=128)


def host_lines(trace_dir):
    """[[(name, start_ns, end_ns, stats)] per thread] of the newest trace."""
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for e in line.events]
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines]


@pytest.fixture(scope="module")
def served(lm, tmp_path_factory):
    """Three requests through ModelServer under a profiler session (the
    benchmark's options: no Python tracer), prefix cache off."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    engine = serving.DecodeEngine(lm, slots=4, page_size=8, max_ctx=64,
                                  prefill_chunk=CHUNK, prefix_cache=False)
    server = serving.ModelServer()
    server.attach_engine("lm", engine)
    host, port = server.start()
    results = [None] * len(PROMPTS)

    def ask(i):
        results[i] = serving.ServingClient(host, port).generate(
            "lm", PROMPTS[i], max_tokens=5)

    try:
        server.metrics.reset()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            threads = [threading.Thread(target=ask, args=(i,))
                       for i in range(len(PROMPTS))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            # a span is written when it ends inside the session: let the
            # worker close the step that finished the last request
            time.sleep(0.2)
        finally:
            jax.profiler.stop_trace()
        assert all(r is not None for r in results)
        snap = server.metrics.snapshot()["models"]["lm"]
        with urllib.request.urlopen(
                "http://%s:%d/metrics" % (host, port)) as r:
            prom = json.loads(r.read().decode())["text"]
        server.metrics.reset()
        assert serving.ServingClient(host, port).generate(
            "lm", [1, 2, 3], max_tokens=2)["tokens"]
        after = server.metrics.snapshot()["models"]["lm"]
    finally:
        server.stop()
    return {"lines": host_lines(trace_dir), "results": results,
            "snap": snap, "prom": prom, "after": after}


# -- (a) the spans -----------------------------------------------------------
@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_is_in_the_host_plane_inside_its_parent(served, name):
    found = [(line, ev) for line in served["lines"] for ev in line
             if ev[0] == name]
    assert found, "no %r event in /host:CPU" % name
    parent = SPANS[name]
    if parent is None:
        return
    for line, (_, start, end, _) in found:
        assert any(p[0] == parent and p[1] <= start and end <= p[2]
                   for p in line), "%r outside %r" % (name, parent)


def test_engine_thread_is_always_inside_a_span(served):
    """engine.wait_for_work and engine.step alternate on the worker's
    line, with no other program span outside them."""
    line = next(l for l in served["lines"]
                if any(ev[0] == "engine.step" for ev in l))
    top = [ev for ev in line if ev[0] in ("engine.step",
                                          "engine.wait_for_work")]
    for name, start, end, _ in line:
        if re.match(r"(engine|request)\.", name):
            assert any(t[1] <= start and end <= t[2] for t in top), name
    steps = [ev[3]["step"] for ev in top if ev[0] == "engine.step"]
    assert steps == list(range(steps[0], steps[0] + len(steps)))


def test_one_rid_ties_a_request_together(served):
    events = [ev for line in served["lines"] for ev in line]
    rids = {ev[3]["rid"] for ev in events if ev[0] == "http.generate"}
    assert len(rids) == len(PROMPTS)
    for rid in rids:
        for name in ("request.admit", "engine.prefill_launch",
                     "engine.first_token_read", "request.finish"):
            assert any(ev[0] == name and ev[3].get("rid") == rid
                       for ev in events), (rid, name)
    launches = [ev[3] for ev in events if ev[0] == "engine.prefill_launch"]
    assert sorted(l["tokens"] for l in launches) == sorted(
        min(CHUNK, len(p) - lo) for p in PROMPTS
        for lo in range(0, len(p), CHUNK))


def test_span_budget_per_step(served):
    """At most 20 spans an engine step plus 3 a prefill launch; none per
    token, none per lane."""
    line = next(l for l in served["lines"]
                if any(ev[0] == "engine.step" for ev in l))
    steps = [ev for ev in line if ev[0] == "engine.step"]
    for _, s0, s1, _ in steps:
        inside = [ev[0] for ev in line if s0 <= ev[1] and ev[2] <= s1
                  and re.match(r"(engine|request)\.", ev[0])]
        launches = inside.count("engine.prefill_launch")
        assert len(inside) <= 20 + 3 * launches, inside


# -- (b) phases --------------------------------------------------------------
def check_phases(results, snap):
    for r in results:
        t = r["timing_ms"]
        assert abs(t["queue_wait"] + t["prefill"] + t["decode"]
                   - t["total"]) < 1.0, t
        assert min(t.values()) >= 0.0, t
    gen = snap["generate"]
    for hist in (snap["queue_wait"], snap["total"], gen["request_prefill"],
                 gen["request_decode"]):
        assert hist["count"] == len(results)
    phase_s = gen["phase_s"]
    parts = sum(phase_s[k] for k in serving.ModelMetrics.STEP_PARTS)
    assert abs(parts - phase_s["step"]) <= 0.01 * phase_s["step"], phase_s
    assert abs(phase_s["host_self"] + phase_s["device_wait"]
               - phase_s["step"]) < 1e-5
    assert min(phase_s.values()) >= 0.0, phase_s
    assert phase_s["device_wait"] > 0.0
    assert gen["engine_step"]["count"] == snap["counters"][
        "engine_steps_total"]
    # the blocking reads by what was read add up to device_wait; the
    # launches' calls and uploads lie inside the rest (each rounded to
    # 1e-6 s in the snapshot)
    wait_s, host_s = gen["wait_s"], gen["host_s"]
    assert set(wait_s) == set(serving.ModelMetrics.WAIT_PARTS)
    assert abs(sum(wait_s.values()) - phase_s["device_wait"]) < 1e-5, (
        wait_s, phase_s)
    assert wait_s["first_token"] > 0.0
    assert min(wait_s.values()) >= 0.0 and min(host_s.values()) > 0.0
    assert host_s["dispatch"] + host_s["upload"] <= (
        phase_s["host_self"] + 2e-6), (host_s, phase_s)


def test_request_phases_add_up_to_its_total(served):
    check_phases(served["results"], served["snap"])
    assert served["snap"]["http_self"]["count"] == len(PROMPTS)
    assert served["snap"]["http_self"]["p50_ms"] > 0.0


@pytest.mark.parametrize("async_decode", [True, False])
def test_phases_add_up_under_preemption(lm, async_decode):
    """An undersized pool preempts and recomputes: a preempted request's
    repeated waits and prefills are summed, not lost."""
    engine = serving.DecodeEngine(
        lm, name="lm", slots=3, page_size=4, max_ctx=32, total_pages=9,
        prefill_chunk=CHUNK, prefix_cache=False, async_decode=async_decode)
    try:
        futures = [engine.submit([i + 1, i + 2, i + 3], max_new_tokens=12)
                   for i in range(3)]
        results = [f.result(timeout=180) for f in futures]
        snap = engine.metrics.snapshot()["models"]["lm"]
    finally:
        assert engine.stop()
    assert snap["counters"]["preemptions_total"] >= 1
    check_phases(results, snap)
    # somebody waited twice: after submit and after its preemption
    assert max(r["timing_ms"]["queue_wait"] for r in results) > 0.0


# -- (c) counters ------------------------------------------------------------
def test_prefill_launches_and_steps_are_counted(served):
    counters = served["snap"]["counters"]
    assert counters["prefill_launches_total"] == sum(
        math.ceil(len(p) / CHUNK) for p in PROMPTS)
    assert counters["engine_steps_total"] >= counters[
        "prefill_launches_total"] / 4      # 4 slots: at most 4 a step
    # the window's reset starts them again: one request of one chunk
    after = served["after"]["counters"]
    assert after["prefill_launches_total"] == 1
    assert 0 < after["engine_steps_total"] < counters["engine_steps_total"]


def test_stats_endpoints_carry_the_new_numbers(served):
    prom = served["prom"]
    for needle in ("mxtpu_serving_engine_steps_total{",
                   "mxtpu_serving_prefill_launches_total{",
                   "mxtpu_serving_http_self_p50_ms{",
                   "mxtpu_serving_queue_wait_p95_ms{",
                   "mxtpu_serving_request_prefill_p50_ms{",
                   "mxtpu_serving_request_decode_p50_ms{",
                   "mxtpu_serving_engine_step_p50_ms{",
                   'mxtpu_serving_engine_phase_seconds{model="lm",'
                   'phase="host_self"}',
                   "mxtpu_serving_launches_total{",
                   "mxtpu_serving_launches_drained_total{",
                   "mxtpu_serving_host_uploads_total{",
                   "mxtpu_serving_host_upload_bytes_total{",
                   'mxtpu_serving_engine_host_seconds{model="lm",'
                   'part="dispatch"}',
                   'mxtpu_serving_engine_wait_seconds{model="lm",'
                   'read="first_token"}'):
        assert needle in prom, needle


# -- (d) cost, and the one primitive -----------------------------------------
def test_span_is_cheap_without_a_session():
    """A loose CPU gate against a regression to per-call imports or a
    generator-based context manager: under 5 us, enter and exit."""
    n = 20000

    def once():
        t0 = time.perf_counter()
        for i in range(n):
            with profiler.span("engine.prefill_launch", rid=i, slot=1):
                pass
        return (time.perf_counter() - t0) / n

    assert min(once() for _ in range(3)) < 5e-6


def test_profiler_scopes_open_a_span(tmp_path):
    """A user's Task lands in the xplane when set_config(xplane_dir=...)
    records, on the clock of the device trace."""
    profiler.set_config(xplane_dir=str(tmp_path))
    profiler.start()
    try:
        with profiler.Task("user_task"):
            with profiler.Frame("user_frame"):
                pass
    finally:
        profiler.stop()
        profiler.set_config(xplane_dir=None)
    names = {ev[0] for line in host_lines(str(tmp_path)) for ev in line}
    assert {"user_task", "user_frame"} <= names


def test_one_primitive_and_no_chrome_trace_sink():
    """TraceAnnotation is constructed only in mxnet_tpu/profiler.py, and
    serving/metrics.py no longer feeds the chrome-trace sink."""
    users = []
    for path in glob.glob(os.path.join(ROOT, "mxnet_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            src = f.read()
        if "TraceAnnotation" in src:
            users.append(os.path.relpath(path, ROOT))
        assert "observe_queue_depth" not in src, path
    assert users == [os.path.join("mxnet_tpu", "profiler.py")]
    with open(os.path.join(ROOT, "mxnet_tpu", "serving", "metrics.py")) as f:
        src = f.read()
    assert "record_counter(" not in src and "record_op_stat(" not in src
