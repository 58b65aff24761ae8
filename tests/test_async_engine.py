"""Async decode engine: step pipelining parity + behavior (`llm`
marker, CPU tier-1).

The async engine reorders WHEN host work happens (launch/retire halves,
device-resident token chaining, deferred reads) but must never change
WHAT is computed.  The acceptance matrix:

- greedy bit-parity with the synchronous engine across the serving
  feature matrix: plain decode, speculative k∈{1,2}, prefix-cache CoW,
  chunked prefill, preemption under page pressure, int8 KV;
- the static launch census is identical to sync — pipelining reorders
  dispatch, it must not add programs;
- reused staging buffers never force a recompile mid-stream;
- an injected ``engine.retire`` fault fails ONLY the poisoned flight's
  lanes (typed), flushes the pipeline, and the engine keeps serving;
- deadlines judged at launch/retire still terminate mid-decode under a
  deep dispatch queue;
- drain with launches in flight completes every stream bit-exactly and
  returns occupancy to zero (pinned in-flight pages are conserved).
"""
from __future__ import annotations

import time

import pytest

import jax

from mxnet_tpu import faults, serving
from mxnet_tpu.models import decoder

pytestmark = pytest.mark.llm

VOCAB = 128

PROMPTS = [[1, 2, 3], [7, 5], [2, 9, 4, 1], [3], [11, 3, 7]]


@pytest.fixture(scope="module")
def lm():
    return decoder.decoder_tiny_lm(seed=0, vocab_size=VOCAB)


def make_engine(lm, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_ctx", 64)
    return serving.DecodeEngine(lm, name="llm", **kw)


def run_workload(lm, prompts, max_new=8, **kw):
    eng = make_engine(lm, **kw)
    try:
        futs = [eng.submit(list(p), max_new_tokens=max_new)
                for p in prompts]
        out = [f.result(timeout=300)["tokens"] for f in futs]
    finally:
        assert eng.stop()
    assert eng.alloc.num_used == 0
    eng.alloc.check_leaks()
    return out


# ---------------------------------------------------------------------------
# bit-parity matrix
# ---------------------------------------------------------------------------
MATRIX = {
    "plain": {},
    "spec_k1": {"speculate": True, "spec_k": 1},
    "spec_k2": {"speculate": True, "spec_k": 2},
    "prefix_cow": {"prefix_cache": True},
    "chunked_prefill": {"prefill_chunk": 4},
    "preemption": {"slots": 3, "page_size": 4, "max_ctx": 32,
                   "total_pages": 9},
    "int8_kv": {"kv_dtype": "int8"},
}


@pytest.mark.parametrize("case", sorted(MATRIX), ids=sorted(MATRIX))
def test_async_sync_greedy_bit_parity(lm, case):
    """Token streams are IDENTICAL with pipelining on and off: the
    async engine is a scheduling change, not a numerics change."""
    kw = dict(MATRIX[case])
    prompts = PROMPTS
    if case == "chunked_prefill":
        # prompts longer than the chunk so prefill spans many steps
        # while decode lanes have launches in flight
        prompts = [list(range(1, 20)), list(range(2, 12)), [5, 6, 7]]
    if case == "prefix_cow":
        shared = list(range(1, 18))  # 2 full pages + a partial
        prompts = [shared + [20, 21], shared + [30, 31], shared + [40]]
    a = run_workload(lm, prompts, async_decode=True, **kw)
    s = run_workload(lm, prompts, async_decode=False, **kw)
    assert a == s


def test_async_session_continuation_matches_one_shot(lm):
    """Session park/resume while flights are in the pipe: continuation
    still equals the one-shot stream and parked pages survive pinning."""
    eng = make_engine(lm, async_decode=True)
    try:
        r1 = eng.submit([1, 2, 3], max_new_tokens=4,
                        session="s").result(timeout=120)
        r2 = eng.submit([7, 8], max_new_tokens=4, session="s",
                        resume=True).result(timeout=120)
        oneshot = eng.submit([1, 2, 3] + r1["tokens"] + [7, 8],
                             max_new_tokens=4).result(timeout=120)
        assert r2["tokens"] == oneshot["tokens"]
        assert eng.alloc.num_used > 0  # parked session holds its pages
    finally:
        assert eng.stop()
    assert eng.alloc.num_used == 0
    eng.alloc.check_leaks()


@pytest.mark.multichip
def test_async_tp_bit_parity(lm):
    """Tensor-parallel serving (8 fake devices) under pipelining."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    from mxnet_tpu.parallel.shardcfg import ShardingConfig
    cfg = ShardingConfig.for_transformer(mesh_shape=(4, 2),
                                         axis_names=("dp", "tp"))
    a = run_workload(lm, PROMPTS, async_decode=True, sharding=cfg)
    s = run_workload(lm, PROMPTS, async_decode=False, sharding=cfg)
    assert a == s


# ---------------------------------------------------------------------------
# launch census + staging recompiles
# ---------------------------------------------------------------------------
def test_async_launch_census_identical_to_sync(lm):
    """Pipelining reorders launches; it must not change the static
    decode program census the tier-1 launch gates pin down."""
    a = make_engine(lm, async_decode=True)
    s = make_engine(lm, async_decode=False)
    try:
        assert dict(a.launch_stats) == dict(s.launch_stats)
    finally:
        a.stop(drain=False)
        s.stop(drain=False)


def test_async_staging_buffers_no_recompile(lm):
    """Pinned staging buffers + the chaining combine are compiled once
    at warmup; steady-state steps add ZERO program-cache compiles."""
    eng = make_engine(lm, async_decode=True)
    try:
        eng.warmup()
        before = decoder.fn_cache_stats()["compiles"]
        for rnd in range(2):
            futs = [eng.submit([rnd + 1, i + 2], max_new_tokens=6)
                    for i in range(4)]
            for f in futs:
                assert len(f.result(timeout=120)["tokens"]) == 6
        assert decoder.fn_cache_stats()["compiles"] == before
    finally:
        assert eng.stop()
    eng.alloc.check_leaks()


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------
def test_async_metrics_and_stats_surface(lm):
    eng = make_engine(lm, async_decode=True, dispatch_ahead=2)
    try:
        st = eng.stats()["async"]
        assert st == {"enabled": True, "dispatch_ahead": 2, "inflight": 0}
        futs = [eng.submit(p, max_new_tokens=10) for p in PROMPTS]
        for f in futs:
            f.result(timeout=120)
        snap = eng.metrics.snapshot()["models"]["llm"]
        assert snap["counters"]["deferred_reads_total"] > 0
        assert snap["generate"]["dispatch_depth"]["count"] > 0
        assert snap["generate"]["dispatch_depth"]["max"] >= 1
        assert snap["generate"]["host_gap_us"]["count"] > 0
        assert eng.stats()["async"]["inflight"] == 0  # all retired
    finally:
        assert eng.stop()


def test_sync_engine_reports_host_gap_for_ab(lm):
    """The sync path records the same host-gap metric so the A/B bench
    can quantify what pipelining hides."""
    eng = make_engine(lm, async_decode=False)
    try:
        eng.submit([1, 2, 3], max_new_tokens=8).result(timeout=120)
        snap = eng.metrics.snapshot()["models"]["llm"]
        assert snap["generate"]["host_gap_us"]["count"] > 0
        assert snap["counters"].get("deferred_reads_total", 0) == 0
    finally:
        assert eng.stop()


# ---------------------------------------------------------------------------
# fault injection: engine.retire
# ---------------------------------------------------------------------------
def test_engine_retire_fault_poisons_flight_only(lm):
    """A retire fault fails exactly the poisoned flight's lanes
    (typed ServingError), discards the rest of the pipeline, and the
    engine keeps serving with a clean page pool."""
    eng = make_engine(lm, async_decode=True, prefix_cache=False)
    try:
        with faults.inject("engine.retire", "error", n=1, max_trips=1):
            fut = eng.submit([1, 2, 3], max_new_tokens=10)
            with pytest.raises(serving.ServingError):
                fut.result(timeout=120)
        assert eng.alloc.num_used == 0  # poisoned lanes freed their pages
        res = eng.submit([1, 2, 3], max_new_tokens=4).result(timeout=120)
        ref = run_workload(lm, [[1, 2, 3]], max_new=4, async_decode=False)
        assert res["tokens"] == ref[0]
        snap = eng.metrics.snapshot()["models"]["llm"]
        assert snap["counters"]["errors_total"] >= 1
    finally:
        assert eng.stop()
    assert eng.alloc.num_used == 0
    eng.alloc.check_leaks()


def test_engine_retire_fault_speculative_pipeline(lm):
    """Same contract with the speculative pipeline in flight."""
    eng = make_engine(lm, async_decode=True, speculate=True, spec_k=2,
                      prefix_cache=False)
    try:
        with faults.inject("engine.retire", "error", n=1, max_trips=1):
            fut = eng.submit([2, 9, 4], max_new_tokens=10)
            with pytest.raises(serving.ServingError):
                fut.result(timeout=120)
        res = eng.submit([2, 9, 4], max_new_tokens=5).result(timeout=120)
        ref = run_workload(lm, [[2, 9, 4]], max_new=5, async_decode=False)
        assert res["tokens"] == ref[0]
    finally:
        assert eng.stop()
    assert eng.alloc.num_used == 0
    eng.alloc.check_leaks()


# ---------------------------------------------------------------------------
# deadlines + drain under a deep pipeline
# ---------------------------------------------------------------------------
def test_async_deadline_expires_under_deep_queue(lm):
    """Deadlines are judged against launch/retire time: with a deep
    dispatch queue an expired stream still terminates promptly with
    finish_reason="deadline" instead of riding the pipeline forever."""
    eng = make_engine(lm, async_decode=True, dispatch_ahead=3,
                      max_ctx=128)
    try:
        eng.warmup()  # deadline must land mid-DECODE, not mid-compile
        # pace one short stream, then give a 120-token stream about the
        # SHORT stream's wall time (~1/6 of its own projection) — the
        # box would have to run ~6x faster than the probe for the
        # stream to hit its length budget before the deadline
        t0 = time.perf_counter()
        eng.submit([9, 9], max_new_tokens=20).result(timeout=120)
        pace = time.perf_counter() - t0
        res = eng.submit([1, 2, 3], max_new_tokens=120,
                         deadline_ms=max(10.0, 1e3 * pace)).result(
                             timeout=120)
        assert res["finish_reason"] == "deadline"
        assert len(res["tokens"]) < 120
    finally:
        assert eng.stop()
    assert eng.alloc.num_used == 0
    eng.alloc.check_leaks()


def test_async_drain_mid_pipeline_completes_all(lm):
    """stop(drain=True) issued while launches are in flight: the worker
    drains the pipe, every stream completes bit-exactly, occupancy ends
    at zero (in-flight pins all released)."""
    ref = run_workload(lm, PROMPTS, max_new=12, async_decode=False)
    eng = make_engine(lm, async_decode=True, dispatch_ahead=2)
    futs = [eng.submit(list(p), max_new_tokens=12) for p in PROMPTS]
    time.sleep(0.2)  # let the pipeline fill mid-generation
    assert eng.stop(drain=True)
    assert [f.result(timeout=10)["tokens"] for f in futs] == ref
    assert eng.alloc.num_used == 0
    eng.alloc.check_leaks()


def test_async_migrate_out_parity(lm):
    """Parked sessions ship to the page store with flights retired; a
    survivor resumes the stream bit-exactly (mid-pipeline migration)."""
    from mxnet_tpu.kvstore.pagestore import PageStoreServer
    store = PageStoreServer()
    store.start()
    try:
        a = make_engine(lm, async_decode=True, pagestore=store.address,
                        prefix_cache=False)
        try:
            r1 = a.submit([1, 2, 3], max_new_tokens=4,
                          session="m").result(timeout=120)
            assert a.migrate_out() == 1
            assert a.alloc.num_used == 0  # pinned pages fully released
        finally:
            a.stop(drain=False)
        b = make_engine(lm, async_decode=True, pagestore=store.address)
        try:
            r2 = b.submit([7, 8], max_new_tokens=4, session="m",
                          resume=True).result(timeout=120)
        finally:
            b.stop(drain=False)
        oneshot = run_workload(lm, [[1, 2, 3] + r1["tokens"] + [7, 8]],
                               max_new=4, async_decode=False)
        assert r2["tokens"] == oneshot[0]
    finally:
        store.stop()
