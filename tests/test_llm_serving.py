"""Continuous-batching LLM decode serving: paged KV cache, decode
engine, sessions, /v1/generate (`llm` marker, CPU tier-1).

The acceptance matrix for the LLM serving tier:
- paged-allocator free-list correctness: no page leaks after
  evict/EOS/preempt, occupancy returns to zero after drain;
- paged decode is BIT-EXACT with the full-cache reference under greedy
  decoding (a full cache is the degenerate one-page-per-sequence
  layout; same values + same math through a different page table must
  produce identical bits — anything else is an allocator/page-table
  bug);
- continuous batching admits/evicts per decode step (a later short
  request finishes while an earlier long one is still decoding);
- chunked prefill never stalls the decode batch;
- the batcher's size-or-timeout flush is capped by the head request's
  deadline (PR-7 satellite regression);
- sticky sessions: continuation == one-shot, typed SessionResetError
  when the holder is gone, fleet-level affinity through the router.
"""
from __future__ import annotations

import threading
import time

import numpy as onp
import pytest

import jax.numpy as jnp

from mxnet_tpu import faults, serving
from mxnet_tpu.models import decoder
from mxnet_tpu.ops.pallas import paged_attention as paged
from mxnet_tpu.serving.kvcache import CacheOOM, PageAllocator, pages_for

pytestmark = pytest.mark.llm

VOCAB = 128


@pytest.fixture(scope="module")
def lm():
    return decoder.decoder_tiny_lm(seed=0, vocab_size=VOCAB)


def make_engine(lm, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_ctx", 64)
    return serving.DecodeEngine(lm, name="llm", **kw)


def greedy_oracle(lm, prompt, n):
    """Token-by-token full causal forward — the independent reference
    the engine's chunked-prefill + paged-decode path must reproduce."""
    params, cfg = lm.jax_params(), lm.config
    toks = list(prompt)
    for _ in range(n):
        logits = decoder.full_forward(params, cfg,
                                      jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


# ---------------------------------------------------------------------------
# paged KV-cache allocator
# ---------------------------------------------------------------------------
def test_allocator_free_list_and_occupancy():
    a = PageAllocator(total_pages=9, page_size=4)  # 8 usable
    assert a.num_free == 8 and a.occupancy() == 0.0
    p1 = a.alloc("s1", 3)
    p2 = a.alloc("s2", 2)
    assert len(set(p1) | set(p2)) == 5 and 0 not in p1 + p2
    assert a.num_used == 5 and a.occupancy() == 5 / 8
    assert a.pages("s1") == p1  # allocation order == token order
    a.check_leaks()
    with pytest.raises(CacheOOM):
        a.alloc("s3", 4)  # only 3 free: nothing partially allocated
    assert a.num_free == 3 and a.counters["failed_allocs"] == 1
    assert a.free("s1") == 3
    assert a.free("s1") == 0  # idempotent
    # LIFO: the freshly freed pages come back out first
    p3 = a.alloc("s3", 3)
    assert set(p3) == set(p1)
    a.free("s2")
    a.free("s3")
    assert a.num_used == 0 and a.occupancy() == 0.0
    a.check_leaks()
    assert pages_for(0, 4) == 0 and pages_for(1, 4) == 1 \
        and pages_for(9, 4) == 3


def test_allocator_fault_site():
    a = PageAllocator(total_pages=4, page_size=4)
    with faults.inject("kvcache.alloc", "error", n=1, max_trips=1):
        with pytest.raises(RuntimeError):
            a.alloc("s", 1)
    a.alloc("s", 1)  # site clean again
    a.free("s")
    a.check_leaks()


# ---------------------------------------------------------------------------
# paged attention op
# ---------------------------------------------------------------------------
def test_paged_attention_reference_matches_naive():
    """Scattered page layout == independent dense-cache math (GQA)."""
    rng = onp.random.RandomState(0)
    B, H, KVH, D, S, PPS = 3, 4, 2, 16, 4, 4
    total = B * PPS + 1
    lengths = onp.array([5, 16, 1], onp.int32)
    # pages handed out in a deliberately shuffled order
    order = list(range(1, total))
    rng.shuffle(order)
    page_indices = onp.array(order[:B * PPS]).reshape(B, PPS)
    k_pages = rng.randn(KVH, total, S, D).astype("float32")
    v_pages = rng.randn(KVH, total, S, D).astype("float32")
    q = rng.randn(B, H, D).astype("float32")

    out = paged.paged_attention(jnp.asarray(q), jnp.asarray(k_pages),
                                jnp.asarray(v_pages), jnp.asarray(lengths),
                                jnp.asarray(page_indices))
    assert paged.last_path == "xla"  # CPU lane: the gather reference

    # naive: contiguous gather + numpy softmax, head h -> kv head h//g
    g = H // KVH
    ref = onp.zeros((B, H, D), "float32")
    for b in range(B):
        kc = k_pages[:, page_indices[b]].reshape(KVH, PPS * S, D)
        vc = v_pages[:, page_indices[b]].reshape(KVH, PPS * S, D)
        for h in range(H):
            kv = h // g
            logits = kc[kv, :lengths[b]] @ q[b, h] / onp.sqrt(D)
            p = onp.exp(logits - logits.max())
            p /= p.sum()
            ref[b, h] = p @ vc[kv, :lengths[b]]
    assert onp.allclose(onp.asarray(out), ref, atol=1e-5)


def test_paged_decode_bit_exact_vs_full_cache():
    """The acceptance bar: greedy decode through a multi-page layout is
    BIT-IDENTICAL to the same decode through a one-page-per-sequence
    (i.e. contiguous full-cache) layout — the paging layer must be
    invisible to the math."""
    lm = decoder.decoder_tiny_lm(seed=0, vocab_size=VOCAB)
    params, cfg = lm.jax_params(), lm.config
    prompt = [1, 2, 3, 4, 5]
    n_steps = 12
    max_ctx = 32

    def drive(page_size):
        S = page_size
        pps = max_ctx // S
        total = pps + 1  # one sequence + the scratch page
        kp = decoder.fresh_pool(cfg, total, S)
        vp = decoder.fresh_pool(cfg, total, S)
        row = onp.arange(1, pps + 1, dtype=onp.int32)
        prefill = decoder.make_prefill_chunk(cfg, S, 8)
        step = decoder.make_decode_step(cfg, S)
        kp, vp, tok, last_logits = prefill(
            params, kp, vp,
            jnp.asarray(onp.pad(prompt, (0, 8 - len(prompt))), jnp.int32),
            jnp.int32(0), jnp.int32(len(prompt)), jnp.asarray(row))
        logits_trace = [onp.asarray(last_logits)]
        tokens = [int(tok)]
        pos = len(prompt)
        tables = jnp.asarray(row[None])
        for _ in range(n_steps):
            kp, vp, nxt, logits = step(
                params, kp, vp, jnp.asarray([tokens[-1]], jnp.int32),
                jnp.asarray([pos], jnp.int32), tables,
                jnp.ones((1,), bool))
            logits_trace.append(onp.asarray(logits[0]))
            tokens.append(int(nxt[0]))
            pos += 1
        return tokens, logits_trace

    toks_paged, trace_paged = drive(4)        # 8 pages of 4 tokens
    toks_full, trace_full = drive(max_ctx)    # 1 page == full cache
    assert toks_paged == toks_full
    for a, b in zip(trace_paged, trace_full):
        assert onp.array_equal(a, b), "paged decode diverged bitwise"


# ---------------------------------------------------------------------------
# decode engine: scheduling
# ---------------------------------------------------------------------------
def test_engine_greedy_parity_with_full_forward(lm):
    eng = make_engine(lm)
    try:
        res = eng.submit([3, 1, 4, 1, 5], max_new_tokens=10).result(
            timeout=120)
        assert res["tokens"] == greedy_oracle(lm, [3, 1, 4, 1, 5], 10)
        assert res["finish_reason"] == "length"
        assert res["completion_tokens"] == 10
    finally:
        assert eng.stop()
    assert eng.alloc.num_used == 0
    eng.alloc.check_leaks()


def test_continuous_admit_evict_per_step(lm):
    """Slots stay saturated: with 2 slots and 4 requests of very
    different lengths, short requests ride along and finish while the
    long ones still decode — batch-level scheduling cannot do this."""
    eng = make_engine(lm, slots=2)
    done = {}

    def watch(key, fut):
        fut.add_done_callback(lambda f: done.setdefault(
            key, time.perf_counter()))

    try:
        # both slots fill with unequal requests; the moment the shorter
        # one evicts, its slot admits the queued shorts — all while the
        # 48-token request is still decoding
        med = eng.submit([1, 2], max_new_tokens=10)
        long = eng.submit([2, 3], max_new_tokens=48)
        watch("med", med)
        watch("long", long)
        # no pause here: admission is in order of submission, and a test
        # thread held up on a loaded machine for longer than the long
        # request's 48 steps would submit the shorts too late
        short1 = eng.submit([4, 5], max_new_tokens=2)
        short2 = eng.submit([5, 6], max_new_tokens=2)
        watch("short1", short1)
        watch("short2", short2)
        for f in (med, long, short1, short2):
            f.result(timeout=120)
        assert done["med"] < done["long"]
        assert done["short1"] < done["long"]
        assert done["short2"] < done["long"]
        snap = eng.metrics.snapshot()["models"]["llm"]
        assert snap["counters"]["sequences_completed_total"] == 4
        assert snap["generate"]["decode_occupancy"] > 0
    finally:
        assert eng.stop()
    assert eng.alloc.num_used == 0


def test_chunked_prefill_does_not_stall_decode(lm):
    """A 56-token prompt prefills in 8-token chunks; an in-flight decode
    keeps emitting between chunks instead of waiting out the prompt."""
    # prefix_cache off: this test asserts the exact chunked prefill
    # token total, which a prefix hit would legitimately shrink
    eng = make_engine(lm, slots=2, prefill_chunk=8, prefix_cache=False)
    try:
        active = eng.submit([1, 2, 3], max_new_tokens=24)
        time.sleep(0.2)  # let it enter decode
        long_prompt = list(range(1, 57))
        big = eng.submit(long_prompt, max_new_tokens=2)
        a = active.result(timeout=120)
        b = big.result(timeout=120)
        assert a["tokens"] == greedy_oracle(lm, [1, 2, 3], 24)
        assert b["tokens"] == greedy_oracle(lm, long_prompt, 2)
        snap = eng.metrics.snapshot()["models"]["llm"]
        # the decode stream never gapped by more than a few engine steps
        # (a full-prompt stall would cost ~7 chunked steps at once)
        itl = snap["generate"]["inter_token"]
        assert itl["count"] >= 20
        assert snap["counters"]["prefill_tokens_total"] >= 59
    finally:
        assert eng.stop()
    assert eng.alloc.num_used == 0


def test_eos_eviction_frees_pages(lm):
    prompt = [1, 2, 3, 4, 5]
    # which tokens the model emits is the weights' business: take the EOS
    # id from a run without one, the token whose first appearance is the
    # latest, so the second run stops exactly there
    eng = make_engine(lm, prefix_cache=False)
    try:
        free = eng.submit(prompt, max_new_tokens=30).result(
            timeout=120)["tokens"]
    finally:
        assert eng.stop()
    k = max(i for i in range(len(free)) if free[i] not in free[:i])
    eos = free[k]
    # prefix_cache off: this test asserts num_used == 0 after eviction;
    # cache-held prefix pages are legitimate retained state, not a leak
    eng = make_engine(lm, eos_id=eos, prefix_cache=False)
    try:
        res = eng.submit(prompt, max_new_tokens=30).result(timeout=120)
        assert res["finish_reason"] == "eos"
        assert res["tokens"] == free[:k + 1]
        assert len(res["tokens"]) < 30
        deadline = time.time() + 5
        while eng.alloc.num_used and time.time() < deadline:
            time.sleep(0.01)
        assert eng.alloc.num_used == 0  # EOS evicted, pages freed
        eng.alloc.check_leaks()
    finally:
        assert eng.stop()


def test_preemption_under_page_pressure(lm):
    """An undersized pool forces recompute-preemption; every request
    still completes with oracle-exact tokens and no page leaks."""
    # 8 usable pages; three 15-token sequences need 12 — somebody gets
    # preempted and recomputed
    eng = make_engine(lm, slots=3, page_size=4, max_ctx=32, total_pages=9)
    try:
        prompts = [[i + 1, i + 2, i + 3] for i in range(3)]
        futs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        outs = [f.result(timeout=180) for f in futs]
        for p, o in zip(prompts, outs):
            assert o["tokens"] == greedy_oracle(lm, p, 12)
        snap = eng.metrics.snapshot()["models"]["llm"]
        assert snap["counters"]["preemptions_total"] >= 1
    finally:
        assert eng.stop()
    assert eng.alloc.num_used == 0
    eng.alloc.check_leaks()


# ---------------------------------------------------------------------------
# deadlines / shedding (the DynamicBatcher satellite + engine parity)
# ---------------------------------------------------------------------------
def test_batcher_deadline_caps_flush_window():
    """PR-7 satellite regression: a short-deadline request with an empty
    queue is rejected in ~deadline, not ~flush_s."""
    reg = serving.ModelRegistry()
    reg.load("m", lambda b: b * 2, item_shape=(4,), max_batch_size=8,
             warmup=False)
    b = serving.DynamicBatcher(reg, flush_ms=2000.0)
    try:
        t0 = time.perf_counter()
        fut = b.submit("m", onp.ones(4, "float32"), deadline_ms=60)
        with pytest.raises(serving.DeadlineExceededError):
            fut.result(timeout=10)
        waited_ms = (time.perf_counter() - t0) * 1e3
        assert waited_ms < 600, (
            "deadline'd request held the flush window open: %.0f ms"
            % waited_ms)
        # deadline-free traffic still batches and serves afterwards
        out = b.submit("m", onp.ones(4, "float32")).result(timeout=10)
        assert (onp.asarray(out) == 2).all()
    finally:
        b.stop()


class DecodeGate(faults.FaultRule):
    """A rule on ``decode.step`` that never trips: every decode step
    waits at it until the test opens it, so a request holds its slot for
    as long as the test says and not for as long as the machine takes."""

    def __init__(self):
        super().__init__("decode.step", "error")
        self.open = threading.Event()

    def should_trip(self):
        self.open.wait(60)
        return False


def test_generate_queue_deadline_and_shed(lm):
    eng = make_engine(lm, slots=1, max_queue_depth=2)
    gate = faults.install(DecodeGate())

    def hold_the_slot(max_new_tokens):
        gate.open.clear()
        busy = eng.submit([1, 2], max_new_tokens=max_new_tokens)
        deadline = time.time() + 60
        while eng.active_count() == 0 and time.time() < deadline:
            time.sleep(0.005)  # busy must hold the slot, not the queue
        assert eng.active_count() == 1
        return busy
    try:
        # fill the slot, then the queue
        busy = hold_the_slot(30)
        q1 = eng.submit([2, 3], max_new_tokens=2)
        q2 = eng.submit([3, 4], max_new_tokens=2)
        with pytest.raises(serving.QueueFullError):
            eng.submit([4, 5], max_new_tokens=2)
        gate.open.set()
        for f in (busy, q1, q2):
            f.result(timeout=120)
        # queued deadline expires typed while the slot is busy (the
        # busy request is held past the queued deadline)
        busy2 = hold_the_slot(60)
        dead = eng.submit([9, 9], max_new_tokens=2, deadline_ms=25)
        time.sleep(0.05)
        gate.open.set()
        with pytest.raises(serving.DeadlineExceededError):
            dead.result(timeout=30)
        busy2.result(timeout=120)
    finally:
        gate.open.set()
        faults.remove(gate)
        assert eng.stop()
    assert eng.alloc.num_used == 0


def test_decode_step_fault_poisons_batch_only(lm):
    """An injected decode.step fault fails the in-flight decode batch
    typed; the engine keeps serving fresh requests."""
    eng = make_engine(lm, prefix_cache=False)  # raw page accounting
    try:
        with faults.inject("decode.step", "error", n=1, max_trips=1):
            fut = eng.submit([1, 2, 3], max_new_tokens=10)
            with pytest.raises(serving.ServingError):
                fut.result(timeout=120)
        assert eng.alloc.num_used == 0  # failed sequence freed its pages
        res = eng.submit([1, 2, 3], max_new_tokens=4).result(timeout=120)
        assert res["tokens"] == greedy_oracle(lm, [1, 2, 3], 4)
        snap = eng.metrics.snapshot()["models"]["llm"]
        assert snap["counters"]["errors_total"] >= 1
    finally:
        assert eng.stop()


# ---------------------------------------------------------------------------
# sessions
# ---------------------------------------------------------------------------
def test_session_continuation_matches_one_shot(lm):
    eng = make_engine(lm)
    try:
        r1 = eng.submit([1, 2, 3], max_new_tokens=4,
                        session="s").result(timeout=120)
        r2 = eng.submit([7, 8], max_new_tokens=4, session="s",
                        resume=True).result(timeout=120)
        oneshot = eng.submit([1, 2, 3] + r1["tokens"] + [7, 8],
                             max_new_tokens=4).result(timeout=120)
        assert r2["tokens"] == oneshot["tokens"]
        # parked session holds pages until drain
        assert eng.alloc.num_used > 0
        with pytest.raises(serving.SessionResetError):
            eng.submit([1], max_new_tokens=2, session="gone", resume=True)
    finally:
        assert eng.stop()
    assert eng.alloc.num_used == 0  # drain released the parked session
    eng.alloc.check_leaks()


def test_session_ttl_expiry_resets(lm):
    eng = make_engine(lm, session_ttl_s=0.2, prefix_cache=False)
    try:
        eng.submit([1, 2, 3], max_new_tokens=2,
                   session="brief").result(timeout=120)
        # keep the engine stepping so the TTL sweep runs
        deadline = time.time() + 10
        while eng.alloc.num_used and time.time() < deadline:
            eng.submit([5, 6], max_new_tokens=1).result(timeout=120)
            time.sleep(0.1)
        assert eng.alloc.num_used == 0
        with pytest.raises(serving.SessionResetError):
            eng.submit([1], max_new_tokens=2, session="brief",
                       resume=True)
    finally:
        assert eng.stop()


# ---------------------------------------------------------------------------
# HTTP surface + fleet affinity
# ---------------------------------------------------------------------------
def test_http_generate_roundtrip_and_metrics(lm):
    eng = make_engine(lm)
    with serving.ModelServer(serving.ModelRegistry()) as srv:
        srv.attach_engine("llm", eng)
        cli = serving.ServingClient(*srv.address)
        r = cli.generate("llm", [1, 2, 3, 4, 5], max_tokens=6)
        assert r["tokens"] == greedy_oracle(lm, [1, 2, 3, 4, 5], 6)
        assert r["model"] == "llm" and r["finish_reason"] == "length"
        # /v1/generate with the model in the body routes identically
        doc = cli._request("POST", "/v1/generate",
                           {"model": "llm", "prompt": [1, 2],
                            "max_tokens": 2})
        assert len(doc["tokens"]) == 2
        # model listed in the registry; engine stats + metrics exported
        assert "llm" in cli.models()
        stats = cli.stats()
        assert stats["generators"]["llm"]["slots"] == 4
        gen = stats["models"]["llm"]["generate"]
        assert gen["ttft"]["count"] >= 2
        assert gen["kv_occupancy"] is not None
        text = cli.metrics_text()
        assert "mxtpu_serving_ttft_p50_ms" in text
        assert "mxtpu_serving_tokens_per_s" in text
        assert "mxtpu_serving_kv_occupancy" in text
        with pytest.raises(serving.SessionResetError):
            cli.generate("llm", [1], max_tokens=2, session="nope",
                         resume=True)
    assert eng.alloc.num_used == 0


def test_router_session_affinity_and_typed_reset(lm):
    """Sticky decode sessions through the fleet: the session id rides
    the consistent-hash ring back to the replica holding the KV pages;
    when that replica dies, resume surfaces SessionResetError — never a
    silent misroute."""
    def mk():
        eng = make_engine(lm, slots=2)
        srv = serving.ModelServer(serving.ModelRegistry())
        srv.start()
        srv.attach_engine("llm", eng)
        return srv, eng

    s1, e1 = mk()
    s2, e2 = mk()
    router = serving.Router(
        ["127.0.0.1:%d" % s1.port, "127.0.0.1:%d" % s2.port],
        policy="hash", probe_ms=0)
    rs = serving.RouterServer(router)
    rs.start()
    try:
        cli = serving.ServingClient(*rs.address)
        cli.generate("llm", [1, 2, 3], max_tokens=3, session="sticky")
        owner_eng = e1 if e1._sessions else e2
        other_eng = e2 if owner_eng is e1 else e1
        assert len(owner_eng._sessions) == 1
        assert len(other_eng._sessions) == 0
        # continuation returns home (the other replica never sees it)
        cli.generate("llm", [5], max_tokens=3, session="sticky",
                     resume=True)
        assert len(other_eng._sessions) == 0
        # kill the owner: the ring remaps to a replica WITHOUT the
        # pages, which must answer with the typed reset
        owner_srv = s1 if owner_eng is e1 else s2
        owner_srv.stop(drain=False)
        with pytest.raises(serving.SessionResetError):
            cli.generate("llm", [5], max_tokens=3, session="sticky",
                         resume=True)
        # sessionless traffic keeps flowing on the survivor
        r = cli.generate("llm", [2, 3], max_tokens=2)
        assert len(r["tokens"]) == 2
    finally:
        rs.stop()
        s1.stop()
        s2.stop()


@pytest.mark.slow
def test_chaos_llm_acceptance():
    """The multi-process drill: SIGKILL a supervised LLM replica under
    sustained decode traffic (tools/chaos.py --scenario llm) — typed
    session resets only, lossless sessionless traffic, full recovery,
    zero router-level failures."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "chaos.py"),
         "--scenario", "llm", "-n", "3"],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo)
    sys.stdout.write(out.stdout[-3000:])
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "chaos: PASS" in out.stdout


def test_server_drain_completes_generations(lm):
    """stop(drain=True) serves queued generations before shutdown and
    ends with the KV pool empty (the leak check after a drain cycle)."""
    eng = make_engine(lm, slots=2)
    srv = serving.ModelServer(serving.ModelRegistry())
    srv.start()
    srv.attach_engine("llm", eng)
    futs = [srv.batcher.submit_generate("llm", [i + 1, 2], max_new_tokens=6)
            for i in range(5)]
    srv.stop(drain=True)
    for f in futs:
        assert len(f.result(timeout=10)["tokens"]) == 6
    with pytest.raises(serving.ServerClosedError):
        srv.batcher.submit_generate("llm", [1], max_new_tokens=1)
    assert eng.alloc.num_used == 0
    eng.alloc.check_leaks()
