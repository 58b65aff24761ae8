"""Speculative decoding: draft/verify parity, KV rollback, adaptive k
(`spec` marker, CPU tier-1).

The acceptance matrix for the speculative path:
- BIT-IDENTICAL greedy output vs non-speculative decode for every
  (k, drafter, prefix-cache) combination — acceptance is longest-prefix
  matching against the target's own argmax, so any divergence is a
  verify-math or rollback bug, never "sampling noise";
- `PageAllocator.trim` frees rejected-tail pages exactly (refcounts
  conserved, shared pages deref'd not destroyed, `check_leaks` clean
  after adversarial all-reject streams — including CoW-shared prefix
  pages, which fork before the truncation);
- the adaptive-k controller opens to the cap under a perfect drafter
  and latches a hostile sequence's speculation off;
- a mixed batch (speculating + plain slots) rides ONE wide launch and
  both halves stay correct;
- `speculate.draft` / `speculate.verify` faults degrade to plain decode
  — sequences complete, bit-identical, engine keeps serving;
- a mid-speculation session exports/imports across engines with the
  greedy continuation unchanged;
- the wide-verify launch census is static: a property of (cfg, width),
  independent of acceptance — the load-independence proof.
"""
from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import faults, serving
from mxnet_tpu.models import decoder
from mxnet_tpu.serving.kvcache import PageAllocator, pages_for
from mxnet_tpu.serving.metrics import ServingMetrics
from mxnet_tpu.serving.speculate import (AdaptiveK, Drafter,
                                         DraftModelDrafter, NGramDrafter,
                                         SpeculativeScheduler)

pytestmark = pytest.mark.spec

VOCAB = 128

# repetitive prompts (the n-gram drafter's home turf) + a plain one
PROMPTS = [[1, 2, 3, 4, 1, 2, 3], [7, 8, 9, 7, 8, 9],
           [5, 5, 5, 5, 5], [10, 20, 30, 10, 20]]


@pytest.fixture(scope="module")
def lm():
    return decoder.decoder_tiny_lm(seed=0, vocab_size=VOCAB)


@pytest.fixture(scope="module")
def draft_lm(lm):
    return decoder.decoder_draft(lm, seed=1)


def make_engine(lm, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_ctx", 64)
    kw.setdefault("prefix_cache", False)
    kw.setdefault("migrate", False)
    return serving.DecodeEngine(lm, name="llm", **kw)


def run_batch(eng, prompts=PROMPTS, max_new=20, **submit_kw):
    futs = [eng.submit(p, max_new_tokens=max_new, **submit_kw)
            for p in prompts]
    return [f.result(60)["tokens"] for f in futs]


def drain(eng):
    """Stop + the allocator-hygiene bar every engine test must clear."""
    eng.stop()
    assert eng.alloc.num_used == 0
    assert not eng.alloc.check_leaks()


@pytest.fixture(scope="module")
def baseline(lm):
    eng = make_engine(lm)
    out = run_batch(eng)
    drain(eng)
    return out


class OracleDrafter(Drafter):
    """Perfect drafter: the target model's own greedy continuation
    (full acceptance every step — the upper bound)."""

    name = "oracle"
    # one trace for every proposal: the context is padded to the engines'
    # max_ctx, and causal attention keeps the padding out of the logits
    # that are read
    PAD = 64

    def __init__(self, lm):
        params, cfg = lm.jax_params(), lm.config
        self._forward = jax.jit(
            lambda toks: decoder.full_forward(params, cfg, toks))

    def propose(self, owner, context, k):
        toks = list(context)
        out = []
        for _ in range(min(int(k), self.PAD - len(toks))):
            padded = toks + [0] * (self.PAD - len(toks))
            logits = self._forward(jnp.asarray([padded], jnp.int32))
            t = int(jnp.argmax(logits[0, len(toks) - 1]))
            out.append(t)
            toks.append(t)
        return out


class WrongDrafter(Drafter):
    """Adversarial drafter: always proposes ``(last + 1) % VOCAB`` —
    (vanishingly unlikely to match greedy argmax) — every draft is
    rejected, every verify rolls back."""

    name = "wrong"

    def propose(self, owner, context, k):
        return [(int(context[-1]) + 1 + i) % VOCAB for i in range(int(k))]


# ---------------------------------------------------------------------------
# the parity matrix: k x drafter x prefix-cache, all bit-identical
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 2, 3, 4])
@pytest.mark.parametrize("kind", ["ngram", "model"])
@pytest.mark.parametrize("pfx", [False, True])
def test_parity_matrix(lm, draft_lm, baseline, k, kind, pfx):
    eng = make_engine(lm, speculate=True, spec_k=k, drafter=kind,
                      draft_model=draft_lm if kind == "model" else None,
                      prefix_cache=pfx)
    got = run_batch(eng)
    st = eng.stats()
    drain(eng)
    assert got == baseline
    assert st["speculative"]["drafter"] == kind
    assert st["speculative"]["k_cap"] == k


@pytest.mark.multichip
@pytest.mark.parametrize("k", [1, 2])
def test_parity_tensor_parallel_engine(lm, baseline, k):
    """TP arm (ISSUE 13): the dp×tp-sharded verify program accepts and
    rejects exactly like the 1-chip engine — greedy tokens bit-equal to
    the plain baseline with the KV pages head-sharded underneath."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from mxnet_tpu.parallel.shardcfg import ShardingConfig
    scfg = ShardingConfig.for_transformer(mesh_shape=(4, 2),
                                          axis_names=("dp", "tp"))
    eng = make_engine(lm, speculate=True, spec_k=k, drafter="ngram",
                      sharding=scfg)
    got = run_batch(eng)
    st = eng.stats()
    drain(eng)
    assert got == baseline
    assert st["sharding"]["tp"] == 2
    assert st["speculative"]["k_cap"] == k


def test_parity_under_adversarial_drafter(lm, baseline):
    # every draft rejected: output still bit-identical, pace = plain
    eng = make_engine(lm, speculate=True, spec_k=4, drafter=WrongDrafter())
    assert run_batch(eng) == baseline
    drain(eng)


def test_parity_under_oracle_drafter(lm, baseline):
    eng = make_engine(lm, speculate=True, spec_k=4,
                      drafter=OracleDrafter(lm))
    got = run_batch(eng)
    snap = eng.metrics.snapshot()["models"]["llm"]
    drain(eng)
    assert got == baseline
    spec = snap["generate"]["speculative"]
    # a perfect drafter accepts nearly everything...
    assert spec["accepted_token_rate"] > 0.8
    # ...so steps emit multiple tokens
    assert snap["generate"]["tokens_per_step"]["max"] >= 2


# ---------------------------------------------------------------------------
# rollback: trim, refcounts, CoW-shared prefix pages
# ---------------------------------------------------------------------------
def test_trim_frees_tail_pages():
    a = PageAllocator(total_pages=9, page_size=4)
    pages = a.alloc("s", 5)
    assert a.trim("s", 2) == 3
    assert a.pages("s") == pages[:2]
    assert a.num_used == 2 and a.counters["trims"] == 1
    assert a.trim("s", 2) == 0          # idempotent
    assert a.trim("missing", 0) == 0    # unknown owner
    assert a.trim("s", 99) == 0         # keep beyond length
    assert a.counters["trims"] == 1     # no-ops don't count
    a.free("s")
    assert not a.check_leaks()


def test_trim_shared_pages_deref_not_destroy():
    a = PageAllocator(total_pages=9, page_size=4)
    pages = a.alloc("a", 3)
    a.share("b", pages)
    assert a.trim("a", 1) == 2
    # b still holds all three: the trimmed pages survive as b's
    assert a.pages("b") == pages
    assert all(a.refcount(p) >= 1 for p in pages)
    a.free("a")
    assert a.pages("b") == pages        # untouched by a's retirement
    a.free("b")
    assert a.num_used == 0 and not a.check_leaks()


def test_trim_to_zero_retires_owner():
    a = PageAllocator(total_pages=9, page_size=4)
    a.alloc("s", 3)
    assert a.trim("s", 0) == 3
    assert a.pages("s") == [] and a.num_used == 0
    assert not a.check_leaks()


def test_rollback_frees_rejected_pages(lm, baseline):
    # prompt of 7 puts the first verify at a page boundary (page_size 8):
    # the rejected draft's page is allocated, written, and trimmed back
    eng = make_engine(lm, speculate=True, spec_k=1, drafter=WrongDrafter())
    got = run_batch(eng, prompts=[[1, 2, 3, 4, 1, 2, 3]], max_new=20)
    snap = eng.metrics.snapshot()["models"]["llm"]
    drain(eng)
    assert got == baseline[:1]
    assert snap["counters"]["spec_rollbacks_total"] >= 1
    assert eng.alloc.counters["trims"] >= 1


def test_rollback_forks_cow_shared_prefix_page(lm):
    # a cacheable prompt publishes its pages (trailing partial page
    # refcount 2: slot + prefix cache); the first rejected verify
    # dirties positions past the confirmed length in that shared page,
    # so rollback forks it copy-on-write before truncating
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]  # 12 tokens: 8 + 4
    base = make_engine(lm)
    want = run_batch(base, prompts=[prompt], max_new=12)
    drain(base)
    eng = make_engine(lm, speculate=True, spec_k=2,
                      drafter=WrongDrafter(), prefix_cache=True)
    first = run_batch(eng, prompts=[prompt], max_new=12)
    snap = eng.metrics.snapshot()["models"]["llm"]["counters"]
    assert first == want
    assert snap["cow_forks_total"] >= 1
    # the published prefix survived the rollback: a second identical
    # prompt hits the cache and still decodes bit-identically
    second = run_batch(eng, prompts=[prompt], max_new=12)
    snap2 = eng.metrics.snapshot()["models"]["llm"]["counters"]
    assert second == want
    assert snap2["prefix_hits_total"] >= 1
    drain(eng)


# ---------------------------------------------------------------------------
# adaptive k
# ---------------------------------------------------------------------------
def test_adaptive_k_unit_converges_up_and_down():
    c = AdaptiveK(cap=4)
    assert c.current() == 1
    for _ in range(8):
        c.update(c.current(), c.current())  # full acceptance
    assert c.current() == 4
    c2 = AdaptiveK(cap=4)
    for _ in range(8):
        if c2.current():
            c2.update(c2.current(), 0)      # total rejection
    assert c2.current() == 0 and c2.disabled
    c2.update(4, 4)                          # latched: no resurrection
    assert c2.current() == 0
    c3 = AdaptiveK(cap=0)
    assert c3.current() == 0                 # cap 0 = speculation off


def test_adaptive_k_poison_latches():
    c = AdaptiveK(cap=4)
    c.poison()
    assert c.current() == 0 and c.disabled


def test_adaptive_k_engine_convergence(lm):
    # session-keyed controllers survive the park, so they are
    # observable after the turn: oracle opens to the cap, the
    # adversary latches off
    eng = make_engine(lm, speculate=True, spec_k=4,
                      drafter=OracleDrafter(lm), session_ttl_s=60)
    eng.submit([1, 2, 3, 4], max_new_tokens=32,
               session="up").result(60)
    assert eng._spec._ctl["up"].current() == 4
    drain(eng)
    eng = make_engine(lm, speculate=True, spec_k=4,
                      drafter=WrongDrafter(), session_ttl_s=60)
    eng.submit([1, 2, 3, 4], max_new_tokens=32,
               session="down").result(60)
    assert eng._spec._ctl["down"].disabled
    assert eng._spec._ctl["down"].current() == 0
    drain(eng)


# ---------------------------------------------------------------------------
# mixed batches, faults, migration
# ---------------------------------------------------------------------------
class PickyDrafter(Drafter):
    """Oracle for sequences whose context starts with an even token,
    nothing for the rest — forces a persistently mixed batch."""

    name = "picky"

    def __init__(self, lm):
        self._oracle = OracleDrafter(lm)

    def propose(self, owner, context, k):
        if int(context[0]) % 2 == 0:
            return self._oracle.propose(owner, context, k)
        return []


def test_mixed_spec_and_plain_batch(lm, baseline):
    # PROMPTS[1] and [3] start even (drafted), [0] and [2] odd (plain):
    # both halves decode in the same wide launches, both bit-identical
    eng = make_engine(lm, speculate=True, spec_k=3,
                      drafter=PickyDrafter(lm))
    got = run_batch(eng)
    st = eng.stats()["speculative"]["counters"]
    drain(eng)
    assert got == baseline
    assert st["proposals"] > 0 and st["empty_drafts"] > 0


def test_draft_fault_degrades_sequence(lm, baseline):
    eng = make_engine(lm, speculate=True, spec_k=4, drafter="ngram")
    with faults.inject("speculate.draft", "error", n=1):
        got = run_batch(eng)
    st = eng.stats()["speculative"]["counters"]
    drain(eng)
    assert got == baseline                 # completed, bit-identical
    assert st["draft_faults"] >= 1


def test_verify_fault_degrades_step_then_recovers(lm, baseline):
    eng = make_engine(lm, speculate=True, spec_k=4, drafter="ngram")
    with faults.inject("speculate.verify", "error", n=1, max_trips=1):
        got = run_batch(eng)
    st = eng.stats()["speculative"]["counters"]
    assert got == baseline
    assert st["verify_faults"] == 1
    # the injector is exhausted: fresh sequences speculate again
    run_batch(eng)
    st2 = eng.stats()["speculative"]["counters"]
    drain(eng)
    assert st2["proposals"] > st["proposals"]
    assert st2["verify_faults"] == 1


def test_migrate_mid_speculation_session(lm):
    turn1, turn2 = [1, 2, 3, 4, 1, 2, 3], [2, 3, 4]
    ref = make_engine(lm)
    r1 = ref.submit(turn1, max_new_tokens=10, session="s").result(60)
    r2 = ref.submit(turn2, max_new_tokens=10, session="s",
                    resume=True).result(60)
    drain(ref)
    a = make_engine(lm, speculate=True, spec_k=4, drafter="ngram")
    g1 = a.submit(turn1, max_new_tokens=10, session="m").result(60)
    blob = a.export_session("m")
    b = make_engine(lm, speculate=True, spec_k=4, drafter="ngram")
    assert b.import_session(blob) == "m"
    g2 = b.submit(turn2, max_new_tokens=10, session="m",
                  resume=True).result(60)
    drain(a)
    drain(b)
    assert g1["tokens"] == r1["tokens"]
    assert g2["tokens"] == r2["tokens"]


# ---------------------------------------------------------------------------
# drafter units
# ---------------------------------------------------------------------------
def test_ngram_drafter_lookup():
    d = NGramDrafter(max_ngram=3)
    # suffix [2, 3] last occurred at index 1; what followed is proposed
    assert d.propose("o", [1, 2, 3, 4, 2, 3], 2) == [4, 2]
    assert d.propose("o", [1, 2, 3, 4, 2, 3], 9) == [4, 2, 3]
    # longest n-gram wins: suffix [2, 3, 4] beats [3, 4]
    assert d.propose("o", [9, 2, 3, 4, 7, 2, 3, 4], 1) == [7]
    assert d.propose("o", [1, 2, 3], 4) == []   # no self-match
    assert d.stats()["misses"] == 1


def test_draft_model_drafter_matches_its_own_greedy(lm, draft_lm):
    d = DraftModelDrafter(draft_lm, page_size=8)
    ctx = [1, 2, 3, 4, 5]

    def oracle(context, k):
        toks = list(context)
        params, cfg = draft_lm.jax_params(), draft_lm.config
        out = []
        for _ in range(k):
            logits = decoder.full_forward(
                params, cfg, jnp.asarray([toks], jnp.int32))
            t = int(jnp.argmax(logits[0, -1]))
            out.append(t)
            toks.append(t)
        return out

    first = d.propose("o", ctx, 3)
    assert first == oracle(ctx, 3)
    # accepted continuation: the incremental cache path must agree with
    # a from-scratch forward over the longer context
    ctx2 = ctx + first[:2]
    assert d.propose("o", ctx2, 3) == oracle(ctx2, 3)
    # a context shorter than the cache (target rolled back) resets
    assert d.propose("o", ctx[:3], 2) == oracle(ctx[:3], 2)
    d.release("o")
    assert d.alloc.num_used == 0
    assert not d.alloc.check_leaks()


def test_scheduler_releases_drafter_state(lm, draft_lm):
    eng = make_engine(lm, speculate=True, spec_k=2,
                      draft_model=draft_lm)
    run_batch(eng)
    # every finished sequence's draft cache was released with its pages
    assert eng._spec.drafter.alloc.num_used == 0
    drain(eng)


# ---------------------------------------------------------------------------
# launch census: static, acceptance-independent
# ---------------------------------------------------------------------------
def test_verify_launch_census_static(lm):
    cfg, params = lm.config, lm.jax_params()
    pps = pages_for(64, 8)
    a = decoder.verify_launch_stats(params, cfg, 8, 5, 4, pps, 33)
    b = decoder.verify_launch_stats(params, cfg, 8, 5, 4, pps, 33)
    assert a == b                       # trace-time census: deterministic
    assert a["width"] == 5 and a["launches_per_step"] >= 1
    # the whole point: one launch amortized over up to W emitted tokens
    # beats the per-token decode step's launch bill
    plain = decoder.decode_launch_stats(params, cfg, 8, 4, pps, 33)
    assert a["launches_per_emitted_token"] < plain["launches_per_step"]


def test_engine_verify_launch_count_independent_of_acceptance(lm):
    # same geometry, opposite acceptance extremes: the compiled verify
    # program (and so its launch count) is identical — acceptance only
    # changes which outputs are KEPT, never what is dispatched
    cfg = lm.config
    key_before = decoder.fn_cache_stats()["compiles"]
    fn1 = decoder.make_verify_step(cfg, 8, 3)
    fn2 = decoder.make_verify_step(cfg, 8, 3)
    assert fn1 is fn2                   # one program per (cfg, S, W)
    assert decoder.fn_cache_stats()["compiles"] <= key_before + 1


# ---------------------------------------------------------------------------
# metrics surfaces
# ---------------------------------------------------------------------------
def test_speculative_metrics_surfaces(lm):
    eng = make_engine(lm, speculate=True, spec_k=4,
                      drafter=OracleDrafter(lm))
    run_batch(eng)
    snap = eng.metrics.snapshot()["models"]["llm"]
    gen, ctr = snap["generate"], snap["counters"]
    assert ctr["spec_draft_tokens_total"] > 0
    assert (ctr["spec_accepted_tokens_total"]
            <= ctr["spec_draft_tokens_total"])
    assert ctr["spec_verify_steps_total"] > 0
    spec = gen["speculative"]
    assert 0.0 <= spec["accepted_token_rate"] <= 1.0
    assert spec["verify_step"]["count"] == ctr["spec_verify_steps_total"]
    assert spec["draft_step"]["count"] > 0
    assert gen["tokens_per_step"]["count"] > 0

    # Prometheus text carries the new counters, histograms and the
    # acceptance gauge (rendered off any object with a .metrics)
    class _Host:
        metrics = eng.metrics
    text = serving.server.ModelServer._prometheus_text(_Host())
    drain(eng)
    assert "mxtpu_serving_spec_draft_tokens_total" in text
    assert "mxtpu_serving_accepted_token_rate" in text
    assert "mxtpu_serving_spec_verify_step_p50" in text
    assert "mxtpu_serving_tokens_per_step_p50" in text


def test_tokens_per_step_feeds_throughput_ema(lm):
    m = ServingMetrics()
    # one step, four tokens: the EMA must credit all four, and the
    # tokens-per-step histogram must see the multi-token step
    m.observe_decode_step("x", 0.01, 4, 4)
    snap = m.snapshot()["models"]["x"]["generate"]
    assert snap["tokens_per_s"] == pytest.approx(400.0, rel=0.01)
    assert snap["tokens_per_step"]["max"] == 4
