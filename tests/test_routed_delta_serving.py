"""A model that routes over delta-rule layers on the serving path (the
``solar_open2`` block: `models/hybrid.py` with the routed layer of
`models/routed.py`), as one chip of an expert-parallel group holds it: the
block against the plain reference the benchmark keeps
(`chipbench/solar_ref.py`, the only copy, imported), the chunk-parallel delta
rule against the token recurrence, the shares of the group adding up to the
uncut layer, the step programs through the paged cache with the delta state
paged beside the KV rows, the engine around them, what it refuses, the
counters, the configuration against the catalog, and the benchmark's counts.

Tiny sizes on the CPU: 4 layers (attention, then three delta-rule layers),
32 wide, 4 heads of 16 on 2 KV heads, 4 delta heads of 8, 16 routed experts
of width 24 of which a share holds 4, 3 a token, vocabulary 96.  No number
here is a measurement of the chip."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import solar_flops_bytes, solar_ref  # noqa: E402
from chipbench import run as harness  # noqa: E402

from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.models import decoder, hybrid, routed  # noqa: E402
from mxnet_tpu.parallel.shardcfg import ShardingConfig  # noqa: E402
from mxnet_tpu.serving import generate  # noqa: E402

pytestmark = pytest.mark.llm

VOCAB = 96
CELL = "solar_open2_ep8_chat_closed"
CONFIG = "solar-open2-ep8-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KW = dict(vocab_size=VOCAB, num_layers=4, units=32, num_heads=4,
          num_kv_heads=2, head_dim=16, attention_layers=[0, 4, 8],
          linear_attn={"num_heads": 4, "head_dim": 8,
                       "short_conv_kernel_size": 4, "num_kv_heads": None},
          experts_held=4, expert_shares=4, experts_per_token=3,
          expert_hidden=24, max_length=128)


@pytest.fixture(scope="module")
def lm():
    return decoder.routed_delta_lm(seed=1, dtype="float32", **KW)


@pytest.fixture(scope="module")
def lm_bf16():
    return decoder.routed_delta_lm(seed=1, **KW)


def ids(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, size=n).tolist()


def reference(lm, fed, n_rows, **kw):
    return np.asarray(solar_ref.reference_logits(
        lm.jax_params(), lm.config, fed, n_rows, **kw))


def ref_greedy(lm, prompt, n):
    fed, out = list(prompt), []
    for _ in range(n):
        out.append(int(reference(lm, fed, 1, pad_to=64)[0].argmax()))
        fed.append(out[-1])
    return out


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------
def test_layer_table_and_config(lm):
    cfg = lm.config
    assert cfg.layer_kinds == ("attention",) + ("delta_rule",) * 3
    assert hybrid.layer_runs(cfg) == [("attention", 0, 1),
                                      ("delta_rule", 1, 4)]
    assert decoder.is_hybrid(cfg) and hybrid.recurrent_layers(cfg) == 3
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token) == (
        16, (0, 4), 3)
    assert cfg.attn_gate and not cfg.tied_head
    share2 = decoder.routed_delta_lm(seed=1, dtype="float32",
                                     **dict(KW, expert_share=2)).config
    assert share2.experts_held == (8, 4)
    # the share is part of the program cache's key
    assert (decoder.make_decode_step(cfg, 8)
            is not decoder.make_decode_step(share2, 8))
    # the state-space block's config is what it was: the new fields default
    jamba = decoder.hybrid_lm(seed=0).config
    assert (jamba.n_experts, jamba.attn_gate, jamba.tied_head) == (
        0, False, True)


@pytest.mark.parametrize("what, kw", [
    ("use_rope", {"rope": True}),
    ("first_k_dense_replace", {"dense_layers": 1}),
    ("kda_use_full_proj", {"full_proj": True}),
    ("kda_allow_neg_eigval", {"neg_eigval": False}),
])
def test_builder_refuses_what_the_block_has_no_code_for(what, kw):
    with pytest.raises(ValueError, match=what):
        decoder.routed_delta_lm(seed=0, **dict(KW, **kw))


@pytest.mark.parametrize("length", [5, 21, 37])
def test_forward_matches_the_reference(lm, length):
    toks = ids(length, length)
    got = lm.forward(jnp.asarray([toks], jnp.int32)).asnumpy()[0]
    assert np.abs(got - reference(lm, toks, length)).max() < 1e-5


def test_forward_of_a_batch_routes_every_sequence_by_itself(lm):
    rows = [ids(3, 9), ids(4, 9)]
    got = lm.forward(jnp.asarray(rows, jnp.int32)).asnumpy()
    for row, g in zip(rows, got):
        assert np.abs(g - reference(lm, row, 9)).max() < 1e-5


@pytest.mark.parametrize("T", [16, 48, 256])
def test_chunked_delta_rule_matches_the_token_recurrence(T):
    """Blocks of tokens solved together against one token at a time, with
    ``beta`` up to 2 (negative eigenvalues) and per-channel decay from none
    to e^-8 a token: in the chunked form every exponent is a difference
    that is never positive, so nothing overflows."""
    rng = np.random.default_rng(T)
    H, d = 3, 8
    q, k, v = (rng.normal(size=(T, H, d)).astype(np.float32)
               for _ in range(3))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d)
    g = -np.exp(rng.uniform(-6, np.log(8.0), size=(T, H, d))).astype(
        np.float32)
    beta = rng.uniform(0, 2, size=(T, H)).astype(np.float32)
    beta[::5] = 2.0
    S = S0 = rng.normal(size=(H, d, d)).astype(np.float32)
    marks = np.array([0, T // 2 + 3, T - 1])
    outs, states = [], []
    for t in range(T):
        S = np.exp(g[t])[:, :, None] * S
        seen = np.einsum("hkv,hk->hv", S, k[t])
        S = S + beta[t][:, None, None] * k[t][:, :, None] * (
            v[t] - seen)[:, None, :]
        outs.append(np.einsum("hkv,hk->hv", S, q[t]))
        states.append(S)
    o, marked = hybrid.delta_rule_chunk(*map(jnp.asarray, (
        q, k, v, g, beta, S0, marks)))
    assert np.isfinite(np.asarray(o)).all()
    assert np.abs(np.asarray(o) - np.stack(outs)).max() < 2e-5
    assert np.abs(np.asarray(marked) - np.stack(states)[marks]).max() < 2e-5


def test_a_token_without_decay_and_beta_leaves_the_state():
    rng = np.random.default_rng(0)
    T, H, d = 16, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(T, H, d)), jnp.float32)
               for _ in range(3))
    S0 = jnp.asarray(rng.normal(size=(H, d, d)), jnp.float32)
    _, marked = hybrid.delta_rule_chunk(
        q, k, v, jnp.zeros((T, H, d)), jnp.zeros((T, H)), S0,
        jnp.array([3, T - 1]))
    assert np.abs(np.asarray(marked) - np.asarray(S0)).max() == 0.0


# ---------------------------------------------------------------------------
# the shares of the expert-parallel group
# ---------------------------------------------------------------------------
def share_of(lm, i, count):
    """The model ``lm`` (which holds every expert) cut to share ``i``: the
    config and every run's leaves with experts [i * count, (i + 1) * count)."""
    cfg = lm.config._replace(experts_held=(i * count, count))
    params = dict(lm.jax_params())
    params["runs"] = [dict(run, **{
        k: run[k][:, i * count:(i + 1) * count]
        for k in routed.EXPERT_LEAVES}) for run in params["runs"]]
    return cfg, params


@pytest.fixture(scope="module")
def uncut():
    """One layer's worth of every kind, all 16 experts held."""
    return {kind: decoder.routed_delta_lm(seed=5, dtype="float32", **dict(
        KW, num_layers=1, attention_layers=layers, experts_held=16,
        expert_shares=1)) for kind, layers in (("attention", [0]),
                                               ("delta_rule", []))}


def test_eight_shares_of_the_routed_layer_add_up_to_the_uncut_reference(
        uncut):
    """What the program computes for each of eight shares of a layer's
    experts, the shared expert counted once, is what the plain reference
    gives for the layer with every expert."""
    lm = uncut["attention"]
    cfg, run = lm.config, lm.jax_params()["runs"][0]
    lp = {k: v[0] for k, v in run.items()}
    u = jnp.asarray(np.random.default_rng(2).normal(size=(23, cfg.units)),
                    jnp.float32)
    whole = np.asarray(solar_ref.reference_moe(lp, cfg, u))
    shared = np.asarray(hybrid._mm(
        jax.nn.silu(hybrid._mm(u, lp["ws_gate"]))
        * hybrid._mm(u, lp["ws_up"]), lp["ws_down"]))
    total, pairs = shared.copy(), 0
    for i in range(8):
        cfg_i, params_i = share_of(lm, i, 2)
        run_i = params_i["runs"][0]
        part, counts = routed.routed_feed_forward(
            u, lp, {k: run_i[k] for k in routed.EXPERT_LEAVES}, 0, cfg_i,
            jnp.ones(23, bool))
        total += np.asarray(part) - shared
        pairs += int(counts[0])
        # a share's own result is the reference's for that share
        assert np.abs(np.asarray(part) - np.asarray(solar_ref.reference_moe(
            dict(lp, **{k: run_i[k][0] for k in routed.EXPERT_LEAVES}),
            cfg_i, u))).max() < 1e-5
    assert pairs == 23 * cfg.experts_per_token  # every pair on one share
    assert np.abs(total - whole).max() < 1e-5
    assert np.abs(whole - shared).max() > 1e-3  # the experts add something


@pytest.mark.parametrize("kind", ["attention", "delta_rule"])
def test_eight_shares_of_a_whole_layer_add_up_to_the_uncut_layer(uncut,
                                                                  kind):
    """The layer's result on each of eight chips (mixer, shared expert and
    that chip's experts) with the mixer and the shared expert counted once
    is the uncut layer's; and the program's logits for a share are the
    reference's for that share."""
    lm = uncut[kind]
    toks = ids(11, 19)
    whole = np.asarray(solar_ref.reference_stream(lm.jax_params(), lm.config,
                                                  toks))
    none_cfg, none_params = share_of(lm, 0, 0)     # mixer + shared expert
    alike = np.asarray(solar_ref.reference_stream(none_params, none_cfg,
                                                  toks))
    total = alike.copy()
    for i in range(8):
        cfg_i, params_i = share_of(lm, i, 2)
        total += np.asarray(solar_ref.reference_stream(
            params_i, cfg_i, toks)) - alike
        got = np.asarray(hybrid.full_forward(
            params_i, cfg_i, jnp.asarray([toks], jnp.int32)))[0]
        ref = np.asarray(solar_ref.reference_logits(params_i, cfg_i, toks,
                                                    len(toks)))
        assert np.abs(got - ref).max() < 1e-5
    assert np.abs(total - whole).max() < 1e-5
    assert np.abs(whole - alike).max() > 1e-3


def test_every_token_on_one_held_expert_is_not_dropped(lm):
    """Dropless: a selection bias that sends every token to the same three
    held experts; every pair is computed (the reference has no capacity to
    run out of) and the fullest expert's pairs are the launch's tokens."""
    cfg, params = lm.config, lm.jax_params()
    bias = np.zeros(cfg.n_experts, np.float32)
    bias[[0, 1, 3]] = 10.0
    params = dict(params, runs=[dict(run, router_bias=jnp.broadcast_to(
        jnp.asarray(bias), run["router_bias"].shape))
        for run in params["runs"]])
    toks = ids(8, 24)
    got = np.asarray(hybrid.full_forward(params, cfg,
                                         jnp.asarray([toks], jnp.int32)))[0]
    ref = np.asarray(solar_ref.reference_logits(params, cfg, toks, 24))
    assert np.abs(got - ref).max() < 1e-5
    run = params["runs"][0]
    lp = {k: v[0] for k, v in run.items() if k not in routed.EXPERT_LEAVES}
    u = jnp.asarray(np.random.default_rng(1).normal(size=(24, cfg.units)),
                    jnp.float32)
    _, counts = routed.routed_feed_forward(
        u, lp, {k: run[k] for k in routed.EXPERT_LEAVES}, 0, cfg,
        jnp.arange(24) < 20)                # four tokens are nobody's
    assert dict(zip(routed.COUNTS, map(int, counts))) == {
        "pairs": 60, "pairs_elsewhere": 0, "experts_hit": 3,
        "pairs_fullest": 20, "layer_launches": 1}


# ---------------------------------------------------------------------------
# the step programs through the paged cache
# ---------------------------------------------------------------------------
def drive(lm, S, chunk, prompts, n_decode, slots, first=None, pools=None,
          rows=None):
    """As tests/test_hybrid_serving.py:drive: prefill each prompt chunk by
    chunk into its slot's page row, then ``n_decode`` greedy steps of all of
    them in one batch whose other lanes are inactive."""
    cfg, params = lm.config, lm.jax_params()
    B, pps = max(slots) + 2, 8
    prefill = decoder.make_prefill_chunk(cfg, S, chunk)
    decode = decoder.make_decode_step(cfg, S)
    total = B * pps + 1
    kp, vp = pools or [decoder.fresh_pool(cfg, total, S) for _ in range(2)]
    tables = np.zeros((B, pps), np.int32)
    fed, logits, toks = {}, {}, {}
    for prompt, slot in zip(prompts, slots):
        tables[slot] = (np.arange(1 + slot * pps, 1 + (slot + 1) * pps)
                        if rows is None else rows[slot])
        lo = 0
        while lo < len(prompt):
            n = min(chunk, len(prompt) - lo, (first or chunk) if lo == 0
                    else chunk)
            padded = np.zeros(chunk, np.int32)
            padded[:n] = prompt[lo:lo + n]
            kp, vp, tok, last = prefill(
                params, kp, vp, jnp.asarray(padded), jnp.int32(lo),
                jnp.int32(n), jnp.asarray(tables[slot]))
            lo += n
        fed[slot], logits[slot], toks[slot] = (
            list(prompt), [np.asarray(last)], int(tok))
    active = np.zeros(B, bool)
    active[list(slots)] = True
    for _ in range(n_decode):
        tokens, positions = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for slot in slots:
            tokens[slot], positions[slot] = toks[slot], len(fed[slot])
            fed[slot].append(toks[slot])
        kp, vp, nxt, lg = decode(
            params, kp, vp, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(tables), jnp.asarray(active))
        for slot in slots:
            logits[slot].append(np.asarray(lg)[slot])
            toks[slot] = int(np.asarray(nxt)[slot])
    return [(fed[s], np.stack(logits[s])) for s in slots], (kp, vp)


def worst(lm, runs, **kw):
    return max(np.abs(got - reference(lm, fed, got.shape[0], **kw)).max()
               for fed, got in runs)


@pytest.mark.parametrize("name, S, chunk, lengths, slots, first", [
    ("ends_inside_a_page", 8, 8, [13], [0], None),
    ("ends_on_a_page_boundary", 8, 8, [16], [1], None),
    ("decode_crosses_a_page", 8, 8, [7], [0], None),
    ("across_a_chunk_boundary", 8, 16, [21], [0], None),
    ("unaligned_pos0", 8, 8, [21], [0], 5),
    ("chunk_over_three_pages", 4, 8, [19], [0], 3),
    ("lanes_of_different_lengths", 8, 8, [3, 9, 16, 22], [0, 2, 3, 5], None),
])
def test_paged_programs_match_the_reference(lm, name, S, chunk, lengths,
                                            slots, first):
    prompts = [ids(100 + n, n) for n in lengths]
    runs, _ = drive(lm, S, chunk, prompts, 6, slots, first=first)
    assert worst(lm, runs) < 1e-5


def test_a_reused_page_and_slot_start_from_the_zero_state(lm):
    S, chunk = 8, 8
    _, pools = drive(lm, S, chunk, [ids(1, 19)], 5, [1])
    runs, pools = drive(lm, S, chunk, [ids(2, 11)], 5, [1], pools=pools)
    assert worst(lm, runs) < 1e-5
    rows = {0: np.array([11, 10, 9, 14, 13, 12, 16, 15], np.int32)}
    runs, _ = drive(lm, S, chunk, [ids(3, 14)], 5, [0], pools=pools,
                    rows=rows)
    assert worst(lm, runs) < 1e-5


def test_pools_are_what_fresh_pool_says_and_count_by_program(lm):
    cfg = lm.config
    kp, vp = (decoder.fresh_pool(cfg, 9, 4) for _ in range(2))
    assert isinstance(kp, hybrid.HybridPool)
    # one attention layer's rows; three layers' S, half of the heads a
    # pool, a head's rows together; the three convolutions' 3 inputs flat
    assert [a.shape for a in jax.tree.leaves(kp)] == [
        a.shape for a in jax.tree.leaves(vp)] == [
        (1, 9, 4, 32), (3, 9, 2 * 8, 8), (3, 9, 9 * 16), (5,)]
    assert kp.counts.dtype == jnp.uint32
    assert hybrid.state_entry_bytes(cfg) == 3 * (4 * 8 * 8 + 9 * 32) * 4 \
        == sum(a[:, 0].nbytes for p in (kp, vp) for a in (p.ssm, p.conv))
    # the state-space block's pools have no such leaf
    assert decoder.fresh_pool(decoder.hybrid_lm(seed=0).config, 9,
                              4).counts is None
    # fork_page takes a page's state entries along and leaves the counts
    kp = kp._replace(ssm=kp.ssm.at[:, 3].set(1.5), counts=kp.counts + 7)
    forked = decoder.fork_page(kp, 3, 5)
    assert float(forked.ssm[2, 5, 7, 3]) == 1.5
    assert forked.counts.tolist() == [7] * 5
    # the prefill chunk counts into the K pool, the decode step into the V
    _, (kp, vp) = drive(lm, 8, 8, [ids(9, 21)], 6, [0])
    k, v = (dict(zip(routed.COUNTS, map(int, p.counts))) for p in (kp, vp))
    assert k["layer_launches"] == 3 * 4 and v["layer_launches"] == 6 * 4
    assert k["pairs"] + k["pairs_elsewhere"] == 21 * 3 * 4
    assert v["pairs"] + v["pairs_elsewhere"] == 6 * 3 * 4   # one live lane
    assert 0 < k["pairs"] < 21 * 3 * 4
    assert k["pairs_fullest"] <= k["pairs"] and k["experts_hit"] <= 12 * 4


def test_a_large_state_is_read_and_written_in_pieces():
    """At the published widths a page's state is 2 MB a pool; many pages at
    once are gathered in blocks of 512 KB (`hybrid._pieces` says why), one
    page is a slice, and the state-space block's entries are not cut."""
    ssm = jax.ShapeDtypeStruct((3, 5, 4096, 128), jnp.float32)
    pages = np.array([1, 4])
    assert hybrid._pieces(ssm, pages) == 4 and hybrid._pieces(ssm, 2) == 1
    assert hybrid._pieces(jax.ShapeDtypeStruct((26, 5, 16, 2560),
                                               jnp.float32), pages) == 1
    # the convolution inputs' flat rows are cut where the pool is made
    assert hybrid._conv_parts(36864) == 2 and hybrid._conv_parts(7680) == 1
    wide = decoder.routed_delta_lm(seed=0, **dict(KW, linear_attn={
        "num_heads": 64, "head_dim": 128, "short_conv_kernel_size": 4},
        experts_held=1, expert_shares=4)).config
    pool = jax.eval_shape(lambda: decoder.fresh_pool(wide, 3, 4))
    assert [c.shape for c in pool.conv] == [(3, 3, 18432)] * 2
    assert pool.ssm.shape == (3, 3, 4096, 128)
    a = jnp.arange(2 * 5 * 8 * 4, dtype=jnp.float32).reshape(2, 5, 8, 4)

    def cut(_a, page):
        return 1 if jnp.ndim(page) == 0 else 4
    real, hybrid._pieces = hybrid._pieces, cut
    try:
        got = hybrid._take_pages(a, 1, jnp.asarray(pages))
        put = hybrid._put_pages(a, 1, jnp.asarray(pages), -got)
    finally:
        hybrid._pieces = real
    assert np.array_equal(got, a[1, pages])
    assert np.array_equal(put, a.at[1, pages].set(-a[1, pages]))


def test_bf16_weights_agree_within_a_tolerance_the_int8_control_exceeds(
        lm_bf16):
    """The engine's programs on bfloat16 weights against the reference on
    the same weights, in units of the reference's standard deviation and by
    the rows' 90th percentile, as the benchmark compares a model that
    routes: the program under the limit, the 8-bit controls above it."""
    runs, _ = drive(lm_bf16, 8, 8, [ids(7, 21)], 20, [0])
    fed, got = runs[0]
    ref = reference(lm_bf16, fed, got.shape[0])

    def q90(x):
        return float(np.percentile(np.abs(x - ref).max(-1) / ref.std(), 90))
    controls = {d: q90(reference(lm_bf16, fed, got.shape[0], dtype=d))
                for d in ("bfloat16", "int8", "float8_e4m3fn")}
    limit = 0.02
    assert q90(got) < limit < controls["int8"] < controls["float8_e4m3fn"], (
        q90(got), controls)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
def make_engine(lm, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("page_size", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_ctx", 40)
    return serving.DecodeEngine(lm, **kw)


def counters(engine):
    return engine.metrics.snapshot()["models"][engine.name]["counters"]


@pytest.mark.parametrize("async_decode", [False, True])
def test_engine_greedy_equals_the_reference_under_preemption(lm,
                                                             async_decode):
    prompts = [ids(40 + n, n) for n in (5, 8, 13, 16, 21, 3)]
    engine = make_engine(lm, total_pages=14, async_decode=async_decode)
    try:
        futs = [engine.submit(p, max_new_tokens=9) for p in prompts]
        got = [f.result(300)["tokens"] for f in futs]
        assert counters(engine)["preemptions_total"] > 0
        assert got == [ref_greedy(lm, p, 9) for p in prompts]
        assert engine.alloc.check_leaks() == len(engine.prefix_cache or ())
    finally:
        engine.stop()


def test_prefix_hit_on_whole_pages_brings_the_delta_state(lm):
    engine = make_engine(lm, prefix_cache=True)
    try:
        first = ids(5, 11)                  # two whole pages and 3 tokens
        a = engine.submit(first, max_new_tokens=4).result(300)["tokens"]
        assert len(engine.prefix_cache) == 2
        second = first[:9] + ids(6, 5)      # shares 9 tokens: 8 are covered
        b = engine.submit(second, max_new_tokens=4).result(300)["tokens"]
        c = counters(engine)
        assert c["prefix_hits_total"] == 1
        assert c["prefix_tokens_saved_total"] == 8
        assert c["state_prefix_pages_shared_total"] == 2
        assert c["cow_forks_total"] == 0
        assert (a, b) == (ref_greedy(lm, first, 4), ref_greedy(lm, second, 4))
    finally:
        engine.stop()


def test_sessions_continue_from_the_parked_state(lm):
    engine = make_engine(lm)
    try:
        p1, p2 = ids(8, 6), ids(9, 3)
        a = engine.submit(p1, max_new_tokens=3, session="s").result(300)
        b = engine.submit(p2, max_new_tokens=3, session="s",
                          resume=True).result(300)
        assert b["tokens"] == ref_greedy(lm, p1 + a["tokens"] + p2, 3)
    finally:
        engine.stop()


def test_counters_and_stats_read_what_a_scripted_run_implies(lm, monkeypatch):
    spans = []
    real = generate.span

    def spy(name, **args):
        spans.append((name, args))
        return real(name, **args)
    monkeypatch.setattr(generate, "span", spy)
    engine = make_engine(lm, total_pages=31)
    try:
        # 11 tokens: a chunk of 8 over pages 0-1 from the zero state, then
        # one of 3 into page 2; 3 answers: the prefill's and two decode steps
        engine.submit(ids(12, 11), max_new_tokens=3).result(300)
        stats = engine.stats()
        c = counters(engine)
        assert c["state_entries_written_total"] == 3
        assert c["state_starts_total"] == 1
        launches = [a for n, a in spans if n == "engine.prefill_launch"]
        assert [(a["pos"], a["tokens"], a["state_pages"], a["expert_pairs"])
                for a in launches] == [(0, 8, 2, 8 * 12), (8, 3, 1, 3 * 12)]
        steps = [a for n, a in spans if n == "engine.decode_launch"]
        assert steps and all(a["expert_pairs"] == a["lanes"] * 12
                             for a in steps)
        entry = 3 * (4 * 8 * 8 + 9 * 32) * 4
        assert stats["state"] == {"layers": 3, "entry_bytes": entry,
                                  "pool_bytes": entry * 31,
                                  "pages_with_state_peak": 4,
                                  "bytes_prefill_total": 4 * entry,
                                  "bytes_decode_total": 2 * 2 * entry}
        assert [a["state_bytes"] for a in launches] == [2 * entry, 2 * entry]
        assert all(a["state_bytes"] == 2 * entry * a["lanes"] for a in steps)
        assert stats["kv"]["pool_bytes"] == 30 * (entry + 2 * 1 * 32 * 4 * 4)
        ex = stats["experts"]
        assert (ex["held"], ex["of"], ex["per_token"]) == (4, 16, 3)
        pre, dec = ex["prefill"], ex["decode"]
        assert pre["layer_launches"] == 2 * 4
        assert pre["pairs"] + pre["pairs_elsewhere"] == 11 * 12
        assert dec["layer_launches"] == len(steps) * 4
        assert dec["pairs"] + dec["pairs_elsewhere"] == len(steps) * 12
        assert pre["pairs_per_launch"] == pre["pairs"] / 8
        assert pre["experts_hit_per_launch"] <= 4
        assert 1.0 <= ex["load_max_over_mean"] <= 4.0
        assert ex["pairs"] == pre["pairs"] + dec["pairs"]
        # the same numbers are the metrics' counters, folded in at the read
        assert c["expert_pairs_total"] == ex["pairs"]
        assert c["expert_pairs_elsewhere_total"] == ex["pairs_elsewhere"]
        assert c["experts_hit_total"] == ex["experts_hit"]
        assert c["expert_pairs_fullest_total"] == ex["pairs_fullest"]
        # a second read adds nothing
        assert engine.stats()["experts"]["pairs"] == ex["pairs"]
        assert counters(engine)["expert_pairs_total"] == ex["pairs"]
    finally:
        engine.stop()
    assert "experts" not in make_engine(decoder.hybrid_lm(
        seed=0, dtype="float32")).stats()


def tp2():
    return ShardingConfig.for_transformer(mesh_shape=(4, 2),
                                          axis_names=("dp", "tp"))


@pytest.mark.parametrize("what, kwargs", [
    ("speculative decoding", {"speculate": True}),
    ("a tp sharding", {"sharding": tp2}),
    ("an int8 KV pool", {"kv_dtype": "int8"}),
    ("weight quantisation", {"quantize": "int8"}),
    ("session migration", {"migrate": True, "pagestore": "127.0.0.1:1"}),
    ("role 'prefill'", {"role": "prefill"}),
    ("role 'decode'", {"role": "decode"}),
])
def test_engine_refuses_by_name(lm, what, kwargs):
    kwargs = {k: v() if callable(v) else v for k, v in kwargs.items()}
    with pytest.raises(ValueError, match=what + ".*delta-rule layers"):
        make_engine(lm, **kwargs)


@pytest.mark.parametrize("build", [
    lambda cfg: decoder.make_verify_step(cfg, 4, 3),
    lambda cfg: decoder.make_decode_step(cfg, 4, kv_dtype="int8"),
    lambda cfg: decoder.make_prefill_chunk(cfg, 4, 8, quant=("int8",)),
    lambda cfg: decoder.make_decode_step(cfg, 4, sharding=tp2()),
], ids=["verify", "int8_kv", "quant", "tp"])
def test_program_factories_refuse_by_name(lm, build):
    with pytest.raises(ValueError, match="delta-rule layers"):
        build(lm.config)


def test_session_export_and_import_are_refused(lm):
    engine = make_engine(lm)
    try:
        engine.submit(ids(1, 5), max_new_tokens=2, session="s").result(300)
        with pytest.raises(ValueError, match="session export.*delta-rule"):
            engine.export_session("s")
        with pytest.raises(ValueError, match="session import.*delta-rule"):
            engine.import_session(b"")
    finally:
        engine.stop()


def test_parallel_moe_is_not_what_routes_here():
    """`parallel/moe.py` (top-1, capacity, dropping) stands alone: the
    serving path's routed layer imports nothing of it."""
    for module in (routed, hybrid, decoder, generate):
        with open(module.__file__) as f:
            source = f.read()
        assert "parallel.moe" not in source and "import moe" not in source


# ---------------------------------------------------------------------------
# the configuration, the benchmark's counts and the cell's rehearsal
# ---------------------------------------------------------------------------
def test_configuration_has_the_catalogs_keys_and_states_its_cuts():
    c = harness.load("configs", CONFIG + ".json")
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    # `reduced` against the file's own statement of the published values
    assert set(c["published"]) == set(c["reduced"])
    assert c["published"] == {"num_hidden_layers": 48,
                              "n_routed_experts": 320, "vocab_size": 196608}
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (4, 40, 24576)
    assert c["n_routed_experts"] * c["expert_parallel"] == 320
    assert c["vocab_size"] * c["expert_parallel"] == 196608
    assert "8 chips share each layer" in c["deployment"]
    # the floors of a cut: a whole period, 8 experts, an eighth of the rows
    assert c["num_hidden_layers"] >= c["gqa_interval"] + 1
    assert c["n_routed_experts"] >= 8
    for key in ("check.limits", "check.hard_choice", "kda", "use_gqa_gate",
                "router", "shared_expert", "intermediate_size", "weights",
                "max_length"):
        assert isinstance(c["assumed"].get(key), str), key
    assert set(c["check"]["limits"]) >= {"program_logits_q90_err",
                                         "served_gap_max"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(e for e in bench["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:       # every other published key as published, groups whole
            assert c[key] == value, key


def test_flops_and_bytes_at_the_published_sizes():
    """ISSUE 35's arithmetic: the chip's share and the whole model."""
    c = harness.load("configs", CONFIG + ".json")
    p = solar_flops_bytes.param_counts(c)
    assert p["expert"] == 15728640 and p["experts_held"] == 4 * 629145600
    assert p["attention_mixer"] == 109051904
    assert p["delta_mixer"] == 137732288
    assert p["attention_layer"] == 126099776
    assert p["delta_layer"] == 154780160
    assert p["embedding"] == 2 * 24576 * 4096 + 4096
    assert p["total"] == 3308353344
    assert solar_flops_bytes.published_param_count(c) == 250287810304
    assert solar_flops_bytes.kv_bytes_per_token(c) == 8192     # float32
    assert solar_flops_bytes.kv_bytes_per_token(
        dict(c, kv_cache_dtype="bfloat16")) == 4096
    assert solar_flops_bytes.state_entry_bytes(c) == 3 * 4489216
    # 27 lanes of 300 tokens whose tokens hit 20 of a layer's 40 experts
    need = solar_flops_bytes.launch_bytes(c, 4 * 20, 27 * 300, 2 * 27)
    assert need == 2 * (p["layers_matmul"] + 24576 * 4096
                        + 80 * 15728640) + 27 * 300 * 8192 \
        + 54 * 3 * 4489216
    assert 4.6e9 < need < 4.8e9
    facts = {"config": c, "peaks": {"flops_bf16": 197e12,
                                    "hbm_bytes_per_s": 819e9},
             "trace": {"modules": {"jit_step": [need / 819e9 * 2],
                                   "jit_prefill": [0.02]}},
             "stats": {"serving": {"generate": {"decode_occupancy": 27 / 48},
                                   "counters": {
                                       "prefill_launches_total": 10,
                                       "prefill_tokens_total": 2000}},
                       "engine": {"experts": {
                           "decode": {"experts_hit_per_launch": 20.0},
                           "prefill": {"experts_hit_per_launch": 39.0,
                                       "pairs_per_launch": 200.0}}}},
             "end_to_end": {"live_tokens_mean": 27 * 300}}
    assert solar_flops_bytes.decode_step_roofline(facts) == pytest.approx(50.0)
    share = solar_flops_bytes.prefill_launch_roofline(facts)
    bytes_s = solar_flops_bytes.launch_bytes(c, 4 * 39, 200, 2) / 819e9
    flops_s = solar_flops_bytes.prefill_launch_flops(c, 200, 800) / 197e12
    assert share == pytest.approx(100 * max(bytes_s, flops_s) / 0.02)
    assert bytes_s > flops_s        # a chunk streams the held experts
    assert solar_flops_bytes.decode_step_roofline({}) is None
    assert solar_flops_bytes.prefill_launch_roofline({}) is None
    # the model the builder makes from the file has the table's parameters
    kw = {k: c[v] for k, v in c["builder_kwargs"].items()}
    net = hybrid.HybridLM(
        vocab_size=kw["vocab_size"], num_layers=kw["num_layers"],
        units=kw["units"], num_heads=kw["num_heads"],
        num_kv_heads=kw["num_kv_heads"], head_dim=kw["head_dim"],
        attention_layers=[0], recurrent=hybrid.DELTA, attn_gate=True,
        tied_head=False, delta_heads=64, delta_head_dim=128, delta_rank=128,
        n_experts=320, experts_held=40, experts_per_token=8,
        expert_hidden=1280, shared_hidden=1280)
    cfg = net.config
    count = 2 * 24576 * 4096 + 4096
    for kind, lo, hi in hybrid.layer_runs(cfg):
        count += (hi - lo) * sum(int(np.prod(s)) for s in
                                 hybrid._run_shapes(cfg, kind).values())
    assert count == p["total"]
    assert hybrid.state_entry_bytes(cfg) == 3 * 4489216
    engine = c["engine"]
    pages = engine["slots"] * engine["max_ctx"] // engine["page_size"] + 1
    assert pages == 193
    pools = pages * (3 * 4489216 + 256 * 8192)
    assert round((2 * p["total"] + pools) / 1e9, 2) == 9.62
    # the cache dtype the builder hands the model is the engine's, so the
    # check drives the programs the engine runs
    assert c["kv_cache_dtype"] == engine["kv_dtype"] == "float32"
    assert decoder.routed_delta_lm(seed=0, kv_dtype="float32", **KW
                                   ).config.kv_dtype == "float32"


TINY = {
    "allow_cpu": True,
    "config": {"num_hidden_layers": 4, "hidden_size": 32,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 16, "moe_intermediate_size": 16,
               "n_routed_experts": 4, "num_experts_per_tok": 2,
               "linear_attn_config": {"num_heads": 4, "head_dim": 8},
               "vocab_size": 128, "max_length": 128,
               "engine": {"slots": 4, "page_size": 16, "max_ctx": 128,
                          "prefill_chunk": 16},
               # a page's edge crossed in prefill and in decode, as the
               # cell's own check crosses one
               "check": {"prompt_tokens": 18, "decode_steps": 16}},
    "traffic": {"clients": 3, "drain_s": 20, "trace_seconds": 0.5,
                "table": {"rows": 8,
                          "prompt": {"dist": "cycle", "values": [9, 24, 40]},
                          "output": {"dist": "cycle", "values": [3, 5]}}},
}


@pytest.mark.parametrize("trace_flag", [0, 1])
def test_the_cell_rehearses_on_the_cpu(trace_flag, tmp_path):
    """``chipbench/run.py`` end to end at a tiny size, as
    ``chipbench/tests/test_chipbench.py`` does for the cells it knows."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", str(trace_flag), "--override", json.dumps(TINY)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "compilations inside the window: 0 (must be 0)" in proc.stdout
    assert "-> ok" in proc.stdout           # the reference check itself held
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False and result["failed"] == 0
    assert result["attempted"] > 0
    assert all(c["value"] <= c["limit"]
               for c in result["checks"].values()), result["checks"]
    assert "program_logits_q90_err" in result["checks"]
    assert "program_logits_max_err" in result["readings"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    group = "per_layer" if trace_flag else "end_to_end"
    allowed = {m["name"] for m in bench[group]
               if CELL in m.get("workloads", [CELL])}
    assert set(result["metrics"]) <= allowed
    if trace_flag:      # what needs no device trace and no memory_stats
        assert {"decode_occupancy", "engine_step_ms_p50",
                "expert_load_max_over_mean", "pipe_flushes_per_step"} <= set(
            result["metrics"])
        assert result["metrics"]["pipe_flushes_per_step"]["value"] == 0
    else:
        assert set(result["metrics"]) == allowed == {"served_tokens_per_s",
                                                     "setup_s"}
