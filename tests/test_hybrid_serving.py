"""A model with state-space layers on the serving path (`models/hybrid.py`):
the block against the plain reference the benchmark keeps
(`chipbench/jamba_ref.py`, the only copy), the step programs through the
paged cache with the recurrent state paged beside the KV rows, the engine
around them, what the engine refuses for such a model, the counters, and the
benchmark's byte and operation counts at the published sizes.

Tiny sizes on the CPU: 6 layers with an attention layer every third, 64 wide,
d_inner 128, d_state 16, dt_rank 4, 4 heads on 1 KV head, vocab 128.  No
number here is a measurement of the chip."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import hybrid_flops_bytes, jamba_ref  # noqa: E402
from chipbench import run as harness  # noqa: E402

from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.models import decoder, hybrid  # noqa: E402
from mxnet_tpu.parallel.shardcfg import ShardingConfig  # noqa: E402
from mxnet_tpu.serving import generate  # noqa: E402

pytestmark = pytest.mark.llm

VOCAB = 128


@pytest.fixture(scope="module")
def lm():
    return decoder.hybrid_lm(seed=3, dtype="float32")


@pytest.fixture(scope="module")
def lm_bf16():
    return decoder.hybrid_lm(seed=3)


def ids(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, size=n).tolist()


def reference(lm, fed, n_rows, **kw):
    return np.asarray(jamba_ref.reference_logits(
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
def test_layer_table_keys_the_programs(lm):
    cfg = lm.config
    assert cfg.layer_kinds == ("state_space", "attention", "state_space",
                               "state_space", "attention", "state_space")
    assert hybrid.layer_runs(cfg) == [
        ("state_space", 0, 1), ("attention", 1, 2), ("state_space", 2, 4),
        ("attention", 4, 5), ("state_space", 5, 6)]
    assert decoder.is_hybrid(cfg)
    assert not decoder.is_hybrid(decoder.decoder_tiny_lm().config)
    # the kind table is part of the program cache's key
    other = cfg._replace(layer_kinds=cfg.layer_kinds[1:] + ("attention",))
    assert (decoder.make_decode_step(cfg, 8)
            is not decoder.make_decode_step(other, 8))


@pytest.mark.parametrize("length", [5, 32, 37])
def test_forward_matches_the_reference(lm, length):
    toks = ids(length, length)
    got = lm.forward(jnp.asarray([toks], jnp.int32)).asnumpy()[0]
    assert np.abs(got - reference(lm, toks, length)).max() < 1e-4


@pytest.mark.parametrize("T", [8, 24, 7, 64])
def test_blocked_scan_matches_the_token_by_token_recurrence(T):
    rng = np.random.default_rng(T)
    C, N = 32, 4
    delta = np.log1p(np.exp(rng.normal(size=(T, C)))).astype(np.float32)
    dc, Bm, Cm = (rng.normal(size=s).astype(np.float32)
                  for s in ((T, C), (T, N), (T, N)))
    A_T = -np.exp(rng.normal(size=(N, C))).astype(np.float32)
    h = h0 = rng.normal(size=(N, C)).astype(np.float32)
    marks = np.array([0, T // 2, T - 1])
    ys, hs = [], []
    for t in range(T):
        h = np.exp(delta[t] * A_T) * h + dc[t] * Bm[t][:, None]
        ys.append((h * Cm[t][:, None]).sum(0))
        hs.append(h)
    y, marked = hybrid.selective_scan(*map(jnp.asarray, (
        delta, dc, Bm, Cm, A_T, h0, marks)))
    assert np.abs(np.asarray(y) - np.stack(ys)).max() < 1e-4
    assert np.abs(np.asarray(marked) - np.stack(hs)[marks]).max() < 1e-4


# ---------------------------------------------------------------------------
# the step programs through the paged cache
# ---------------------------------------------------------------------------
def drive(lm, S, chunk, prompts, n_decode, slots, first=None, pools=None,
          rows=None):
    """Prefill each prompt chunk by chunk into the page row of its slot,
    then ``n_decode`` greedy steps of all of them in one batch whose other
    lanes are inactive.  ``first``: tokens of each prompt's first chunk, so
    that the next starts inside a page.  Returns per prompt (tokens fed,
    logits of every fed position from the last prompt token on) and the
    pools."""
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
    assert worst(lm, runs) < 1e-4


def test_a_reused_page_and_slot_start_from_the_zero_state(lm):
    S, chunk = 8, 8
    _, pools = drive(lm, S, chunk, [ids(1, 19)], 5, [1])
    # another sequence in the same slot, on the same pages, pools as left
    runs, pools = drive(lm, S, chunk, [ids(2, 11)], 5, [1], pools=pools)
    assert worst(lm, runs) < 1e-4
    # and on the first sequence's pages in another order, in another slot
    rows = {0: np.array([11, 10, 9, 14, 13, 12, 16, 15], np.int32)}
    runs, _ = drive(lm, S, chunk, [ids(3, 14)], 5, [0], pools=pools,
                    rows=rows)
    assert worst(lm, runs) < 1e-4


def test_pools_are_what_fresh_pool_says(lm):
    cfg = lm.config
    kp, vp = (decoder.fresh_pool(cfg, 9, 4) for _ in range(2))
    assert isinstance(kp, hybrid.HybridPool)
    assert [a.shape for a in kp[:3]] == [a.shape for a in vp[:3]] == [
        (2, 9, 4, 16), (4, 9, 16, 64), (4, 9, 3 * 64)]
    assert kp.counts is None        # only a model that routes counts
    assert kp.ssm.dtype == kp.conv.dtype == jnp.float32
    assert decoder.fresh_pool(cfg, 9, 4, "bfloat16").rows.dtype \
        == jnp.bfloat16
    assert hybrid.state_entry_bytes(cfg) == 4 * 128 * (16 + 3) * 4 \
        == sum(a[:, 0].nbytes for p in (kp, vp) for a in (p.ssm, p.conv))
    # a caller that names no cache dtype meets the engine's program
    bf = decoder.hybrid_lm(seed=0).config
    assert bf.kv_dtype == "bfloat16"
    assert (decoder.make_decode_step(bf, 4)
            is decoder.make_decode_step(bf, 4, kv_dtype="bfloat16"))
    assert (decoder.make_prefill_chunk(bf, 4, 8)
            is decoder.make_prefill_chunk(bf, 4, 8, kv_dtype="bfloat16"))
    # fork_page takes a page's state entries along
    kp = kp._replace(ssm=kp.ssm.at[:, 3].set(1.5))
    assert float(decoder.fork_page(kp, 3, 5).ssm[2, 5, 7, 11]) == 1.5


def test_bf16_weights_agree_within_a_tolerance_the_int8_control_exceeds(
        lm_bf16):
    """The engine's programs on bfloat16 weights against the reference on the
    same weights (raised to float32, exactly), in units of the reference's
    standard deviation as the benchmark compares: the program's error lies
    under the limit, the 8-bit controls' above it."""
    runs, _ = drive(lm_bf16, 8, 8, [ids(7, 21)], 8, [0])
    fed, got = runs[0]
    ref = reference(lm_bf16, fed, got.shape[0])
    err = np.abs(got - ref).max() / ref.std()
    controls = {d: np.abs(reference(lm_bf16, fed, got.shape[0], dtype=d)
                          - ref).max() / ref.std()
                for d in ("bfloat16", "int8", "float8_e4m3fn")}
    limit = 0.03
    assert err < limit < controls["int8"] < controls["float8_e4m3fn"], (
        err, controls)
    assert controls["bfloat16"] < limit


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


def test_prefix_hit_on_whole_pages_only(lm):
    engine = make_engine(lm, prefix_cache=True)
    try:
        first = ids(5, 11)                  # two whole pages and 3 tokens
        a = engine.submit(first, max_new_tokens=4).result(300)["tokens"]
        assert len(engine.prefix_cache) == 2    # the partial page is not in
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
        whole = p1 + a["tokens"] + p2
        assert b["tokens"] == ref_greedy(lm, whole, 3)
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
        # one of 3 into page 2; 3 answers end at position 13 in page 3
        engine.submit(ids(12, 11), max_new_tokens=3).result(300)
        c = counters(engine)
        assert c["state_entries_written_total"] == 3
        assert c["state_starts_total"] == 1
        assert c["state_prefix_pages_shared_total"] == 0
        launches = [a for n, a in spans if n == "engine.prefill_launch"]
        assert [(a["pos"], a["tokens"], a["state_pages"])
                for a in launches] == [(0, 8, 2), (8, 3, 1)]
        state = engine.stats()["state"]
        entry = 4 * 128 * (16 + 3) * 4
        # the entries the launches read and write: a chunk its pages and
        # the entry before pos0, a decode step two a lane (PR 37)
        assert [a["state_bytes"] for a in launches] == [2 * entry, 2 * entry]
        assert state == {"layers": 4, "entry_bytes": entry,
                         "pool_bytes": entry * 31,
                         "pages_with_state_peak": 4,
                         "bytes_prefill_total": 4 * entry,
                         "bytes_decode_total": 2 * 2 * entry}
        assert (c["state_bytes_prefill_total"],
                c["state_bytes_decode_total"]) == (4 * entry, 4 * entry)
        kv = engine.stats()["kv"]
        assert kv["pool_bytes"] == 30 * (entry + 2 * 2 * 16 * 4 * 4)
    finally:
        engine.stop()
    assert "state" not in make_engine(decoder.decoder_tiny_lm()).stats()


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
])
def test_engine_refuses_by_name(lm, what, kwargs):
    kwargs = {k: v() if callable(v) else v for k, v in kwargs.items()}
    with pytest.raises(ValueError, match=what + ".*state-space layers"):
        make_engine(lm, **kwargs)


@pytest.mark.parametrize("build", [
    lambda cfg: decoder.make_verify_step(cfg, 4, 3),
    lambda cfg: decoder.make_decode_step(cfg, 4, kv_dtype="int8"),
    lambda cfg: decoder.make_prefill_chunk(cfg, 4, 8, quant=("int8",)),
    lambda cfg: decoder.make_decode_step(cfg, 4, sharding=tp2()),
], ids=["verify", "int8_kv", "quant", "tp"])
def test_program_factories_refuse_by_name(lm, build):
    with pytest.raises(ValueError, match="state-space layers"):
        build(lm.config)


def test_session_export_and_import_are_refused(lm):
    engine = make_engine(lm)
    try:
        engine.submit(ids(1, 5), max_new_tokens=2, session="s").result(300)
        with pytest.raises(ValueError, match="session export"):
            engine.export_session("s")
        with pytest.raises(ValueError, match="session import"):
            engine.import_session(b"")
    finally:
        engine.stop()


def test_the_classic_block_takes_a_bfloat16_pool_too():
    lm = decoder.decoder_tiny_lm(seed=0, vocab_size=VOCAB)
    prompt = ids(4, 13)
    out = {}
    for kv in ("float32", "bfloat16"):
        engine = serving.DecodeEngine(lm, slots=2, page_size=8, max_ctx=64,
                                      prefill_chunk=8, kv_dtype=kv)
        try:
            assert decoder._codes(engine._kp).dtype == jnp.dtype(kv)
            out[kv] = engine.submit(prompt, max_new_tokens=6).result(300)
        finally:
            engine.stop()
    same = sum(a == b for a, b in zip(out["float32"]["tokens"],
                                      out["bfloat16"]["tokens"]))
    assert same >= 4        # rounding may move a greedy token, not the rest
    with pytest.raises(ValueError, match="kv_dtype"):
        serving.DecodeEngine(lm, slots=2, kv_dtype="float16")


# ---------------------------------------------------------------------------
# the benchmark's counts and the cell's rehearsal
# ---------------------------------------------------------------------------
def test_flops_and_bytes_at_the_published_sizes():
    """ISSUE 29's table: the arithmetic of the new metrics."""
    c = harness.load("configs", "jamba2-3b-serve.json")
    p = hybrid_flops_bytes.param_counts(c)
    assert p["ssm_mixer"] == 41241792 and p["mlp"] == 62914560
    assert p["attention_mixer"] == 13762560
    assert (p["ssm_layer"], p["attention_layer"]) == (104161472, 76682240)
    assert p["embedding"] == 167774720 and p["total"] == 3029337472
    assert hybrid_flops_bytes.kv_bytes_per_token(c) == 1024
    assert hybrid_flops_bytes.state_entry_bytes(c) == 26 * 5120 * 19 * 4
    # 48 lanes of 300 tokens: 6.06 GB of weights, 0.97 GB of state, the KV
    need = hybrid_flops_bytes.decode_step_bytes(c, 48, 48 * 300)
    assert need == 2 * (p["layers_matmul"] + 65536 * 2560) \
        + 48 * 300 * 1024 + 48 * 2 * 10117120
    assert 7.0e9 < need < 7.1e9
    flops = hybrid_flops_bytes.prefill_launch_flops(c, 256)
    nbytes = hybrid_flops_bytes.prefill_launch_bytes(c, 256)
    assert 7.0e-3 < flops / 197e12 < 7.6e-3     # on the ridge: both 7.4 ms
    assert 7.0e-3 < nbytes / 819e9 < 7.6e-3
    facts = {"config": c, "peaks": {"flops_bf16": 197e12,
                                    "hbm_bytes_per_s": 819e9},
             "trace": {"modules": {"jit_step": [need / 819e9 * 2],
                                   "jit_prefill": [0.0148]}},
             "stats": {"serving": {"generate": {"decode_occupancy": 0.75}}},
             "end_to_end": {"live_tokens_mean": 48 * 300}}
    assert hybrid_flops_bytes.decode_step_roofline(facts) == \
        pytest.approx(50.0)
    assert 47 < hybrid_flops_bytes.prefill_launch_roofline(facts) < 52
    assert hybrid_flops_bytes.decode_step_roofline({}) is None
    assert hybrid_flops_bytes.prefill_launch_roofline({}) is None
    # the model the builder makes from the file has the table's parameters
    kinds = tuple("attention" if i % 14 == 7 else "state_space"
                  for i in range(28))
    cfg = hybrid.HybridConfig(65536, 28, 2560, 8192, 20, 1, 128, 1024, kinds,
                              5120, 16, 4, 160, 1e-6, "bfloat16")
    count = 65536 * 2560 + 2560
    for kind, lo, hi in hybrid.layer_runs(cfg):
        count += (hi - lo) * sum(int(np.prod(s)) for s in
                                 hybrid._run_shapes(cfg, kind).values())
    assert count == p["total"]


TINY = {
    "allow_cpu": True,
    "config": {"num_hidden_layers": 6, "hidden_size": 64,
               "intermediate_size": 128, "num_attention_heads": 4,
               "attn_layer_period": 3, "attn_layer_offset": 1,
               "mamba_dt_rank": 4, "vocab_size": 128, "max_length": 128,
               "engine": {"slots": 4, "page_size": 16, "max_ctx": 128,
                          "prefill_chunk": 16},
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
    cell = "jamba2_3b_chat_closed"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds", "2",
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
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    group = "per_layer" if trace_flag else "end_to_end"
    allowed = {m["name"] for m in bench[group]
               if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) <= allowed
    if trace_flag:      # what needs no device trace and no memory_stats
        assert {"decode_occupancy", "engine_step_ms_p50"} <= set(
            result["metrics"])
    else:
        assert set(result["metrics"]) == allowed
