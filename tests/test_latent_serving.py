"""A model with latent attention layers on the serving path (the
``xing4_0`` block: `models/hybrid.py`'s ``"latent_attention"`` mixer over a
paged latent cache, a leading dense layer, routed layers, and mHC's residual
streams): the rotation and the streams' mix by themselves, the forward and
the step programs through the paged latent cache against the plain reference
the benchmark keeps (`chipbench/xing_ref.py`, the only copy, imported), the
absorbed decode against the expanded form, the decode kernel in the
interpreter, the lanes' pages kept apart, the engine around the programs and
its counters, the builder's refusals, the configuration and its counts at
the published widths, the planted faults of the check, the cell's
rehearsal, and the lowered text of the other models' programs against the
parent's.

Tiny sizes on the CPU: 3 layers (1 dense, 2 routed over 8 experts) 32 wide,
4 heads, a latent row of 16 + 8 (padded to 128), 4 streams (4 Sinkhorn
iterations: the programs compile in a fraction of 20's time), vocabulary
96.
No number here is a measurement of the chip."""
from __future__ import annotations

import hashlib
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

from chipbench import run as harness  # noqa: E402
from chipbench import xing_flops_bytes, xing_ref  # noqa: E402

from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.models import decoder, hybrid, latent  # noqa: E402
from mxnet_tpu.ops.pallas import latent_attention  # noqa: E402

pytestmark = pytest.mark.llm

VOCAB = 96
CELL = "xing4_l5_longdoc_closed"
CONFIG = "xing4-29b-l5-serve"
# the published configuration's catalog entry, as the model-configs catalog
# gives it
CATALOG = os.path.join(ROOT, "tests", "data", "xing4_catalog_entry.jsonl")
YARN = {"type": "yarn", "factor": 4, "original_max_position_embeddings": 32,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
KW = dict(vocab_size=VOCAB, num_layers=3, units=32, hidden_size=48,
          num_heads=4, num_kv_heads=4, q_lora_rank=24, kv_lora_rank=16,
          qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
          rope_theta=1e4, rope_scaling=YARN, dense_layers=1, n_experts=8,
          experts_per_token=2, expert_hidden=12, routed_scale=2.0,
          hc_mult=4, hc_sinkhorn_iters=4, hc_eps=1e-6, hc_clamp_min=-30,
          hc_clamp_max=30, max_length=128)
S, CHUNK = 16, 16           # pages of 16 (8 a pool), chunks of one page


@pytest.fixture(scope="module")
def lm():
    return decoder.latent_routed_lm(seed=1, dtype="float32", **KW)


def ids(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, size=n).tolist()


def reference(lm, fed, n_rows, **kw):
    return np.asarray(xing_ref.reference_logits(
        lm.jax_params(), lm.config, fed, n_rows, **kw))


def err(got, ref):
    return float(np.abs(got - ref).max() / ref.std())


# ---------------------------------------------------------------------------
# the parts by themselves
# ---------------------------------------------------------------------------
def test_yarn_frequencies_and_scale_are_deepseeks():
    """The published rotation: factor 64 over 4 096, beta 32 / 1; the score
    scale m^2 / sqrt(192), m = 0.1 ln 64 + 1."""
    spec = latent.Latent(q_rank=768, kv_rank=512, nope=128, rope=64,
                         v_dim=128, theta=1e4, factor=64, original=4096,
                         beta_fast=32, beta_slow=1, mscale=1,
                         mscale_all_dim=1)
    inv = latent.yarn_inv_freq(spec)
    ref = xing_ref._yarn(64, 1e4, 64, 4096, 32, 1)
    np.testing.assert_allclose(inv, ref, rtol=1e-6)
    plain = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    assert inv[0] == pytest.approx(plain[0])              # fast: kept
    assert inv[-1] == pytest.approx(plain[-1] / 64)       # slow: / factor
    m = 0.1 * np.log(64) + 1
    assert latent.score_scale(spec) == pytest.approx(m * m / np.sqrt(192))
    assert latent.row_width(spec) == 640


@pytest.mark.parametrize("spread", [0.1, 0.24])
def test_h_res_is_doubly_stochastic_after_twenty_iterations(spread):
    """Sinkhorn over the logits the configuration's weights give (2 on the
    diagonal, a spread of 0.24 at the published widths) and below."""
    rng = np.random.default_rng(0)
    logits = 2 * np.eye(4) + rng.normal(0, spread, (256, 4, 4))
    m = np.asarray(hybrid._sinkhorn(jnp.exp(jnp.asarray(logits,
                                                        jnp.float32)),
                                    20, 1e-6))
    assert (m > 0).all()
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)


def test_layer_table_pool_and_config(lm):
    cfg = lm.config
    assert cfg.layer_kinds == (hybrid.LATENT,) * 3
    assert hybrid.layer_runs(cfg) == [(hybrid.LATENT, 0, 1),
                                      (hybrid.LATENT, 1, 3)]
    assert hybrid.recurrent_layers(cfg) == 0
    assert hybrid.state_entry_bytes(cfg) == 0
    runs = lm.jax_params()["runs"]
    assert "w_gate" in runs[0] and "we_in" not in runs[0]
    assert "we_in" in runs[1] and "w_gate" not in runs[1]
    pool = decoder.fresh_pool(cfg, 5, S)
    assert pool.rows.shape == (3, 5, S // 2, 128)       # half a page a pool
    assert pool.ssm is None and pool.conv is None
    assert pool.counts.shape == (5,)
    assert hybrid.token_row_elems(cfg) == 3 * 128
    assert decoder.recurrent_name(cfg) == "latent attention layers"


# ---------------------------------------------------------------------------
# the step programs through the paged latent cache
# ---------------------------------------------------------------------------
def drive(lm, prompts, n_decode, slots=2, tables=None):
    """Prefill each of ``prompts`` chunk by chunk into its own pages, then
    decode ``n_decode`` greedy steps with every prompt a lane: the fed
    tokens of each lane and a logits row per fed position from its last
    prompt token on."""
    cfg = lm.config
    pps = cfg.max_length // S
    kp, vp = (decoder.fresh_pool(cfg, slots * pps + 1, S) for _ in range(2))
    prefill = decoder.make_prefill_chunk(cfg, S, CHUNK)
    decode = decoder.make_decode_step(cfg, S)
    if tables is None:
        tables = np.zeros((slots, pps), np.int32)
        for b in range(len(prompts)):
            tables[b] = 1 + b * pps + np.arange(pps)
    rows = [[] for _ in prompts]
    toks = []
    for b, prompt in enumerate(prompts):
        for lo in range(0, len(prompt), CHUNK):
            part = prompt[lo:lo + CHUNK]
            padded = np.zeros(CHUNK, np.int32)
            padded[:len(part)] = part
            kp, vp, tok, last = prefill(lm.jax_params(), kp, vp,
                                        jnp.asarray(padded), jnp.int32(lo),
                                        jnp.int32(len(part)),
                                        jnp.asarray(tables[b]))
        rows[b].append(np.asarray(last))
        toks.append(int(tok))
    fed = [list(p) for p in prompts]
    active = np.zeros(slots, bool)
    active[:len(prompts)] = True
    for i in range(n_decode):
        tokens = np.zeros(slots, np.int32)
        positions = np.zeros(slots, np.int32)
        for b in range(len(prompts)):
            tokens[b], positions[b] = toks[b], len(prompts[b]) + i
            fed[b].append(toks[b])
        kp, vp, nxt, logits = decode(lm.jax_params(), kp, vp,
                                     jnp.asarray(tokens),
                                     jnp.asarray(positions),
                                     jnp.asarray(tables), jnp.asarray(active))
        for b in range(len(prompts)):
            rows[b].append(np.asarray(logits)[b])
        toks = [int(t) for t in np.asarray(nxt)[:len(prompts)]]
    return fed, [np.stack(r) for r in rows], (kp, vp, tables)


@pytest.fixture(params=["xla", "interpret"])
def path(request, monkeypatch):
    """The decode step's attention: every page gathered (the CPU's), or the
    kernel's walk over the live pages in the Pallas interpreter."""
    if request.param == "interpret":
        monkeypatch.setenv("MXNET_PAGED_ATTENTION", "interpret")
    else:
        monkeypatch.delenv("MXNET_PAGED_ATTENTION", raising=False)
    decoder._fn_cache.clear()
    yield request.param
    decoder._fn_cache.clear()


def test_paged_programs_match_the_reference(lm, path):
    """Three chunks (the third partial) and 20 decode steps from position
    37: the decode steps cross the page boundary at 48, the rows attend
    over three pages of both pools; two lanes at once."""
    fed, rows, _ = drive(lm, [ids(1, 37), ids(2, 21)], 20)
    assert latent_attention.last_path == {"xla": "xla",
                                          "interpret": "pallas-interpret"}[
                                              path]
    for f, got in zip(fed, rows):
        ref = reference(lm, f, got.shape[0])
        assert err(got, ref) < 1e-5


def test_absorbed_decode_matches_the_expanded_form(lm):
    """The decode step's attention of one layer, the query absorbed into
    the latent space and W_UV after the sum, against the heads' keys and
    values up-projected from the same rows (the prefill's form)."""
    cfg = lm.config
    lp = jax.tree.map(lambda a: a[0], lm.jax_params()["runs"][1])
    rng = np.random.default_rng(3)
    B, H, L = 2, cfg.num_heads, 2 * S + 5
    u = jnp.asarray(rng.normal(size=(B, L, cfg.units)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(L), (B, L))
    q_nope, q_rope, rows = latent.latent_inputs(u, lp, cfg, pos)
    want = latent.full_attention(q_nope, q_rope, rows, lp, cfg)[:, -1]
    pps = 4
    kp, vp = (decoder.fresh_pool(cfg, B * pps + 1, S) for _ in range(2))
    tables = 1 + np.arange(B * pps, dtype=np.int32).reshape(B, pps)
    t = np.arange(L)
    kp, vp = latent.write_rows(kp, vp, 1, jnp.asarray(tables[:, t // S]
                                                      .reshape(-1)),
                               jnp.asarray(np.tile(t % S, B)),
                               rows.reshape(B * L, -1))
    got = jax.jit(latent.absorbed_step, static_argnums=(8,))(
        q_nope[:, -1], q_rope[:, -1], kp, vp, 1, jnp.full(B, L),
        jnp.asarray(tables), lp, cfg)
    assert got.shape == (B, H * cfg.latent.v_dim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_a_lane_never_reads_another_lanes_pages(lm, monkeypatch):
    """Lane 0's logits are the same whatever lies in lane 1's pages and in
    the pages nobody holds: here NaN everywhere but lane 0's pages and the
    scratch page, read by the kernel's walk (in the interpreter)."""
    monkeypatch.setenv("MXNET_PAGED_ATTENTION", "interpret")
    decoder._fn_cache.clear()
    prompts = [ids(4, 29), ids(5, 40)]
    _, rows, (kp, vp, tables) = drive(lm, prompts, 1)
    cfg = lm.config
    decode = decoder.make_decode_step(cfg, S)
    keep = np.zeros(kp.rows.shape[1], bool)
    keep[tables[0]] = keep[0] = True
    poison = jnp.asarray(keep)[None, :, None, None]
    poisoned = [jax.tree.map(jnp.copy, p)._replace(
        rows=jnp.where(poison, p.rows, jnp.nan)) for p in (kp, vp)]
    out = []
    for pools in (poisoned, (kp, vp)):      # each call takes its pools
        _, _, _, logits = decode(
            lm.jax_params(), *pools, jnp.asarray([7, 9], jnp.int32),
            jnp.asarray([30, 41], jnp.int32), jnp.asarray(tables),
            jnp.asarray([True, False]))
        out.append(np.asarray(logits)[0])
    decoder._fn_cache.clear()
    assert latent_attention.last_path == "pallas-interpret"
    assert np.isfinite(out[0]).all()
    np.testing.assert_array_equal(out[0], out[1])


def test_engine_greedy_equals_the_reference_and_counts_latent_bytes(lm):
    """The engine end to end (chunked prefill, async decode, the prefix
    cache on): greedy tokens as the reference's, and the latent rows its
    launches read counted by launch."""
    eng = serving.DecodeEngine(lm, slots=2, page_size=S, max_ctx=128,
                               prefill_chunk=CHUNK, async_decode=True,
                               prefix_cache=True)
    try:
        prompts, n = [ids(6, 35), ids(7, 18)], 6
        futs = [eng.submit(p, max_new_tokens=n) for p in prompts]
        outs = [f.result(timeout=300)["tokens"] for f in futs]
        for p, out in zip(prompts, outs):
            fed, want = list(p), []
            for _ in range(n):
                want.append(int(reference(lm, fed, 1, pad_to=64)[0]
                                .argmax()))
                fed.append(want[-1])
            assert list(out) == want
        stats = eng.stats()
        row = 3 * 128 * 4
        assert stats["latent"]["row_bytes"] == row
        # a chunk reads the sequence's rows up to its end
        want_prefill = row * sum(min(lo + CHUNK, len(p))
                                 for p in prompts
                                 for lo in range(0, len(p), CHUNK))
        assert stats["latent"]["bytes_prefill_total"] == want_prefill
        assert stats["latent"]["bytes_decode_total"] > 0
        assert stats["latent"]["bytes_decode_total"] % row == 0
        assert "state" not in stats
        assert stats["experts"]["per_token"] == 2
    finally:
        eng.stop(drain=False)


@pytest.mark.parametrize("what, kw", [
    ("scoring_func", {"scoring_func": "softmax"}),
    ("topk_method", {"topk_method": "greedy"}),
    ("grouped routing", {"n_group": 8, "topk_group": 4}),
    ("ep_size", {"ep_size": 8}),
    ("next-n prediction layers", {"nextn_layers": 1}),
    ("rope_scaling of type", {"rope_scaling": dict(YARN, type="linear")}),
    ("asymmetric clamp", {"hc_clamp_min": -10}),
])
def test_builder_refuses_what_the_block_has_no_code_for(what, kw):
    with pytest.raises(ValueError, match=what):
        decoder.latent_routed_lm(seed=0, dtype="float32", **dict(KW, **kw))


def test_engine_refuses_speculation_by_name(lm):
    with pytest.raises(ValueError, match="latent attention layers"):
        serving.DecodeEngine(lm, slots=2, page_size=S, max_ctx=128,
                             prefill_chunk=CHUNK, speculate=True)


# ---------------------------------------------------------------------------
# the check's faults, planted in the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fault", xing_ref.FAULTS)
def test_each_planted_fault_moves_the_rows(lm, fault):
    """Every fault the chip's check plants moves the reference's rows by
    far more than the program's own error, the whole-sequence forward's
    (``HybridLM.forward``; here with the streams' alpha at 1, so that the
    Sinkhorn is no near-identity at this width)."""
    params = dict(lm.jax_params())
    params["runs"] = [dict(r, hc_attn_alpha=jnp.ones_like(r["hc_attn_alpha"]),
                           hc_ffn_alpha=jnp.ones_like(r["hc_ffn_alpha"]))
                      for r in params["runs"]]
    fed = ids(8, 40)
    sound = np.asarray(xing_ref.reference_logits(params, lm.config, fed, 40))
    got = np.asarray(hybrid.full_forward(params, lm.config,
                                         jnp.asarray([fed]))[0])
    planted = np.asarray(xing_ref.reference_logits(params, lm.config, fed,
                                                   40, fault=fault))
    assert err(got, sound) < 1e-5
    assert err(planted, sound) > 1e-3


# ---------------------------------------------------------------------------
# the benchmark: configuration, counts, rehearsal
# ---------------------------------------------------------------------------
def test_configuration_has_the_catalogs_keys_and_states_its_cut():
    c = harness.load("configs", CONFIG + ".json")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, filter(str.strip, f))
                   if r["name"] == "Xing4.0-29B-A4B")
    changed = {k for k, v in row["config"].items() if c.get(k) != v}
    assert changed == set(c["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers"}
    assert c["source"] == row["source_url"]
    assert {c[k] for k in c["reduced"]} == {5, 1, 0}
    for key in ("deployment", "published"):
        assert c[key]
    for key in ("mhc", "rope_layout", "cache", "weights", "check.limits",
                "check.hard_choice", "matmul_precision"):
        assert key in c["assumed"]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longdoc_closed_c4"


def test_parameters_and_pool_at_the_published_widths():
    """The published widths' arithmetic: 4 047.7 M parameters (8.10 GB),
    a latent row of 576 floats padded to 640, 12 800 bytes a token over
    five layers."""
    c = harness.load("configs", CONFIG + ".json")
    p = xing_flops_bytes.param_counts(c)
    assert p["total"] == 4_047_691_776
    assert p["attention"] == 28_409_856
    assert xing_flops_bytes.latent_row_bytes(c) == 12_800
    hybrid_lm = hybrid.hybrid_lm
    try:
        hybrid.hybrid_lm = lambda seed=0, **kw: hybrid.HybridLM(**kw)
        net = harness.resolve(c["builder"])(
            seed=0, **{k: c[v] for k, v in c["builder_kwargs"].items()})
    finally:
        hybrid.hybrid_lm = hybrid_lm
    counted = sum(int(np.prod(getattr(r, k).shape)) for r in net.runs
                  for k in r.leaves)
    counted += sum(int(np.prod(getattr(net, k).shape))
                   for k in net.hc_leaves)
    counted += 2 * 131072 * 3584 + 3584
    small = (5 * (2 * 3584 + 768 + 512 + 2 * (3 + 24)) + 4 * 64 + 1 + 4
             + 3584)                # norms, alphas, biases; the final norm
    assert counted == p["total"] + small
    pool = jax.eval_shape(lambda: decoder.fresh_pool(net.config, 769, 256,
                                                     "float32"))
    assert pool.rows.shape == (5, 769, 128, 640)


def test_rooflines_read_the_engines_counters():
    c = harness.load("configs", CONFIG + ".json")
    facts = {"config": c, "peaks": {"hbm_bytes_per_s": 819e9,
                                    "flops_bf16": 197e12},
             "trace": {"modules": {"jit_step": [0.010], "jit_prefill":
                                   [0.050]}},
             "stats": {"serving": {"counters": {
                 "latent_bytes_decode_total": 100 * 4 * 16000 * 12800,
                 "decode_steps_total": 100,
                 "latent_bytes_prefill_total": 10 * 8192 * 12800,
                 "prefill_launches_total": 10,
                 "prefill_tokens_total": 10 * 512}},
                 "engine": {"experts": {
                     "decode": {"experts_hit_per_launch": 12.0},
                     "prefill": {"experts_hit_per_launch": 64.0,
                                 "pairs_per_launch": 2048.0}}}}}
    dec = xing_flops_bytes.decode_step_roofline(facts)
    need = xing_flops_bytes.weight_bytes(c, 48) + 4 * 16000 * 12800
    assert dec == pytest.approx(100 * need / 819e9 / 0.010)
    pre = xing_flops_bytes.prefill_launch_roofline(facts)
    assert 0 < pre < 100
    # a program that counts no latent bytes reads nothing
    facts["stats"]["serving"]["counters"].pop("latent_bytes_decode_total")
    assert xing_flops_bytes.decode_step_roofline(facts) is None
    assert xing_flops_bytes.prefill_launch_roofline({}) is None


TINY = {
    "allow_cpu": True,
    "config": {"num_hidden_layers": 2, "hidden_size": 32,
               "intermediate_size": 48, "num_attention_heads": 4,
               "num_key_value_heads": 4, "q_lora_rank": 24,
               "kv_lora_rank": 16, "qk_nope_head_dim": 8,
               "qk_rope_head_dim": 8, "v_head_dim": 8,
               "moe_intermediate_size": 12, "n_routed_experts": 8,
               "num_experts_per_tok": 2, "vocab_size": 128,
               "max_length": 128, "rope_scaling": YARN,
               "hc_sinkhorn_iters": 2,
               "engine": {"slots": 4, "page_size": 16, "max_ctx": 128,
                          "prefill_chunk": 16},
               # three chunks and a page boundary in the decode steps
               "check": {"prompt_tokens": 40, "decode_steps": 12,
                         "served_tokens": 24}},
    "traffic": {"clients": 3, "drain_s": 30, "trace_seconds": 0.5,
                "table": {"rows": 8,
                          "prompt": {"dist": "cycle", "values": [20, 45, 70]},
                          "output": {"dist": "cycle", "values": [3, 5]}}},
}


@pytest.mark.parametrize("trace_flag", [0, 1])
def test_the_cell_rehearses_on_the_cpu(trace_flag, tmp_path):
    """``chipbench/run.py`` end to end at a tiny size."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "1",
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
    assert {"program_logits_q90_err", "served_gap_max"} <= set(
        result["checks"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    group = "per_layer" if trace_flag else "end_to_end"
    allowed = {m["name"] for m in bench[group]
               if CELL in m.get("workloads", [CELL])}
    assert set(result["metrics"]) <= allowed
    if trace_flag:      # what needs no device trace and no memory_stats
        assert {"decode_occupancy", "engine_step_ms_p50",
                "expert_load_max_over_mean"} <= set(result["metrics"])
    else:
        assert set(result["metrics"]) == allowed == {"served_tokens_per_s",
                                                     "setup_s"}


# ---------------------------------------------------------------------------
# the other models' programs are the parent's
# ---------------------------------------------------------------------------
#: sha256 of the lowered text (CPU) of the two step programs of the classic
#: and the retention serving configurations at their cells' geometry, over
#: the separate inputs (``StepProgram.body``) and over one buffer a launch,
#: taken at the parent of the latent kind (3a1a90d);
#: ``tests/test_retention_serving.py`` holds jamba's and solar's.
PARENT_LOWERED = {
    "gpt2-small-serve": {
        "body": ("df7389831e1fcff1034d6e80f9813af8dcc87bf9b069b56aec5863cd792cbcd1",
                 "5c91b6be34e9fc4cdec2d94484c6e79eb93e0024b0bdb1cbe991aa9318285832"),
        "packed": ("84d7203b5c9616b1eaef3ce8de32839e6369e0fd04cb448fac2e0e271d9d02fa",
                   "3392907e73085b53f34189c990efe8da3c05fad8721eba5f08dd73bd8b51c5e8")},
    "brumby-14b-l4-serve": {
        "body": ("7e2989040b8f5a74c90fb52350b1d6a6646406130ab9edf3b41b0d093e07e713",
                 "c074ead54549e7c2172d82b4ccc16cd36c3d8d4d2b7ea95ba3de30f03f9e093c"),
        "packed": ("ad78c57c8ef040cbee1380457ffb0aa8bc88ed196d7403829ad96b6bd3f4ad81",
                   "81fcd7653cce441aadcdcb5d9d4422c433c7b2a20ac693dcaf11c29e2b833df6")},
}


@pytest.mark.parametrize("name", sorted(PARENT_LOWERED))
def test_gpt2_and_brumby_programs_lower_to_the_parents_text(name,
                                                            monkeypatch):
    """Shapes only: the builder's model is made without its weights."""
    monkeypatch.setattr(hybrid, "hybrid_lm",
                        lambda seed=0, **kw: hybrid.HybridLM(**kw))
    c = harness.load("configs", name + ".json")
    kw = {k: c[v] for k, v in c["builder_kwargs"].items()}
    if c["builder"].endswith(":causal_lm"):
        net = decoder.CausalLM(**kw)
        net.initialize()
        params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape,
                                                             a.dtype),
                              net.jax_params())
    else:
        net = harness.resolve(c["builder"])(seed=0, **kw)

        def sds(p):
            return jax.ShapeDtypeStruct(tuple(p.shape), net.dtype)
        params = {"embed": sds(net.embed), "norm_f": sds(net.norm_f),
                  "head": sds(net.head),
                  "runs": [{k: sds(getattr(r, k)) for k in r.leaves}
                           for r in net.runs]}
    cfg = net.config
    e = c["engine"]
    S_, B, T, kvd = (e["page_size"], e["slots"], e["prefill_chunk"],
                     e.get("kv_dtype"))
    pps = e["max_ctx"] // S_
    pool = jax.eval_shape(lambda: decoder.fresh_pool(cfg, B * pps + 1, S_,
                                                     kvd))

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)
    programs = (decoder.make_decode_step(cfg, S_, kv_dtype=kvd),
                decoder.make_prefill_chunk(cfg, S_, T, kv_dtype=kvd))
    inputs = ((i32(B), i32(B), i32(B, pps), jax.ShapeDtypeStruct(
        (B,), jnp.bool_)), (i32(T), i32(), i32(), i32(pps)))

    def digests(lower):
        return tuple(hashlib.sha256(lower(p).lower(
            params, pool, pool, *rest).as_text().encode()).hexdigest()
            for p, rest in zip(programs, inputs))
    assert digests(lambda p: jax.jit(p.body, donate_argnums=(1, 2))
                   ) == PARENT_LOWERED[name]["body"]
    assert digests(lambda p: p) == PARENT_LOWERED[name]["packed"]
