"""A model with power-retention layers on the serving path (the ``brumby``
block: `models/hybrid.py`'s ``"retention"`` mixer in every layer, no
attention layer): the feature map, the attention form, the token recurrence
and the blocked form against each other, the step programs through the
paged cache against the plain reference the benchmark keeps
(`chipbench/brumby_ref.py`, the only copy, imported), the planted faults of
the check, the engine around the programs and what it refuses, the counts
of the benchmark at the published widths, the cell's rehearsal, and the
lowered text of the other recurrent models' programs against the parent's.

Tiny sizes on the CPU: 2 layers 32 wide, 4 query heads on 2 KV heads of 8
(36 features), an MLP of 48, vocabulary 96.  No number here is a
measurement of the chip."""
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

from chipbench import brumby_flops_bytes, brumby_ref  # noqa: E402
from chipbench import run as harness  # noqa: E402

from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.models import decoder, hybrid  # noqa: E402
from mxnet_tpu.parallel.shardcfg import ShardingConfig  # noqa: E402
from mxnet_tpu.serving import generate  # noqa: E402

pytestmark = pytest.mark.llm

VOCAB = 96
CELL = "brumby14b_l4_chat_closed"
CONFIG = "brumby-14b-l4-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KW = dict(vocab_size=VOCAB, num_layers=2, units=32, hidden_size=48,
          num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=1e4,
          max_length=128)


@pytest.fixture(scope="module")
def lm():
    return decoder.retention_lm(seed=1, dtype="float32", **KW)


def ids(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, size=n).tolist()


def reference(lm, fed, n_rows, **kw):
    return np.asarray(brumby_ref.reference_logits(
        lm.jax_params(), lm.config, fed, n_rows, **kw))


def ref_greedy(lm, prompt, n):
    fed, out = list(prompt), []
    for _ in range(n):
        out.append(int(reference(lm, fed, 1, pad_to=64)[0].argmax()))
        fed.append(out[-1])
    return out


# ---------------------------------------------------------------------------
# the mixer's three forms
# ---------------------------------------------------------------------------
def test_phi_of_q_dot_phi_of_k_is_the_square_of_q_dot_k():
    q, k = (jax.random.normal(jax.random.PRNGKey(i), (5, 8)) for i in (0, 1))
    pq, pk = hybrid.phi(q), hybrid.phi(k)
    assert pq.shape == (5, 36) == (5, hybrid.phi_width(8))
    np.testing.assert_allclose((pq * pk).sum(-1), ((q * k).sum(-1)) ** 2,
                               rtol=1e-5)


def forms(T=40, H=4, KVH=2, d=8, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (T, H, d)) / d ** 0.5
    k, v = (jax.random.normal(kk, (T, KVH, d)) for kk in keys[1:3])
    log_g = jax.nn.log_sigmoid(jax.random.normal(keys[3], (T, KVH)) + 3.0)
    return q, k, v, log_g


def attention_form(q, k, v, log_g):
    """sum_{s<=t} (q_t.k_s)^2 exp(c_t - c_s) v_s over the same weights."""
    T, H, _ = q.shape
    G = H // k.shape[1]
    c = jnp.cumsum(log_g, 0)
    out = np.zeros(q.shape)
    for t in range(T):
        for h in range(H):
            w = (k[:t + 1, h // G] @ q[t, h]) ** 2 * jnp.exp(
                c[t, h // G] - c[:t + 1, h // G])
            out[t, h] = (w @ v[:t + 1, h // G]) / w.sum()
    return out


def state_form(q, k, v, log_g, S, z):
    """S <- g S + phi(k) v^T, z <- g z + phi(k), token by token."""
    T, H, _ = q.shape
    G = H // k.shape[1]
    out, g = [], jnp.exp(log_g)
    for t in range(T):
        pk = hybrid.phi(k[t])                           # (KVH, F)
        S = g[t][:, None, None] * S + pk[:, :, None] * v[t][:, None, :]
        z = g[t][:, None] * z + pk
        pq = hybrid.phi(q[t]).reshape(k.shape[1], G, -1)
        out.append((jnp.einsum("kgf,kfd->kgd", pq, S)
                    / jnp.einsum("kgf,kf->kg", pq, z)[..., None]
                    ).reshape(H, -1))
    return np.stack(out), S, z


@pytest.mark.parametrize("block", [8, 20, 40])
def test_attention_state_and_blocked_forms_agree(block):
    """From the zero state all three agree; the blocked form hands the state
    across the block boundaries and returns it after the marks."""
    q, k, v, log_g = forms()
    F = hybrid.phi_width(8)
    S0, z0 = jnp.zeros((2, F, 8)), jnp.zeros((2, F))
    want = attention_form(q, k, v, log_g)
    got_state, S, z = state_form(q, k, v, log_g, S0, z0)
    marks = jnp.array([5, 23, 39])
    got, S_m, z_m = hybrid.retention_chunk(q, k, v, log_g, S0, z0, marks,
                                           block=block)
    # a quotient of sums of squares: where the first weights are small its
    # rounding shows at 1e-4 of outputs of unit size
    np.testing.assert_allclose(got_state, want, atol=5e-4, rtol=1e-4)
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=1e-4)
    for i, m in enumerate(marks.tolist()):
        _, S, z = state_form(q[:m + 1], k[:m + 1], v[:m + 1], log_g[:m + 1],
                             S0, z0)
        np.testing.assert_allclose(S_m[i], S, atol=1e-4, rtol=1e-5)
        np.testing.assert_allclose(z_m[i], z, atol=1e-4, rtol=1e-5)


def test_blocked_form_continues_from_a_state():
    """A chunk from the state the chunk before it left is the two at once."""
    q, k, v, log_g = forms(T=32, seed=3)
    F = hybrid.phi_width(8)
    zero = (jnp.zeros((2, F, 8)), jnp.zeros((2, F)))
    whole, _, _ = hybrid.retention_chunk(q, k, v, log_g, *zero,
                                         jnp.array([31]), block=8)
    _, S, z = hybrid.retention_chunk(q[:16], k[:16], v[:16], log_g[:16],
                                     *zero, jnp.array([15]), block=8)
    second, _, _ = hybrid.retention_chunk(q[16:], k[16:], v[16:],
                                          log_g[16:], S[0], z[0],
                                          jnp.array([15]), block=8)
    np.testing.assert_allclose(second, whole[16:], atol=5e-4, rtol=1e-4)


def test_forward_matches_the_reference(lm):
    fed = ids(3, 45)
    got = np.asarray(hybrid.full_forward(lm.jax_params(), lm.config,
                                         jnp.asarray([fed], jnp.int32)))[0]
    assert np.abs(got - reference(lm, fed, 45)).max() < 1e-5


def test_layer_table_and_config(lm):
    cfg = lm.config
    assert cfg.layer_kinds == ("retention",) * 2
    assert hybrid.layer_runs(cfg) == [("retention", 0, 2)]
    assert hybrid.recurrent_layers(cfg) == 2
    assert (cfg.rope_theta, cfg.tied_head) == (1e4, False)
    assert decoder.recurrent_name(cfg) == "retention layers"
    assert sorted(lm.jax_params()["runs"][0]) == sorted(
        ["wq", "wk", "wv", "wo", "w_decay", "b_decay", "q_norm", "k_norm",
         "norm1", "norm2", "w_gate", "w_up", "w_down"])


@pytest.mark.parametrize("what, kw", [
    ("degree other than 2", {"degree": 1}),
    ("hidden_act", {"hidden_act": "gelu"}),
    ("attention_bias", {"attention_bias": True}),
    ("sliding window", {"use_sliding_window": True}),
    ("rope_scaling", {"rope_scaling": {"type": "yarn"}}),
])
def test_builder_refuses_what_the_block_has_no_code_for(what, kw):
    with pytest.raises(ValueError, match=what):
        decoder.retention_lm(seed=0, **dict(KW, **kw))


def test_gates_lie_where_the_state_carries_across_a_chunk():
    """The weights as drawn (bfloat16, as the cell's): the gates of a
    random stream lie mostly between 0.9 and 0.999."""
    lm = decoder.retention_lm(seed=2, **dict(KW, units=256))
    lp = lm.jax_params()["runs"][0]
    u = jax.random.normal(jax.random.PRNGKey(0), (400, 256))
    g = jax.nn.sigmoid(u @ lp["w_decay"][0].astype(jnp.float32).T
                       + lp["b_decay"][0].astype(jnp.float32))
    inside = float(((g > 0.9) & (g < 0.999)).mean())
    assert inside > 0.8, inside


# ---------------------------------------------------------------------------
# the step programs through the paged cache
# ---------------------------------------------------------------------------
def drive(lm, S, chunk, prompts, n_decode, slots, first=None, fed=None):
    """Prefill each prompt chunk by chunk into its slot's one page, then
    ``n_decode`` greedy steps of all of them in one batch whose other
    lanes are inactive; with ``fed`` (one prompt) the decode steps take
    fed's tokens instead of their own."""
    cfg, params = lm.config, lm.jax_params()
    B = max(slots) + 2
    if fed is None:
        prefill = decoder.make_prefill_chunk(cfg, S, chunk)
        decode = decoder.make_decode_step(cfg, S)
    else:       # a planted fault: programs traced anew, not the cached ones
        prefill = hybrid.build_prefill_chunk(cfg, S, chunk)
        decode = hybrid.build_decode_step(cfg, S)
    kp, vp = (decoder.fresh_pool(cfg, B + 1, S) for _ in range(2))
    tables = np.zeros((B, 1), np.int32)
    out, logits, toks = {}, {}, {}
    for prompt, slot in zip(prompts, slots):
        tables[slot] = B - slot     # a lane's page is not its slot's
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
        out[slot], logits[slot], toks[slot] = (
            list(prompt), [np.asarray(last)], int(tok))
    active = np.zeros(B, bool)
    active[list(slots)] = True
    for i in range(n_decode):
        tokens, positions = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for slot in slots:
            if fed is not None:
                toks[slot] = fed[len(prompts[0]) + i]
            tokens[slot], positions[slot] = toks[slot], len(out[slot])
            out[slot].append(toks[slot])
        kp, vp, nxt, lg = decode(
            params, kp, vp, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(tables), jnp.asarray(active))
        for slot in slots:
            logits[slot].append(np.asarray(lg)[slot])
            toks[slot] = int(np.asarray(nxt)[slot])
    return [(out[s], np.stack(logits[s])) for s in slots], (kp, vp)


@pytest.mark.parametrize("name, S, chunk, lengths, slots, first", [
    ("one_chunk", 64, 16, [21], [0], None),
    ("across_a_chunk_boundary", 64, 16, [34], [1], None),
    ("unaligned_pos0", 64, 16, [30], [0], 5),
    ("lanes_of_different_lengths", 64, 16, [3, 9, 17, 30], [0, 2, 3, 5],
     None),
    ("a_page_of_one_chunk", 16, 16, [9], [0], None),
    ("a_chunk_longer_than_the_page", 8, 16, [2], [0], None),
    ("decode_to_the_page_end", 64, 16, [57], [0], None),
    ("lanes_from_unaligned_starts", 64, 16, [7, 30], [1, 4], 3),
])
def test_paged_programs_match_the_reference(lm, name, S, chunk, lengths,
                                            slots, first):
    prompts = [ids(100 + n, n) for n in lengths]
    runs, _ = drive(lm, S, chunk, prompts, 6, slots, first=first)
    for fed, got in runs:
        assert np.abs(got - reference(lm, fed, got.shape[0])).max() < 1e-5


def test_pools_are_what_fresh_pool_says(lm):
    cfg = lm.config
    kp = decoder.fresh_pool(cfg, 9, 64)
    F = hybrid.phi_width(8)
    # no attention layer: no rows; S of one KV head a pool, its rows
    # together, an array a layer (the decode step reads and writes each
    # whole, with no copy); z beside it as one flat row
    assert [a.shape for a in jax.tree.leaves(kp)] == [
        (0, 9, 64, 16), (9, 1, F, 8), (9, 1, F, 8), (2, 9, F)]
    assert kp.counts is None
    assert hybrid.state_entry_bytes(cfg) == 2 * (
        sum(a[0].nbytes for a in kp.ssm) + kp.conv[:, 0].nbytes) == (
        2 * 2 * (F * 8 + F) * 4)


def test_fork_page_takes_every_layers_entry_along(lm):
    """A copy-on-write fork copies the page's S in each layer's own array
    and its z; no other page moves."""
    cfg = lm.config
    pool = jax.tree.map(
        lambda a: jnp.arange(a.size, dtype=a.dtype).reshape(a.shape),
        decoder.fresh_pool(cfg, 5, 64))
    got = decoder.fork_page(jax.tree.map(jnp.copy, pool), 3, 1)
    for a, b in zip(pool.ssm, got.ssm):
        want = np.asarray(a).copy()
        want[1] = want[3]
        np.testing.assert_array_equal(np.asarray(b), want)
    want = np.asarray(pool.conv).copy()
    want[:, 1] = want[:, 3]
    np.testing.assert_array_equal(np.asarray(got.conv), want)


def test_a_large_state_is_read_and_written_in_whole_tiles():
    """At the published widths a pool's S is 33 024 rows a page: many pages
    at once are read in 43 blocks of 768 rows (a whole number of 8-row
    tiles, so the view is free), one page as a slice; the delta rule's and
    the state-space block's cuts are as they were."""
    ssm = jax.ShapeDtypeStruct((4, 21, 33024, 128), jnp.float32)
    pages = np.array([1, 4])
    assert hybrid._pieces(ssm, pages) == 43 and hybrid._pieces(ssm, 2) == 1
    assert hybrid._pieces(jax.ShapeDtypeStruct((3, 5, 4096, 128),
                                               jnp.float32), pages) == 4
    assert hybrid._pieces(jax.ShapeDtypeStruct((26, 5, 16, 2560),
                                               jnp.float32), pages) == 1
    assert hybrid._conv_parts(33024) == 2


# ---------------------------------------------------------------------------
# the planted faults of the check
# ---------------------------------------------------------------------------
H = hybrid
REAL = {n: getattr(H, n) for n in ("_retention_inputs", "phi", "_quotient",
                                   "_read_entry", "_write_entry",
                                   "_put_layer", "_put_state", "_rope",
                                   "_retention_pool_step")}


def _no_gate(u, lp, cfg, pos):
    q, k, v, log_g = REAL["_retention_inputs"](u, lp, cfg, pos)
    return q, k, v, jnp.zeros_like(log_g)


def _degree_one(x):
    width = H.phi_width(x.shape[-1])
    return jnp.concatenate(
        [x, jnp.zeros(x.shape[:-1] + (width - x.shape[-1],), x.dtype)], -1)


def _no_sqrt2(x):
    return jnp.concatenate([x[..., i:i + 1] * x[..., i:]
                            for i in range(x.shape[-1])], -1)


def _no_qk_norm(u, lp, cfg, pos):
    q, k, v = H._qkv(u, lp, cfg)
    log_g = jax.nn.log_sigmoid(H._mm_hi(u, lp["w_decay"])
                               + lp["b_decay"].astype(jnp.float32))
    return (H._rope(q, pos, cfg.rope_theta) / cfg.head_dim ** 0.5,
            H._rope(k, pos, cfg.rope_theta), v, log_g)


def _prefill_from_zero(kp, vp, li, page, fresh, cfg, join=True):
    return REAL["_read_entry"](kp, vp, li, page, True, cfg, join)


def _misread(u, lp, cfg, kp, vp, li, pages, fresh, positions, active):
    """Each lane's output off the page of the lane before it (lane 0: the
    last lane's, the scratch page); the writes are the sound ones."""
    step = REAL["_retention_pool_step"]
    _, kp2, vp2 = step(u, lp, cfg, kp, vp, li, pages, fresh, positions,
                       active)
    out, _, _ = step(u, lp, cfg, kp, vp, li, jnp.roll(pages, 1), fresh,
                     positions, active)
    return out, kp2, vp2


def _bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _bf16_entries(kp, vp, li, pages, h, conv, cfg):
    return REAL["_write_entry"](kp, vp, li, pages, jax.tree.map(_bf16, h),
                                _bf16(conv), cfg)


def _bf16_layer(pool, li, S, z):
    return REAL["_put_layer"](pool, li, _bf16(S), _bf16(z))


#: what each fault replaces in models/hybrid.py (the cell's decode step
#: hands a layer's new state to the pool, ``_put_state``, in the XLA form
#: through ``_put_layer``; a prefill chunk writes
#: its entries, ``_write_entry``)
FAULTS = {
    "gate_left_out": {"_retention_inputs": _no_gate},
    "degree_1": {"phi": _degree_one},
    "sqrt2_left_out": {"phi": _no_sqrt2},
    "normaliser_left_out": {"_quotient": lambda num, den: num},
    "prefill_hand_over_left_out": {"_read_entry": _prefill_from_zero},
    "decode_writes_no_entry": {"_put_state": lambda pool, li, S, z: pool},
    "a_lane_reads_another_page": {"_retention_pool_step": _misread},
    "rope_left_out": {"_rope": lambda x, pos, theta: x},
    "qk_norm_left_out": {"_retention_inputs": _no_qk_norm},
    "state_in_bfloat16": {"_write_entry": _bf16_entries,
                          "_put_layer": _bf16_layer},
}


@pytest.fixture(scope="module")
def sound(lm):
    """The sound programs' tokens and the reference over them: 2 chunks of
    16 into a page of 64 (the second from the state the first wrote), then
    decode steps across... the same page."""
    prompt = ids(77, 18)
    runs, _ = drive(lm, 64, 16, [prompt], 30, [0])
    fed, got = runs[0]
    ref = reference(lm, fed, got.shape[0])
    return prompt, fed, got, ref


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_planted_fault_moves_the_rows_past_the_limit(lm, sound, fault,
                                                          monkeypatch):
    """Each fault of the check, planted in the programs and fed the sound
    run's tokens, reads the rows' max error over the configuration's limit
    where the sound programs read under a thousandth of it."""
    limit = harness.load("configs", CONFIG + ".json")["check"]["limits"][
        "program_logits_max_err"]
    prompt, fed, got, ref = sound
    assert np.abs(got - ref).max() / ref.std() < limit / 1000
    for attr, fn in FAULTS[fault].items():
        monkeypatch.setattr(H, attr, fn)
    runs, _ = drive(lm, 64, 16, [prompt], 30, [0], fed=fed)
    err = np.abs(runs[0][1] - ref).max(-1) / ref.std()
    if fault == "state_in_bfloat16":
        # rounding the state shows by the width it is summed over: 36
        # features here read 0.008, 8 256 on the chip 0.145, seven times
        # the limit (PERF.md, PR 37); here a thousand times the sound
        assert err.max() > 1e3 * np.abs(got - ref).max() / ref.std()
    else:
        assert err.max() > limit, (fault, err.max())


@pytest.mark.parametrize("fault", ["decode_writes_no_entry",
                                   "a_lane_reads_another_page"])
def test_the_decode_steps_faults_reach_the_state_kernel(lm, sound, fault,
                                                        monkeypatch):
    """With the decode step's state kernel selected (in the Pallas
    interpreter, `ops/pallas/retention_state.py`), the sound programs read
    the reference's rows as the XLA form's do, and the decode step's two
    faults move them past the limit."""
    from mxnet_tpu.ops.pallas import retention_state as rs
    limit = harness.load("configs", CONFIG + ".json")["check"]["limits"][
        "program_logits_max_err"]
    prompt, fed, got, ref = sound
    monkeypatch.setattr(rs, "kernel_mode", lambda S, z: "interpret")
    runs, _ = drive(lm, 64, 16, [prompt], 30, [0], fed=fed)
    assert rs.last_path == "pallas-interpret"
    assert np.abs(runs[0][1] - ref).max() / ref.std() < limit / 1000
    for attr, fn in FAULTS[fault].items():
        monkeypatch.setattr(H, attr, fn)
    runs, _ = drive(lm, 64, 16, [prompt], 30, [0], fed=fed)
    err = np.abs(runs[0][1] - ref).max(-1) / ref.std()
    assert err.max() > limit, (fault, err.max())


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
def make_engine(lm, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("page_size", 32)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_ctx", 32)
    return serving.DecodeEngine(lm, **kw)


def counters(engine):
    return engine.metrics.snapshot()["models"][engine.name]["counters"]


@pytest.mark.parametrize("async_decode", [False, True])
def test_engine_greedy_equals_the_reference_under_preemption(lm,
                                                             async_decode):
    prompts = [ids(40 + n, n) for n in (5, 8, 13, 16, 21, 3)]
    engine = make_engine(lm, total_pages=3, async_decode=async_decode)
    try:
        futs = [engine.submit(p, max_new_tokens=9) for p in prompts]
        got = [f.result(300)["tokens"] for f in futs]
        assert counters(engine)["preemptions_total"] > 0
        assert got == [ref_greedy(lm, p, 9) for p in prompts]
        assert engine.alloc.check_leaks() == len(engine.prefix_cache or ())
    finally:
        engine.stop()


def test_state_bytes_are_counted_by_launch(lm, monkeypatch):
    spans = []
    real = generate.span

    def spy(name, **args):
        spans.append((name, args))
        return real(name, **args)
    monkeypatch.setattr(generate, "span", spy)
    engine = make_engine(lm, total_pages=4, async_decode=True)
    try:
        # 11 tokens: a chunk of 8 from the zero state, then one of 3 that
        # reads the entry the first wrote; 3 answers: two decode steps
        engine.submit(ids(12, 11), max_new_tokens=3).result(300)
        c, state = counters(engine), engine.stats()["state"]
    finally:
        engine.stop()
    entry = hybrid.state_entry_bytes(lm.config)
    launches = [a for n, a in spans if n == "engine.prefill_launch"]
    assert [(a["pos"], a["state_pages"], a["state_bytes"])
            for a in launches] == [(0, 1, entry), (8, 1, 2 * entry)]
    steps = [a for n, a in spans if n == "engine.decode_launch"]
    assert [a["state_bytes"] for a in steps] == [2 * entry] * 2
    assert state == {"layers": 2, "entry_bytes": entry,
                     "pool_bytes": 4 * entry, "pages_with_state_peak": 1,
                     "bytes_prefill_total": 3 * entry,
                     "bytes_decode_total": 4 * entry, "decode_path": "xla"}
    assert (c["state_bytes_prefill_total"],
            c["state_bytes_decode_total"]) == (3 * entry, 4 * entry)
    # a page is all state: no layer caches keys or values
    assert engine.alloc.stats()["pool_bytes"] == 3 * entry
    assert "state" not in make_engine(decoder.decoder_tiny_lm()).stats()


def test_the_engine_names_the_decode_steps_state_path(lm, monkeypatch):
    """With the state kernel selected the engine's answers are the
    reference's, `stats()["state"]["decode_path"]` says the kernel ran, and
    the decode launches still count two entries a live lane."""
    from mxnet_tpu.ops.pallas import retention_state as rs
    monkeypatch.setattr(rs, "kernel_mode", lambda S, z: "interpret")
    decoder._fn_cache.clear()           # the programs traced anew
    engine = make_engine(lm, total_pages=4, async_decode=True)
    try:
        prompt = ids(12, 11)
        got = engine.submit(prompt, max_new_tokens=3).result(300)["tokens"]
        c, state = counters(engine), engine.stats()["state"]
    finally:
        engine.stop()
        decoder._fn_cache.clear()
    assert got == ref_greedy(lm, prompt, 3)
    assert state["decode_path"] == "pallas-interpret"
    entry = hybrid.state_entry_bytes(lm.config)
    assert c["state_bytes_decode_total"] == 4 * entry


def tp2():
    return ShardingConfig.for_transformer(mesh_shape=(4, 2),
                                          axis_names=("dp", "tp"))


@pytest.mark.parametrize("what, kwargs", [
    ("speculative decoding", {"speculate": True}),
    ("a tp sharding", {"sharding": tp2}),
    ("an int8 KV pool", {"kv_dtype": "int8"}),
    ("weight quantisation", {"quantize": "int8"}),
    ("session migration", {"migrate": True, "pagestore": "127.0.0.1:1"}),
])
def test_engine_refuses_by_name(lm, what, kwargs):
    kwargs = {k: v() if callable(v) else v for k, v in kwargs.items()}
    with pytest.raises(ValueError, match=what + ".*retention layers"):
        make_engine(lm, **kwargs)


@pytest.mark.parametrize("where", ["engine", "decode", "prefill"])
def test_pages_shorter_than_a_sequence_are_refused(lm, where):
    """The entry is held once a sequence: a page table of more than one
    page is refused by name, by the engine and by the programs alike."""
    cfg, S = lm.config, 16
    with pytest.raises(ValueError, match="a sequence.*retention layers"):
        if where == "engine":
            make_engine(lm, page_size=S, max_ctx=32)
        pool = decoder.fresh_pool(cfg, 5, S)
        i32 = jax.ShapeDtypeStruct
        if where == "decode":
            decoder.make_decode_step(cfg, S).lower(
                lm.jax_params(), pool, pool, i32((2,), jnp.int32),
                i32((2,), jnp.int32), i32((2, 2), jnp.int32),
                i32((2,), jnp.bool_))
        else:
            decoder.make_prefill_chunk(cfg, S, 8).lower(
                lm.jax_params(), pool, pool, i32((8,), jnp.int32),
                i32((), jnp.int32), i32((), jnp.int32), i32((2,), jnp.int32))


def test_verify_program_is_refused(lm):
    with pytest.raises(ValueError, match="retention layers"):
        decoder.make_verify_step(lm.config, 32, 3)


# ---------------------------------------------------------------------------
# the configuration, the benchmark's counts and the cell's rehearsal
# ---------------------------------------------------------------------------
def test_configuration_has_the_catalogs_keys_and_states_its_cut():
    c = harness.load("configs", CONFIG + ".json")
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 40}
    assert c["num_hidden_layers"] == 4
    assert "pipeline of whole layers" in c["deployment"]
    for key in ("layer", "retention_degree", "rope_and_qk_norm", "gate",
                "q_scale", "state_form", "weights", "state", "check.limits"):
        assert isinstance(c["assumed"].get(key), str), key
    assert set(c["check"]["limits"]) == {"program_logits_max_err",
                                         "served_gap_max"}
    e = c["engine"]
    assert e["page_size"] == e["max_ctx"] == 4 * e["prefill_chunk"] == 1024
    assert e["total_pages"] == e["slots"] + 1 == 21
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(x for x in bench["configs"] if x["name"] == CONFIG)
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Brumby-14B-Base")
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value, key
        else:
            assert c[key] == value, key


def test_parameters_and_state_at_the_published_widths():
    """ISSUE 37's arithmetic, and the model the builder makes from the file
    has the same parameters and the same entry."""
    c = harness.load("configs", CONFIG + ".json")
    p = brumby_flops_bytes.param_counts(c)
    assert (p["q"], p["k"], p["v"], p["o"]) == (26214400, 5242880, 5242880,
                                                26214400)
    assert (p["mlp"], p["norms"], p["qk_norm"], p["gate"]) == (
        267386880, 10240, 256, 40968)
    assert p["layer"] == 330352904
    assert p["embedding"] == 1555824640
    assert p["total"] == 2877241376
    assert round(2 * p["total"] / 1e9, 2) == 5.75
    whole = dict(c, **c["published"])
    assert brumby_flops_bytes.param_counts(whole)["total"] == (
        40 * 330352904 + 1555824640 + 5120)
    assert brumby_flops_bytes.state_entry_bytes(c, layers=1) == 34080768
    assert brumby_flops_bytes.state_entry_bytes(c) == 136323072
    assert 21 * 136323072 == 2862784512
    net = hybrid.HybridLM(
        vocab_size=c["vocab_size"], num_layers=4, units=c["hidden_size"],
        hidden_size=c["intermediate_size"], num_heads=40, num_kv_heads=8,
        head_dim=128, recurrent="retention", rope_theta=1e6,
        tied_head=False)
    cfg = net.config
    count = 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
    for kind, lo, hi in hybrid.layer_runs(cfg):
        count += (hi - lo) * sum(int(np.prod(s)) for s in
                                 hybrid._run_shapes(cfg, kind).values())
    assert count == p["total"]
    assert hybrid.state_entry_bytes(cfg) == 136323072
    pool = jax.eval_shape(lambda: decoder.fresh_pool(cfg, 21, 1024))
    assert pool.rows.shape == (0, 21, 1024, 1024)
    assert 2 * sum(a.size * 4 for a in jax.tree.leaves(pool)) == 2862784512


def test_rooflines_read_the_engines_state_counters():
    c = harness.load("configs", CONFIG + ".json")
    weights = brumby_flops_bytes.weight_bytes(c)
    assert weights == 2 * (2877241376 - 151936 * 5120)
    assert 4.19e9 < weights < 4.21e9
    lane = 2 * 136323072
    facts = {"config": c, "peaks": {"flops_bf16": 197e12,
                                    "hbm_bytes_per_s": 819e9},
             "trace": {"modules": {"jit_step": [(weights + 12 * lane)
                                                / 819e9 * 2],
                                   "jit_prefill": [0.05]}},
             "stats": {"serving": {"counters": {
                 "decode_steps_total": 100,
                 "state_bytes_decode_total": 100 * 12 * lane,
                 "prefill_launches_total": 10,
                 "prefill_tokens_total": 2000,
                 "state_bytes_prefill_total": 10 * lane}}}}
    assert brumby_flops_bytes.decode_step_roofline(facts) == pytest.approx(
        50.0)
    share = brumby_flops_bytes.prefill_launch_roofline(facts)
    bytes_s = (weights + lane) / 819e9
    flops_s = brumby_flops_bytes.prefill_launch_flops(c, 200) / 197e12
    assert share == pytest.approx(100 * max(bytes_s, flops_s) / 0.05)
    # a program that counts no state bytes reads nothing
    facts["stats"]["serving"]["counters"].pop("state_bytes_decode_total")
    assert brumby_flops_bytes.decode_step_roofline(facts) is None
    assert brumby_flops_bytes.prefill_launch_roofline({}) is None


TINY = {
    "allow_cpu": True,
    "config": {"num_hidden_layers": 2, "hidden_size": 32,
               "intermediate_size": 48, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 8, "vocab_size": 128,
               "max_length": 64,
               "engine": {"slots": 4, "page_size": 64, "max_ctx": 64,
                          "total_pages": 5, "prefill_chunk": 16},
               # the second prefill chunk from the state the first wrote,
               # as the cell's own check
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
    assert {"program_logits_max_err", "served_gap_max"} <= set(
        result["checks"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    group = "per_layer" if trace_flag else "end_to_end"
    allowed = {m["name"] for m in bench[group]
               if CELL in m.get("workloads", [CELL])}
    assert set(result["metrics"]) <= allowed
    if trace_flag:      # what needs no device trace and no memory_stats
        assert {"decode_occupancy", "engine_step_ms_p50",
                "pipe_flushes_per_step", "prefill_launches_per_step"} <= set(
            result["metrics"])
    else:
        assert set(result["metrics"]) == allowed == {"served_tokens_per_s",
                                                     "setup_s"}


# ---------------------------------------------------------------------------
# the other recurrent models' programs are the parent's
# ---------------------------------------------------------------------------
#: sha256 of the lowered text (CPU) of the two step programs of jamba's and
#: solar's serving configurations at their cells' geometry, taken at
#: 9e85419, before the retention kind: it adds no operation to them.  The
#: programs have since taken a launch's inputs in one buffer; their bodies
#: over the separate inputs (``StepProgram.body``) still lower to this text.
PARENT_LOWERED = {
    "jamba2-3b-serve": (
        "e08a62de2f667845bab5abd4f2d51a8c0891268472b8654a70824e1f30e1460e",
        "6892ec347a9bf52c0dea50db11edcbfef5f388f14cd52e0a5c467b5456bcbd11"),
    "solar-open2-ep8-serve": (
        "9738871932d49995c4cd550fe0be1e670f0b6d5a5908866c205ff70397368a43",
        "a1d3497f0f02941b8ec3ee6a0b709eb15ab02e9397101d1c8385c948a933f562"),
}
#: the same of the programs over one buffer a launch, as the engine runs them
PACKED_LOWERED = {
    "jamba2-3b-serve": (
        "6d366689354938e80fe535a9775bb851627e15eaf4e408cbef22a9c5a0265297",
        "affa97ec244660c2e8c54053dba065197f1311aab7acbdc3eaf6be4d24cc5469"),
    "solar-open2-ep8-serve": (
        "f4338a2dee2b37dbae29496013cd44a727ea28b423297c58d4f061e9fb20c32f",
        "41127f942eb922b89b10afa0ee3b8f0b6cc19a455762fdb2f1d68833fdc7cc42"),
}


@pytest.mark.parametrize("name", sorted(PARENT_LOWERED))
def test_jamba_and_solar_programs_lower_to_the_parents_text(name,
                                                            monkeypatch):
    """Shapes only: the builder's model is made without its weights."""
    monkeypatch.setattr(hybrid, "hybrid_lm",
                        lambda seed=0, **kw: hybrid.HybridLM(**kw))
    c = harness.load("configs", name + ".json")
    net = harness.resolve(c["builder"])(
        seed=0, **{k: c[v] for k, v in c["builder_kwargs"].items()})
    cfg = net.config

    def sds(p):
        return jax.ShapeDtypeStruct(tuple(p.shape), net.dtype)
    params = {"embed": sds(net.embed), "norm_f": sds(net.norm_f),
              "runs": [{k: sds(getattr(r, k)) for k in r.leaves}
                       for r in net.runs]}
    if not cfg.tied_head:
        params["head"] = sds(net.head)
    e = c["engine"]
    S, B, T, kvd = (e["page_size"], e["slots"], e["prefill_chunk"],
                    e.get("kv_dtype"))
    pps = e["max_ctx"] // S
    pool = jax.eval_shape(lambda: decoder.fresh_pool(cfg, B * pps + 1, S,
                                                     kvd))

    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)
    programs = (decoder.make_decode_step(cfg, S, kv_dtype=kvd),
                decoder.make_prefill_chunk(cfg, S, T, kv_dtype=kvd))
    inputs = ((i32(B), i32(B), i32(B, pps), jax.ShapeDtypeStruct(
        (B,), jnp.bool_)), (i32(T), i32(), i32(), i32(pps)))

    def digests(lower):
        return tuple(hashlib.sha256(lower(p).lower(
            params, pool, pool, *rest).as_text().encode()).hexdigest()
            for p, rest in zip(programs, inputs))
    assert digests(lambda p: jax.jit(p.body, donate_argnums=(1, 2))
                   ) == PARENT_LOWERED[name]
    assert digests(lambda p: p) == PACKED_LOWERED[name]
