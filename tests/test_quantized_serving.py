"""Quantized LLM serving (ISSUE 16): weight-only int8/int4 decode +
int8 KV-cache pages.

The acceptance posture is two-tier, mirroring the paper's CNN
quantization story lifted to serving:

- WITHIN the quantized engine everything stays BIT-parity: spec-decode
  vs plain greedy, migrated vs unmigrated continuations, prefix-cache
  CoW vs cold prefill — quantization changes the numbers, not the
  invariants, because every path reads the same integer weights and the
  same per-page KV scales.
- ACROSS the fp32 <-> quantized boundary the oracle is greedy-token
  AGREEMENT (thresholded >= 0.99 for the int8 rung), because bit-parity
  is definitionally gone the moment weights drop bits.

Kernel-level: the fused dequant-matmul under
``MXNET_QUANT_MATMUL=interpret`` must be bit-exact against the XLA
reference (they compute the identical formula op-for-op), and the wire
format (pack_session v2) must round-trip scales with their own CRC and
still read v1 blobs.
"""
from __future__ import annotations

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.models import decoder
from mxnet_tpu.ops.pallas import quant_matmul as qmm
from mxnet_tpu.serving.kvcache import (PageAllocator, pack_session,
                                       unpack_session)
from mxnet_tpu.serving.quantize import (QuantizedLM, calibrate_kv_ranges,
                                        quantize_lm, quantize_params)

pytestmark = [pytest.mark.quant, pytest.mark.llm]

VOCAB = 128

# the agreement battery: varied prompts, enough tokens that a 0.99
# threshold tolerates exactly one greedy tie-flip across the battery
PROMPTS = [[1, 2, 3, 4, 5], [7, 7, 7, 7], [3, 1, 4, 1, 5, 9, 2, 6],
           [11, 13, 17, 19, 23], [2, 4, 6, 8, 10, 12], [42, 17]]
NEW = 20


@pytest.fixture(scope="module")
def lm():
    return decoder.decoder_tiny_lm(seed=0, vocab_size=VOCAB)


def make_engine(lm, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_ctx", 64)
    return serving.DecodeEngine(lm, name="llm", **kw)


def greedy_oracle(model, prompt, n):
    """Token-by-token full forward.  Works for the fp model AND a
    QuantizedLM — full_forward dispatches quantized leaves through
    quant_matmul, so this is the same-weights oracle for the engine."""
    params, cfg = model.jax_params(), model.config
    toks = list(prompt)
    for _ in range(n):
        logits = decoder.full_forward(params, cfg,
                                      jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def run_battery(eng, prompts=PROMPTS, n=NEW):
    futs = [eng.submit(list(p), n) for p in prompts]
    return [f.result(timeout=300)["tokens"] for f in futs]


def agreement(a, b):
    """Positionwise greedy-token agreement across a battery."""
    tot = hit = 0
    for xa, xb in zip(a, b):
        tot += max(len(xa), len(xb))
        hit += sum(1 for x, y in zip(xa, xb) if x == y)
    return hit / max(tot, 1)


# The int8 rungs are held to the float32 engine where float32 itself
# decides: the battery's float32 sequences are runs of one repeated token
# (random weights), and 4 of their 120 positions are near-ties whose top
# two logits lie 0.016-0.099 of the row's std apart.  The int8 engine's
# logits stand 0.108-0.155 of the std off the float32 reference (PERF.md
# section 6, PR 28, at GPT-2-small's sizes), so below that margin the two
# engines may name either token and neither is wrong.
INT8_LOGITS_ERR = 0.155


def tf_agreement(eng, fp_tokens, prompts=PROMPTS, max_ctx=64, decided=None):
    """Teacher-forced greedy agreement: for every position of the fp
    engine's trajectories, ask ``eng`` for ONE next token off the same
    prefix and compare.  Free-running comparison is the wrong oracle
    for a quantized engine — a single near-tie flip cascades the rest
    of the trajectory into a different attractor, so one flipped token
    would read as ~17% disagreement.  Per-step agreement is what the
    quantization actually changes.  ``decided`` (one list of bools per
    prompt, `fp_decided`) leaves out the positions float32 does not
    decide."""
    futs, want = [], []
    for n, (p, t) in enumerate(zip(prompts, fp_tokens)):
        hist = list(p) + t
        for i in range(len(t)):
            pre = hist[:len(p) + i]
            if len(pre) + 1 > max_ctx:
                break
            if decided is not None and not decided[n][i]:
                continue
            futs.append(eng.submit(pre, 1))
            want.append(t[i])
    got = [f.result(timeout=300)["tokens"][0] for f in futs]
    return sum(1 for g, w in zip(got, want) if g == w) / len(want)


@pytest.fixture(scope="module")
def fp_tokens(lm):
    eng = make_engine(lm)
    try:
        return run_battery(eng)
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def fp_decided(lm, fp_tokens):
    """Per position of `fp_tokens`: the float32 forward's top two logits
    lie further apart than the int8 engine's error."""
    params, cfg = lm.jax_params(), lm.config
    out = []
    for p, t in zip(PROMPTS, fp_tokens):
        logits = onp.asarray(decoder.full_forward(
            params, cfg, jnp.asarray([list(p) + t], jnp.int32)))[0]
        rows = logits[len(p) - 1:len(p) - 1 + len(t)]
        top2 = onp.sort(rows, axis=-1)[:, -2:]
        out.append(list((top2[:, 1] - top2[:, 0]) / rows.std(axis=-1)
                        > INT8_LOGITS_ERR))
    # the bar must not empty the battery: 116 of 120 positions stand
    assert sum(map(sum, out)) >= 0.9 * sum(map(len, fp_tokens))
    return out


# ---------------------------------------------------------------------------
# quantize / dequantize units
# ---------------------------------------------------------------------------
def test_w8_round_trip_per_channel():
    rng = onp.random.RandomState(0)
    w = rng.randn(24, 32).astype("float32") * rng.rand(24, 1).astype("f")
    w[3] = 0.0                                  # dead output channel
    qw = qmm.quantize_w8(w)
    assert qw.q.dtype == jnp.int8 and qw.s.dtype == jnp.float32
    assert qw.q.shape == (24, 32) and qw.s.shape == (24,)
    assert int(jnp.abs(qw.q).max()) <= 127
    deq = onp.asarray(qmm.dequantize_weight(qw))
    # symmetric rounding error is at most half a step per channel
    err = onp.abs(deq - w).max(axis=1)
    assert (err <= onp.asarray(qw.s) * 0.5 + 1e-7).all()
    # zero channel: scale 1.0 (no div-by-zero), codes exactly zero
    assert float(qw.s[3]) == 1.0 and not onp.asarray(qw.q[3]).any()


def test_w4_pack_groups_and_shapes():
    rng = onp.random.RandomState(1)
    w = rng.randn(16, 64).astype("float32")
    qw = qmm.quantize_w4(w, group=16)
    assert qw.q.dtype == jnp.uint8 and qw.q.shape == (16, 32)
    assert qw.s.shape == (16, 4)                # 64 / 16 groups
    # the group size is derivable from the shapes (wire/TP invariant)
    assert 2 * qw.q.shape[1] // qw.s.shape[1] == 16
    vals = onp.asarray(qmm.unpack_int4(qw.q))
    assert vals.min() >= -7 and vals.max() <= 7  # symmetric codebook
    deq = onp.asarray(qmm.dequantize_weight(qw))
    step = onp.repeat(onp.asarray(qw.s), 16, axis=1)
    assert (onp.abs(deq - w) <= step * 0.5 + 1e-7).all()
    # pack/unpack is lossless for in-range codes
    codes = rng.randint(-7, 8, size=(8, 10)).astype("int8")
    assert (onp.asarray(qmm.unpack_int4(qmm.pack_int4(jnp.asarray(codes))))
            == codes).all()
    # group clamps to a divisor of the input dim
    assert qmm.group_for(48, 128) == 48 and qmm.group_for(64, 24) == 8
    with pytest.raises(ValueError, match="even"):
        qmm.quantize_w4(w[:, :63])


def test_quantize_params_structure(lm):
    params = lm.jax_params()
    qp = quantize_params(params, "int8")
    for lp, qlp in zip(params["layers"], qp["layers"]):
        for kind in decoder._QUANT_KINDS:
            assert isinstance(qlp[kind], qmm.QuantW8)
            assert qlp[kind].q.shape == lp[kind].shape  # (O, I) storage
        # everything else untouched (embeddings/biases/norms stay fp32)
        assert qlp["bq"] is lp["bq"] and qlp["ln1g"] is lp["ln1g"]
    assert qp["embed"] is params["embed"]
    with pytest.raises(ValueError, match="mode"):
        quantize_params(params, "int2")
    # int4 under tp=2: row-parallel leaves (wo, w2) shrink the group to
    # the per-shard input dim so scales never straddle shards
    qp4 = quantize_params(params, "int4", group=128, tp=2)
    lp4 = qp4["layers"][0]
    units = lm.config.units
    assert 2 * lp4["wo"].q.shape[1] // lp4["wo"].s.shape[1] \
        == qmm.group_for(units // 2, 128)
    assert 2 * lp4["wq"].q.shape[1] // lp4["wq"].s.shape[1] \
        == qmm.group_for(units, 128)            # column-parallel: full I


def test_quantize_lm_wrapper(lm):
    q = quantize_lm(lm, "int8")
    assert isinstance(q, QuantizedLM)
    assert q.config is lm.config and q.quant_token() == ("int8",)
    # re-quantizing unwraps to fp first (modes don't compose)
    q4 = quantize_lm(q, "int4", group=32)
    assert q4.model is lm and q4.quant_token() == ("int4", 32)
    with pytest.raises(ValueError, match="mode"):
        quantize_lm(lm, "fp8")
    # params cached per tp degree only where groups depend on it
    assert q.jax_params(tp=1) is q.jax_params(tp=2)      # int8: tp-blind
    assert q4.jax_params(tp=1) is not q4.jax_params(tp=2)


# ---------------------------------------------------------------------------
# fused kernel vs XLA reference (interpret-mode bit-exactness oracle)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_quant_matmul_interpret_bit_exact(monkeypatch, mode):
    # Inputs on which every float32 product and partial sum is exact, so
    # the bytes do not depend on the order in which a host's dot adds
    # them: whole-number activations, and weights that are whole numbers
    # times a power of two with the full range in every row / group (the
    # quantizer's amax / 127 or amax / 7 is then that power of two and the
    # codes are the whole numbers back).
    rng = onp.random.RandomState(2)
    x = jnp.asarray(rng.randint(-8, 9, (4, 64)).astype("float32"))
    top, group = (127, 64) if mode == "int8" else (7, 16)
    codes = rng.randint(-top, top + 1, (48, 64 // group, group))
    codes[:, :, 0] = top
    scale = 2.0 ** rng.randint(-3, 4, (48, 64 // group, 1))
    w = (codes * scale).reshape(48, 64).astype("float32")
    qw = (qmm.quantize_w8(w) if mode == "int8"
          else qmm.quantize_w4(w, group=16))
    onp.testing.assert_array_equal(onp.asarray(qw.s).reshape(scale.shape),
                                   scale)
    onp.testing.assert_array_equal(onp.asarray(qmm.dequantize_weight(qw)), w)
    ref = qmm.quant_matmul_reference(x, qw)
    monkeypatch.setenv("MXNET_QUANT_MATMUL", "interpret")
    before = qmm.trace_counts["quant_matmul"]
    out = qmm.quant_matmul(x, qw)
    assert qmm.last_path == "pallas-interpret"
    assert qmm.trace_counts["quant_matmul"] == before + 1
    assert onp.asarray(out).tobytes() == onp.asarray(ref).tobytes()
    assert onp.array_equal(onp.asarray(ref),
                           onp.asarray(x, "float64") @ w.T.astype("float64"))
    # leading dims flow through
    x3 = jnp.asarray(rng.randn(2, 3, 64).astype("float32"))
    assert qmm.quant_matmul(x3, qw).shape == (2, 3, 48)


def test_quant_matmul_disabled_uses_reference(monkeypatch):
    monkeypatch.setenv("MXNET_QUANT_MATMUL", "0")
    assert qmm.quant_mode() is None
    qw = qmm.quantize_w8(onp.eye(8, dtype="float32") * 2.0)
    out = qmm.quant_matmul(jnp.ones((1, 8), jnp.float32), qw)
    assert qmm.last_path == "xla"
    assert onp.allclose(onp.asarray(out), 2.0)
    monkeypatch.setenv("MXNET_QUANT_MATMUL", "interpret")
    assert qmm.quant_mode() == "interpret"


# ---------------------------------------------------------------------------
# engine parity: same-weights bit-parity, cross-precision agreement
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode,group", [("int8", None), ("int4", 32)])
def test_engine_bit_parity_with_quantized_oracle(lm, mode, group):
    """fp KV pages + quantized weights: the engine's chunked-prefill +
    paged-decode path must reproduce the quantized full_forward oracle
    token-for-token — quantization must not break PR-7's core
    invariant."""
    qlm = quantize_lm(lm, mode, group=group or 128)
    eng = make_engine(lm, quantize=mode,
                      **({"quant_group": group} if group else {}))
    try:
        for p in PROMPTS[:3]:
            got = eng.submit(list(p), 8).result(60)["tokens"]
            assert got == greedy_oracle(qlm, p, 8)
        st = eng.stats()
        assert st["quant"]["weights"] == mode
        assert st["quant"]["kv_dtype"] == "float32"
    finally:
        eng.stop()
    assert eng.alloc.num_used == 0
    eng.alloc.check_leaks()


def test_int8_engine_agreement_battery(lm, fp_tokens, fp_decided):
    """The serving acceptance gate: int8 weights + int8 KV pages agree
    with the fp32 engine on >= 99% of the greedy tokens that float32
    decides (`INT8_LOGITS_ERR`) across the battery."""
    eng = make_engine(lm, quantize="int8", kv_dtype="int8")
    try:
        score = tf_agreement(eng, fp_tokens, decided=fp_decided)
        st = eng.stats()
    finally:
        eng.stop()
    assert score >= 0.99
    assert st["quant"] == {"weights": "int8", "group": None,
                           "kv_dtype": "int8", "tokens_resident": 0}
    eng.alloc.check_leaks()


def test_int4_engine_agreement_battery(lm, fp_tokens):
    # int4 is the lossier rung: the gate is looser but still must track
    # the fp engine on a strong majority of greedy steps
    eng = make_engine(lm, quantize="int4", quant_group=32)
    try:
        score = tf_agreement(eng, fp_tokens)
    finally:
        eng.stop()
    assert score >= 0.9
    eng.alloc.check_leaks()


def test_int8_kv_only_agreement(lm, fp_tokens, fp_decided):
    # kv_dtype=int8 with fp weights: per-page scale latch alone
    eng = make_engine(lm, kv_dtype="int8")
    try:
        score = tf_agreement(eng, fp_tokens, decided=fp_decided)
        st = eng.stats()
    finally:
        eng.stop()
    assert score >= 0.99
    assert st["quant"]["weights"] is None
    assert st["quant"]["kv_dtype"] == "int8"
    eng.alloc.check_leaks()


# ---------------------------------------------------------------------------
# in-engine invariants survive quantization: spec, prefix CoW, capacity
# ---------------------------------------------------------------------------
@pytest.mark.spec
@pytest.mark.parametrize("k", [1, 2])
def test_speculative_bit_parity_in_quantized_engine(lm, k):
    """Spec-vs-plain stays BIT-identical inside the quantized engine:
    draft and verify read the same integer weights and the same KV page
    scales (the page-start latch makes scales write-order-invariant)."""
    plain = make_engine(lm, quantize="int8", kv_dtype="int8")
    spec = make_engine(lm, quantize="int8", kv_dtype="int8",
                       speculate=True, spec_k=k, drafter="ngram")
    try:
        t_plain = run_battery(plain, PROMPTS[:4], 12)
        t_spec = run_battery(spec, PROMPTS[:4], 12)
        assert t_spec == t_plain
        assert spec.stats()["speculative"]["drafter"] == "ngram"
    finally:
        plain.stop()
        spec.stop()
    for e in (plain, spec):
        assert e.alloc.num_used == 0
        e.alloc.check_leaks()


@pytest.mark.migration
def test_prefix_cache_cow_on_int8_pages(lm):
    """Prefix sharing + CoW forks carry int8 pages: page codes AND their
    scales alias on a hit and copy together on the fork, so warm paths
    stay bit-identical to cold ones within the quantized engine."""
    cold_eng = make_engine(lm, quantize="int8", kv_dtype="int8")
    eng = make_engine(lm, quantize="int8", kv_dtype="int8",
                      prefix_cache=True)
    sys_prompt = list(range(1, 17))             # 2 full pages
    tails = [[20, 21], [30, 31], [20, 21, 60, 61]]
    try:
        cold = [cold_eng.submit(sys_prompt + t, 6).result(60)["tokens"]
                for t in tails]
        warm = [eng.submit(sys_prompt + t, 6).result(60)["tokens"]
                for t in tails]
        assert warm == cold
        snap = eng.metrics.snapshot()["models"]["llm"]
        assert snap["counters"]["prefix_hits_total"] >= 1
        eng.alloc.check_leaks()
    finally:
        cold_eng.stop()
        eng.stop()
    for e in (cold_eng, eng):
        assert e.alloc.num_used == 0
        e.alloc.check_leaks()


def test_int8_kv_capacity_ratio(lm):
    """The capacity win the int8 KV pages exist for: bytes per cached
    token (codes + amortized per-page scales) is >= 1.9x smaller than
    fp32 pages, so a fixed pool byte budget holds >= 1.9x the resident
    sessions."""
    fp = make_engine(lm)
    q = make_engine(lm, kv_dtype="int8")
    try:
        fpb = fp.alloc.stats()["kv_bytes_per_token"]
        qb = q.alloc.stats()["kv_bytes_per_token"]
        assert fpb / qb >= 1.9
        assert q.alloc.stats()["kv_dtype"] == "int8"
        assert fp.alloc.stats()["kv_dtype"] == "float32"
        # tokens-resident gauge: parked session holds its pages (the
        # final emitted token was never fed back, so its KV isn't
        # cached: 4 prompt + 3 decoded inputs)
        q.submit([1, 2, 3, 4], 4, session="s").result(60)
        assert q.stats()["quant"]["tokens_resident"] == 7
        snap = q.metrics.snapshot()["models"]["llm"]["generate"]
        assert snap["kv_bytes_per_token"] == qb
        assert "kv_tokens_resident" in snap
    finally:
        fp.stop()
        q.stop()


# ---------------------------------------------------------------------------
# migration: int8 pages travel; dtype mismatch is typed, never garbage
# ---------------------------------------------------------------------------
@pytest.mark.migration
def test_export_import_int8_bit_identical(lm):
    e1 = make_engine(lm, quantize="int8", kv_dtype="int8")
    e2 = make_engine(lm, quantize="int8", kv_dtype="int8")
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
    try:
        r1 = e1.submit(prompt, 5, session="mig").result(60)
        blob = e1.export_session("mig")
        meta, k, v, ks, vs = unpack_session(blob, with_scales=True)
        assert k.dtype == onp.int8 and ks is not None
        assert ks.shape == k.shape[:3] and ks.dtype == onp.float32
        e2.import_session(blob)
        # the continuation both engines would produce is the SAME
        # program over the SAME codes + scales: bit-identical
        r1b = e1.submit([7], 5, session="mig", resume=True).result(60)
        # (re-import after e1 advanced: fresh copy of the original blob)
        e2.submit([7], 5, session="mig", resume=True).result(60)
        e2b = make_engine(lm, quantize="int8", kv_dtype="int8")
        try:
            e2b.import_session(blob)
            r2 = e2b.submit([7], 5, session="mig", resume=True).result(60)
            assert r2["tokens"] == r1b["tokens"]
        finally:
            e2b.stop()
    finally:
        e1.stop()
        e2.stop()
    for e in (e1, e2):
        assert e.alloc.num_used == 0
        e.alloc.check_leaks()


@pytest.mark.migration
def test_kv_dtype_mismatch_typed_error(lm):
    qe = make_engine(lm, kv_dtype="int8")
    fe = make_engine(lm)
    try:
        qe.submit([1, 2, 3], 3, session="a").result(60)
        fe.submit([1, 2, 3], 3, session="b").result(60)
        qblob = qe.export_session("a")
        fblob = fe.export_session("b")
        with pytest.raises(ValueError, match="does not match"):
            fe.import_session(qblob)            # int8 blob -> fp engine
        with pytest.raises(ValueError, match="does not match"):
            qe.import_session(fblob)            # fp blob -> int8 engine
    finally:
        qe.stop()
        fe.stop()
    for e in (qe, fe):
        assert e.alloc.num_used == 0
        e.alloc.check_leaks()


# ---------------------------------------------------------------------------
# tensor parallelism: the agreement oracle composes with TP
# ---------------------------------------------------------------------------
@pytest.mark.multichip
@pytest.mark.parametrize("mode,group", [("int8", None), ("int4", 16)])
def test_quantized_engine_tensor_parallel(lm, mode, group):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    from mxnet_tpu.parallel.shardcfg import ShardingConfig
    scfg = ShardingConfig.for_transformer(mesh_shape=(4, 2),
                                          axis_names=("dp", "tp"))
    kw = {"quant_group": group} if group else {}
    one = make_engine(lm, quantize=mode, kv_dtype="int8", **kw)
    tp = make_engine(lm, quantize=mode, kv_dtype="int8", sharding=scfg,
                     **kw)
    try:
        assert tp.tp == 2
        t1 = run_battery(one, PROMPTS[:4], 12)
        # TP reorders the row-parallel reduction, so the oracle is the
        # same thresholded per-step agreement as the fp<->quant boundary
        assert tf_agreement(tp, t1, prompts=PROMPTS[:4]) >= 0.99
        st = tp.stats()
        assert st["quant"]["weights"] == mode
        assert st["sharding"]["tp"] == 2
    finally:
        one.stop()
        tp.stop()
    for e in (one, tp):
        assert e.alloc.num_used == 0
        e.alloc.check_leaks()


# ---------------------------------------------------------------------------
# wire format v2: scales blob + own CRC, v1 back-compat
# ---------------------------------------------------------------------------
def test_pack_session_v2_round_trip_and_scales_crc():
    rng = onp.random.RandomState(3)
    k = rng.randint(-127, 128, size=(2, 2, 3, 8, 4)).astype("int8")
    v = rng.randint(-127, 128, size=(2, 2, 3, 8, 4)).astype("int8")
    ks = rng.rand(2, 2, 3).astype("float32")
    vs = rng.rand(2, 2, 3).astype("float32")
    meta = {"sid": "s", "pos": 17, "history": [1, 2]}
    blob = pack_session(meta, k, v, k_scales=ks, v_scales=vs)
    m2, k2, v2, ks2, vs2 = unpack_session(blob, with_scales=True)
    assert m2 == meta
    assert k2.tobytes() == k.tobytes() and v2.tobytes() == v.tobytes()
    assert ks2.tobytes() == ks.tobytes() and vs2.tobytes() == vs.tobytes()
    assert k2.dtype == onp.int8 and ks2.dtype == onp.float32
    # a flipped byte in the scales tail trips the SCALES CRC, not the
    # payload one (independent failure domains)
    bad = bytearray(blob)
    bad[-1] ^= 0xFF
    with pytest.raises(ValueError, match="scales CRC"):
        unpack_session(bytes(bad), with_scales=True)
    with pytest.raises(ValueError, match="truncated"):
        unpack_session(blob[:-8], with_scales=True)
    # both-or-neither: half a scale pair is a caller bug
    with pytest.raises(ValueError):
        pack_session(meta, k, v, k_scales=ks)


def test_pack_session_v1_compat():
    rng = onp.random.RandomState(4)
    k = rng.randn(2, 2, 3, 8, 4).astype("float32")
    v = rng.randn(2, 2, 3, 8, 4).astype("float32")
    blob = pack_session({"sid": "s"}, k, v)
    # no scales -> the v1 wire image: header carries no kv_dtype key, a
    # v1 reader decodes it unchanged
    hlen = int(onp.frombuffer(blob[4:8], "<u4")[0])
    assert b'"kv_dtype"' not in blob[8:8 + hlen]
    m, k2, v2 = unpack_session(blob)
    assert k2.tobytes() == k.tobytes()
    # a v1 blob read through the v2 API reports no scales
    m, k2, v2, ks, vs = unpack_session(blob, with_scales=True)
    assert ks is None and vs is None


def test_allocator_scales_pool_accounting():
    a = PageAllocator(total_pages=9, page_size=4, kv_dtype="int8",
                      page_bytes=128, scale_page_bytes=16)
    st = a.stats()
    assert st["kv_dtype"] == "int8"
    assert st["scale_page_bytes"] == 16
    # 8 usable pages (page 0 reserved); scales pool counted in
    assert st["pool_bytes"] == 8 * (128 + 16)
    assert st["kv_bytes_per_token"] == (128 + 16) / 4
    a.alloc("s", 2)
    assert a.stats()["used_bytes"] == 2 * (128 + 16)
    a.free("s")
    a.check_leaks()
    with pytest.raises(ValueError, match="kv_dtype"):
        PageAllocator(total_pages=4, page_size=4, kv_dtype="fp8")


# ---------------------------------------------------------------------------
# config knobs, replica spec plumbing, calibration diagnostic
# ---------------------------------------------------------------------------
def test_env_knobs_boot_quantized_engine(lm, monkeypatch):
    monkeypatch.setenv("MXNET_QUANT_WEIGHTS", "int4")
    monkeypatch.setenv("MXNET_QUANT_GROUP", "32")
    monkeypatch.setenv("MXNET_QUANT_KV", "int8")
    eng = make_engine(lm)
    try:
        st = eng.stats()["quant"]
        assert st["weights"] == "int4" and st["group"] == 32
        assert st["kv_dtype"] == "int8"
    finally:
        eng.stop()
    with pytest.raises(ValueError):
        make_engine(lm, kv_dtype="int4")        # KV ladder is int8-only
    with pytest.raises(ValueError):
        make_engine(lm, quantize="fp8")


def test_config_registry_covers_quant_knobs():
    from mxnet_tpu import config
    d = config.describe()
    for knob in ("MXNET_QUANT_WEIGHTS", "MXNET_QUANT_KV",
                 "MXNET_QUANT_GROUP", "MXNET_QUANT_MATMUL"):
        assert knob in d and d[knob].status == "honored"
        assert d[knob].consumer


def test_replica_resolve_quant_block():
    from mxnet_tpu.serving.replica import resolve_quant
    assert resolve_quant(None) == {}
    assert resolve_quant({}) == {}
    assert resolve_quant({"weights": "int8", "kv": "int8"}) \
        == {"quantize": "int8", "kv_dtype": "int8"}
    assert resolve_quant({"weights": "int4", "group": 64}) \
        == {"quantize": "int4", "quant_group": 64}


def test_launch_census_quant_arm(lm):
    """The dispatch bill of the quantized decode step: int8 weights
    dequantize inside the GEMMs the float tower already launches (same
    count), int8 KV pages add the scale latch, and on the CPU every GEMM
    is the XLA reference (no Pallas call)."""
    cfg, geometry = lm.config, (8, 4, 8, 33)
    fp = decoder.decode_launch_stats(lm.jax_params(), cfg, *geometry)
    qparams = quantize_lm(lm, "int8").jax_params()
    w8 = decoder.decode_launch_stats(qparams, cfg, *geometry,
                                     quant=("int8",))
    w8kv8 = decoder.decode_launch_stats(qparams, cfg, *geometry,
                                        quant=("int8",), kv_dtype="int8")
    assert w8 == fp and fp["launches_per_step"] > 0
    assert w8kv8["launches_per_step"] > fp["launches_per_step"]
    assert w8kv8["pallas_per_step"] == 0  # CPU: XLA reference


def test_calibrate_kv_ranges_diagnostic(lm):
    rng = onp.random.RandomState(5)
    batches = [rng.randint(0, VOCAB, size=(2, 12)) for _ in range(3)]
    th = calibrate_kv_ranges(lm, batches)
    L = lm.config.num_layers
    assert set(th) == {"L%d/%s" % (i, kv)
                      for i in range(L) for kv in ("k", "v")}
    for lo, hi in th.values():
        assert hi > 0 and hi >= lo
    # works on the wrapped model too (observes the fp forward)
    assert set(calibrate_kv_ranges(quantize_lm(lm), batches[:1])) == set(th)
