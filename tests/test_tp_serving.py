"""Tensor-parallel LLM decode serving (ISSUE 13).

`DecodeEngine(sharding=ShardingConfig)` composes the PR-9 dp×tp mesh
into the PR-7/8/12 decode stack: params go Megatron column/row-parallel
through the unchanged `for_transformer()` rules, KV pages shard along
KV heads, and the decode/prefill/verify programs run per-shard under
shard_map with the row-parallel all-reduce as the only cross-chip
traffic.  Oracles on the 8-fake-device lane:

- greedy tokens BIT-IDENTICAL to the 1-chip engine — including chunked
  prefill, preemption-by-recompute, and prefix-cache-on runs;
- step-fn logits within the 1e-4 band of the unsharded builders;
- collective census: all-reduce ONLY (2 per layer), invariant to batch
  size;
- per-shard launch census identical to the 1-chip program (sharding
  must not change what each chip dispatches);
- a mesh that cannot shard the geometry (GQA kv_heads % tp != 0) warns
  loudly and serves replicated — never silently wrong.
"""
from __future__ import annotations
import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mxnet_tpu import serving
from mxnet_tpu.models import decoder
from mxnet_tpu.parallel.shardcfg import ShardingConfig

pytestmark = [pytest.mark.llm, pytest.mark.multichip]

VOCAB = 64


@pytest.fixture
def eight_devices():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return jax.devices()[:8]


@pytest.fixture(scope="module")
def lm():
    return decoder.decoder_tiny_lm(seed=0, vocab_size=VOCAB)


def tp_config(mesh_shape=(4, 2), axis_names=("dp", "tp")):
    return ShardingConfig.for_transformer(mesh_shape=mesh_shape,
                                          axis_names=axis_names)


def make_engine(lm, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("max_ctx", 64)
    return serving.DecodeEngine(lm, name="llm", **kw)


def run_workload(lm, reqs, **kw):
    eng = make_engine(lm, **kw)
    try:
        futs = [eng.submit(p, max_new_tokens=n) for p, n in reqs]
        outs = [f.result(timeout=300)["tokens"] for f in futs]
        snap = eng.metrics.snapshot()["models"]["llm"]
    finally:
        assert eng.stop()
    assert eng.alloc.num_used == 0
    eng.alloc.check_leaks()
    return outs, snap, eng


# ---------------------------------------------------------------------------
# plan resolution
# ---------------------------------------------------------------------------
def test_tp_plan_resolves_megatron_layout(eight_devices, lm):
    plan = decoder.tp_plan(lm.config, tp_config())
    assert plan is not None and plan.tp == 2
    # local geometry: heads/kv-heads/hidden halve, head_dim stays full
    assert plan.local_cfg.num_heads == lm.config.num_heads // 2
    assert plan.local_cfg.num_kv_heads == lm.config.num_kv_heads // 2
    assert plan.local_cfg.hidden_size == lm.config.hidden_size // 2
    assert plan.local_cfg.head_dim == lm.config.head_dim
    assert tuple(plan.kv_rows_spec) == (None, None, None, "tp")


def test_tp_plan_none_without_tp_axis(eight_devices, lm):
    assert decoder.tp_plan(lm.config, None) is None
    dp_only = ShardingConfig.for_transformer(mesh_shape=(8,),
                                             axis_names=("dp",))
    assert decoder.tp_plan(lm.config, dp_only) is None


def test_tp_plan_refuses_what_it_cannot_shard(eight_devices, lm):
    """tp > 1 asked for and not possible is an error, never a one-chip
    engine under a TP label: kv_heads=2 cannot split 8 ways, and rules
    that are not the Megatron column/row layout are not a TP plan."""
    bad = tp_config(mesh_shape=(1, 8))
    with pytest.raises(ValueError, match="tp=8 does not divide"):
        decoder.tp_plan(lm.config, bad)
    with pytest.raises(ValueError, match="tp=8 does not divide"):
        make_engine(lm, sharding=bad)
    no_rules = ShardingConfig(mesh_shape=(4, 2), axis_names=("dp", "tp"))
    with pytest.raises(ValueError, match="Megatron"):
        decoder.tp_plan(lm.config, no_rules)


# ---------------------------------------------------------------------------
# step-fn parity (logits band) + census gates
# ---------------------------------------------------------------------------
def _struct_args(cfg, page_size, slots, pps, total):
    return (decoder.fresh_pool(cfg, total, page_size),
            decoder.fresh_pool(cfg, total, page_size))


def test_decode_step_logits_band(eight_devices, lm):
    """One decode step, same state: the sharded program's logits sit
    within the 1e-4 band of the unsharded tower (same reduction order
    per shard; the psum is the only new float op)."""
    cfg, params = lm.config, lm.jax_params()
    page, slots, pps = 8, 4, 8
    total = slots * pps + 1
    ref_fn = decoder.make_decode_step(cfg, page)
    tp_fn = decoder.make_decode_step(cfg, page, sharding=tp_config())
    kp, vp = _struct_args(cfg, page, slots, pps, total)
    toks = jnp.asarray([3, 5, 7, 9], jnp.int32)
    lengths = jnp.asarray([1, 1, 1, 1], jnp.int32)
    tables = jnp.zeros((slots, pps), jnp.int32).at[:, 0].set(
        jnp.arange(1, slots + 1))
    active = jnp.ones(slots, bool)
    rkp, rvp, rtok, rlog = ref_fn(params, kp, vp, toks, lengths, tables,
                                  active)
    kp, vp = _struct_args(cfg, page, slots, pps, total)
    skp, svp, stok, slog = tp_fn(params, kp, vp, toks, lengths, tables,
                                 active)
    assert onp.array_equal(onp.asarray(rtok), onp.asarray(stok))
    assert float(jnp.max(jnp.abs(rlog - slog))) < 1e-4
    assert float(jnp.max(jnp.abs(rkp - skp))) < 1e-4


def test_collective_census_all_reduce_only_and_batch_invariant(
        eight_devices, lm):
    cfg, params = lm.config, lm.jax_params()
    page, pps = 8, 8
    seen = []
    for slots in (4, 8):
        stats = decoder.decode_collective_stats(
            params, cfg, page, slots, pps, slots * pps + 1, tp_config())
        c = stats["collectives"]
        # 2 all-reduces per layer: proj + ffn2 row-parallel sums
        assert c["all-reduce"] == 2 * cfg.num_layers, c
        bad = {k: v for k, v in c.items()
               if k not in ("all-reduce", "total") and v}
        assert not bad, bad
        seen.append(c)
    assert seen[0] == seen[1], seen


def test_launch_census_per_shard_unchanged(eight_devices, lm):
    """Sharding must not change what each chip DISPATCHES: the launch
    census of the sharded program equals the 1-chip tower's (psum is
    not a launch-class primitive)."""
    cfg, params = lm.config, lm.jax_params()
    page, slots, pps = 8, 4, 8
    total = slots * pps + 1
    ref = decoder.decode_launch_stats(params, cfg, page, slots, pps,
                                      total)
    tp = decoder.decode_launch_stats(params, cfg, page, slots, pps,
                                     total, sharding=tp_config())
    assert tp["launches_per_step"] == ref["launches_per_step"], (ref, tp)


def test_fn_cache_keys_include_sharding_token(eight_devices, lm):
    """Satellite: toggling the mesh must never serve a stale program —
    unsharded, tp=2 and dp-only resolve to three distinct cache keys
    (dp-only degrades to the unsharded program object contract: at
    minimum it must not return the tp=2 program)."""
    cfg = lm.config
    plain = decoder.make_decode_step(cfg, 8)
    tp = decoder.make_decode_step(cfg, 8, sharding=tp_config())
    assert plain is not tp
    assert decoder.make_decode_step(cfg, 8) is plain        # hit
    assert decoder.make_decode_step(cfg, 8,
                                    sharding=tp_config()) is tp  # hit
    # same tp degree, different mesh (4 devices): distinct key too
    other = decoder.make_decode_step(cfg, 8,
                                     sharding=tp_config((2, 2)))
    assert other is not tp and other is not plain


# ---------------------------------------------------------------------------
# engine-level parity (the tentpole oracle)
# ---------------------------------------------------------------------------
def test_tp_engine_greedy_parity(eight_devices, lm):
    rng = onp.random.RandomState(0)
    reqs = [(list(rng.randint(1, VOCAB, size=rng.randint(2, 12))),
             int(rng.randint(4, 16))) for _ in range(8)]
    ref, _, _ = run_workload(lm, reqs)
    tp, snap, eng = run_workload(lm, reqs, sharding=tp_config())
    assert tp == ref
    assert eng.tp == 2
    assert snap["generate"]["sharding"]["tp"] == 2


def test_tp_engine_chunked_prefill_parity(eight_devices, lm):
    """Prompts longer than prefill_chunk force multi-chunk prefill; the
    sharded prefill program must land the same pages and tokens."""
    rng = onp.random.RandomState(1)
    reqs = [(list(rng.randint(1, VOCAB, size=30)), 8) for _ in range(3)]
    ref, _, _ = run_workload(lm, reqs, prefill_chunk=8)
    tp, _, _ = run_workload(lm, reqs, prefill_chunk=8,
                            sharding=tp_config())
    assert tp == ref


def test_tp_engine_preemption_parity(eight_devices, lm):
    """Undersized pool: preemption-by-recompute must reproduce the same
    tokens under TP (replayed prefill through the sharded program)."""
    rng = onp.random.RandomState(2)
    reqs = [([int(t) for t in rng.randint(1, VOCAB, size=3)], 12)
            for _ in range(3)]
    kw = dict(slots=3, page_size=4, max_ctx=32, total_pages=9)
    ref, rsnap, _ = run_workload(lm, reqs, **kw)
    tp, tsnap, _ = run_workload(lm, reqs, sharding=tp_config(), **kw)
    assert tp == ref
    assert tsnap["counters"]["preemptions_total"] >= 1


def test_tp_engine_prefix_cache_parity(eight_devices, lm):
    """Shared system prompt + CoW forks on head-sharded pages: the
    prefix-cache-on TP run must match the cache-off 1-chip run."""
    rng = onp.random.RandomState(3)
    sysp = [int(t) for t in rng.randint(1, VOCAB, size=9)]
    reqs = [(sysp + [int(t) for t in rng.randint(1, VOCAB, size=4)], 8)
            for _ in range(4)]
    ref, _, _ = run_workload(lm, reqs)
    # serialize: the first request must FINISH (populating the cache)
    # before the rest submit, or nobody hits
    eng = make_engine(lm, prefix_cache=True, sharding=tp_config())
    try:
        tp = [eng.submit(reqs[0][0],
                         max_new_tokens=reqs[0][1]).result(300)["tokens"]]
        futs = [eng.submit(p, max_new_tokens=n) for p, n in reqs[1:]]
        tp += [f.result(timeout=300)["tokens"] for f in futs]
        snap = eng.metrics.snapshot()["models"]["llm"]
    finally:
        assert eng.stop()
    assert tp == ref
    assert snap["counters"].get("prefix_hits_total", 0) >= 1
    eng.alloc.check_leaks()


def test_tp_engine_kv_pages_head_sharded(eight_devices, lm):
    eng = make_engine(lm, sharding=tp_config())
    try:
        # the pool holds one row of all KV heads per token: splitting
        # the row over tp gives each shard its own heads
        for pages in (eng._kp, eng._vp):
            spec = pages.sharding.spec
            assert tuple(spec) == (None, None, None, "tp"), spec
    finally:
        assert eng.stop()


def test_tp_engine_speculative_parity(eight_devices, lm):
    """Spec-decode rides on top unmodified: the sharded verify program
    accepts/rejects exactly like the 1-chip engine (exactness oracle)."""
    motifs = [[3, 5, 7, 9], [2, 4, 6, 8]]
    reqs = [(motifs[i % 2] * 4, 10) for i in range(4)]
    ref, _, _ = run_workload(lm, reqs)
    tp, snap, _ = run_workload(lm, reqs, sharding=tp_config(),
                               speculate=True, spec_k=2, drafter="ngram")
    assert tp == ref
    assert snap["counters"].get("spec_verify_steps_total", 0) >= 1


def test_tp_engine_session_roundtrip(eight_devices, lm):
    """pack_session from a TP engine (gather-to-host) imports into a
    1-chip engine and vice versa: same greedy continuation."""
    prompt, n1, n2 = [5, 9, 2, 7, 4], 6, 6

    def first_turn(**kw):
        eng = make_engine(lm, session_ttl_s=60, **kw)
        out = eng.submit(prompt, max_new_tokens=n1,
                         session="s").result(timeout=300)
        blob = eng.export_session("s")
        assert eng.stop()
        return out["tokens"], blob

    def second_turn(blob, **kw):
        eng = make_engine(lm, session_ttl_s=60, **kw)
        eng.import_session(blob)
        out = eng.submit([1, 2], max_new_tokens=n2, session="s",
                         resume=True).result(timeout=300)
        assert eng.stop()
        return out["tokens"]

    t1_ref, blob_ref = first_turn()
    t1_tp, blob_tp = first_turn(sharding=tp_config())
    assert t1_tp == t1_ref
    # TP-exported blob carries FULL-head pages (same geometry both ways)
    cont_ref = second_turn(blob_ref)
    assert second_turn(blob_tp) == cont_ref            # tp -> 1chip
    assert second_turn(blob_ref,
                       sharding=tp_config()) == cont_ref  # 1chip -> tp


# ---------------------------------------------------------------------------
# metrics / fleet plumbing
# ---------------------------------------------------------------------------
def test_metrics_report_mesh_and_collectives_at_attach(eight_devices, lm):
    """Satellite: the census lands in the metrics snapshot at engine
    attach, BEFORE any traffic (static census, not runtime polling)."""
    eng = make_engine(lm, sharding=tp_config())
    try:
        snap = eng.metrics.snapshot()["models"]["llm"]
        shd = snap["generate"]["sharding"]
        assert shd["tp"] == 2 and "tp=2" in shd["mesh"]
        assert shd["collectives"]["all-reduce"] == 2 * lm.config.num_layers
        assert eng.stats()["sharding"]["collectives"]["all-to-all"] == 0
    finally:
        assert eng.stop()


def test_replica_spec_sharding_resolution(eight_devices, monkeypatch):
    from mxnet_tpu.serving.replica import resolve_sharding
    assert resolve_sharding(None) is None
    assert resolve_sharding({}) is None
    cfg = resolve_sharding({"mesh_shape": [4, 2],
                            "axis_names": ["dp", "tp"]})
    assert cfg.axis_size("tp") == 2 and cfg.rules
    monkeypatch.setenv("MXNET_MESH_SHAPE", "4,2")
    monkeypatch.setenv("MXNET_MESH_AXES", "dp,tp")
    env_cfg = resolve_sharding({"from_env": True})
    assert env_cfg.axis_size("tp") == 2
    # the Megatron rules ride along either way
    assert [r.spec for r in env_cfg.rules] == \
        [r.spec for r in cfg.rules]


def test_fleet_stamps_mesh_env(eight_devices):
    """Satellite: a fleet spec's "sharding" block stamps MXNET_MESH_*
    into the replica's environment (construction only — no processes)."""
    from mxnet_tpu.serving.fleet import ServingFleet
    spec = {"models": []}
    fleet = ServingFleet(
        spec, replicas=2,
        sharding=[None, {"mesh_shape": [1, 2],
                         "axis_names": ["dp", "tp"],
                         "host_devices": 2}])
    reps = fleet.supervisor.replicas
    assert fleet.supervisor.env_by_rid.get(reps[0].rid, {}).get(
        "MXNET_MESH_SHAPE") is None
    env1 = fleet.supervisor.env_by_rid[reps[1].rid]
    assert env1["MXNET_MESH_SHAPE"] == "1,2"
    assert env1["MXNET_MESH_AXES"] == "dp,tp"
    assert "--xla_force_host_platform_device_count=2" in env1["XLA_FLAGS"]
