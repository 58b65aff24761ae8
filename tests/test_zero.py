"""ZeRO-sharded training state + activation rematerialization (ISSUE 15).

Covers: slot_spec/zero_dim placement units (first dp-divisible dim, tp
composition, the slot0::/slot1:: checkpoint-name routing), the zero/remat
knob surface (validation, env seeding, to_dict/shrink_to round-trip), the
tentpole identity matrix — zero ∈ {0,1} trains BIT-identically (losses
AND params, 3 adam steps) on the 8-fake-device lane and remat ∈
{attention, tokens} to a few float32 ulps of a gradient, with zero-3
keeping params sharded at rest — the
static collective-census gates (zero-1 dp grad comm is reduce-scatter +
all-gather, one per sharded param; counts batch-invariant; zero-0
unchanged), the remat residual proof (saved_residuals shrink + remat2 in
the jaxpr), the GradBucketer interplay (satellite: zero >= 1 disables
bucketed pushpull with a warning; comm_stats reports zero_stage), and
the format-2 sharded checkpoint round-trip of dp-sharded slot slabs
(same mesh and shrunken mesh).
"""
import re

import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import np, autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.models.bert import TransformerLayer
from mxnet_tpu.parallel import (DataParallelTrainer, ShardingConfig,
                                ShardingRule, collective_census)
from mxnet_tpu.parallel import shardcfg

try:
    from jax.ad_checkpoint import saved_residuals
except ImportError:  # jax<0.5 keeps it private
    from jax._src.ad_checkpoint import saved_residuals

pytestmark = [pytest.mark.multichip, pytest.mark.zero]


@pytest.fixture
def eight_devices():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return jax.devices()[:8]


# ---------------------------------------------------------------------------
# slot placement units: first dp-divisible dim, composition, routing
# ---------------------------------------------------------------------------
def test_slot_spec_equals_param_spec_at_zero0(eight_devices):
    cfg = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=0)
    assert cfg.slot_spec("x.weight", (64, 32)) == P()
    assert cfg.zero_dim("x.weight", (64, 32)) is None


def test_slot_spec_shards_first_divisible_dim(eight_devices):
    cfg = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=1)
    assert cfg.slot_spec("x.weight", (64, 32)) == P("dp")
    assert cfg.slot_spec("x.bias", (64,)) == P("dp")
    # first dim indivisible -> dp moves to the next divisible one
    assert cfg.slot_spec("y.weight", (6, 32)) == P(None, "dp")
    # nothing divisible -> replicated slot (counted, never silent)
    assert cfg.slot_spec("y.bias", (6,)) == P()
    assert cfg.zero_dim("y.bias", (6,)) is None


def test_slot_spec_composes_with_tp_rule(eight_devices):
    cfg = ShardingConfig(
        mesh_shape=(4, 2), axis_names=("dp", "tp"), zero=1,
        rules=[ShardingRule(r"weight$", ("tp", None))])
    # dim0 already tp-sharded (factor 2); 64 % (2*4) == 0 -> dp stacks
    # onto the same dim
    assert cfg.slot_spec("q.weight", (64, 64)) == P(("tp", "dp"))
    # a param rule that already consumes dp -> no double-sharding
    cfg2 = ShardingConfig(
        mesh_shape=(8,), axis_names=("dp",), zero=1,
        rules=[ShardingRule(r"weight$", ("dp", None))])
    assert cfg2.zero_dim("q.weight", (64, 64)) is None


def test_param_spec_routes_slot_prefixes(eight_devices):
    cfg = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=1)
    shape = (64, 32)
    assert cfg.param_spec("slot0::x.weight", shape) \
        == cfg.slot_spec("x.weight", shape) == P("dp")
    assert cfg.param_spec("slot1::x.weight", shape) == P("dp")
    # the param itself stays replicated below zero-3...
    assert cfg.param_spec("x.weight", shape) == P()
    # ...and gains the dp dim at zero-3 (params sharded at rest)
    cfg3 = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=3)
    assert cfg3.param_spec("x.weight", shape) == P("dp")


# ---------------------------------------------------------------------------
# knob surface: validation, env, round-trips
# ---------------------------------------------------------------------------
def test_zero_and_remat_validation():
    with pytest.raises(ValueError):
        ShardingConfig(mesh_shape=(1,), axis_names=("dp",), zero=5)
    with pytest.raises(ValueError):
        ShardingConfig(mesh_shape=(1,), axis_names=("dp",), remat="bogus")
    # off-spellings normalize to None
    for off in ("", "off", "none", "0", None):
        cfg = ShardingConfig(mesh_shape=(1,), axis_names=("dp",), remat=off)
        assert cfg.remat is None and cfg.remat_policy() is None
    assert ShardingConfig(mesh_shape=(1,), axis_names=("dp",),
                          remat="Attention").remat == "attention"


def test_from_env_seeds_zero_and_remat(monkeypatch, eight_devices):
    monkeypatch.setenv("MXNET_ZERO_STAGE", "1")
    monkeypatch.setenv("MXNET_REMAT_POLICY", "tokens")
    cfg = ShardingConfig.from_env()
    assert cfg.zero == 1 and cfg.remat == "tokens"
    # explicit kwargs win over the env
    cfg = ShardingConfig.from_env(zero=0, remat=None)
    assert cfg.zero == 0 and cfg.remat is None
    monkeypatch.setenv("MXNET_ZERO_STAGE", "two")
    with pytest.raises(ValueError):
        ShardingConfig.from_env()


def test_dict_and_shrink_preserve_zero_remat(eight_devices):
    cfg = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=1,
                         remat="attention")
    back = ShardingConfig.from_dict(cfg.to_dict())
    assert back.zero == 1 and back.remat == "attention"
    shrunk = cfg.shrink_to(4)
    assert shrunk.zero == 1 and shrunk.remat == "attention"
    assert shrunk.slot_spec("x.bias", (64,)) == P("dp")
    # old configs (no zero/remat keys) load as stage 0
    d = cfg.to_dict()
    d.pop("zero"), d.pop("remat")
    assert ShardingConfig.from_dict(d).zero == 0


def test_remat_names_tokens_subset_of_attention():
    assert set(shardcfg.REMAT_POLICIES["tokens"]) \
        < set(shardcfg.REMAT_POLICIES["attention"])


# ---------------------------------------------------------------------------
# tentpole: the bit-identity matrix on the 8-device lane
# ---------------------------------------------------------------------------
def _train(zero, remat, opt="adam", steps=3, B=8, L=8, U=64):
    cfg = ShardingConfig.for_transformer(mesh_shape=(8,), axis_names=("dp",),
                                         zero=zero, remat=remat)
    mx.random.seed(0)
    net = TransformerLayer(units=U, hidden_size=2 * U, num_heads=2,
                           dropout=0.0)
    net.initialize()
    x = np.array(onp.random.RandomState(0).randn(B, L, U).astype("float32"))
    net(x)
    tr = DataParallelTrainer(net, lambda o, l: ((o - l) ** 2).mean(axis=-1),
                             opt, {"learning_rate": 0.01}, sharding=cfg)
    state = tr.init_state()
    step = tr.build_step(donate=False)
    xb = x._data
    yb = jnp.zeros_like(xb)
    key, lr = jax.random.key(0), jnp.float32(0.01)
    st, losses = state, []
    for _ in range(steps):
        st, l = step(st, xb, yb, key, lr)
        losses.append(float(l))
    params = {k: onp.asarray(v)
              for k, v in jax.device_get(st["params"]).items()}
    return losses, params, st, step, cfg


@pytest.fixture(scope="module")
def baseline_run():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    return _train(0, None)


@pytest.mark.parametrize("zero,remat", [
    (0, "attention"), (0, "tokens"),
    (1, None), (1, "attention"), (1, "tokens"),
])
def test_zero_remat_matrix_bit_identical(eight_devices, baseline_run,
                                         zero, remat):
    l0, p0 = baseline_run[0], baseline_run[1]
    l1, p1, _st, _step, _cfg = _train(zero, remat)
    assert p0.keys() == p1.keys()
    if remat is None:
        # ZeRO moves where the state lives, not what is computed: bytes
        assert l0 == l1, (zero, remat, l0, l1)
        rtol = atol = 0.0
    else:
        # remat computes the backward from a forward XLA compiles anew, and
        # a sum taken in another order differs in the last bit of a float32
        # gradient.  Adam divides by sqrt(v): where a gradient is small the
        # step moves by more than that bit.  Seen after 3 steps of 1e-2:
        # every parameter differs somewhere, most in ffn.ffn1.weight by
        # 1.26e-6 absolute; the three losses are the same bytes.  A wrong
        # recomputation is off by a step, 1e-2: the bar is a thousandth.
        rtol, atol = 1e-6, 1e-5
        onp.testing.assert_allclose(l0, l1, rtol=rtol, atol=0)
    for k in p0:
        onp.testing.assert_allclose(p1[k], p0[k], rtol=rtol, atol=atol,
                                    err_msg="%s (zero=%s remat=%s)"
                                    % (k, zero, remat))


def test_zero1_slots_dp_sharded(eight_devices, baseline_run):
    _l, _p, st, _step, cfg = _train(1, None)
    for k, s in st["slots"].items():
        arrs = s if isinstance(s, tuple) else (s,)
        d = cfg.zero_dim(k, arrs[0].shape)
        for a in arrs:
            spec = a.sharding.spec
            flat = [n for e in spec if e
                    for n in ((e,) if isinstance(e, str) else e)]
            if d is None:
                assert "dp" not in flat, (k, spec)
            else:
                assert "dp" in flat, (k, spec)
    # baseline slots stay co-sharded with their (replicated) param
    st0 = baseline_run[2]
    for s in jax.tree_util.tree_leaves(st0["slots"]):
        assert s.sharding.spec == P()


def test_zero3_params_sharded_at_rest(eight_devices, baseline_run):
    l0, p0 = baseline_run[0], baseline_run[1]
    l3, p3, st, _step, cfg = _train(3, None)
    assert l0 == l3
    for k in p0:
        onp.testing.assert_array_equal(p0[k], p3[k], err_msg=k)
    # params with a dp-divisible dim stay sharded at rest
    sharded = 0
    for k, v in st["params"].items():
        flat = [n for e in v.sharding.spec if e
                for n in ((e,) if isinstance(e, str) else e)]
        if cfg.zero_dim(k, v.shape) is not None:
            assert "dp" in flat, (k, v.sharding.spec)
            sharded += 1
    assert sharded > 0


def test_zero1_aux_state_not_supported(eight_devices):
    """BatchNorm running stats are forward-pass aux updates; the explicit
    ZeRO step refuses them loudly instead of silently dropping them."""
    cfg = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=1)
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, flatten=False, in_units=32), nn.BatchNorm())
    net.initialize()
    x = np.random.uniform(size=(8, 32))
    net(x)
    tr = DataParallelTrainer(net, lambda o, l: ((o - l) ** 2).mean(axis=-1),
                             "sgd", {"learning_rate": 0.1}, sharding=cfg)
    state = tr.init_state()
    step = tr.build_step(donate=False)
    with pytest.raises(NotImplementedError):
        step(state, x._data, jnp.zeros_like(x._data), jax.random.key(0),
             jnp.float32(0.1))


# ---------------------------------------------------------------------------
# census gates: the static layout proof (tier-1, load-independent)
# ---------------------------------------------------------------------------
def _dense_step_lowered(cfg, B=8, units=32, opt="sgd"):
    mx.random.seed(2)
    net = nn.HybridSequential()
    net.add(nn.Dense(units, activation="relu", flatten=False,
                     in_units=units),
            nn.Dense(units, flatten=False, in_units=units))
    net.initialize()
    x = np.random.uniform(size=(B, units))
    net(x)
    tr = DataParallelTrainer(net, lambda o, l: ((o - l) ** 2).mean(axis=-1),
                             opt, {"learning_rate": 0.1}, sharding=cfg)
    state = tr.init_state()
    step = tr.build_step(donate=False)
    xb = x._data
    return step.lower(
        state, xb, jnp.zeros_like(xb), jax.random.key(0), jnp.float32(0.1))


def _dense_step_census(cfg, **kw):
    return collective_census(_dense_step_lowered(cfg, **kw))


def _all_reduced_shapes(lowered):
    """Every array that passes through an all-reduce of the optimized
    HLO.  The instruction count is the compiler's: its combiner leaves one
    all-reduce per array on the chip (CHANGES.md, PR 21) and makes one of
    all five on this CPU pipeline.  What is reduced is the program's."""
    shapes = []
    for line in lowered.compile().as_text().splitlines():
        if shardcfg._hlo_opcode(line) in ("all-reduce", "all-reduce-start"):
            result = line.partition(" = ")[2].partition(" all-reduce")[0]
            shapes += re.findall(r"[a-z]+[0-9]+\[[0-9,]*\]", result)
    return sorted(shapes)


def test_census_zero1_reduce_scatter_all_gather_only(eight_devices):
    """The dp step flips from all-reduce-everything to reduce-scatter +
    all-gather, ONE of each per sharded param; the single remaining
    all-reduce is the scalar loss mean.  Nothing silently replicated:
    every one of the 4 params (2 weights + 2 biases, all dp-divisible)
    is accounted for."""
    cfg = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=1)
    c = _dense_step_census(cfg)
    assert c["reduce-scatter"] == 4, c
    assert c["all-gather"] == 4, c
    assert c["all-reduce"] == 1, c
    assert c["all-to-all"] == 0 and c["collective-permute"] == 0


def test_census_zero1_unshardable_param_allreduced(eight_devices):
    """A param with no dp-divisible dim keeps the psum'd replicated
    update: its gradient is all-reduced whole, and no reduce-scatter or
    all-gather touches it."""
    cfg = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=1)
    low = _dense_step_lowered(cfg, units=6)  # (6,6) weights, (6,) biases
    c = collective_census(low)
    # weights/biases of size 6: nothing divides by 8 -> all 4 params
    # replicated, 4 gradients + the scalar loss all-reduced
    assert c["reduce-scatter"] == 0 and c["all-gather"] == 0, c
    assert 1 <= c["all-reduce"] <= 5, c
    assert _all_reduced_shapes(low) == sorted(
        ["f32[6,6]", "f32[6]", "f32[6,6]", "f32[6]", "f32[]"])


def test_census_zero1_batch_invariant(eight_devices):
    cfg = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=1)
    assert _dense_step_census(cfg, B=8) == _dense_step_census(cfg, B=32)


def test_census_zero0_unchanged(eight_devices):
    """The zero-0 program is untouched: all-reduce grad sync only (the
    regression guard for the seed's census gate)."""
    cfg = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=0)
    c = _dense_step_census(cfg)
    assert c["all-reduce"] >= 1
    assert c["all-gather"] == 0 and c["reduce-scatter"] == 0


def test_census_remat_does_not_change_layout(eight_devices):
    cfg = ShardingConfig.for_transformer(mesh_shape=(8,), axis_names=("dp",),
                                         zero=1)
    cfg_r = ShardingConfig.for_transformer(mesh_shape=(8,),
                                           axis_names=("dp",), zero=1,
                                           remat="attention")
    _l, _p, st, step, _ = _train(1, None, steps=1)
    _lr, _pr, str_, step_r, _ = _train(1, "attention", steps=1)
    del cfg, cfg_r
    xb = jnp.zeros((8, 8, 64), jnp.float32)
    c = collective_census(step.lower(st, xb, xb, jax.random.key(0),
                                     jnp.float32(0.01)))
    cr = collective_census(step_r.lower(str_, xb, xb, jax.random.key(0),
                                        jnp.float32(0.01)))
    assert c == cr


# ---------------------------------------------------------------------------
# remat: the residual proof
# ---------------------------------------------------------------------------
def _loss_and_resid(remat, B=8, L=16, U=64):
    cfg = ShardingConfig(mesh_shape=(1,), axis_names=("dp",), remat=remat)
    from mxnet_tpu.parallel import functionalize
    from mxnet_tpu.ndarray import _wrap_value, ndarray as _nd
    mx.random.seed(0)
    net = TransformerLayer(units=U, hidden_size=2 * U, num_heads=2,
                           dropout=0.0)
    net.initialize()
    x = np.array(onp.random.RandomState(0).randn(B, L, U).astype("float32"))
    net(x)
    fn, params = functionalize(net, train=True)
    pvals = {k: p._data._data for k, p in params.items()}
    xb = x._data

    def loss_of(pv):
        with cfg.scope():
            out, _aux = fn(pv, xb, key=jax.random.key(0))
        out_nd = _wrap_value(out)
        with autograd._RecordingStateScope(False, True):
            loss = ((out_nd - _wrap_value(jnp.zeros_like(xb))) ** 2).mean()
        return jnp.mean(loss._data if isinstance(loss, _nd) else loss)

    pol = cfg.remat_policy()
    if pol is not None:
        loss_of = jax.checkpoint(loss_of, policy=pol)
    res = saved_residuals(loss_of, pvals)
    nbytes = sum(int(onp.prod(a.shape)) * a.dtype.itemsize
                 for a, _ in res if hasattr(a, "shape"))
    return loss_of, pvals, int(nbytes)


def test_remat_drops_saved_residuals():
    _f0, _p0, full = _loss_and_resid(None)
    f_att, p_att, att = _loss_and_resid("attention")
    _f_tok, _p_tok, tok = _loss_and_resid("tokens")
    # the ladder: save-everything > attention (+q/k/v) > tokens-only
    assert full > att > tok, (full, att, tok)
    # and the policy is structural: the jaxpr carries the remat call
    jaxpr = str(jax.make_jaxpr(f_att)(p_att))
    assert "remat" in jaxpr


# ---------------------------------------------------------------------------
# satellite: GradBucketer auto-disable under zero >= 1
# ---------------------------------------------------------------------------
def _bucketing_trainer(bucketing, cfg):
    mx.random.seed(7)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05}, kvstore="device",
                            bucketing=bucketing)
    x = np.array(onp.random.RandomState(0).rand(8, 8).astype("float32"))
    with cfg.scope():
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        trainer.step(8)
    return trainer


def test_bucketing_disabled_under_zero(eight_devices):
    cfg = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=1)
    with pytest.warns(UserWarning, match="ZeRO stage 1"):
        tr = _bucketing_trainer(True, cfg)
    assert tr._bucketer is None
    s = tr.comm_stats()
    assert s["zero_stage"] == 1 and not s["bucketing"]


def test_bucketing_unaffected_at_zero0(eight_devices):
    cfg = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=0)
    tr = _bucketing_trainer(True, cfg)
    assert tr._bucketer is not None
    s = tr.comm_stats()
    assert s["zero_stage"] == 0 and s["bucketing"]


# ---------------------------------------------------------------------------
# satellite: format-2 sharded checkpoints of dp-sharded slot slabs
# ---------------------------------------------------------------------------
def _ckpt_trainer(cfg, opt="adam"):
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", flatten=False, in_units=32),
            nn.Dense(32, flatten=False, in_units=32))
    net.initialize()
    x = np.random.uniform(size=(8, 32))
    net(x)
    tr = DataParallelTrainer(net, lambda o, l: ((o - l) ** 2).mean(axis=-1),
                             opt, {"learning_rate": 0.05}, sharding=cfg)
    return tr, x


def test_save_load_state_roundtrip_zero1(eight_devices, tmp_path):
    cfg = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=1)
    tr, x = _ckpt_trainer(cfg)
    state = tr.init_state()
    step = tr.build_step(donate=False)
    xb = x._data
    state, _l = step(state, xb, jnp.zeros_like(xb), jax.random.key(0),
                     jnp.float32(0.05))
    tr.save_state(str(tmp_path), state, step=1)
    out, meta = tr.load_state(str(tmp_path))
    assert int(out["t"]) == int(state["t"]) == 1
    assert meta["extra"]["opt_kind"] == "adam"
    for k in state["params"]:
        onp.testing.assert_array_equal(onp.asarray(state["params"][k]),
                                       onp.asarray(out["params"][k]), k)
    for k, s in state["slots"].items():
        for i, a in enumerate(s if isinstance(s, tuple) else (s,)):
            b = out["slots"][k][i] if isinstance(s, tuple) else out["slots"][k]
            onp.testing.assert_array_equal(onp.asarray(a), onp.asarray(b),
                                           "slot%d::%s" % (i, k))
            # restored slots come back dp-sharded, not replicated
            flat = [n for e in b.sharding.spec if e
                    for n in ((e,) if isinstance(e, str) else e)]
            assert "dp" in flat, (k, b.sharding.spec)


def test_load_state_under_shrunk_mesh(eight_devices, tmp_path):
    """Slot slabs written under dp=8 reload under dp=4 (slice-on-read):
    the elastic path covers ZeRO state, not just params."""
    cfg8 = ShardingConfig(mesh_shape=(8,), axis_names=("dp",), zero=1)
    tr8, x = _ckpt_trainer(cfg8)
    state = tr8.init_state()
    step = tr8.build_step(donate=False)
    xb = x._data
    state, _l = step(state, xb, jnp.zeros_like(xb), jax.random.key(0),
                     jnp.float32(0.05))
    tr8.save_state(str(tmp_path), state, step=1)

    cfg4 = cfg8.shrink_to(4)
    assert cfg4.zero == 1
    tr4, _x = _ckpt_trainer(cfg4)
    out, _meta = tr4.load_state(str(tmp_path))
    for k, s in state["slots"].items():
        a8 = s[0] if isinstance(s, tuple) else s
        a4 = out["slots"][k][0] if isinstance(s, tuple) else out["slots"][k]
        onp.testing.assert_array_equal(onp.asarray(a8), onp.asarray(a4), k)
        assert a4.sharding.mesh.devices.size == 4
