"""The decode step's attention over the gathered token rows as they lie
(PR 30) against the head-major view it replaced in that program.

``_decode_attention`` reads the context as ``(B, C, KVH * D)`` rows
(``_gather_rows`` + ``attend_rows``); prefill, verify and the hybrid
programs still read ``_gather_kv``'s head-major view ``(B, KVH, C, D)``,
which with ``attend_ctx`` is what the decode step computed until then.  The two are the same mathematics with the float
additions in another order (the rows form adds exact zeros from the
other heads' lanes), so each case here holds them to a float32
tolerance, max abs difference under 1e-5 of the outputs' std, over what
could tell them apart: the GQA group, a row that is no whole number of
lane tiles, aliased and unallocated page-table entries, the lengths at a
page's edges, a length of 0, int8 pages with per page and head scales,
and a ``tp`` plan's local head counts.  One structural case keeps the
relayout from coming back unseen on a CPU run: the decode step's jaxpr
holds no context-sized array with the head axis split off.

Since PR 36 the rows form has a second reader, the kernel that walks each
lane's page table up to its length (``paged_attend_rows``), selected by
what the code sees: the cases of the file's second half run it in the
Pallas interpreter against ``attend_rows(_gather_rows(..))``, to the same
1e-5 of the outputs' std, and compile the step that calls it for the
chip.
"""
from __future__ import annotations

import re

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models import decoder
from mxnet_tpu.ops.pallas import paged_attention as paged
from mxnet_tpu.parallel.shardcfg import ShardingConfig

pytestmark = [pytest.mark.llm]

B, PPS, S, L, LI = 5, 6, 4, 3, 1
TOTAL = B * PPS + 1
C = PPS * S


def random_pool(kv, kvh, d, seed):
    """One rows-form pool (L, P, S, KVH * D); the scratch page reads as
    zeros, as the engine leaves it."""
    rs = onp.random.RandomState(seed)
    shape = (L, TOTAL, S, kvh * d)
    if kv == "int8":
        q = rs.randint(-127, 128, size=shape).astype(onp.int8)
        q[:, 0] = 0
        s = rs.uniform(0.01, 0.1, size=(L, kvh, TOTAL)).astype(onp.float32)
        s[:, :, 0] = 1.0
        return paged.QPages(q=jnp.asarray(q), s=jnp.asarray(s))
    a = rs.randn(*shape).astype(onp.float32)
    a[:, 0] = 0.0
    return jnp.asarray(a)


def tables_and_lengths():
    """Rows 0 and 1 share their first two pages (a cached prefix), row 2
    is an idle slot (all scratch, length 0), unallocated tail entries
    point at the scratch page; the lengths end at a page's first slot,
    at a page's last slot, nowhere, in the middle of a page and at the
    table's end."""
    t = onp.zeros((B, PPS), onp.int32)
    t[0] = [1, 2, 3, 4, 0, 0]
    t[1] = [1, 2, 5, 6, 0, 0]
    t[3] = [7, 8, 9, 0, 0, 0]
    t[4] = [10, 11, 12, 13, 14, 15]
    lengths = onp.array([3 * S + 1, 4 * S, 0, 2 * S + 2, C], onp.int32)
    return jnp.asarray(t), jnp.asarray(lengths)


def head_major(q, k_pool, v_pool, lengths, tables, kvh):
    return paged.attend_ctx(
        q, decoder._gather_kv(k_pool, LI, tables, kvh),
        decoder._gather_kv(v_pool, LI, tables, kvh), lengths,
        1.0 / (q.shape[-1] ** 0.5))


def assert_close(new, old):
    new, old = onp.asarray(new), onp.asarray(old)
    assert new.shape == old.shape and new.dtype == old.dtype
    assert onp.isfinite(new).all()
    assert onp.abs(new - old).max() < 1e-5 * old.std()


# (heads, kv heads, head_dim): MHA, GQA groups of 2 and 4, and rows of
# 96 and 40 lanes, no whole number of 128-lane tiles
GEOMETRIES = [(4, 4, 32), (4, 2, 64), (8, 2, 64), (6, 3, 32), (5, 5, 8)]


@pytest.mark.parametrize("kv", ["float32", "int8"])
@pytest.mark.parametrize("h, kvh, d", GEOMETRIES,
                         ids=["h%d-kvh%d-d%d" % g for g in GEOMETRIES])
def test_rows_form_agrees_with_head_major_view(h, kvh, d, kv):
    k_pool, v_pool = random_pool(kv, kvh, d, 1), random_pool(kv, kvh, d, 2)
    tables, lengths = tables_and_lengths()
    q = jnp.asarray(onp.random.RandomState(3).randn(B, h, d), jnp.float32)
    paged.last_path = None
    new = decoder._decode_attention(q, k_pool, v_pool, LI, lengths, tables,
                                    kvh)
    assert paged.last_path == "xla"
    assert_close(new, head_major(q, k_pool, v_pool, lengths, tables, kvh))
    assert not onp.asarray(new)[2].any()        # length 0: zeros, no NaN
    # aliased pages read alike: rows 0 and 1 at a length inside the
    # shared prefix say the same for the same query
    short = jnp.full((B,), 2 * S, jnp.int32)
    same_q = jnp.broadcast_to(q[:1], q.shape)
    out = onp.asarray(decoder._decode_attention(
        same_q, k_pool, v_pool, LI, short, tables, kvh))
    assert out[0].tobytes() == out[1].tobytes()


@pytest.mark.parametrize("kv", ["float32", "int8"])
def test_gathered_rows_are_the_head_major_context_value_for_value(kv):
    kvh, d = 3, 32
    pool = random_pool(kv, kvh, d, 4)
    tables, _ = tables_and_lengths()
    rows = onp.asarray(decoder._gather_rows(pool, LI, tables))
    ctx = onp.asarray(decoder._gather_kv(pool, LI, tables, kvh))
    assert rows.dtype == onp.float32 and rows.shape == (B, C, kvh * d)
    assert (rows.reshape(B, C, kvh, d).transpose(0, 2, 1, 3).tobytes()
            == ctx.tobytes())


@pytest.mark.parametrize("position", ["page_first_slot", "page_last_slot"])
def test_length_at_a_pages_edge_reads_that_token_and_no_further(position):
    """Moving the one token past ``length`` changes nothing; moving the
    last one inside it does."""
    h = kvh = 4
    d = 32
    k_pool, v_pool = random_pool("float32", kvh, d, 5), random_pool(
        "float32", kvh, d, 6)
    tables, _ = tables_and_lengths()
    n = 2 * S + 1 if position == "page_first_slot" else 3 * S
    lengths = jnp.full((B,), n, jnp.int32)
    q = jnp.asarray(onp.random.RandomState(7).randn(B, h, d), jnp.float32)
    base = onp.asarray(decoder._decode_attention(
        q, k_pool, v_pool, LI, lengths, tables, kvh))

    def with_token_changed(t):
        page, slot = int(tables[4, t // S]), t % S
        changed = v_pool.at[LI, page, slot].add(1.0)
        return onp.asarray(decoder._decode_attention(
            q, k_pool, changed, LI, lengths, tables, kvh))

    assert with_token_changed(n)[4].tobytes() == base[4].tobytes()
    assert onp.abs(with_token_changed(n - 1)[4] - base[4]).max() > 1e-3


@pytest.mark.parametrize("kv", ["float32", "int8"])
def test_a_tp_plans_shard_computes_its_own_heads_of_the_whole(kv):
    """Under a ``tp`` plan the step runs per shard with the local head
    counts over the shard's lanes of every row: the shards' outputs side
    by side are the unsharded attention."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    lm = decoder.decoder_tiny_lm(seed=0, vocab_size=64)
    cfg = lm.config
    plan = decoder.tp_plan(
        cfg, ShardingConfig.for_transformer(mesh_shape=(4, 2),
                                            axis_names=("dp", "tp")),
        kv_int8=(kv == "int8"))
    local = plan.local_cfg
    assert local.num_kv_heads * plan.tp == cfg.num_kv_heads
    d, kvh = cfg.head_dim, cfg.num_kv_heads
    k_pool, v_pool = random_pool(kv, kvh, d, 8), random_pool(kv, kvh, d, 9)
    tables, lengths = tables_and_lengths()
    q = jnp.asarray(onp.random.RandomState(10).randn(B, cfg.num_heads, d),
                    jnp.float32)

    def shard(pool, i):
        lanes = slice(i * local.num_kv_heads * d,
                      (i + 1) * local.num_kv_heads * d)
        heads = slice(i * local.num_kv_heads, (i + 1) * local.num_kv_heads)
        if kv == "int8":
            return paged.QPages(q=pool.q[..., lanes], s=pool.s[:, heads])
        return pool[..., lanes]

    parts = [decoder._decode_attention(
        q[:, i * local.num_heads:(i + 1) * local.num_heads],
        shard(k_pool, i), shard(v_pool, i), LI, lengths, tables,
        local.num_kv_heads) for i in range(plan.tp)]
    assert_close(jnp.concatenate(parts, axis=1),
                 head_major(q, k_pool, v_pool, lengths, tables, kvh))


def split_context_arrays(jaxpr, sizes):
    """Every array in ``jaxpr`` (sub-jaxprs included) of rank five or
    more whose dimensions are a permutation of ``sizes``."""
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                shape = getattr(v.aval, "shape", ())
                if len(shape) >= 5 and sorted(shape) == sorted(sizes):
                    found.append((eqn.primitive.name, tuple(shape)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("kv", ["float32", "int8"])
def test_decode_step_holds_no_context_with_the_head_axis_split_off(kv):
    """What PR 30 took out: ``(B, pps, S, KVH, D)`` and any permutation
    of it.  ``_gather_kv`` (prefill's and verify's view) has exactly
    that intermediate, which proves the search finds it."""
    lm = decoder.decoder_tiny_lm(seed=0, vocab_size=64, num_kv_heads=2,
                                 num_heads=4, units=96)
    cfg = lm.config
    slots, pps, page = 3, 7, 4
    total = slots * pps + 1
    sizes = (slots, pps, page, cfg.num_kv_heads, cfg.head_dim)
    args = decoder._decode_step_structs(
        lm.jax_params(), cfg, page, slots, pps, total, kv_dtype=kv)
    step = decoder._build_decode_step(cfg, page)
    assert split_context_arrays(jax.make_jaxpr(step)(*args), sizes) == []
    pool = args[1]
    tables = jax.ShapeDtypeStruct((slots, pps), jnp.int32)
    view = jax.make_jaxpr(
        lambda p, t: decoder._gather_kv(p, 0, t, cfg.num_kv_heads))(
            pool, tables)
    assert split_context_arrays(view, sizes)


# ---------------------------------------------------------------------------
# the kernel that walks each lane's live pages (PR 36), in the interpreter
# ---------------------------------------------------------------------------
KS, KPPS = 16, 64           # a page of 16 tokens, 64 pages a sequence
KC = KS * KPPS              # 1 024 positions, as the serving cells run


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("MXNET_PAGED_ATTENTION", "interpret")


def kernel_pool(lanes, kvh, d, seed, dtype=onp.float32):
    """A rows-form pool of two layers whose every page is random, the
    scratch page included: a page the kernel must not read is told from
    one it may by the answer, not by zeros."""
    rs = onp.random.RandomState(seed)
    return jnp.asarray(rs.randn(2, lanes * KPPS + 1, KS, kvh * d)
                       .astype(dtype))


def own_tables(lanes):
    return onp.arange(1, lanes * KPPS + 1, dtype=onp.int32).reshape(
        lanes, KPPS)


def gathered(q, k_pool, v_pool, lengths, tables, kvh):
    return paged.attend_rows(
        q, decoder._gather_rows(k_pool, LI, tables),
        decoder._gather_rows(v_pool, LI, tables), lengths,
        1.0 / (q.shape[-1] ** 0.5), kvh)


def walked(q, k_pool, v_pool, lengths, tables, kvh):
    paged.last_path = None
    out = decoder._decode_attention(q, k_pool, v_pool, LI, lengths, tables,
                                    kvh)
    assert paged.last_path == "pallas-interpret"
    return out


# the lengths at a page's and a block's edges, an idle batch, a full table
# and what a batch looks like: idle lanes between live ones of every size
LENGTHS = {"0": [0, 0, 0], "1": [1, 1, 1], "16": [16, 16, 16],
           "17": [17, 17, 17], "1024": [KC, KC],
           "mixed": [300, 0, 1, KC, 0, 129, 128, 16]}
# 12 heads of 64 (gpt2-small-serve's row of 768 lanes) and grouped heads
# of 128 (4 query heads a KV head)
KERNEL_GEOMETRIES = [(12, 12, 64), (8, 2, 128)]
kernel_geometries = pytest.mark.parametrize(
    "h, kvh, d", KERNEL_GEOMETRIES,
    ids=["h%d-kvh%d-d%d" % g for g in KERNEL_GEOMETRIES])


@kernel_geometries
@pytest.mark.parametrize("lengths", list(LENGTHS.values()),
                         ids=list(LENGTHS))
def test_walk_agrees_with_the_gathered_rows(interpreted, lengths, h, kvh, d):
    lanes = len(lengths)
    k_pool, v_pool = kernel_pool(lanes, kvh, d, 11), kernel_pool(
        lanes, kvh, d, 12)
    tables, lengths = jnp.asarray(own_tables(lanes)), jnp.asarray(
        lengths, jnp.int32)
    q = jnp.asarray(onp.random.RandomState(13).randn(lanes, h, d),
                    jnp.float32)
    new = walked(q, k_pool, v_pool, lengths, tables, kvh)
    old = gathered(q, k_pool, v_pool, lengths, tables, kvh)
    if not onp.asarray(lengths).any():
        assert new.shape == old.shape and not onp.asarray(new).any()
        return
    assert_close(new, old)
    idle = onp.asarray(lengths) == 0
    assert not onp.asarray(new)[idle].any()     # zeros, not NaN


def test_walk_reads_shared_pages_and_the_scratch_page_as_the_gather_does(
        interpreted):
    """Two tables share their first three pages (a cached prefix) and end
    on pages of their own; the unallocated tails of every table point at
    the scratch page, as the engine leaves them."""
    h, kvh, d = KERNEL_GEOMETRIES[0]
    k_pool, v_pool = kernel_pool(3, kvh, d, 14), kernel_pool(3, kvh, d, 15)
    t = onp.zeros((3, KPPS), onp.int32)
    t[0, :5] = [1, 2, 3, 4, 5]
    t[1, :4] = [1, 2, 3, 6]
    t[2, :1] = [7]
    lengths = jnp.asarray([5 * KS, 3 * KS + 2, 9], jnp.int32)
    q = jnp.asarray(onp.random.RandomState(16).randn(3, h, d), jnp.float32)
    assert_close(walked(q, k_pool, v_pool, lengths, jnp.asarray(t), kvh),
                 gathered(q, k_pool, v_pool, lengths, jnp.asarray(t), kvh))
    same_q = jnp.broadcast_to(q[:1], q.shape)
    inside = jnp.full((3,), 3 * KS, jnp.int32)
    out = onp.asarray(walked(same_q, k_pool, v_pool, inside, jnp.asarray(t),
                             kvh))
    assert out[0].tobytes() == out[1].tobytes()


@kernel_geometries
def test_walk_touches_no_page_past_a_length(interpreted, h, kvh, d):
    """Every page past each lane's length holds NaN, the scratch page
    too: the gather's masked product reads them (0 * NaN), the walk gives
    what the clean pools give."""
    lengths = onp.array([300, 0, 1, KC - KS, 129, 128, 16], onp.int32)
    lanes = len(lengths)
    k_pool, v_pool = kernel_pool(lanes, kvh, d, 17), kernel_pool(
        lanes, kvh, d, 18)
    tables = own_tables(lanes)
    dead = onp.concatenate(
        [[0]] + [tables[b, -(-int(n) // KS):] for b, n in enumerate(lengths)])
    q = jnp.asarray(onp.random.RandomState(19).randn(lanes, h, d),
                    jnp.float32)
    args = (jnp.asarray(lengths), jnp.asarray(tables), kvh)
    clean = gathered(q, k_pool, v_pool, *args)
    k_bad, v_bad = (pool.at[:, dead].set(jnp.nan) for pool in (k_pool,
                                                                v_pool))
    assert not onp.isfinite(onp.asarray(gathered(q, k_bad, v_bad,
                                                 *args))).all()
    assert_close(walked(q, k_bad, v_bad, *args), clean)


# what selects the walk and what keeps the gather, by shapes and dtype
SELECTION = {
    "float32-page16-row128": ("float32", 16, 2, 64, True),
    "bfloat16-page16-row256": ("bfloat16", 16, 2, 128, True),
    "float32-page8-row128": ("float32", 8, 4, 32, True),
    "int8": ("int8", 16, 2, 64, False),
    "row-of-96-lanes": ("float32", 16, 3, 32, False),
    "page-of-4-tokens": ("float32", 4, 2, 64, False),
    "bfloat16-page-of-8": ("bfloat16", 8, 2, 64, False),
}


@pytest.mark.parametrize("kv, page, kvh, d, kernel", list(SELECTION.values()),
                         ids=list(SELECTION))
def test_the_walk_is_selected_by_what_the_pool_is(interpreted, kv, page,
                                                  kvh, d, kernel):
    rs = onp.random.RandomState(20)
    lanes, pps = 2, 3
    shape = (2, lanes * pps + 1, page, kvh * d)
    if kv == "int8":
        pool = paged.QPages(
            q=jnp.asarray(rs.randint(-127, 128, size=shape), jnp.int8),
            s=jnp.asarray(rs.uniform(0.01, 0.1, size=(2, kvh, shape[1])),
                          jnp.float32))
    else:
        pool = jnp.asarray(rs.randn(*shape), jnp.dtype(kv))
    tables = jnp.asarray(onp.arange(1, shape[1]).reshape(lanes, pps),
                         jnp.int32)
    lengths = jnp.asarray([page + 1, 3 * page], jnp.int32)
    q = jnp.asarray(rs.randn(lanes, 2 * kvh, d), jnp.float32)
    paged.last_path = None
    new = decoder._decode_attention(q, pool, pool, LI, lengths, tables, kvh)
    assert paged.last_path == ("pallas-interpret" if kernel else "xla")
    assert_close(new, gathered(q, pool, pool, lengths, tables, kvh))


def test_decode_step_through_the_walk_is_the_step_through_the_gather(
        monkeypatch):
    """The whole step program of the tiny model, kernel against gather:
    the first layer's rows to the bit (the attention only reads the
    pools), the later layers' and the logits to float32 rounding, the
    greedy tokens the same."""
    lm = decoder.decoder_tiny_lm(seed=0, vocab_size=64, units=256)
    cfg, params = lm.config, lm.jax_params()      # a row of 2 x 64 lanes
    slots, pps, page = 4, 4, 8
    rs = onp.random.RandomState(21)
    pools = [jnp.asarray(rs.randn(*decoder.pool_shape(
        cfg, slots * pps + 1, page)).astype(onp.float32)) for _ in range(2)]
    tables = jnp.asarray(onp.arange(1, slots * pps + 1).reshape(slots, pps),
                         jnp.int32)
    rest = (jnp.asarray([3, 5, 0, 7], jnp.int32),
            jnp.asarray([0, 8, 0, 30], jnp.int32), tables,
            jnp.asarray([True, True, False, True]))
    outs = {}
    for mode in ("interpret", "off"):
        monkeypatch.setenv("MXNET_PAGED_ATTENTION", mode)
        paged.last_path = None
        outs[mode] = decoder._build_decode_step(cfg, page)(
            params, *(jnp.array(p) for p in pools), *rest)
        assert paged.last_path == {"interpret": "pallas-interpret",
                                   "off": "xla"}[mode]
    new, old = outs["interpret"], outs["off"]
    for a, b in zip(new[:2], old[:2]):
        assert onp.asarray(a[0]).tobytes() == onp.asarray(b[0]).tobytes()
        assert_close(a, b)
    assert onp.array_equal(onp.asarray(new[2]), onp.asarray(old[2]))
    live = onp.asarray(rest[3])
    assert_close(onp.asarray(new[3])[live], onp.asarray(old[3])[live])


# ---------------------------------------------------------------------------
# the same, in the program compiled for the chip (no chip needed)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def test_decode_step_compiled_for_the_chip_never_splits_the_context(
        one_chip):
    """One layer at ``gpt2-small-serve``'s geometry (32 slots x 64 pages
    x 16 tokens, 12 heads of 64) through the v5e's compiler: XLA's own
    passes bring no head-split copy of the gathered context back either
    (PERF.md, PR 30: 24 ``reshape f32[32,64,16,12,64]`` and 12 ``copy
    f32[32,12,64,16,64]`` were 14.7 of the step's 35 ms)."""
    slots, pps, page, heads, d = 32, 64, 16, 12, 64
    lm = decoder.decoder_tiny_lm(seed=0, vocab_size=256, num_layers=1,
                                 units=heads * d, hidden_size=256,
                                 num_heads=heads, num_kv_heads=heads,
                                 max_length=pps * page)
    cfg = lm.config
    args = decoder._decode_step_structs(
        lm.jax_params(), cfg, page, slots, pps, slots * pps + 1)
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), args)
    hlo = decoder._build_decode_step(cfg, page).lower(
        *args).compile().as_text()
    assert "f32[1,2049,16,768]" in hlo          # the pool, as it lies
    want = sorted((slots, pps, page, heads, d))
    split = {m.group(0) for m in re.finditer(r"\w+\[([\d,]+)\]", hlo)
             if sorted(map(int, m.group(1).split(","))) == want}
    assert split == set()


def test_decode_step_compiled_for_the_chip_walks_the_pages(one_chip,
                                                           monkeypatch):
    """The same layer with the kernel selected as on the chip (PR 36):
    the v5e's compiler takes it, two pools whole and the layer as a
    scalar, and the program is left with no operation whose result is a
    gathered context, a layer's slab or a pool (the pools come in, are
    written in place and go out)."""
    monkeypatch.delenv("MXNET_PAGED_ATTENTION", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, pps, page, heads, d, layers = 32, 64, 16, 12, 64, 2
    lm = decoder.decoder_tiny_lm(seed=0, vocab_size=256, num_layers=layers,
                                 units=heads * d, hidden_size=256,
                                 num_heads=heads, num_kv_heads=heads,
                                 max_length=pps * page)
    cfg = lm.config
    args = decoder._decode_step_structs(
        lm.jax_params(), cfg, page, slots, pps, slots * pps + 1)
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), args)
    hlo = decoder._build_decode_step(cfg, page).lower(
        *args).compile().as_text()
    assert paged.last_path == "pallas"
    assert len(re.findall(r"%paged_attend_rows[.\d]* = \S+ custom-call\(",
                          hlo)) == layers
    pool = "f32[%d,2049,16,768]" % layers
    made = re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])",
                      hlo, re.M)
    assert any(shape == pool for _, shape in made)
    in_place = ("dynamic-update-slice", "dynamic_update_slice",
                "get-tuple-element", "param", "k_pages", "v_pages", "tuple",
                "while", "bitcast")
    for name, shape in made:
        dims = shape[shape.index("[") + 1:-1]
        assert dims not in ("2048,16,768", "32,64,16,768", "32,1024,768",
                            "2049,16,768", "1,2049,16,768"), (name, shape)
        if shape == pool:
            assert any(k in name for k in in_place), (name, shape)


# ---------------------------------------------------------------------------
# the retention block's two programs compiled for the chip (PR 37)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_retention_programs_compiled_for_the_chip_move_no_pool(one_chip,
                                                               program):
    """Two layers at ``brumby-14b-l4-serve``'s state geometry (8 KV heads of
    128: S 4 x 8 256 rows of 128 a page, layer and pool, z 33 024 floats,
    21 pages of 1 024, 20 slots, chunks of 256) through the v5e's
    compiler.  Nothing with a layer's S of every page as its shape, ``(..,
    21, 4, 8256, 128)`` or ``(.., 21, 33024, 128)`` whatever leads it, nor
    the prefill chunk's view of it in 768-row blocks, is made but in place:
    the prefill chunk's writes are scatters into the view (43 blocks of 768
    rows, a bitcast: 64 blocks of 516 made two relayouts of the whole pool
    a layer), and the decode step updates each layer's S where it lies
    and keeps no temporary as large as one (read back after the write, or
    sliced out of a pool of every layer, XLA copied each updated layer
    first: 0.62 of 4 traced seconds on the chip, PERF.md, PR 37).  The z
    pool's parts are 5.5 MB: the compiler may stage them in the chip's own
    memory around the layers (copy-start / copy-done), and does nothing
    else with them."""
    from mxnet_tpu.models import hybrid
    layers, S, B, T, total = 2, 1024, 20, 256, 21
    net = hybrid.HybridLM(vocab_size=512, num_layers=layers, units=256,
                          hidden_size=512, num_heads=40, num_kv_heads=8,
                          head_dim=128, recurrent="retention",
                          rope_theta=1e6, tied_head=False,
                          max_length=S)
    cfg = net.config

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    params = {"embed": sds(net.embed.shape, net.dtype),
              "norm_f": sds(net.norm_f.shape, net.dtype),
              "head": sds(net.head.shape, net.dtype),
              "runs": [{k: sds(getattr(r, k).shape, net.dtype)
                        for k in r.leaves} for r in net.runs]}
    pool = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: decoder.fresh_pool(cfg, total, S)))
    assert [a.shape for a in pool.ssm] == [(total, 4, 8256, 128)] * layers
    assert [a.shape for a in pool.conv] == [(layers, total, 16512)] * 2

    def i32(*shape):
        return sds(shape, jnp.int32)
    if program == "decode":
        fn, rest = decoder.make_decode_step(cfg, S), (
            i32(B), i32(B), i32(B, 1), sds((B,), jnp.bool_))
    else:
        fn, rest = decoder.make_prefill_chunk(cfg, S, T), (
            i32(T), i32(), i32(), i32(1))
    compiled = fn.lower(params, pool, pool, *rest).compile()
    hlo = compiled.as_text()
    # what the program makes: the computations a fusion calls left out
    fused = set(re.findall(r"calls=%?([\w.\-]+)", hlo))
    made = [m for comp in re.split(r"\n(?=\S)", hlo)
            if comp.split(" ", 2)[int(comp.startswith("ENTRY"))].lstrip("%")
            not in fused
            for m in re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = "
                                r"f32\[([\d,]+)\]\S* ([\w\-]+)\(", comp,
                                re.M)]
    slab = re.compile(r"^(\d+,)*(21,4,8256|21,33024|903,768),128$")
    z = "%d,21,16512" % layers
    writes = {"scatter", "dynamic-update-slice"}
    in_place = {"parameter", "get-tuple-element", "bitcast",
                "fusion"} | writes
    seen = [(name, op) for name, dims, op in made if slab.match(dims)]
    assert {op for _, op in seen} <= in_place, seen
    if program == "decode":
        # the update of each layer's two halves, in the pools' own buffers
        assert sum(op == "fusion" for _, op in seen) == 2 * layers, seen
        assert compiled.memory_analysis().temp_size_in_bytes < (
            total * 33024 * 128 * 4)
    else:
        # every fusion with a slab's shape is an in-place write
        assert {op for _, op in seen} & writes or any(
            op == "fusion" for _, op in seen)
        for name, op in seen:
            if op != "fusion":
                continue
            called = re.search(r"%" + re.escape(name) + r" = [^\n]*calls=%?"
                               r"([\w.\-]+)", hlo).group(1)
            body = hlo[hlo.index("%" + called + " ("):]
            body = body[:body.index("\n}")]
            root = re.search(r"ROOT %?\S+ = \S+ ([\w\-]+)\(",
                             body).group(1)
            assert root in writes or (root == "tuple" and any(
                "%s(" % w in body for w in writes)), (name, root)
    moves = {op for _, dims, op in made if dims == z} - in_place
    assert moves <= {"copy-done", "custom-call"}, moves


def test_retention_decode_step_compiled_for_the_chip_runs_the_state_kernel(
        one_chip, monkeypatch):
    """The same decode step with the state kernel selected as on the chip
    (`ops/pallas/retention_state.py`): the v5e's compiler takes
    one kernel a layer, the layer's two S halves and the pools' z parts
    passed whole and written in place; nothing else has a layer's S or a
    z array as its result but parameters, tuple elements and bitcasts (and
    the compiler's staging of z in the chip's own memory), and the
    program's temporaries are under the XLA form's."""
    from mxnet_tpu.models import hybrid
    from mxnet_tpu.ops.pallas import retention_state as rs
    layers, S, B, total = 2, 1024, 20, 21
    net = hybrid.HybridLM(vocab_size=512, num_layers=layers, units=256,
                          hidden_size=512, num_heads=40, num_kv_heads=8,
                          head_dim=128, recurrent="retention",
                          rope_theta=1e6, tied_head=False, max_length=S)
    cfg = net.config

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    params = {"embed": sds(net.embed.shape, net.dtype),
              "norm_f": sds(net.norm_f.shape, net.dtype),
              "head": sds(net.head.shape, net.dtype),
              "runs": [{k: sds(getattr(r, k).shape, net.dtype)
                        for k in r.leaves} for r in net.runs]}
    pool = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: decoder.fresh_pool(cfg, total, S)))
    rest = (sds((B,), jnp.int32), sds((B,), jnp.int32),
            sds((B, 1), jnp.int32), sds((B,), jnp.bool_))

    def compiled():
        return hybrid.build_decode_step(cfg, S).lower(
            params, pool, pool, *rest).compile()
    xla = compiled()
    assert rs.last_path == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernel = compiled()
    assert rs.last_path == "pallas"
    hlo = kernel.as_text()
    assert len(re.findall(r"%retention_state_step[.\d]* = \(.*\) custom-call\(",
                          hlo)) == layers
    fused = set(re.findall(r"calls=%?([\w.\-]+)", hlo))
    made = [m for comp in re.split(r"\n(?=\S)", hlo)
            if comp.split(" ", 2)[int(comp.startswith("ENTRY"))].lstrip("%")
            not in fused
            for m in re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = "
                                r"f32\[([\d,]+)\]\S* ([\w\-]+)\(", comp,
                                re.M)]
    slab = re.compile(r"^(\d+,)*(21,4,8256|21,33024|903,768),128$")
    z = re.compile(r"^(%d,21|21,%d),16512$" % (layers, layers))
    as_it_lies = {"parameter", "get-tuple-element", "bitcast"}
    assert {op for _, dims, op in made if slab.match(dims)} <= as_it_lies
    assert {op for _, dims, op in made if z.match(dims)} <= as_it_lies | {
        "copy-done"}
    assert kernel.memory_analysis().temp_size_in_bytes < (
        xla.memory_analysis().temp_size_in_bytes)


# ---------------------------------------------------------------------------
# the latent attention block's two programs compiled for the chip (the
# xing4_0 block: models/latent.py, ops/pallas/latent_attention.py)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_latent_programs_compiled_for_the_chip_move_no_pool(one_chip,
                                                            monkeypatch,
                                                            program):
    """Two layers (one dense, one routed over 8 experts) at the published
    latent widths and ``xing4-29b-l5-serve``'s pool (769 pages of 256
    positions, half a page a pool: ``(2, 769, 128, 640)`` float32), six
    slots, chunks of 512, four residual streams, with the decode kernel
    selected as on the chip, through the v5e's compiler: the decode step
    calls the kernel once a layer with the pools whole; in both programs
    nothing with a pool's shape is made but in place (the rows' scatters,
    parameters, tuple elements), and neither keeps a temporary as large as
    one layer of a pool."""
    from mxnet_tpu.models import hybrid, latent
    from mxnet_tpu.ops.pallas import latent_attention
    monkeypatch.delenv("MXNET_PAGED_ATTENTION", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    decoder._fn_cache.clear()
    layers, S, B, T, pps = 2, 256, 6, 512, 128
    spec = latent.Latent(q_rank=768, kv_rank=512, nope=128, rope=64,
                         v_dim=128, theta=1e4, factor=64, original=4096,
                         beta_fast=32, beta_slow=1, mscale=1,
                         mscale_all_dim=1)
    net = hybrid.HybridLM(vocab_size=1024, num_layers=layers, units=3584,
                          hidden_size=9216, num_heads=32, num_kv_heads=32,
                          head_dim=128, latent=spec, dense_layers=1,
                          n_experts=8, experts_per_token=4,
                          expert_hidden=1024, shared_hidden=1024,
                          routed_scale=2.0, streams=4, sinkhorn_iters=20,
                          hc_eps=1e-6, hc_clamp=30.0, tied_head=False,
                          max_length=S * pps, kv_dtype="float32")
    cfg = net.config

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    params = {"embed": sds(net.embed.shape, net.dtype),
              "norm_f": sds(net.norm_f.shape, net.dtype),
              "head": sds(net.head.shape, net.dtype),
              "runs": [{k: sds(getattr(r, k).shape, net.dtype)
                        for k in r.leaves} for r in net.runs]}
    params.update({k: sds(getattr(net, k).shape, net.dtype)
                   for k in net.hc_leaves})
    total = B * pps + 1
    pool = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: decoder.fresh_pool(cfg, total, S)))
    assert pool.rows.shape == (layers, total, S // 2, 640)

    def i32(*shape):
        return sds(shape, jnp.int32)
    if program == "decode":
        fn, rest = decoder.make_decode_step(cfg, S), (
            i32(B), i32(B), i32(B, pps), sds((B,), jnp.bool_))
    else:
        fn, rest = decoder.make_prefill_chunk(cfg, S, T), (
            i32(T), i32(), i32(), i32(pps))
    compiled = fn.lower(params, pool, pool, *rest).compile()
    decoder._fn_cache.clear()
    hlo = compiled.as_text()
    if program == "decode":
        assert latent_attention.last_path == "pallas"
        assert len(re.findall(r"%paged_latent_rows[.\d]* = \S+ custom-call\(",
                              hlo)) == layers
    fused = set(re.findall(r"calls=%?([\w.\-]+)", hlo))
    made = [m for comp in re.split(r"\n(?=\S)", hlo)
            if comp.split(" ", 2)[int(comp.startswith("ENTRY"))].lstrip("%")
            not in fused
            for m in re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = "
                                r"f32\[([\d,]+)\]\S* ([\w\-]+)\(", comp,
                                re.M)]
    whole = "%d,%d,128,640" % (layers, total)
    in_place = {"parameter", "get-tuple-element", "bitcast", "fusion",
                "scatter", "custom-call", "tuple"}
    seen = [(name, op) for name, dims, op in made if dims == whole]
    assert seen and {op for _, op in seen} <= in_place, seen
    assert compiled.memory_analysis().temp_size_in_bytes < (
        total * 128 * 640 * 4)
