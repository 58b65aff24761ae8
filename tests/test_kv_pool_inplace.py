"""The KV pool written in place (PR 26) against the formulation it replaced.

The step programs hold K and V as token rows ``(L, P, S, KVH * D)`` and
write them a page at a time with ``dynamic_update_slice``; until PR 26
they held ``(L, KVH, P, S, D)``, scattered with ``.at[li, :, wp, ws,
:].set`` and read back through ``gather_pages``.  The change moves bytes
and nothing else, so each program x each pool dtype is held here to
**bit-identical** pools and logits against a test-local copy of the old
helpers, plugged into the same step functions, over the cases where a
write path could differ: page tables that alias pages, inactive slots
and padded tokens (which write the scratch page), a chunk whose
``n_valid`` is short of the chunk, a ``pos0`` in the middle of a page.

Since PR 30 the decode step's attention reads the gathered token rows as
they lie (``attend_rows``) and no longer the head-major view the old
helpers build: the same products with the float additions in another
order.  The two ``decode-*`` cases therefore keep the pools' comparison
bit for bit where the writes alone decide it (the first layer: the
writes did not change; a later layer's K and V come through the layers
before it, attention included, so they are held to 1e-5 of their std
and int8 codes to one step) and the next tokens exact, and hold the
logits to 1e-5 of their std; ``prefill-*`` and ``verify-*`` still read
the head-major view and stay bit-identical throughout.
``tests/test_decode_attention_rows.py`` holds the new form itself.

The scratch page (page 0) is left out of the pool comparison: nothing
reads it validly, duplicate writes to it land in an order XLA does not
define for a scatter, and the page-at-a-time prefill write leaves it as
it was.  It has to stay finite (a masked 0 x inf is a NaN).

An engine-level case then drives the host-side edits of the pool: a
copy-on-write fork, a page export and import, and a ``pack_session``
round trip, each followed by a launch that decodes the oracle's tokens.
"""
from __future__ import annotations

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.models import decoder
from mxnet_tpu.ops.pallas import paged_attention as paged
from mxnet_tpu.serving.kvcache import pack_session, unpack_session

pytestmark = [pytest.mark.llm]

VOCAB, S, B, PPS, CHUNK, W = 128, 4, 4, 6, 8, 3
TOTAL = B * PPS + 1


@pytest.fixture(scope="module")
def lm():
    return decoder.decoder_tiny_lm(seed=0, vocab_size=VOCAB)


# ---------------------------------------------------------------------------
# the old formulation: pages-form pools, one scatter, one gather
# ---------------------------------------------------------------------------
def old_kv_append(pages, li, wp, ws, val, chunk=None):
    if not isinstance(pages, paged.QPages):
        return pages.at[li, :, wp, ws, :].set(val)
    amax = jnp.abs(val.astype(jnp.float32)).max(axis=-1)
    fresh = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    old = pages.s[li, :, wp]
    t = ws.shape[-1]
    src = jnp.arange(t, dtype=jnp.int32) - ws
    start_fresh = jnp.take_along_axis(
        fresh, jnp.clip(src, 0, t - 1)[..., None], axis=-2)
    snew = jnp.where((src >= 0)[..., None], start_fresh, old)
    codes = jnp.clip(jnp.round(val.astype(jnp.float32) / snew[..., None]),
                     -127, 127).astype(jnp.int8)
    return paged.QPages(q=pages.q.at[li, :, wp, ws, :].set(codes),
                        s=pages.s.at[li, :, wp].set(snew))


def old_kv_layer(pages, li):
    if isinstance(pages, paged.QPages):
        return paged.QPages(q=pages.q[li], s=pages.s[li])
    return pages[li]


def old_gather_kv(pages, li, tables, num_kv_heads):
    pages_li = old_kv_layer(pages, li)
    if isinstance(pages_li, paged.QPages):
        return paged.gather_pages_deq(pages_li.q, pages_li.s, tables)
    return paged.gather_pages(pages_li, tables)


def old_decode_attention(q, k_pages, v_pages, li, lengths, tables,
                         num_kv_heads):
    return paged.paged_attention(q, old_kv_layer(k_pages, li),
                                 old_kv_layer(v_pages, li), lengths, tables)


@pytest.fixture()
def old_formulation(monkeypatch):
    """decoder's step functions over the old helpers: the builders called
    directly, so the program cache never sees these."""
    monkeypatch.setattr(decoder, "_kv_append", old_kv_append)
    monkeypatch.setattr(decoder, "_gather_kv", old_gather_kv)
    monkeypatch.setattr(decoder, "_decode_attention", old_decode_attention)


def build(cfg, which):
    """The un-cached program of ``which``: traced when first called,
    so under ``old_formulation`` it is the old one."""
    if which == "decode":
        return decoder._build_decode_step(cfg, S)
    if which == "prefill":
        return decoder._build_prefill_chunk(cfg, S, CHUNK)
    return decoder._build_verify_step(cfg, S, W)


def random_pools(cfg, kv, seed):
    """(pages form, rows form) of one random pool; scratch page zero."""
    rs = onp.random.RandomState(seed)
    shape = (cfg.num_layers, cfg.num_kv_heads, TOTAL, S, cfg.head_dim)
    if kv == "int8":
        q = rs.randint(-127, 128, size=shape).astype(onp.int8)
        q[:, :, 0] = 0
        s = rs.uniform(0.01, 0.1, size=shape[:3]).astype(onp.float32)
        s[:, :, 0] = 1.0
        pages = paged.QPages(q=jnp.asarray(q), s=jnp.asarray(s))
    else:
        a = rs.randn(*shape).astype(onp.float32)
        a[:, :, 0] = 0.0
        pages = jnp.asarray(a)
    return pages, decoder.rows_from_pages(pages)


def tables_with_aliases():
    """Four page-table rows: slots 0 and 1 share their first two pages
    (a cached prefix), slot 2 is idle (all scratch), slot 3 starts a
    fresh page; unallocated tail entries point at the scratch page."""
    t = onp.zeros((B, PPS), onp.int32)
    t[0] = [1, 2, 3, 4, 0, 0]
    t[1] = [1, 2, 5, 6, 0, 0]
    t[3] = [7, 8, 9, 0, 0, 0]
    return t


def step_args(which, step):
    tables = tables_with_aliases()
    if which == "decode":
        # slot 0 mid-page, slot 1 at a page's last slot, slot 2 inactive,
        # slot 3 at a page start (the int8 scale latch)
        positions = onp.array([9, 11, 0, 8], onp.int32) + step
        return (jnp.asarray(onp.array([5, 9, 0, 17], onp.int32) + step),
                jnp.asarray(positions), jnp.asarray(tables),
                jnp.asarray([True, True, False, True]))
    if which == "prefill":
        # first a chunk that starts mid-page with n_valid short of the
        # chunk (three padded tokens), then one that fills a whole chunk
        pos0, n_valid = ((6, 5), (11, CHUNK))[step]
        toks = onp.arange(3, 3 + CHUNK, dtype=onp.int32) * (step + 2) % VOCAB
        return (jnp.asarray(toks), jnp.int32(pos0), jnp.int32(n_valid),
                jnp.asarray(onp.array([10, 11, 12, 13, 14, 15], onp.int32)))
    # verify: a full window, a window of one (plain decode riding the
    # wide program), an inactive slot, and a window that crosses a page
    toks = (onp.arange(B * W, dtype=onp.int32).reshape(B, W) * 7
            + step) % VOCAB
    return (jnp.asarray(toks),
            jnp.asarray(onp.array([9, 11, 0, 7], onp.int32) + step),
            jnp.asarray(onp.array([3, 1, 2, 2], onp.int32)),
            jnp.asarray(tables), jnp.asarray([True, True, False, True]))


def run_two_steps(fn, params, k_pool, v_pool, which):
    outs = []
    for step in range(2):
        k_pool, v_pool, *out = fn(params, k_pool, v_pool,
                                  *step_args(which, step))
        outs.append([onp.asarray(o) for o in out])
    return k_pool, v_pool, outs


def assert_same_pool(new_rows, old_pages, exact_layers=None):
    """Bit for bit in the first ``exact_layers`` layers (all by default).
    In the layers after them what is written came through an attention
    whose additions ran in another order, so the rows agree to 1e-5 of
    their std, int8 codes to one step and their scales to 1e-5."""
    old_rows = decoder.rows_from_pages(old_pages)
    for name, new, old in zip(("codes", "scales"),
                              jax.tree.leaves(new_rows),
                              jax.tree.leaves(old_rows)):
        new, old = onp.asarray(new), onp.asarray(old)
        assert new.shape == old.shape and new.dtype == old.dtype
        # the page axis is 1 in rows-form codes, last in the scales
        live = ((slice(None), slice(1, None)) if new.ndim == 4
                else (Ellipsis, slice(1, None)))
        new_live, old_live = new[live], old[live]
        n = new.shape[0] if exact_layers is None else exact_layers
        assert new_live[:n].tobytes() == old_live[:n].tobytes(), name
        gap = onp.abs(new_live[n:].astype(onp.float32)
                      - old_live[n:].astype(onp.float32))
        if gap.size:
            assert gap.max() <= (1 if new.dtype == onp.int8
                                 else 1e-5 * old_live[n:].std()), name
        assert onp.isfinite(new.astype(onp.float32)).all(), name


@pytest.fixture()
def old_result(request, lm, old_formulation):
    which, kv = request.param
    kp, _ = random_pools(lm.config, kv, 1)
    vp, _ = random_pools(lm.config, kv, 2)
    return run_two_steps(build(lm.config, which), lm.jax_params(), kp, vp,
                         which)


CASES = [(w, kv) for w in ("decode", "prefill", "verify")
         for kv in ("float32", "int8")]


@pytest.mark.parametrize("old_result, case", [(c, c) for c in CASES],
                         indirect=["old_result"],
                         ids=["%s-%s" % c for c in CASES])
def test_in_place_pool_is_bit_identical_to_scatter_and_gather(
        lm, old_result, case, monkeypatch):
    which, kv = case
    old_k, old_v, old_outs = old_result
    monkeypatch.undo()                  # back to the program's own helpers
    assert decoder._kv_append is not old_kv_append
    _, kp = random_pools(lm.config, kv, 1)
    _, vp = random_pools(lm.config, kv, 2)
    new_k, new_v, new_outs = run_two_steps(
        build(lm.config, which), lm.jax_params(), kp, vp, which)
    for step, (new, old) in enumerate(zip(new_outs, old_outs)):
        for n, o in zip(new, old):
            assert n.dtype == o.dtype and n.shape == o.shape, step
            if which == "decode" and n.dtype == onp.float32:
                # the logits: attention's additions run in another order
                assert onp.abs(n - o).max() < 1e-5 * o.std(), step
            else:
                assert n.tobytes() == o.tobytes(), step
    # the decode step's first layer writes what the embeddings alone
    # decide; the later layers write what its attention handed on
    exact = 1 if which == "decode" else None
    assert_same_pool(new_k, old_k, exact)
    assert_same_pool(new_v, old_v, exact)


def test_pool_forms_round_trip(lm):
    pages, rows = random_pools(lm.config, "int8", 3)
    assert rows.q.shape == decoder.pool_shape(lm.config, TOTAL, S)
    back = decoder.pages_from_rows(rows, lm.config.num_kv_heads)
    assert onp.asarray(back.q).tobytes() == onp.asarray(pages.q).tobytes()
    assert back.s is rows.s or onp.array_equal(back.s, pages.s)
    fresh = decoder.fresh_pool(lm.config, TOTAL, S, "int8")
    assert fresh.q.dtype == jnp.int8 and float(fresh.s.min()) == 1.0


def test_factories_take_a_converted_pages_pool(lm):
    """The cached programs take rows form only.  A caller that holds a
    pages-form pool converts it itself (`rows_from_pages`); rows form
    comes back, is fed back, and says what a fresh pool says."""
    cfg, params = lm.config, lm.jax_params()
    prefill = decoder.make_prefill_chunk(cfg, S, CHUNK)
    decode = decoder.make_decode_step(cfg, S)
    shape = (cfg.num_layers, cfg.num_kv_heads, TOTAL, S, cfg.head_dim)
    results = []
    for hand_built in (True, False):
        if hand_built:
            kp, vp = (decoder.rows_from_pages(jnp.zeros(shape, jnp.float32))
                      for _ in range(2))
        else:
            kp, vp = (decoder.fresh_pool(cfg, TOTAL, S) for _ in range(2))
        kp, vp, tok, last = prefill(params, kp, vp,
                                    *step_args("prefill", 1)[:1],
                                    jnp.int32(0), jnp.int32(CHUNK),
                                    step_args("prefill", 1)[3])
        assert kp.shape == decoder.pool_shape(cfg, TOTAL, S)
        kp, vp, nxt, logits = decode(params, kp, vp,
                                     *step_args("decode", 0))
        results.append((onp.asarray(last), onp.asarray(logits),
                        onp.asarray(kp)))
    for a, b in zip(*results):
        assert a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# the engine's host-side edits of the pool
# ---------------------------------------------------------------------------
def greedy_oracle(lm, prompt, n):
    params, cfg = lm.jax_params(), lm.config
    toks = list(prompt)
    for _ in range(n):
        logits = decoder.full_forward(params, cfg,
                                      jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def make_engine(lm, **kw):
    return serving.DecodeEngine(lm, name="llm", slots=4, page_size=8,
                                prefill_chunk=8, max_ctx=64, **kw)


def test_engine_pool_edits_are_followed_by_launches_that_decode_alike(lm):
    cfg = lm.config
    e1, e2 = make_engine(lm, prefix_cache=True), make_engine(lm)
    try:
        assert e1._kp.shape == decoder.pool_shape(
            cfg, e1.alloc.total_pages, e1.page_size)
        # a copy-on-write fork: a prompt that extends a cached one in the
        # middle of a page writes into a private copy of that page
        base = list(range(1, 19))
        assert (e1.submit(base, 4).result(30)["tokens"]
                == greedy_oracle(lm, base, 4))
        ext = base + [60, 61]
        assert (e1.submit(ext, 4, session="s").result(30)["tokens"]
                == greedy_oracle(lm, ext, 4))
        counters = e1.metrics.snapshot()["models"]["llm"]["counters"]
        assert counters["cow_forks_total"] >= 1
        # page export: the wire's pages form, byte for byte the rows
        hist = ext + greedy_oracle(lm, ext, 4)
        blob = e1.export_session("s")
        meta, k, v = unpack_session(blob)
        n = k.shape[2]
        assert k.shape == (cfg.num_layers, cfg.num_kv_heads, n, 8,
                           cfg.head_dim)
        assert pack_session(meta, k, v) == blob
        # page import, then a launch over the imported pages
        e2.import_session(blob)
        rows = onp.asarray(e2._kp)[:, e2.alloc.pages(("imp", 1))]
        assert rows.tobytes() == onp.ascontiguousarray(
            k.transpose(0, 2, 3, 1, 4)).tobytes()
        assert (e2.submit([7], 4, session="s", resume=True).result(30)[
            "tokens"] == greedy_oracle(lm, hist + [7], 4))
        # the exporter launches again after its export too
        assert (e1.submit([9], 3, session="s", resume=True).result(30)[
            "tokens"] == greedy_oracle(lm, hist + [9], 3))
    finally:
        e1.stop()
        e2.stop()
    for e in (e1, e2):
        assert e.alloc.num_used == 0
        e.alloc.check_leaks()
