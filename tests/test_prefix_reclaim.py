"""Pages the prefix cache keeps are allocatable (`serving/kvcache.py`).

A page whose only reference is the prefix cache's is *reclaimable*: an
allocation that finds the free list short takes such pages, least recently
used first, in O(1) each, without raising and without touching the decode
pipeline.  The cases: the allocator and the cache alone (order, refresh,
what is out of reach, chains, cost as a count, one lock), then a tiny
engine on a pool that the cache keeps full, float32, int8 KV and a model
with state-space layers.

Counts on the CPU; no number here is a measurement of the chip."""
from __future__ import annotations

import os
import random
import sys
import threading
from collections import OrderedDict

import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import jamba_ref  # noqa: E402

from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.models import decoder  # noqa: E402
from mxnet_tpu.serving import kvcache  # noqa: E402
from mxnet_tpu.serving.kvcache import (CacheOOM, PageAllocator,  # noqa: E402
                                       PrefixCache)

pytestmark = pytest.mark.llm

S = 4          # page size of the allocator cases


def pool(pages, page_size=S):
    alloc = PageAllocator(total_pages=pages + 1, page_size=page_size)
    return alloc, PrefixCache(alloc)


def publish(alloc, cache, owner, tokens, release=True):
    """What a sequence does: lookup, share the hit, allocate the rest,
    publish, and (``release``) leave.  Returns its page table."""
    hit, covered, _ = cache.lookup(tokens)
    alloc.share(owner, hit)
    alloc.alloc(owner, kvcache.pages_for(len(tokens), alloc.page_size)
                - len(hit))
    table = alloc.pages(owner)
    cache.insert(tokens, table)
    if release:
        alloc.free(owner)
    return table


def prompt(seed, n):
    rng = random.Random(seed)
    return [rng.randrange(1, 1000) for _ in range(n)]


def reachable(cache):
    """Entries found by walking the chains down from the root."""
    kids = {}
    for (parent, _), e in cache._entries.items():
        kids.setdefault(parent, []).append(e)
    seen, todo = 0, [0]
    while todo:
        for e in kids.get(todo.pop(), ()):
            seen += 1
            todo.append(e.owner[1])
    return seen


# ---------------------------------------------------------------------------
# the allocator and the cache alone
# ---------------------------------------------------------------------------
def test_allocation_on_a_pool_full_of_cached_pages_succeeds():
    alloc, cache = pool(8)
    for i in range(4):
        publish(alloc, cache, ("seq", i), prompt(i, 2 * S))
    st = alloc.stats()
    assert (st["free_pages"], st["used_pages"], st["reclaimable_pages"]) \
        == (0, 8, 8)
    assert alloc.num_free == 0 and alloc.num_available == 8
    got = alloc.alloc("live", 5)
    assert len(set(got)) == 5
    st = alloc.stats()
    assert st["counters"]["failed_allocs"] == 0
    assert st["counters"]["reclaimed"] == 5
    assert cache.counters["evictions"] == 5 and len(cache) == 3
    # pages the cache keeps count as used: the peak is the whole pool
    assert st["used_pages"] == 8 and st["peak_used_pages"] == 8
    assert st["reclaimable_pages"] == 3
    alloc.check_leaks()


def test_fork_takes_a_reclaimable_page_too():
    alloc, cache = pool(4)
    table = publish(alloc, cache, "a", prompt(1, 2 * S + 2), release=False)
    gone = publish(alloc, cache, "c", prompt(2, S))
    alloc.share("b", table)
    alloc.free("a")
    assert alloc.num_free == 0
    new = alloc.fork("b", table[-1])
    assert [new] == gone and alloc.counters["failed_allocs"] == 0
    assert alloc.counters["reclaimed"] == 1 and len(cache) == 3
    # the forked-off partial page is the cache's alone now
    assert alloc.stats()["reclaimable_pages"] == 1
    assert alloc.alloc("d", 1) == [table[-1]]
    alloc.check_leaks()


def test_cache_oom_means_live_owners_hold_the_pool():
    alloc, cache = pool(4)
    publish(alloc, cache, "a", prompt(1, 2 * S), release=False)
    alloc.alloc("b", 2)
    with pytest.raises(CacheOOM):
        alloc.alloc("c", 1)
    with pytest.raises(CacheOOM):
        alloc.fork("b", alloc.pages("b")[0])
    assert alloc.counters["failed_allocs"] == 2
    assert alloc.counters["reclaimed"] == 0 and len(cache) == 2
    alloc.check_leaks()


def test_lru_order_and_refresh_on_hit():
    alloc, cache = pool(3)
    a, b, c = (prompt(i, S) for i in range(3))
    pa, pb, pc = (publish(alloc, cache, n, t)[0]
                  for n, t in (("a", a), ("b", b), ("c", c)))
    # a hit on the oldest (a lookup that takes no reference) refreshes it
    assert cache.lookup(a + [7]) == ([pa], S, False)
    assert alloc.alloc("x", 1) == [pb]
    assert alloc.alloc("y", 1) == [pc]
    assert alloc.alloc("z", 1) == [pa]
    assert len(cache) == 0
    # and so does publishing a prompt whose pages are cached already
    alloc.free("x"), alloc.free("y"), alloc.free("z")
    pa = publish(alloc, cache, "a", a)[0]
    pb = publish(alloc, cache, "b", b)[0]
    other = alloc.alloc("again", 1)
    assert cache.insert(a, other) == 0      # the first writer's page stays
    alloc.free("again")
    assert alloc.alloc("x", 2) == [pb] + other and len(cache) == 1
    assert cache.lookup(a + [7])[0] == [pa]
    alloc.check_leaks()


def test_a_hit_takes_its_pages_out_of_reach():
    alloc, cache = pool(6)
    tokens = prompt(1, 2 * S)
    kept = publish(alloc, cache, "a", tokens)
    for i in range(2, 4):
        publish(alloc, cache, ("seq", i), prompt(i, 2 * S))
    hit, covered, _ = cache.lookup(tokens + [5])
    assert hit == kept and covered == 2 * S
    alloc.share("hitter", hit)
    assert alloc.stats()["reclaimable_pages"] == 4
    got = alloc.alloc("other", 4)
    assert not set(got) & set(kept)
    with pytest.raises(CacheOOM):
        alloc.alloc("other", 1)
    assert cache.lookup(tokens + [5])[0] == kept     # the entries stayed
    # the last sharer gone, they are within reach again
    alloc.free("hitter")
    assert sorted(alloc.alloc("other", 2)) == sorted(kept)
    alloc.check_leaks()


def test_a_page_a_live_owner_shares_is_skipped_and_its_entry_kept():
    alloc, cache = pool(6)
    live = publish(alloc, cache, "live", prompt(1, 2 * S), release=False)
    for i in range(2, 4):
        publish(alloc, cache, ("seq", i), prompt(i, 2 * S))
    # the live chain is the least recently published of the three
    got = alloc.alloc("other", 4)
    assert not set(got) & set(live) and len(cache) == 2
    assert not cache.evict_one()            # nothing frees a page: nothing goes
    assert len(cache) == 2 and reachable(cache) == 2
    alloc.check_leaks()


def test_a_chain_goes_tail_first_and_leaves_nothing_unreachable():
    alloc, cache = pool(10)
    trunk = prompt(1, 2 * S)
    publish(alloc, cache, "a", trunk + prompt(2, 2 * S + 1))   # 5 pages
    publish(alloc, cache, "b", trunk + prompt(3, S + 2))       # + 2 of its own
    publish(alloc, cache, "c", prompt(4, 3 * S))               # 3 pages
    assert len(cache) == 10 == reachable(cache)
    taken = []
    while len(cache):
        before = len(cache)
        taken += alloc.alloc("x", 1)
        assert len(cache) == before - 1 == reachable(cache)
    # the trunk outlived both branches that hang on it
    a_table = taken[:3] + taken[5:7]
    assert len(set(taken)) == 10 and len(set(a_table)) == 5
    alloc.check_leaks()


def test_an_entry_hangs_only_on_a_page_its_publisher_holds():
    """Two sequences prefill the same prompt side by side: the second to
    publish finds the first's entries under pages it does not hold, and
    publishes nothing that would hang on them."""
    alloc, cache = pool(8)
    tokens = prompt(1, 2 * S)
    first = alloc.alloc("first", 2)
    second = alloc.alloc("second", 3)
    assert cache.insert(tokens, first) == 2
    assert cache.insert(tokens + prompt(2, S), second) == 0
    alloc.free("first")
    # "second" lives on, and the first's chain can go whole
    assert sorted(alloc.alloc("x", 5)[:2]) == sorted(first)
    assert len(cache) == 0
    alloc.check_leaks()


def test_evict_one_and_clear_stay_public():
    alloc, cache = pool(6)
    publish(alloc, cache, "a", prompt(1, 2 * S + 1))
    held = publish(alloc, cache, "b", prompt(2, S), release=False)
    assert alloc.num_used == 4
    assert cache.evict_one() and alloc.num_free == 3    # a's partial page
    assert cache.counters["evictions"] == 1
    assert cache.clear() == 3 and len(cache) == 0
    assert alloc.num_used == 1 and alloc.refcount(held[0]) == 1
    assert alloc.stats()["reclaimable_pages"] == 0
    assert not cache.evict_one()
    alloc.check_leaks()


class CountingIndex(OrderedDict):
    """The index of reclaimable pages, counting the entries it is asked
    about or hands out."""
    examined = 0

    def __iter__(self):
        for key in OrderedDict.__iter__(self):
            CountingIndex.examined += 1
            yield key

    def __contains__(self, key):
        CountingIndex.examined += 1
        return OrderedDict.__contains__(self, key)

    def pop(self, *args):
        CountingIndex.examined += 1
        return OrderedDict.pop(self, *args)

    def popitem(self, last=True):
        CountingIndex.examined += 1
        return OrderedDict.popitem(self, last)

    def move_to_end(self, key, last=True):
        CountingIndex.examined += 1
        return OrderedDict.move_to_end(self, key, last)


class NoScanDict(dict):
    """The cache's index of entries: a pass over it is a fault."""

    def _refuse(self, *args, **kw):
        raise AssertionError("a pass over every cache entry")
    values = items = keys = __iter__ = _refuse


def test_reclaiming_n_of_2000_entries_examines_order_n(monkeypatch):
    alloc, cache = pool(2000, page_size=16)
    for i in range(50):
        publish(alloc, cache, ("seq", i), prompt(i, 40 * 16))
    assert len(cache) == 2000 and alloc.num_free == 0
    assert alloc.stats()["reclaimable_pages"] == 2000
    CountingIndex.examined = 0
    alloc._reclaimable = CountingIndex(alloc._reclaimable)
    cache._entries = NoScanDict(cache._entries)
    forgotten = []
    monkeypatch.setattr(alloc, "_forget",
                        lambda e, inner=alloc._forget: (
                            forgotten.append(e), inner(e)))
    n = 40
    got = alloc.alloc("x", n)
    assert len(got) == n and len(forgotten) == n
    assert dict.__len__(cache._entries) == 2000 - n
    # a constant number of looks at the index per page, none at the rest
    assert CountingIndex.examined <= 4 * n
    # the oldest chain went, whole and from its tail
    assert [e.owner[1] for e in forgotten] == list(range(n, 0, -1))


def test_a_960_token_prompt_builds_keys_as_long_as_itself(monkeypatch):
    built = []

    def counting_tuple(items=()):
        out = tuple(items)
        built.append(len(out))
        return out
    monkeypatch.setattr(kvcache, "tuple", counting_tuple, raising=False)
    alloc, cache = pool(64, page_size=16)
    tokens = prompt(1, 960)
    publish(alloc, cache, "a", tokens)
    # one lookup (a miss at the first page) and one insert
    assert sum(built) <= 16 + 960 and len(built) <= 1 + 60
    assert sum(len(k[1]) for k in cache._entries) == 960
    del built[:]
    hit, covered, _ = cache.lookup(tokens + [1])
    assert covered == 960 and len(hit) == 60
    assert sum(built) == 960


def test_one_lock_for_allocator_and_cache_under_threads():
    """``insert`` goes cache -> allocator and a reclaim goes allocator ->
    cache; ``stats()`` is called from HTTP threads meanwhile."""
    alloc, cache = pool(16)
    assert cache._lock is alloc._lock
    stop, seen = threading.Event(), []

    def reader():
        while not stop.is_set():
            st = alloc.stats()
            seen.append((st["used_pages"] + st["free_pages"],
                         cache.stats()["entries"] <= 16))

    def writer(k):
        for i in range(300):
            publish(alloc, cache, ("w", k, i), prompt(1000 * k + i, 3 * S))

    readers = [threading.Thread(target=reader, daemon=True) for _ in range(2)]
    writers = [threading.Thread(target=writer, args=(k,), daemon=True)
               for k in range(3)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join(60)
    stop.set()
    for t in readers:
        t.join(10)
    assert not any(t.is_alive() for t in readers + writers)
    assert seen and set(seen) == {(16, True)}
    assert alloc.counters["failed_allocs"] == 0
    assert alloc.counters["reclaimed"] > 0
    alloc.check_leaks()
    assert reachable(cache) == len(cache)


# ---------------------------------------------------------------------------
# a tiny engine on a pool that the cache keeps full
# ---------------------------------------------------------------------------
VOCAB = 128


def greedy(lm, tokens, n):
    params, cfg = lm.jax_params(), lm.config
    toks = list(tokens)
    for _ in range(n):
        logits = decoder.full_forward(params, cfg,
                                      jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(tokens):]


def hybrid_greedy(lm, tokens, n):
    fed, out = list(tokens), []
    for _ in range(n):
        logits = jamba_ref.reference_logits(lm.jax_params(), lm.config,
                                            fed, 1)
        out.append(int(jnp.argmax(logits[-1])))
        fed.append(out[-1])
    return out


def roomy_answers(lm, requests, **kw):
    """The oracle of the many: the same model behind an engine with room
    for everything, the prefix cache and the pipeline off (which
    ``tests/test_llm_serving.py`` holds to ``greedy``)."""
    engine = serving.DecodeEngine(lm, name="oracle", prefix_cache=False,
                                  async_decode=False, **kw)
    try:
        futs = [engine.submit(p, max_new_tokens=n) for p, n in requests]
        return [f.result(300)["tokens"] for f in futs]
    finally:
        engine.stop()


KINDS = {
    "float32": dict(page_size=8, max_ctx=64, prefill_chunk=8),
    "int8_kv": dict(page_size=8, max_ctx=64, prefill_chunk=8,
                    kv_dtype="int8"),
    "hybrid": dict(page_size=4, max_ctx=40, prefill_chunk=8),
}


@pytest.fixture(scope="module")
def models():
    return {"float32": decoder.decoder_tiny_lm(seed=0, vocab_size=VOCAB),
            "hybrid": decoder.hybrid_lm(seed=3, dtype="float32")}


def counters(engine):
    return engine.metrics.snapshot()["models"][engine.name]["counters"]


def watch_handouts(engine, monkeypatch):
    """Every page an allocation hands out, against the pages of the
    launches in flight at that moment (their owners' tables as they
    were at the launch)."""
    handed = []
    pin, take = engine._pin_owners, engine.alloc._take_locked

    def pin_owners(fl):
        fl.pages = {p for o in fl.owners for p in engine.alloc.pages(o)}
        return pin(fl)

    def take_locked(n):
        pages = take(n)
        busy = set().union(*(getattr(fl, "pages", ())
                             for fl in list(engine._pipe)))
        handed.append((len(pages), sorted(set(pages) & busy)))
        return pages
    monkeypatch.setattr(engine, "_pin_owners", pin_owners)
    monkeypatch.setattr(engine.alloc, "_take_locked", take_locked)
    return handed


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_engine_on_a_full_pool_never_flushes_for_a_page(kind, models,
                                                        monkeypatch):
    """Fresh prompts until the cache has the pool, then 100 more requests
    from closed-loop callers: no allocation fails, the pipeline is never
    flushed for a page, no page of a launch in flight is handed out, and
    every answer is the greedy oracle's."""
    lm = models["hybrid" if kind == "hybrid" else "float32"]
    kw = KINDS[kind]
    engine = serving.DecodeEngine(lm, name="llm", slots=4, async_decode=True,
                                  prefix_cache=True, **kw)
    handed = watch_handouts(engine, monkeypatch)
    rng = random.Random(32)
    top = kw["max_ctx"] // 2

    def fresh():
        return ([rng.randrange(1, VOCAB) for _ in range(rng.randrange(5, top))],
                rng.randrange(3, 9))
    asked, got = [], {}
    try:
        # until the cache has the pool: the first page taken back from it
        while not engine.alloc.counters["reclaimed"]:
            asked.append(fresh())
            got[len(asked) - 1] = engine.submit(
                asked[-1][0], max_new_tokens=asked[-1][1]).result(300)["tokens"]
        filled = len(asked)
        asked += [fresh() for _ in range(100)]
        todo = iter(range(filled, len(asked)))
        lock = threading.Lock()

        def caller():
            while True:
                with lock:
                    i = next(todo, None)
                if i is None:
                    return
                got[i] = engine.submit(
                    asked[i][0],
                    max_new_tokens=asked[i][1]).result(300)["tokens"]
        callers = [threading.Thread(target=caller) for _ in range(4)]
        for t in callers:
            t.start()
        for t in callers:
            t.join(600)
        c, kv = counters(engine), engine.stats()["kv"]
    finally:
        assert engine.stop()
    assert len(got) == len(asked)
    assert c["pipe_flushes_page_pressure_total"] == 0
    assert c["pipe_flushes_total"] == c["pipe_flushes_ops_total"] == 0
    assert c["preemptions_total"] == 0
    assert kv["counters"]["failed_allocs"] == 0
    assert c["kv_reclaimed_pages_total"] > 100
    assert c["kv_reclaimed_pages_total"] <= kv["counters"]["reclaimed"]
    assert kv["peak_used_pages"] == kv["total_pages"]
    assert kv["used_pages"] - kv["reclaimable_pages"] <= 4 * (
        kw["max_ctx"] // kw["page_size"])
    if kind == "hybrid":
        # whole pages only: no entry of a partially filled page
        assert engine.prefix_cache._partials == 0
    # an in-flight launch's pages were never handed out before its retire
    assert handed and not [h for h in handed if h[1]]
    assert engine.alloc.num_used == 0
    engine.alloc.check_leaks()
    assert [got[i] for i in range(len(asked))] == roomy_answers(
        lm, asked, slots=4, **kw)
    # and the independent reference, for the three shortest
    oracle = {"float32": greedy, "hybrid": hybrid_greedy}.get(kind)
    for i in sorted(range(len(asked)), key=lambda i: sum(map(len, (
            asked[i][0], got[i]))))[:3 if oracle else 0]:
        assert got[i] == oracle(lm, *asked[i])


def test_a_pool_held_by_live_sequences_still_flushes_and_preempts(models):
    """``CacheOOM`` keeps its meaning: with the cache's pages gone and live
    sequences holding the rest, page growth flushes the pipeline once for
    each failed allocation and preempts as before."""
    lm = models["float32"]
    engine = serving.DecodeEngine(lm, name="llm", slots=3, page_size=4,
                                  max_ctx=32, total_pages=9, prefill_chunk=8,
                                  async_decode=True, prefix_cache=True)
    prompts = [[1, 2, 3], [7, 5], [2, 9, 4, 1], [3], [11, 3, 7]]
    try:
        futs = [engine.submit(list(p), max_new_tokens=14) for p in prompts]
        got = [f.result(300)["tokens"] for f in futs]
        c, kv = counters(engine), engine.stats()["kv"]
    finally:
        assert engine.stop()
    assert got == roomy_answers(lm, [(p, 14) for p in prompts], slots=3,
                                page_size=4, max_ctx=32, prefill_chunk=8)
    assert c["preemptions_total"] > 0
    assert 0 < c["pipe_flushes_page_pressure_total"] \
        <= kv["counters"]["failed_allocs"]
    assert c["pipe_flushes_total"] == c["pipe_flushes_page_pressure_total"]
    assert engine.alloc.num_used == 0
    engine.alloc.check_leaks()
