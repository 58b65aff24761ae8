"""Page-granular KV-cache allocator for continuous-batching decode.

The vLLM memory model at the serving layer: the device-side KV cache is
a fixed pool of ``total_pages`` pages of ``page_size`` tokens each
(``ops/pallas/paged_attention.py`` owns the device layout and the
attention over it); this module owns the HOST-side bookkeeping —

- a LIFO **free list** (freed pages are re-used hottest-first), topped
  up at allocation from the **reclaimable** pages: those the prefix cache
  keeps and nothing else refers to, least recently used first,
- per-owner **page lists** (the sequence's page table, in allocation
  order == token order),
- per-page **refcounts**: a page may appear in several owners' page
  tables at once (vLLM-style prefix sharing); it returns to the free
  list only when the last reference drops.  :meth:`PageAllocator.share`
  attaches existing pages to another owner, :meth:`PageAllocator.fork`
  is the copy-on-write bookkeeping half (the caller copies the device
  contents),
- exact **occupancy accounting** (used/total, peak, shared pages,
  alloc/free/fail counters) — the admission-control signal and the
  serving metric.

Page 0 is reserved as the *scratch page*: inactive batch slots and
padded prefill tokens scatter their (garbage) KV there, so the decode
step never needs a dynamic shape or a host round-trip to mask writes.
It is excluded from the free list and from occupancy math.

On top of the allocator this module provides the two pieces that make
KV state portable and shareable:

- :func:`pack_session` / :func:`unpack_session` — the flat, CRC-guarded
  wire format for one session's page table + live pages (the
  serialization half of KV migration; the engine owns gathering and
  scattering the device arrays),
- :class:`PrefixCache` — content-addressed prompt-prefix pages (a chain
  of full pages, each keyed by its parent entry and its own tokens, plus
  the trailing partial page), shared copy-on-write so N sequences with
  a common system prompt pay its prefill once.  The pages it keeps
  count as used and are handed out again by ``alloc`` when the free
  list runs short.

Tensor-parallel serving (``DecodeEngine(sharding=...)``) changes NONE
of this bookkeeping: page ids, refcounts, and occupancy are per-page
regardless of how the device pool is laid out, and the pool splits
along the KV-head axis — every shard holds the same pages, each with
``num_kv_heads // tp`` of the heads.  ``pack_session`` blobs always
carry FULL-head pages: the engine gathers shards to host on export and
re-pins to the mesh on import, so a session migrates freely between
replicated and TP replicas of any degree.

The allocator is synchronous and oblivious to device timing: a freed
page goes back on the (LIFO) free list immediately and may be handed
out on the very next ``alloc``.  Callers that overlap host scheduling
with device decode steps (the async engine, ISSUE 17) must therefore
treat pages referenced by a launched-but-unretired step as PINNED —
``DecodeEngine`` defers such frees onto the pinning step's retire
(``generate._free_owner``) so the free list never recycles a page an
in-flight launch still writes.  Once the pipeline drains, the usual
invariant holds: occupancy returns to zero and ``check_leaks`` is
clean.

Fault site ``kvcache.alloc`` (``mxnet_tpu.faults``) trips inside
:meth:`PageAllocator.alloc`, so chaos tests can fail allocations
deterministically; genuine exhaustion (live sequences and parked
sessions hold the pool) raises :class:`CacheOOM`, which
the decode engine turns into preemption (evict-youngest + recompute)
rather than an error.  Invariant violations raise the typed
:class:`~.errors.KVLeakError` from :meth:`PageAllocator.check_leaks`.
"""
from __future__ import annotations

import json
import struct
import threading
import zlib
from collections import OrderedDict

import numpy as onp

from .. import faults
from .errors import KVLeakError

__all__ = ["CacheOOM", "PageAllocator", "PrefixCache", "pages_for",
           "pack_session", "unpack_session"]

#: page id reserved for garbage writes from inactive/padded batch rows
SCRATCH_PAGE = 0


class CacheOOM(RuntimeError):
    """Free and reclaimable pages together cannot satisfy an allocation:
    live sequences and parked sessions hold the pool.  Internal to the
    decode engine: the scheduler responds by preempting (or, with
    nothing to preempt, failing the request typed) — callers outside
    the engine never see this."""


def pages_for(tokens, page_size):
    """Pages needed to hold ``tokens`` cache slots."""
    return -(-int(tokens) // int(page_size))


class PageAllocator:
    """Thread-safe refcounted free-list allocator over a fixed pool.

    ``total_pages`` counts the scratch page, mirroring the device
    arrays' leading page dimension; capacity available to sequences is
    ``total_pages - 1``.  A page freshly allocated has refcount 1;
    :meth:`share` bumps it (prefix hits, cache retention), and
    :meth:`free`/:meth:`fork` drop references — the page rejoins the
    free list only at refcount zero, so occupancy counts every
    physically-resident page exactly once however many tables map it.

    A page the prefix cache keeps (:meth:`keep`) whose only reference is
    the cache's is *reclaimable*: it counts as used, and an allocation
    that finds the free list short takes such pages, least recently
    released first, in O(1) each.  :class:`CacheOOM` therefore means that
    ``free + reclaimable`` is short.  The index of reclaimable pages
    lives here, kept by the reference counts as they change; the cache
    runs under this allocator's (reentrant) lock, so the two have one
    lock between them and no order to invert.
    """

    def __init__(self, total_pages, page_size, kv_dtype="float32",
                 page_bytes=0, scale_page_bytes=0):
        if total_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the scratch page)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        if str(kv_dtype) not in ("float32", "bfloat16", "int8"):
            raise ValueError("kv_dtype must be float32, bfloat16 or int8, "
                             "got %r" % (kv_dtype,))
        self.total_pages = int(total_pages)
        self.page_size = int(page_size)
        # quantized pools (ISSUE 16): int8 pages carry a parallel scales
        # pool indexed by the SAME page ids, so one refcount/free-list
        # conservation check covers both pools — check_leaks needs no
        # second ledger.  The byte costs are optional engine-supplied
        # geometry (k+v codes per page, k+v scales per page) so stats()
        # can report physical bytes and the per-token cost with the
        # scales amortized over the page.
        self.kv_dtype = str(kv_dtype)
        self.page_bytes = int(page_bytes)
        self.scale_page_bytes = int(scale_page_bytes)
        self._lock = threading.RLock()
        # LIFO: freshly freed pages go back out first (warm reuse)
        self._free = list(range(self.total_pages - 1, SCRATCH_PAGE, -1))
        self._owned = {}   # owner -> [page, ...] in allocation order
        self._refs = {}    # page -> live reference count
        # the prefix cache's pages: page -> its entry, and of those the
        # ones at refcount 1 (the cache's own), least recently released
        # first.  A sequence holds every page of the chain it hangs on
        # and releases its table last page first, so an entry always
        # lies before its parent here and a chain goes from its tail.
        self._kept = {}
        self._reclaimable = OrderedDict()
        self._forget = None   # the cache's: take an entry out of its index
        self.peak_used = 0
        self.counters = {"allocs": 0, "frees": 0, "failed_allocs": 0,
                         "shares": 0, "forks": 0, "trims": 0,
                         "reclaimed": 0, "leak_checks": 0}
        self.last_leak = []

    # -- allocation -------------------------------------------------------
    def alloc(self, owner, n=1):
        """Append ``n`` fresh (refcount-1) pages to ``owner``'s page
        list; returns the new pages.  Raises :class:`CacheOOM` when free
        and reclaimable pages together are short (nothing is allocated
        then), and whatever the ``kvcache.alloc`` fault site injects."""
        n = int(n)
        if n <= 0:
            return []
        faults.check("kvcache.alloc")
        with self._lock:
            pages = self._take_locked(n)
            self._owned.setdefault(owner, []).extend(pages)
            return pages

    def _take_locked(self, n):
        """``n`` pages at refcount 1 off the free list, which is topped
        up from the reclaimable pages first.  Taking one of those needs
        no quiesced engine: a page that a launch in flight reads or
        writes is in its owner's table until that flight retires (the
        engine defers the free, ``generate._free_owner``), so its count
        is above the cache's one and it is not in the index."""
        short = n - len(self._free)
        if short > len(self._reclaimable):
            self.counters["failed_allocs"] += 1
            raise CacheOOM(
                "kv cache exhausted: want %d page(s), %d free and %d "
                "reclaimable of %d" % (n, len(self._free),
                                       len(self._reclaimable),
                                       self.total_pages - 1))
        for _ in range(short):
            self._drop_locked(next(iter(self._reclaimable)))
        self.counters["reclaimed"] += max(short, 0)
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        self.counters["allocs"] += n
        self.peak_used = max(self.peak_used, self._used_locked())
        return pages

    def share(self, owner, pages):
        """Attach already-live ``pages`` to ``owner``'s table as shared
        (read-only by convention) references — the prefix-cache hit
        path.  Refcounts go up; occupancy does not."""
        pages = list(pages)
        with self._lock:
            for p in pages:
                if p not in self._refs:
                    raise ValueError("share: page %d is not live" % p)
            for p in pages:
                self._refs[p] += 1
                self._reclaimable.pop(p, None)
            self._owned.setdefault(owner, []).extend(pages)
            self.counters["shares"] += len(pages)
        return pages

    def fork(self, owner, page):
        """Copy-on-write bookkeeping: replace ``owner``'s reference to a
        shared ``page`` with a fresh private page (same position in the
        table) and drop the shared reference.  Returns the new page id;
        the CALLER must copy the device contents old -> new before
        writing.  Raises :class:`CacheOOM` when no page is free or
        reclaimable."""
        with self._lock:
            table = self._owned.get(owner)
            if not table or page not in table:
                raise ValueError("fork: owner %r does not hold page %d"
                                 % (owner, page))
            new, = self._take_locked(1)
            table[table.index(page)] = new
            self._deref_locked(page)
            self.counters["forks"] += 1
            return new

    def _deref_locked(self, page):
        left = self._refs[page] - 1
        if left:
            self._refs[page] = left
            if left == 1 and page in self._kept:
                self._reclaimable[page] = None   # the cache's is the last
        else:
            del self._refs[page]
            self._free.append(page)
            self.counters["frees"] += 1

    def free(self, owner):
        """Drop ALL of ``owner``'s page references (eviction, EOS,
        drain).  Returns the number of pages actually returned to the
        free list (shared pages survive under their other owners);
        unknown owners free 0 (idempotent — a preempted slot may race
        its own completion)."""
        with self._lock:
            pages = self._owned.pop(owner, None)
            if not pages:
                return 0
            freed0 = self.counters["frees"]
            # reversed: LIFO free list re-issues the owner's last pages
            # first, keeping page ids dense for the next sequence
            for p in reversed(pages):
                self._deref_locked(p)
            return self.counters["frees"] - freed0

    def trim(self, owner, keep):
        """Truncate ``owner``'s page list to its first ``keep`` pages,
        dereferencing the tail in reverse allocation order — the
        speculative-decode rollback primitive (rejected draft tokens
        hand their pages straight back).  Copy-on-write aware the same
        way :meth:`free` is: a trimmed page that other owners (a prefix
        cache entry, a peer sequence) still reference only drops this
        owner's refcount and stays resident; it rejoins the free list at
        refcount zero.  The page CONTAINING the new write boundary is
        kept — when it is shared, the caller must :meth:`fork` it before
        re-writing rolled-back offsets (the engine's ``_rollback_kv``
        does exactly that).  Returns the number of references dropped;
        unknown owners and ``keep >= len(pages)`` trim 0 (idempotent).
        """
        keep = max(0, int(keep))
        with self._lock:
            pages = self._owned.get(owner)
            if pages is None or len(pages) <= keep:
                return 0
            tail = pages[keep:]
            del pages[keep:]
            if not pages:
                del self._owned[owner]
            # reversed: LIFO free list re-issues the rolled-back pages
            # first, same warm-reuse policy as free()
            for p in reversed(tail):
                self._deref_locked(p)
            self.counters["trims"] += 1
            return len(tail)

    # -- the prefix cache's pages -----------------------------------------
    def keep(self, entry):
        """The prefix cache's one reference on the live ``entry.page``,
        held under ``entry.owner``.  From here on the page is reclaimable
        whenever that reference is its last.  False when the page is not
        live (its owner raced off) or is kept already."""
        with self._lock:
            page = entry.page
            if page not in self._refs or page in self._kept:
                return False
            self._refs[page] += 1
            self._owned[entry.owner] = [page]
            self._kept[page] = entry
            self.counters["shares"] += 1
            return True

    def touch(self, pages):
        """A use of the chain ``pages`` (root first) that took no
        reference: those of them that are reclaimable become the most
        recently used, the chain's tail still before its head."""
        with self._lock:
            for p in reversed(pages):
                if p in self._reclaimable:
                    self._reclaimable.move_to_end(p)

    def unkeep(self, page=None):
        """Drop the cache's reference on ``page``, or on the least
        recently used reclaimable page, which then rejoins the free
        list.  False when there is none."""
        with self._lock:
            if page is None:
                page = next(iter(self._reclaimable), None)
            if page not in self._kept:
                return False
            self._drop_locked(page)
            return True

    def _drop_locked(self, page):
        entry = self._kept.pop(page)
        self._reclaimable.pop(page, None)
        self._forget(entry)
        del self._owned[entry.owner]
        self._deref_locked(page)

    def pages(self, owner):
        """The owner's page list (copy), allocation order == token order."""
        with self._lock:
            return list(self._owned.get(owner, ()))

    def refcount(self, page):
        with self._lock:
            return self._refs.get(page, 0)

    # -- accounting -------------------------------------------------------
    def _used_locked(self):
        return (self.total_pages - 1) - len(self._free)

    @property
    def num_free(self):
        with self._lock:
            return len(self._free)

    @property
    def num_used(self):
        with self._lock:
            return self._used_locked()

    @property
    def num_available(self):
        """Pages an allocation can have now: free and reclaimable."""
        with self._lock:
            return len(self._free) + len(self._reclaimable)

    def occupancy(self):
        """Used fraction of the allocatable pool (scratch page excluded)."""
        with self._lock:
            cap = self.total_pages - 1
            return self._used_locked() / cap if cap else 0.0

    def owners(self):
        with self._lock:
            return sorted(self._owned, key=str)

    def _shared_locked(self):
        return sum(1 for c in self._refs.values() if c > 1)

    def check_leaks(self):
        """Conservation check: every allocatable page is either in the
        free list (refcount 0) or referenced by at least one owner list,
        with refcounts exactly matching the table references.  With an
        int8 pool the per-page scales ride the SAME page ids as the
        codes (``QPages`` keeps the two device arrays parallel), so
        this single check conserves the scales pool too — a page id can
        no more leak its scale row than its code block.  Raises
        the typed :class:`KVLeakError` (leaked/duplicated page ids
        attached) on violation; returns the owner count when clean."""
        with self._lock:
            self.counters["leak_checks"] += 1
            want = dict.fromkeys(range(1, self.total_pages), 0)
            bad = set()
            for pages in self._owned.values():
                for p in pages:
                    if p in want:
                        want[p] += 1
                    else:
                        bad.add(p)   # scratch or out-of-range id
            for p in self._free:
                if p not in want or want[p]:
                    bad.add(p)       # freed while referenced / bogus id
            free = set(self._free)
            if len(free) != len(self._free):
                bad |= {p for p in free if self._free.count(p) > 1}
            for p, n in want.items():
                have = self._refs.get(p, 0)
                in_free = p in free
                if n != have or (n == 0) == (not in_free):
                    # refcount drift, or a page neither free nor held
                    if not (n == 0 and have == 0 and in_free):
                        bad.add(p)
            # the index of reclaimable pages: exactly the kept pages
            # whose one reference is the cache's
            bad |= set(self._reclaimable) - set(self._kept)
            bad |= {p for p in self._kept if (self._refs.get(p) == 1)
                    != (p in self._reclaimable)}
            if bad:
                self.last_leak = sorted(bad)
                raise KVLeakError(
                    "kv page conservation violated: %d page(s) leaked, "
                    "duplicated, or miscounted: %s"
                    % (len(bad), self.last_leak), pages=bad)
            self.last_leak = []
            return len(self._owned)

    def stats(self):
        with self._lock:
            cap = self.total_pages - 1
            used = self._used_locked()
            out = {
                "page_size": self.page_size,
                "total_pages": cap,
                "used_pages": used,
                "free_pages": len(self._free),
                "reclaimable_pages": len(self._reclaimable),
                "occupancy": round(used / cap, 4) if cap else 0.0,
                "peak_used_pages": self.peak_used,
                "owners": len(self._owned),
                "shared_pages": self._shared_locked(),
                "leaked_pages": len(self.last_leak),
                "kv_dtype": self.kv_dtype,
                "counters": dict(self.counters),
            }
            if self.page_bytes:
                # physical footprint incl. the int8 scales pool, and the
                # per-resident-token cost with scales amortized over the
                # page — the capacity lever the bench's 1.9x gate pins
                per_page = self.page_bytes + self.scale_page_bytes
                out["scale_page_bytes"] = self.scale_page_bytes
                out["pool_bytes"] = per_page * cap
                out["used_bytes"] = per_page * used
                out["kv_bytes_per_token"] = round(
                    per_page / self.page_size, 2)
            return out


# -- session wire format --------------------------------------------------
#
# One exported session is a flat self-describing buffer:
#
#   v1: b"MXKV" | u32 header_len | header JSON | k_pages | v_pages
#   v2: b"MXKV" | u32 header_len | header JSON | k_pages | v_pages
#                                              | k_scales | v_scales
#
# The header carries the session metadata dict, the block shape/dtype of
# the gathered pages (layers, kv_heads, n_pages, page_size, head_dim),
# and a CRC32 over the raw page bytes — a torn transfer fails loudly at
# import instead of decoding against garbage.  numpy round-trips the
# bytes exactly, so serialize -> ship -> import is bit-identical (the
# oracle the migration tests pin).
#
# Format v2 (ISSUE 16) carries an int8-quantized cache: the header gains
# ``kv_dtype`` plus the scales blocks' dtype/shape and their OWN CRC —
# scales are ~1/(4*head_dim) of the payload but corrupting one poisons a
# whole page of tokens, so they fail independently and loudly.  A v1
# blob (no ``kv_dtype`` key) still unpacks: old fp sessions keep
# migrating into new replicas unchanged.

_MAGIC = b"MXKV"
_U32 = struct.Struct(">I")


def pack_session(meta, k_block, v_block, k_scales=None, v_scales=None):
    """Serialize one session: ``meta`` (JSON-safe dict) plus the k/v
    page blocks (numpy arrays, identical shape/dtype) into one buffer.
    With ``k_scales``/``v_scales`` (int8 pages: per-(layer, kv_head,
    page) f32 scales) the blob is format v2; without, the v1 wire is
    emitted byte-for-byte as before."""
    k = onp.ascontiguousarray(k_block)
    v = onp.ascontiguousarray(v_block)
    if k.shape != v.shape or k.dtype != v.dtype:
        raise ValueError("pack_session: k/v block shape or dtype mismatch")
    kb, vb = k.tobytes(), v.tobytes()
    head = {
        "v": 1,
        "meta": meta,
        "dtype": k.dtype.str,
        "shape": list(k.shape),
        "crc": zlib.crc32(vb, zlib.crc32(kb)) & 0xFFFFFFFF,
    }
    tail = []
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pack_session: k/v scales must come together")
    if k_scales is not None:
        ks = onp.ascontiguousarray(k_scales)
        vs = onp.ascontiguousarray(v_scales)
        if ks.shape != vs.shape or ks.dtype != vs.dtype:
            raise ValueError(
                "pack_session: k/v scales shape or dtype mismatch")
        ksb, vsb = ks.tobytes(), vs.tobytes()
        head["v"] = 2
        head["kv_dtype"] = onp.dtype(k.dtype).name
        head["s_dtype"] = ks.dtype.str
        head["s_shape"] = list(ks.shape)
        head["s_crc"] = zlib.crc32(vsb, zlib.crc32(ksb)) & 0xFFFFFFFF
        tail = [ksb, vsb]
    header = json.dumps(head).encode("utf-8")
    return b"".join([_MAGIC, _U32.pack(len(header)), header, kb, vb]
                    + tail)


def unpack_session(blob, with_scales=False):
    """Inverse of :func:`pack_session`; returns ``(meta, k_block,
    v_block)``, or ``(meta, k_block, v_block, k_scales, v_scales)``
    with ``with_scales=True`` (the scales are ``None`` for a v1/fp
    blob).  Raises ``ValueError`` on a torn or corrupt buffer (bad
    magic, truncation, CRC mismatch on either the page payload or the
    v2 scales payload)."""
    if len(blob) < len(_MAGIC) + _U32.size or blob[:4] != _MAGIC:
        raise ValueError("unpack_session: bad magic (torn transfer?)")
    (hlen,) = _U32.unpack_from(blob, 4)
    off = 4 + _U32.size
    if len(blob) < off + hlen:
        raise ValueError("unpack_session: truncated header")
    header = json.loads(blob[off:off + hlen].decode("utf-8"))
    off += hlen
    dtype = onp.dtype(header["dtype"])
    shape = tuple(header["shape"])
    nbytes = dtype.itemsize * int(onp.prod(shape)) if shape else 0
    quantized = "kv_dtype" in header
    if quantized:
        s_dtype = onp.dtype(header["s_dtype"])
        s_shape = tuple(header["s_shape"])
        snbytes = (s_dtype.itemsize * int(onp.prod(s_shape))
                   if s_shape else 0)
    else:
        snbytes = 0
    if len(blob) != off + 2 * nbytes + 2 * snbytes:
        raise ValueError("unpack_session: truncated page payload "
                         "(%d != %d)"
                         % (len(blob) - off, 2 * nbytes + 2 * snbytes))
    kb = blob[off:off + nbytes]
    vb = blob[off + nbytes:off + 2 * nbytes]
    crc = zlib.crc32(vb, zlib.crc32(kb)) & 0xFFFFFFFF
    if crc != header["crc"]:
        raise ValueError("unpack_session: CRC mismatch (torn transfer)")
    k = onp.frombuffer(kb, dtype=dtype).reshape(shape)
    v = onp.frombuffer(vb, dtype=dtype).reshape(shape)
    ks = vs = None
    if quantized:
        soff = off + 2 * nbytes
        ksb = blob[soff:soff + snbytes]
        vsb = blob[soff + snbytes:soff + 2 * snbytes]
        scrc = zlib.crc32(vsb, zlib.crc32(ksb)) & 0xFFFFFFFF
        if scrc != header["s_crc"]:
            raise ValueError(
                "unpack_session: scales CRC mismatch (torn transfer)")
        ks = onp.frombuffer(ksb, dtype=s_dtype).reshape(s_shape)
        vs = onp.frombuffer(vsb, dtype=s_dtype).reshape(s_shape)
    if with_scales:
        return header["meta"], k, v, ks, vs
    return header["meta"], k, v


# -- prefix cache ---------------------------------------------------------
class _PrefixEntry:
    __slots__ = ("key", "page", "owner")

    def __init__(self, key, page, owner):
        self.key = key          # (parent entry's serial, this page's tokens)
        self.page = page
        self.owner = owner      # ("pfx", serial): holds the cache's reference


class PrefixCache:
    """Content-addressed prompt-prefix pages, shared copy-on-write.

    A prompt's pages form a chain from the root: an entry is found by
    its parent entry and its own page's tokens, compared exactly
    (position-dependent KV makes anything weaker unsound), so a prompt's
    keys are as long as the prompt.  The trailing partial page of a
    prompt is cached too, under fewer tokens than a page holds.  A
    lookup returns the longest chain of cached pages covering a strict
    prefix of the prompt (at least one token is always left to prefill —
    its logits seed generation).  The cache holds one allocator
    reference per entry (:meth:`PageAllocator.keep`), so hit pages stay
    live across the inserting sequence's exit.  They count as used until
    an allocation that finds the free list short takes them back, least
    recently used first and a chain from its tail; an entry whose page a
    sequence still shares is out of that allocation's reach and stays.

    The cache has no lock of its own: it runs under the allocator's.

    Writers never mutate a shared full page (decode appends past it);
    a hit on a *partial* page is forked copy-on-write by the engine
    before its first write lands (``cow_forks`` in the metrics).
    """

    def __init__(self, alloc):
        self.alloc = alloc
        self._lock = alloc._lock
        alloc._forget = self._forget
        self._entries = {}   # (parent's serial, page tokens) -> _PrefixEntry
        self._serial = 0     # the root is 0
        self._partials = 0   # entries of a partially filled page
        self.counters = {"hits": 0, "misses": 0, "inserts": 0,
                         "evictions": 0, "tokens_saved": 0}

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def lookup(self, prompt):
        """Longest cached cover of a strict prefix of ``prompt``;
        returns ``(pages, covered_tokens, partial_hit)`` (all falsy on
        a miss).  The returned pages are NOT yet referenced — the
        caller must :meth:`PageAllocator.share` them before its next
        allocation, which could otherwise take them."""
        S = self.alloc.page_size
        limit = len(prompt) - 1          # always leave >=1 token to prefill
        with self._lock:
            pages, covered, parent = [], 0, 0
            while covered + S <= limit:
                e = self._entries.get(
                    (parent, tuple(prompt[covered:covered + S])))
                if e is None:
                    break
                pages.append(e.page)
                covered += S
                parent = e.owner[1]
            partial = False
            longest = min(S - 1, limit - covered) if self._partials else 0
            for m in range(longest, 0, -1):
                e = self._entries.get(
                    (parent, tuple(prompt[covered:covered + m])))
                if e is not None:
                    pages.append(e.page)
                    covered += m
                    partial = True
                    break
            self.alloc.touch(pages)
            if covered:
                self.counters["hits"] += 1
                self.counters["tokens_saved"] += covered
            else:
                self.counters["misses"] += 1
            return pages, covered, partial

    def insert(self, tokens, owner_pages):
        """Publish a freshly-prefilled sequence's pages: every full page
        (and the trailing partial one) becomes a cache entry under its
        parent and its own tokens, with the cache taking one shared
        reference.  Existing entries win (first writer published
        identical KV); where one holds another page than the caller's,
        nothing further is published: an entry hangs only on a page its
        publisher holds, which is what lets a chain go from its tail."""
        S = self.alloc.page_size
        new = 0
        with self._lock:
            parent, chain = 0, []
            for i in range(min(pages_for(len(tokens), S), len(owner_pages))):
                key = (parent, tuple(tokens[i * S:(i + 1) * S]))
                e = self._entries.get(key)
                if e is None:
                    self._serial += 1
                    e = _PrefixEntry(key, owner_pages[i],
                                     ("pfx", self._serial))
                    if not self.alloc.keep(e):
                        break   # page raced off (owner already freed)
                    self._entries[key] = e
                    self._partials += len(key[1]) < S
                    self.counters["inserts"] += 1
                    new += 1
                chain.append(e.page)
                if e.page != owner_pages[i]:
                    break
                parent = e.owner[1]
            self.alloc.touch(chain)
        return new

    def _forget(self, entry):
        """The allocator dropped ``entry``'s reference (its lock held)."""
        if self._entries.pop(entry.key, None) is not None:
            self._partials -= len(entry.key[1]) < self.alloc.page_size
            self.counters["evictions"] += 1

    def evict_one(self):
        """Give the least recently used page that nothing but the cache
        refers to back to the free list, with its entry.  False when
        there is none: entries whose pages sequences share stay."""
        return self.alloc.unkeep()

    def clear(self):
        with self._lock:
            entries, self._entries = self._entries, {}
            self._partials = 0
            for e in entries.values():
                self.alloc.unkeep(e.page)
        return len(entries)

    def stats(self):
        with self._lock:
            return {"entries": len(self._entries),
                    "counters": dict(self.counters)}
