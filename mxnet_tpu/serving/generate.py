"""Continuous-batching autoregressive decode engine over a paged KV cache.

The Orca + vLLM serving recipe, grown onto this repo's serving stack:

- **Iteration-level (continuous) batching** — the decode batch is
  re-formed every step: a sequence is admitted into a free slot the
  moment one opens, and evicted the step it finishes (EOS / max tokens /
  deadline).  A static batch runs at the speed (and occupancy) of its
  longest member; continuous batching keeps every slot producing real
  tokens, which is the whole throughput story of LLM serving.
- **Paged KV cache** — per-sequence KV lives in fixed-size pages handed
  out by ``kvcache.PageAllocator`` (free list, exact occupancy);
  attention reads through per-slot page tables
  (``ops/pallas/paged_attention``: Pallas kernel on TPU, XLA gather
  reference on CPU — the engine is tier-1 testable end to end).
  When the pool runs dry the engine **preempts** the youngest sequence
  (frees its pages, requeues it for recompute with its progress kept)
  instead of failing — vLLM's recompute eviction.
- **Chunked prefill** — prompts are cached ``prefill_chunk`` tokens per
  engine step (Sarathi-style), interleaved with decode steps, so a long
  prompt costs every in-flight sequence one bounded slice of latency
  per step instead of a full-prompt stall.
- **Decode sessions** — a request carrying ``session=<id>`` parks its
  pages on completion; a later request with the same id continues
  decoding against the cached context (multi-turn without re-prefill).
  Resuming a session this process does not hold raises the typed
  :class:`~.errors.SessionResetError` — the fleet router's
  consistent-hash ``affinity_key`` keeps a session on its replica, and
  the typed error is what a client sees when that replica was replaced.
- **Copy-on-write prefix caching** (``MXNET_GEN_PREFIX_CACHE``) —
  prompt-prefix pages are content-addressed in ``kvcache.PrefixCache``
  and attached to new sequences as shared references; a hit on the
  trailing partial page is forked copy-on-write before its first write
  lands.  N users sharing a system prompt pay its prefill once
  (``prefix_hits`` / ``prefix_tokens_saved`` / ``cow_forks`` metrics).
  The pages the cache keeps count as used until an allocation takes
  back those nothing else refers to (``kv_reclaimed_pages``).
- **Session migration** (``MXNET_GEN_MIGRATE`` +
  ``MXNET_GEN_PAGESTORE``) — sessions outlive their replica.  Every
  park synchronously pushes the session's replay transcript to the
  fleet page store (before the client sees the response, so any acked
  turn is recoverable); drain/rollout pushes full KV page blobs via
  :meth:`DecodeEngine.migrate_out`.  A resume this replica does not
  hold first tries to PULL the session from the store — a page blob
  imports bit-identically, a transcript rebuilds the pages by replay
  (prefix caching makes that cheap) — and only a store miss raises the
  typed reset.  Fault sites ``session.export`` / ``session.import``
  make torn transfers injectable.
- **Speculative decoding** (``MXNET_GEN_SPECULATE``) — a cheap drafter
  (n-gram prompt lookup or a small draft model, ``serving/speculate``)
  proposes up to ``MXNET_GEN_SPEC_K`` tokens per slot and ONE wide
  verify launch scores the whole batch; longest-prefix greedy
  acceptance keeps the emitted stream bit-identical to plain decode,
  rejected positions roll back via ``PageAllocator.trim`` (CoW-aware),
  and a per-sequence adaptive-k controller turns speculation off for
  streams that stop accepting.
- **Role specialization** (``MXNET_GEN_ROLE``) — a ``prefill`` engine
  hands each finished prompt's KV pages to the store for a ``decode``
  replica to claim (DistServe/Splitwise disaggregation); the fleet
  router splits long fresh prompts across the two pools.

- **Async step pipelining** (``MXNET_GEN_ASYNC``, default on) — the
  decode step splits into a *launch* half and a *retire* half with a
  depth-``MXNET_GEN_DISPATCH_AHEAD`` in-flight queue.  JAX dispatch is
  asynchronous: a launched step returns device futures immediately, so
  the sampled tokens stay on-device and the next step's token input
  CHAINS on them (the decode step's ``chained`` input, merged with the
  host-staged tokens inside the step) — the host forces a result only
  once the next launch is already in flight.  Admission,
  eviction, EOS, emission, and metrics shift to retire time; deadlines
  are checked at launch time so pipelining never extends one; pages an
  in-flight step writes are pinned (frees defer to that step's retire).
  Under speculation the verify input depends on host-side acceptance,
  so verify steps retire-then-relaunch instead of chaining — but
  drafting overlaps the in-flight verify (``reuse_predraft``) and the
  deferred bookkeeping runs while the next launch computes.
  ``MXNET_GEN_ASYNC=0`` restores the fully synchronous loop; either
  way the emitted greedy streams are bit-identical.

Admission control mirrors ``DynamicBatcher`` exactly (and composes with
it via ``DynamicBatcher.register_engine``): bounded queue sheds with
``QueueFullError``, draining rejects with ``ServerClosedError``,
deadlines expire typed, and a failed sequence poisons only its own
future.  Fault sites: ``decode.step`` (one decode iteration),
``engine.retire`` (one in-flight step's deferred read) and
``kvcache.alloc`` (page allocation) — see ``tools/chaos.py
--scenario llm``.
"""
from __future__ import annotations

import collections
import itertools
import logging
import threading
import time
from concurrent.futures import Future

import numpy as onp

import jax
import jax.numpy as jnp

from .. import config as _config
from .. import faults
from ..models import decoder as _decoder
from ..models import hybrid as _hybrid
from ..models import routed as _routed
from ..ops.pallas import paged_attention as _paged
from .autoscale import SLOPolicy
from .errors import (BadRequestError, DeadlineExceededError, QueueFullError,
                     ServerClosedError, ServingError, SessionResetError)
from .kvcache import (CacheOOM, PageAllocator, PrefixCache, pack_session,
                      pages_for, unpack_session)
from .metrics import ModelMetrics, ServingMetrics
from ..profiler import span

#: the metrics' counters of the first four of ``models.routed.COUNTS``
_EXPERT_COUNTERS = ("expert_pairs_total", "expert_pairs_elsewhere_total",
                    "experts_hit_total", "expert_pairs_fullest_total")

__all__ = ["DecodeEngine", "next_rid"]

_log = logging.getLogger(__name__)

#: one identifier per request of this process, on every span of the request
next_rid = itertools.count(1).__next__


# a request's phases on the engine's clock (_Request.mark)
_QUEUE, _PREFILL, _DECODE = range(3)


class _Request:
    __slots__ = ("prompt", "max_new", "deadline", "future", "session",
                 "resume", "t_enqueue", "prefix", "ttft_recorded",
                 "prompt_tokens", "started", "tier", "tenant", "rank",
                 "vstart", "rid", "t_mark", "phases")

    def __init__(self, prompt, max_new, deadline, session, resume,
                 tier="latency", tenant=None, rank=0, vstart=0.0, rid=None):
        self.rid = next_rid() if rid is None else rid
        self.prompt = list(prompt)
        self.prompt_tokens = len(self.prompt)  # as submitted (reporting)
        self.max_new = int(max_new)
        self.deadline = deadline          # absolute perf_counter or None
        self.session = session
        self.resume = bool(resume)
        self.future = Future()
        self.t_enqueue = self.t_mark = time.perf_counter()
        self.phases = [0.0, 0.0, 0.0]     # seconds queued, in prefill, decode
        self.prefix = []                  # tokens emitted before a preempt
        self.ttft_recorded = False
        self.started = False              # future already marked running
        self.tier = tier                  # "latency" | "bulk" (SLO class)
        self.tenant = tenant
        self.rank = rank                  # tier priority (0 = latency)
        self.vstart = vstart              # weighted-fair start tag

    @property
    def sort_key(self):
        return (self.rank, self.vstart)

    def expired(self, now):
        return self.deadline is not None and now > self.deadline

    def mark(self, phase, now):
        """The phase the request was in since the last mark ends at
        ``now``.  Every second since submit goes to one of the three, so
        they add up to the request's total, preemptions included."""
        self.phases[phase] += now - self.t_mark
        self.t_mark = now


class _Slot:
    __slots__ = ("req", "state", "owner", "prompt", "done", "pos",
                 "history", "generated", "pending", "t_last", "admit_seq",
                 "idx", "cacheable", "flight", "predraft")

    def __init__(self, idx):
        self.idx = idx
        self.req = None
        self.state = "idle"   # idle | prefill | decode | finishing
        self.flight = 0       # launched-but-unretired lanes (async)
        self.predraft = None  # overlapped draft awaiting the next launch

    @property
    def active(self):
        return self.state != "idle"


class _Flight:
    """One launched-but-unretired decode step (async engine).

    Holds the on-device results (forced only at retire), the lanes it
    carries as ``(slot, admit_seq-at-launch)`` pairs — a slot recycled
    since launch fails the seq check and its lane is discarded — the
    owners whose pages the step writes (pinned: the allocator must not
    recycle them until this retire), and deferred page-release callbacks
    from sequences that ended while the step was still in flight."""

    __slots__ = ("kind", "out", "t_launch", "lanes", "owners", "fed",
                 "on_retire")

    def __init__(self, kind, out, t_launch, lanes, owners, fed=None):
        self.kind = kind          # "plain" | "verify"
        self.out = out            # jax.Array device future(s)
        self.t_launch = t_launch
        self.lanes = lanes
        self.owners = owners
        self.fed = fed or {}      # slot idx -> fed token row (spec path)
        self.on_retire = []


class _Session:
    __slots__ = ("sid", "owner", "pos", "pending", "history", "last_used",
                 "busy", "replay", "gen")

    def __init__(self, sid, owner):
        self.sid = sid
        self.owner = owner
        self.pos = 0
        self.pending = None
        self.history = []
        self.last_used = time.monotonic()
        self.busy = False
        # migration: a pulled transcript record parks here until the
        # next request replays it (pages rebuilt by recompute); gen is
        # the generation fence stamped onto every page-store push
        self.replay = None
        self.gen = 0


class DecodeEngine:
    """Continuous-batching decode scheduler for one causal LM.

    ``model`` is a :class:`mxnet_tpu.models.decoder.CausalLM` (or any
    object with ``jax_params()``/``config``).  One worker thread owns
    the KV pages and re-forms the decode batch every step.

    Knobs (env defaults in parentheses):
      slots          — decode batch width (``MXNET_GEN_SLOTS``)
      page_size      — tokens per KV page (``MXNET_GEN_PAGE_SIZE``)
      total_pages    — KV pool size incl. the scratch page
                       (``MXNET_GEN_PAGES``; 0 = fully provision
                       ``slots * pages_per_seq + 1`` — no preemption)
      max_ctx        — max prompt+output tokens per sequence
                       (``MXNET_GEN_MAX_CTX``; 0 = model max_length)
      prefill_chunk  — prompt tokens cached per engine step
                       (``MXNET_GEN_PREFILL_CHUNK``)
      session_ttl_s  — idle parked-session lifetime
                       (``MXNET_GEN_SESSION_TTL``)

    ``kv_dtype`` is float32, bfloat16 or int8; left out it is the
    model's own (``config.kv_dtype``: a model published in bfloat16
    caches in bfloat16), else float32.  A model with recurrent layers,
    state-space, delta-rule or retention (:mod:`mxnet_tpu.models.hybrid`),
    keeps its recurrent state paged beside the KV rows, one entry a page
    and recurrent layer; for such a model speculation,
    a tp sharding, int8 KV, weight quantisation, session migration and
    the prefill / decode roles are refused at construction
    (``ValueError``), session export / import when called,
    and the prefix cache publishes whole pages only.

    The programs the engine builds are the ones that run: a compile
    failure fails the step, there is no second program behind it.  The
    decode step's static launch census lands in ``stats()["launches"]``
    and the metrics ``generate`` snapshot; the per-geometry
    decode/prefill program cache is LRU-bounded by ``MXNET_GEN_FN_CACHE``
    with compile/evict gauges next to it.
    """

    def __init__(self, model, *, name="llm", slots=None, page_size=None,
                 total_pages=None, max_ctx=None, prefill_chunk=None,
                 eos_id=None, max_queue_depth=256, metrics=None,
                 session_ttl_s=None, prefix_cache=None, role=None,
                 migrate=None, pagestore=None, speculate=None,
                 spec_k=None, drafter=None, draft_model=None,
                 sharding=None, quantize=None, quant_group=None,
                 kv_dtype=None, async_decode=None, dispatch_ahead=None,
                 slo=None):
        # quantized serving (weight-only int8/int4 + int8 KV pages):
        # accept a pre-wrapped serving.quantize.QuantizedLM, or wrap
        # here from the kwarg/env knob.  Weights and KV cache quantize
        # independently — each is its own program-cache key axis.
        qmode = getattr(model, "quant_mode", None)
        want = str(quantize if quantize is not None
                   else _config.get("MXNET_QUANT_WEIGHTS") or "")
        if qmode is None and want:
            from .quantize import quantize_lm
            model = quantize_lm(model, want, group=int(
                quant_group if quant_group is not None
                else _config.get("MXNET_QUANT_GROUP")))
            qmode = model.quant_mode
        self.quant = model.quant_token() if qmode is not None else None
        self.model = model
        self.name = name
        self.cfg = model.config
        # the cache dtype: the one asked for, else the model's own (a
        # model published in bfloat16 caches in bfloat16), else float32
        self.kv_dtype = str(kv_dtype if kv_dtype is not None
                            else _config.get("MXNET_QUANT_KV")
                            or getattr(self.cfg, "kv_dtype", "float32"))
        if self.kv_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError("kv_dtype must be float32, bfloat16 or int8, "
                             "got %r" % (self.kv_dtype,))
        self.slots = int(slots if slots is not None
                         else _config.get("MXNET_GEN_SLOTS"))
        self.page_size = int(page_size if page_size is not None
                             else _config.get("MXNET_GEN_PAGE_SIZE"))
        self.max_ctx = int(max_ctx or _config.get("MXNET_GEN_MAX_CTX")
                           or self.cfg.max_length)
        self.max_ctx = min(self.max_ctx, self.cfg.max_length)
        self.pages_per_seq = pages_for(self.max_ctx, self.page_size)
        # a model with recurrent layers (state-space, delta-rule,
        # retention) keeps a recurrent state beside its KV rows, one entry
        # a page (models/hybrid.py); what the engine cannot keep exact for
        # it is refused here, by name
        self.hybrid = _decoder.hybrid_program(
            self.cfg, sharding, self.quant, self.kv_dtype,
            page_size=self.page_size, max_ctx=self.max_ctx)
        if self.hybrid:
            self._refuse_for_hybrid(speculate=speculate, migrate=migrate,
                                    pagestore=pagestore, role=role)
        self.params = model.jax_params()
        total = int(total_pages if total_pages is not None
                    else _config.get("MXNET_GEN_PAGES"))
        if not total:
            total = self.slots * self.pages_per_seq + 1
        self.prefill_chunk = int(prefill_chunk if prefill_chunk is not None
                                 else _config.get("MXNET_GEN_PREFILL_CHUNK"))
        self.eos_id = eos_id if eos_id is not None else getattr(
            model, "eos_id", None)
        self.max_queue_depth = int(max_queue_depth)
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.session_ttl_s = float(
            session_ttl_s if session_ttl_s is not None
            else _config.get("MXNET_GEN_SESSION_TTL"))

        cfg = self.cfg
        # a token's rows over both pools: keys and values, or latent rows
        elems = (_hybrid.token_row_elems(cfg) if self.hybrid
                 else 2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim)
        #: bytes of one page's state entries (0 without recurrent layers)
        self.state_entry_bytes = (_hybrid.state_entry_bytes(cfg)
                                  if self.hybrid else 0)
        #: bytes of state entries the staged launches read and write
        self._state_bytes = {"decode": 0, "prefill": 0}
        #: bytes of one token's latent rows over the latent layers (0
        #: without them), and those the staged launches read
        self.latent_row_bytes = (
            elems * jnp.dtype(self.kv_dtype).itemsize
            if self.hybrid and _hybrid.LATENT in cfg.layer_kinds else 0)
        self._latent_bytes = {"decode": 0, "prefill": 0}
        self.alloc = PageAllocator(
            total, self.page_size, kv_dtype=self.kv_dtype,
            page_bytes=elems * self.page_size
            * jnp.dtype(self.kv_dtype).itemsize + self.state_entry_bytes,
            scale_page_bytes=(2 * cfg.num_layers * cfg.num_kv_heads * 4
                              if self.kv_dtype == "int8" else 0))
        # tensor-parallel serving (ISSUE 13): resolve the sharding into a
        # TPPlan BEFORE building any program — params go column/row-
        # parallel, KV pages split along KV heads, and every decode/
        # prefill/verify builder below gets the config so its program
        # runs per-shard under shard_map.  A config that asks for tp > 1
        # and cannot shard this geometry is an error (decoder.tp_plan
        # raises); one with no tp axis resolves to None and the engine
        # serves one chip.  PageAllocator bookkeeping is host-side and
        # shard-agnostic either way.
        self._tp_plan = _decoder.tp_plan(
            cfg, sharding, quant=self.quant,
            kv_int8=self.kv_dtype == "int8")
        self.sharding = sharding if self._tp_plan is not None else None
        self.tp = self._tp_plan.tp if self._tp_plan is not None else 1
        if self.quant is not None and self.quant[0] == "int4" \
                and self.tp > 1:
            # int4 scale groups must not straddle row-parallel shards:
            # re-derive the quantized params with the shard-local group
            self.params = self.model.jax_params(tp=self.tp)
        if self._tp_plan is not None:
            self.params = self._tp_plan.place_params(self.params)
        self._kp, self._vp = (
            self._place_kv(_decoder.fresh_pool(
                cfg, total, self.page_size, self.kv_dtype))
            for _ in range(2))
        self._tables = onp.zeros((self.slots, self.pages_per_seq),
                                 onp.int32)
        self._decode_fn = _decoder.make_decode_step(
            cfg, self.page_size, sharding=self.sharding,
            quant=self.quant, kv_dtype=self.kv_dtype)
        self._prefill_fn = _decoder.make_prefill_chunk(
            cfg, self.page_size, self.prefill_chunk,
            sharding=self.sharding, quant=self.quant,
            kv_dtype=self.kv_dtype)
        # the launch census is static (trace-time) and exported as the
        # engine's dispatch-count metric — the _bulk-flush analog
        try:
            self.launch_stats = _decoder.decode_launch_stats(
                self.params, cfg, self.page_size, self.slots,
                self.pages_per_seq, total,
                sharding=self.sharding, quant=self.quant,
                kv_dtype=self.kv_dtype)
        except Exception:  # pragma: no cover - tracing is best-effort
            _log.exception("decode launch census failed")
            self.launch_stats = {}
        self.metrics.observe_decode_launches(self.name, self.launch_stats)
        # static collective census (once, at engine attach): what the
        # sharded decode step moves cross-chip per step — all-reduce
        # only, counts invariant to batch size.  Surfaces in /v1/stats
        # so the fleet router can tell a TP replica from a dp replica.
        self.collective_stats = None
        if self._tp_plan is not None:
            try:
                self.collective_stats = _decoder.decode_collective_stats(
                    self.params, cfg, self.page_size, self.slots,
                    self.pages_per_seq, total, self.sharding,
                    quant=self.quant, kv_dtype=self.kv_dtype)
            except Exception:  # pragma: no cover - census is best-effort
                _log.exception("decode collective census failed")
                self.collective_stats = {
                    "mesh": self.sharding.describe(), "tp": self.tp}
            self.metrics.observe_decode_collectives(self.name,
                                                    self.collective_stats)

        self._slots = [_Slot(i) for i in range(self.slots)]
        self._sessions = {}           # sid -> _Session (parked or busy)
        self._queue = collections.deque()
        # SLO admission policy (tiers / weighted-fair tags / deadline
        # infeasibility); DynamicBatcher.register_engine replaces it
        # with the replica-wide shared instance
        self.slo = slo if slo is not None else SLOPolicy()
        self._cond = threading.Condition()
        self._worker = None
        self._stopping = False
        self._drain_mode = True
        self._seq = 0                 # admission counter (owner ids)
        self._prefill_rr = 0
        self.steps = 0
        # the current step's seconds by part, its host seconds in launches
        # and uploads, its blocking reads by what was read, and its
        # counts: plain numbers of the worker thread, handed to the
        # metrics in one call at the step's end (_step, _lap,
        # _device_wait, _dispatch, _upload)
        self._parts = dict.fromkeys(ModelMetrics.STEP_PARTS, 0.0)
        self._host_s = dict.fromkeys(ModelMetrics.HOST_PARTS, 0.0)
        self._wait_s = dict.fromkeys(ModelMetrics.WAIT_PARTS, 0.0)
        self._counts = dict.fromkeys(ModelMetrics.STEP_COUNTERS, 0)
        self._inner_s = 0.0
        # the newest launch's first output that is no pool: the next
        # launch finds it ready where the device has run dry (_dispatch);
        # None after the worker waited for work
        self._last_out = None
        self._reclaimed_seen = 0   # of alloc's "reclaimed", already counted

        # prefix caching + session migration + role specialization
        self.role = str(role if role is not None
                        else _config.get("MXNET_GEN_ROLE") or "mixed")
        if self.role not in ("prefill", "decode", "mixed"):
            raise ValueError("role must be prefill|decode|mixed, got %r"
                             % (self.role,))
        use_pfx = (bool(prefix_cache) if prefix_cache is not None
                   else bool(_config.get("MXNET_GEN_PREFIX_CACHE")))
        self.prefix_cache = PrefixCache(self.alloc) if use_pfx else None
        self.migrate = (bool(migrate) if migrate is not None
                        else bool(_config.get("MXNET_GEN_MIGRATE")))
        self._pagestore_addr = str(
            pagestore if pagestore is not None
            else _config.get("MXNET_GEN_PAGESTORE") or "")
        self._store_client = None     # lazy; False = gave up connecting
        self._ops = collections.deque()   # (fn, Future|None) — worker ops
        self._pending_imports = set()     # sids with a queued import op

        # speculative decoding (MXNET_GEN_SPECULATE): a drafter proposes
        # k tokens per decode slot and one wide verify launch scores all
        # of them — see serving/speculate.py.  A prefill-role engine
        # never decodes, so it never speculates.
        self._spec = None
        use_spec = (bool(speculate) if speculate is not None
                    else bool(_config.get("MXNET_GEN_SPECULATE")))
        if use_spec and self.role != "prefill":
            self._spec = self._build_spec(drafter, draft_model, spec_k)

        # async step pipelining (MXNET_GEN_ASYNC): the decode step
        # splits into launch/retire halves with a bounded in-flight
        # queue — see the module docstring and _decode_async below
        self.async_decode = (bool(async_decode) if async_decode is not None
                             else bool(_config.get("MXNET_GEN_ASYNC")))
        self.dispatch_ahead = max(1, int(
            dispatch_ahead if dispatch_ahead is not None
            else _config.get("MXNET_GEN_DISPATCH_AHEAD")))
        self._pipe = collections.deque()  # in-flight _Flight entries
        self._flight_owners = {}          # owner -> in-flight refcount
        self._t_last_retire = None        # retire cadence (decode_step)
        # pinned staging buffers, reused every step: batch formation
        # fills these in place, and a launch sends them with the page
        # table as one buffer (_launch_decode_step).  Uploads go through
        # _upload (a private host copy): jnp.asarray zero-copy-aliases
        # numpy memory on CPU, and a buffer an in-flight launch still
        # reads must never be mutated in place.
        self._stage_tokens = onp.zeros(self.slots, onp.int32)
        self._stage_positions = onp.zeros(self.slots, onp.int32)
        self._stage_active = onp.zeros(self.slots, bool)
        self._stage_carry = onp.zeros(self.slots, bool)
        #: the decode step's ``chained`` input where no lane chains
        self._no_chain = jnp.zeros(self.slots, jnp.int32)
        #: a model that routes: the token-expert pairs a token makes over
        #: its routed layers, and what the programs counted so far (the
        #: pools' ``counts``, folded in whenever ``stats()`` is asked)
        self._pairs_per_token = (
            cfg.experts_per_token * (cfg.num_layers - cfg.dense_layers)
            if getattr(cfg, "n_experts", 0) else 0)
        self._expert_counts = {
            phase: {"seen": onp.zeros(len(_routed.COUNTS), onp.uint32),
                    "total": [0] * len(_routed.COUNTS)}
            for phase in ("prefill", "decode")}

    # -- a model with state-space layers: what is refused ------------------
    def _refuse_for_hybrid(self, speculate, migrate, pagestore, role):
        """Raise a ``ValueError`` that names the first thing asked of this
        engine which it cannot keep exact for a model with recurrent layers
        (state-space, delta-rule or retention; ROADMAP, "What the system
        cannot run yet"); what the programs cannot do (a tp sharding,
        quantisation, int8 KV) ``decoder.hybrid_program`` has refused
        already."""
        def want(arg, knob):
            return bool(arg if arg is not None else _config.get(knob))
        refused = [
            ("speculative decoding (a rejected draft would need the "
             "recurrent state rolled back inside a page)",
             want(speculate, "MXNET_GEN_SPECULATE")),
            ("session migration to a page store (the wire format carries "
             "no state entries)",
             want(migrate, "MXNET_GEN_MIGRATE")
             and want(pagestore, "MXNET_GEN_PAGESTORE")),
            ("role %r (its hand-off exports sessions)" % (role,),
             str(role if role is not None
                 else _config.get("MXNET_GEN_ROLE") or "mixed") != "mixed"),
        ]
        for what, asked in refused:
            if asked:
                raise ValueError(
                    "decode engine %r: %s is not supported for a model "
                    "with %s" % (self.name, what,
                                 _decoder.recurrent_name(self.cfg)))

    def _refuse_session_wire(self, what):
        if self.hybrid:
            raise ValueError(
                "decode engine %r: %s is not supported for a model with "
                "%s (the wire format carries no state entries)"
                % (self.name, what, _decoder.recurrent_name(self.cfg)))

    # -- admission --------------------------------------------------------
    @property
    def draining(self):
        return self._stopping

    def queue_depth(self):
        with self._cond:
            return len(self._queue)

    def active_count(self):
        with self._cond:
            return sum(1 for s in self._slots if s.active)

    def set_role(self, role):
        """Runtime prefill↔decode role flip (the autoscaler's pool
        rebalance): the role is read per-request at the disaggregation
        handoff, so in-flight work finishes under the OLD role and new
        admissions follow the new one.  Returns the previous role."""
        role = str(role)
        if role not in ("prefill", "decode", "mixed"):
            raise BadRequestError(
                "role must be prefill|decode|mixed, got %r" % (role,))
        with self._cond:
            prev, self.role = self.role, role
        return prev

    def _evict_bulk_locked(self):
        """Degradation ladder rung 1 (generate path): a full queue
        admits a latency-tier request by evicting the newest queued
        bulk-tier one.  Returns True when a victim was found."""
        victim = None
        for r in self._queue:
            if r.rank > 0 and (victim is None
                               or r.vstart > victim.vstart):
                victim = r
        if victim is None:
            return False
        self._queue.remove(victim)
        self.metrics.count(self.name, "shed_total")
        self.metrics.count(self.name, "bulk_evicted_total")
        victim.future.set_exception(QueueFullError(
            "bulk-tier generate evicted to admit a latency-tier one "
            "(queue at max_queue_depth=%d)" % self.max_queue_depth,
            queued=len(self._queue)))
        return True

    def submit(self, prompt, max_new_tokens=16, *, deadline_ms=None,
               session=None, resume=False, tier=None, tenant=None,
               rid=None):
        """Enqueue one generation; returns a Future resolving to
        ``{"tokens", "finish_reason", "session", "prompt_tokens",
        "completion_tokens", "timing_ms"}``.  Shed/deadline/reset
        failures rethrow typed at ``future.result()`` (or synchronously
        at submit for admission-time refusals), matching the batcher's
        contract.  ``timing_ms`` is the request on the engine's clock:
        ``queue_wait`` + ``prefill`` + ``decode`` = ``total``.  ``rid``
        (default: :func:`next_rid`) is on every span of the request; a
        caller that opened a span of its own passes the one it used.

        ``tier``/``tenant`` drive SLO-aware admission (see
        :class:`~.autoscale.SLOPolicy`): latency-tier requests queue
        ahead of (and under overload evict) bulk-tier ones, tenants
        share by weight, and a provably-unmeetable deadline sheds
        synchronously with a drain-estimate ``retry_after``."""
        rank, vstart = self.slo.stamp(tier, tenant)
        tier = self.slo.normalize_tier(tier)
        prompt = [int(t) for t in prompt]
        if not prompt and not (resume and session is not None):
            # an empty prompt is legal only as a resume continuation
            # (the disaggregated decode phase: "keep generating from the
            # migrated context, nothing new to prefill")
            raise BadRequestError("generate: prompt must be non-empty")
        if any(t < 0 or t >= self.cfg.vocab_size for t in prompt):
            raise BadRequestError(
                "generate: token ids must be in [0, %d)"
                % self.cfg.vocab_size)
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise BadRequestError("generate: max_tokens must be >= 1")
        if session is None and len(prompt) + max_new > self.max_ctx:
            raise BadRequestError(
                "generate: prompt (%d) + max_tokens (%d) exceeds "
                "max_ctx=%d" % (len(prompt), max_new, self.max_ctx))
        deadline = (time.perf_counter() + float(deadline_ms) / 1e3
                    if deadline_ms is not None else None)
        self.metrics.count(self.name, "requests_total")
        with self._cond:
            if self._stopping:
                self.metrics.count(self.name, "shed_total")
                raise ServerClosedError(
                    "decode engine is draining; not accepting new requests")
            if len(self._queue) >= self.max_queue_depth:
                # bulk sheds first: a latency-tier arrival evicts the
                # newest bulk request instead of being refused itself
                if rank > 0 or not self._evict_bulk_locked():
                    self.metrics.count(self.name, "shed_total")
                    raise QueueFullError(
                        "model %r generate queue full (%d >= %d)"
                        % (self.name, len(self._queue),
                           self.max_queue_depth),
                        queued=len(self._queue))
            if deadline_ms is not None and self._queue:
                # provably-late requests shed at admission (no-op while
                # the service-rate estimator is cold)
                try:
                    self.slo.check_deadline(len(self._queue),
                                            float(deadline_ms) / 1e3)
                except Exception:
                    self.metrics.count(self.name, "shed_total")
                    self.metrics.count(self.name,
                                       "infeasible_shed_total")
                    raise
            missing = (session is not None
                       and session not in self._sessions
                       and session not in self._pending_imports)
        if missing:
            # migration pull-on-miss: before declaring the session dead,
            # try to claim its state from the fleet page store (outside
            # the lock — this is a network round trip)
            self._pull_session(session)
        with self._cond:
            if self._stopping:
                self.metrics.count(self.name, "shed_total")
                raise ServerClosedError(
                    "decode engine is draining; not accepting new requests")
            if resume and session is not None \
                    and session not in self._sessions \
                    and session not in self._pending_imports:
                self.metrics.count(self.name, "sessions_reset_total")
                raise SessionResetError(
                    "session %r is not held by this replica (restarted or "
                    "expired); restart generation" % (session,))
            req = _Request(prompt, max_new, deadline, session, resume,
                           tier=tier, tenant=tenant, rank=rank,
                           vstart=vstart, rid=rid)
            # priority insertion: latency tier ahead of bulk, weighted-
            # fair tags within a tier (all-default traffic appends)
            i = len(self._queue)
            while i > 0 and self._queue[i - 1].sort_key > req.sort_key:
                i -= 1
            self._queue.insert(i, req)
            self._ensure_worker_locked()
            self._cond.notify_all()
        return req.future

    def _ensure_worker_locked(self):
        if self._worker is None:
            self._worker = threading.Thread(
                target=self._run, name="mxtpu-decode-%s" % self.name,
                daemon=True)
            self._worker.start()

    # -- worker -----------------------------------------------------------
    def _run(self):
        # the worker is always inside engine.wait_for_work or engine.step:
        # a device-idle gap in a trace finds one of the two over it
        while True:
            with span("engine.wait_for_work"), self._cond:
                while (not self._stopping and not self._queue
                       and not self._ops and not self._pipe
                       and not any(s.active for s in self._slots)):
                    self._cond.wait(0.1)
                    # the device idled for want of work, not for the
                    # host: the next launch counts as no drained one
                    self._last_out = None
                    self._expire_sessions_locked()
                if self._stopping:
                    busy = (any(s.active for s in self._slots)
                            or self._ops or self._pipe
                            or (self._drain_mode and self._queue))
                    if not busy:
                        return
            try:
                self._step()
            except Exception:  # pragma: no cover - defensive
                _log.exception("decode engine step failed; continuing")
                time.sleep(0.01)

    def _step(self):
        t0 = t = time.perf_counter()
        parts = self._parts
        parts["device_wait"] = parts["retire_host"] = self._inner_s = 0.0
        for acc in (self._host_s, self._wait_s, self._counts):
            for k in acc:
                acc[k] = 0
        with span("engine.step", step=self.steps):
            with span("engine.ops"):
                if self._pipe:
                    with self._cond:
                        ops = bool(self._ops)
                    if ops:
                        # worker ops (session imports/exports) read or
                        # rewrite the page pools and tables; run them
                        # against retired, fully materialized state
                        self._flush_pipe(cause="ops")
                self._drain_ops()
            with span("engine.expire"):
                self._expire_queued(t0)
                with self._cond:
                    self._expire_sessions_locked()
            t = self._lap("ops", t)
            with span("engine.admit") as sp:
                sp.set_metadata(admitted=self._admit())
            t = self._lap("admit", t)
            with span("engine.prefill") as sp:
                self._prefill_phase()
                sp.set_metadata(
                    launches=self._counts["prefill_launches_total"])
            t = self._lap("prefill_host", t)
            with span("engine.decode"):
                self._decode()
            t = self._lap("launch", t)
            with span("engine.account"):
                kv = self.alloc.stats()
                seen, self._reclaimed_seen = (self._reclaimed_seen,
                                              kv["counters"]["reclaimed"])
                self.metrics.count(self.name, "kv_reclaimed_pages_total",
                                   self._reclaimed_seen - seen)
                self.metrics.observe_kv_cache(
                    self.name, kv["used_pages"], kv["total_pages"],
                    kv["shared_pages"], kv["leaked_pages"],
                    tokens_resident=self._tokens_resident(),
                    bytes_per_token=kv.get("kv_bytes_per_token", 0.0))
                self.metrics.observe_fn_cache(self.name,
                                              _decoder.fn_cache_stats())
            t = self._lap("account", t)
            self.steps += 1
            self.metrics.observe_engine_step(self.name, t - t0, parts,
                                             self._host_s, self._wait_s,
                                             self._counts)

    def _lap(self, part, t_from):
        """End one top-level part of the step: its wall since ``t_from``
        less the blocking reads and the retire bookkeeping that ran
        inside it, which are parts of their own (``device_wait``,
        ``retire_host``).  The parts are contiguous, so they add up to
        the step's wall.  Returns the time now."""
        now = time.perf_counter()
        parts = self._parts
        inner = parts["device_wait"] + parts["retire_host"]
        parts[part] = now - t_from - (inner - self._inner_s)
        self._inner_s = inner
        return now

    def _device_wait(self, part, read, value, name="engine.device_wait",
                     **args):
        """``read(value)``, a blocking read of a device result, under a
        span; its wall is the step's ``device_wait`` and its ``part`` of
        ``ModelMetrics.WAIT_PARTS``."""
        t0 = time.perf_counter()
        with span(name, **args):
            out = read(value)
        dt = time.perf_counter() - t0
        self._parts["device_wait"] += dt
        self._wait_s[part] += dt
        return out

    def _dispatch(self, fn, *args):
        """``fn(*args)``, one program of the step launched: the host's
        wall until the call returns is the step's ``host_s.dispatch``.
        Where the previous launch's output is ready already, the device
        ran dry before this launch (it runs its programs in order), and
        the launch is counted as drained.  The step programs hand back
        the K and V pools first, which the next launch donates, so what
        is polled is their first output after them."""
        counts = self._counts
        if self._last_out is not None and self._last_out.is_ready():
            counts["launches_drained_total"] += 1
        t0 = time.perf_counter()
        out = fn(*args)
        self._host_s["dispatch"] += time.perf_counter() - t0
        counts["launches_total"] += 1
        self._last_out = out[2] if isinstance(out, tuple) else out
        return out

    def _upload(self, host):
        """``host`` (a numpy array or scalar the engine may go on to
        reuse) on the device, from a private copy: the step's
        ``host_s.upload``, counted with its bytes.  ``jnp.asarray`` may
        alias aligned numpy memory on the CPU backend, and ``jnp.array``
        then copies on the device, asynchronously: a launch would read a
        staging buffer that the next launch has refilled by then (seen as
        a fresh lane fed token 0, in the processes whose buffer happened
        to be aligned)."""
        t0 = time.perf_counter()
        host = onp.array(host)
        out = jnp.asarray(host)
        self._host_s["upload"] += time.perf_counter() - t0
        self._counts["host_uploads_total"] += 1
        self._counts["host_upload_bytes_total"] += host.nbytes
        return out

    def _drain_ops(self):
        """Run queued worker-thread ops (session imports/exports).  Only
        the worker may touch the donated ``_kp``/``_vp`` arrays, so
        other threads enqueue here and the ops run at step start —
        imports land before this step's admissions."""
        while True:
            with self._cond:
                if not self._ops:
                    return
                fn, fut = self._ops.popleft()
            try:
                out = fn()
            except Exception as e:
                if fut is not None:
                    fut.set_exception(e)
                else:
                    _log.warning("engine %s op failed: %r", self.name, e)
            else:
                if fut is not None:
                    fut.set_result(out)

    # -- session migration (fleet page store) -----------------------------
    def _migration_active(self):
        return bool(self.migrate and self._pagestore_addr)

    def _store(self):
        """Lazy page-store client (``False`` latches a failed connect so
        an unreachable store costs one warning, not one per park)."""
        if self._store_client is None:
            if not self._migration_active():
                self._store_client = False
            else:
                try:
                    from ..kvstore.pagestore import PageStoreClient
                    self._store_client = PageStoreClient.from_addr(
                        self._pagestore_addr)
                except Exception as e:
                    _log.warning("page store %r unusable: %r",
                                 self._pagestore_addr, e)
                    self._store_client = False
        return self._store_client or None

    def _store_key(self, sid):
        return "%s/%s" % (self.name, sid)

    def _count_store_refusal(self, store):
        """A refused store put degrades (the session stays local) but is
        never silent: count it, and count the budget-eviction flavor
        separately so capacity pressure is visible as itself."""
        self.metrics.count(self.name, "store_rejected_total")
        if getattr(store, "last_refusal", None) == "over_budget":
            self.metrics.count(self.name, "store_over_budget_total")

    def _run_op(self, fn, timeout=30.0):
        """Run ``fn`` on the worker thread — the only thread allowed to
        touch the donated ``_kp``/``_vp`` arrays.  Runs inline when no
        worker is alive (stopped or never-started engine) or when
        already called from the worker itself."""
        with self._cond:
            worker = self._worker
            if (worker is not None and worker.is_alive()
                    and worker is not threading.current_thread()):
                fut = Future()
                self._ops.append((fn, fut))
                self._cond.notify_all()
            else:
                fut = None
        if fut is None:
            return fn()
        return fut.result(timeout)

    def _pull_session(self, sid):
        """Pull-on-miss: before declaring a session dead, try to claim
        its record from the fleet page store.  On a claim, the import
        is queued as a worker op (it writes device pages) and ``sid``
        parks in ``_pending_imports`` so admission waits for it."""
        if not self._migration_active():
            return False
        store = self._store()
        if store is None:
            return False
        rec, gen = store.take(self._store_key(sid))
        if rec is None:
            return False

        def op():
            try:
                self._install_record(sid, rec, gen)
            finally:
                with self._cond:
                    self._pending_imports.discard(sid)
                    self._cond.notify_all()

        with self._cond:
            self._pending_imports.add(sid)
            self._ops.append((op, None))
            self._ensure_worker_locked()
            self._cond.notify_all()
        return True

    def _install_record(self, sid, rec, gen):
        """Materialize a page-store record as a parked session (worker
        thread only).  ``pages`` records scatter the serialized KV back
        into the pool (bit-exact); on pool pressure (or any import
        damage) they degrade to the transcript-replay path, which
        recomputes the same cache from tokens."""
        faults.check("session.import")
        if rec.get("kind") == "pages":
            try:
                self._install_pages(sid, bytes(rec["blob"]), gen)
                self.metrics.count(self.name, "migrations_in_total")
                return sid
            except Exception as e:
                try:
                    meta, _k, _v = unpack_session(bytes(rec["blob"]))
                except Exception:
                    raise e
                _log.warning(
                    "session %r page import failed (%r); falling back to "
                    "transcript replay", sid, e)
                rec = {"kind": "transcript",
                       "history": meta.get("history", []),
                       "pending": meta.get("pending")}
        hist = [int(t) for t in rec.get("history") or []]
        pending = rec.get("pending")
        sess = _Session(sid, None)
        sess.replay = hist + ([int(pending)] if pending is not None else [])
        sess.gen = int(gen)
        with self._cond:
            self._sessions[sid] = sess
        self.metrics.count(self.name, "migrations_in_total")
        return sid

    def _install_pages(self, sid, blob, gen=None):
        """Unpack a ``pack_session`` blob into fresh pool pages and park
        the session (worker thread only).  A KV-dtype mismatch between
        the blob and this engine raises typed: int8 codes are only
        meaningful next to their page scales and the latch that wrote
        them, and re-quantizing an fp blob here would silently change
        cached values — the transcript-replay path recomputes the right
        cache instead."""
        meta, k, v, ks, vs = unpack_session(blob, with_scales=True)
        sid = sid if sid is not None else meta["sid"]
        cfg = self.cfg
        blob_kv = "int8" if ks is not None else "float32"
        if blob_kv != self.kv_dtype:
            raise ValueError(
                "imported session KV dtype %r does not match this "
                "engine's %r (weight-only requantization is lossy; "
                "resume via transcript replay instead)"
                % (blob_kv, self.kv_dtype))
        want = (cfg.num_layers, cfg.num_kv_heads, self.page_size,
                cfg.head_dim)
        got = (k.shape[0], k.shape[1], k.shape[3], k.shape[4])
        if got != want:
            raise ValueError(
                "imported session KV geometry %r does not match this "
                "engine's %r" % (got, want))
        n = k.shape[2]
        self._seq += 1
        owner = ("imp", self._seq)
        while True:
            try:
                pages = self.alloc.alloc(owner, n) if n else []
                break
            except CacheOOM:
                if not self._evict_lru_session(keep=sid):
                    raise
        if n:
            # the wire speaks pages form (L, KVH, n, S, D); the pool
            # holds token rows: convert at the edge, write in place
            idx = jnp.asarray(onp.asarray(pages, onp.int32))

            def rows(codes, scales):
                codes = jnp.asarray(codes)
                return _decoder.rows_from_pages(
                    codes if scales is None
                    else _paged.QPages(q=codes, s=jnp.asarray(scales)))
            self._kp = self._place_kv(
                _decoder.put_pages(self._kp, idx, rows(k, ks)))
            self._vp = self._place_kv(
                _decoder.put_pages(self._vp, idx, rows(v, vs)))
        sess = _Session(sid, owner)
        sess.pos = int(meta["pos"])
        sess.pending = (int(meta["pending"])
                        if meta.get("pending") is not None else None)
        sess.history = [int(t) for t in meta.get("history") or []]
        sess.gen = int(gen if gen is not None else meta.get("gen", 0))
        with self._cond:
            self._sessions[sid] = sess
        return sid

    def _export_state(self, sid, pos, pending, history, owner, gen):
        """Serialize one sequence's page table + live KV pages into a
        flat ``pack_session`` buffer (worker thread only).  Shared
        prefix pages are copied out like any other page — the importer
        gets private copies, refcounts stay conserved on both sides."""
        faults.check("session.export")
        pages = self.alloc.pages(owner)
        cfg = self.cfg
        ks = vs = None
        if pages:
            idx = jnp.asarray(onp.asarray(pages, onp.int32))

            def wire(rows):
                # token rows (L, n, S, KVH * D) -> the wire's pages
                # form (L, KVH, n, S, D), byte for byte what it was
                return onp.asarray(_decoder.pages_from_rows(
                    jnp.take(rows, idx, axis=1), cfg.num_kv_heads))
            if self.kv_dtype == "int8":
                # quantized pages ship as-is: codes + per-page scales
                # (format v2) — the importer writes them back without
                # a single dequant/requant round trip, so migration
                # stays bit-identical like the fp path
                k, v = wire(self._kp.q), wire(self._vp.q)
                ks = onp.asarray(jnp.take(self._kp.s, idx, axis=2))
                vs = onp.asarray(jnp.take(self._vp.s, idx, axis=2))
            else:
                k, v = wire(self._kp), wire(self._vp)
        else:
            shape = (cfg.num_layers, cfg.num_kv_heads, 0, self.page_size,
                     cfg.head_dim)
            if self.kv_dtype == "int8":
                k = onp.zeros(shape, onp.int8)
                v = onp.zeros(shape, onp.int8)
                ks = onp.zeros(shape[:3], onp.float32)
                vs = onp.zeros(shape[:3], onp.float32)
            else:
                k = onp.zeros(shape, onp.float32)
                v = onp.zeros(shape, onp.float32)
        meta = {"sid": sid, "pos": int(pos),
                "pending": int(pending) if pending is not None else None,
                "history": [int(t) for t in history],
                "gen": int(gen)}
        return pack_session(meta, k, v, ks, vs)

    def export_session(self, session):
        """Serialize a parked session into a flat buffer;
        :meth:`import_session` on any engine with the same model
        geometry restores it bit-exactly (same pages, same greedy
        continuation).  Raises ``KeyError`` for unknown sessions and
        ``RuntimeError`` for busy or replay-pending ones."""
        self._refuse_session_wire("session export")

        def op():
            with self._cond:
                sess = self._sessions.get(session)
                if sess is None:
                    raise KeyError("unknown session %r" % (session,))
                if sess.busy:
                    raise RuntimeError(
                        "session %r is mid-generation; drain first"
                        % (session,))
                if sess.replay is not None:
                    raise RuntimeError(
                        "session %r holds a replay transcript, not pages"
                        % (session,))
            return self._export_state(session, sess.pos, sess.pending,
                                      sess.history, sess.owner, sess.gen)
        return self._run_op(op)

    def import_session(self, blob, gen=None):
        """Install an :meth:`export_session` buffer as a parked session
        on this engine; returns the session id."""
        self._refuse_session_wire("session import")

        def op():
            faults.check("session.import")
            sid = self._install_pages(None, bytes(blob), gen)
            self.metrics.count(self.name, "migrations_in_total")
            return sid
        return self._run_op(op)

    def migrate_out(self):
        """Push every parked session to the fleet page store (drain,
        rollout, role handoff); returns the number shipped.  Sessions
        the store refuses (stale generation or unreachable) stay local —
        migration degrades, it never destroys."""
        def op():
            store = self._store()
            if store is None:
                return 0
            moved = 0
            with self._cond:
                parked = [s for s in self._sessions.values() if not s.busy]
            for sess in parked:
                sess.gen += 1
                try:
                    if sess.replay is not None:
                        rec = {"kind": "transcript",
                               "history": [int(t) for t in sess.replay],
                               "pending": None}
                    else:
                        rec = {"kind": "pages",
                               "blob": self._export_state(
                                   sess.sid, sess.pos, sess.pending,
                                   sess.history, sess.owner, sess.gen)}
                except Exception as e:
                    _log.warning("migrate_out: export of session %r "
                                 "failed: %r", sess.sid, e)
                    continue
                if store.put(self._store_key(sess.sid), rec,
                             gen=sess.gen):
                    with self._cond:
                        self._sessions.pop(sess.sid, None)
                    self._free_owner(sess.owner)
                    self._spec_release(sess.owner, sess.sid)
                    moved += 1
                    self.metrics.count(self.name, "migrations_out_total")
                else:
                    self._count_store_refusal(store)
                    _log.warning("migrate_out: store refused session %r "
                                 "(%s); kept local", sess.sid,
                                 getattr(store, "last_refusal", None))
            return moved
        return self._run_op(op, timeout=60.0)

    def _push_transcript(self, sess):
        """Courier the park-point transcript to the page store BEFORE
        the client sees this turn's result: once a turn is acked, even
        SIGKILL cannot lose it — a survivor replays the transcript and
        recomputes the identical cache (worker thread only)."""
        store = self._store() if self._migration_active() else None
        if store is None:
            return
        sess.gen += 1
        rec = {"kind": "transcript",
               "history": [int(t) for t in sess.history],
               "pending": (int(sess.pending)
                           if sess.pending is not None else None)}
        if not store.put(self._store_key(sess.sid), rec, gen=sess.gen):
            self._count_store_refusal(store)
            _log.warning("transcript push for session %r refused (%s)",
                         sess.sid, getattr(store, "last_refusal", None))

    def _handoff(self, slot, req):
        """Prefill-role disaggregation: ship the freshly prefilled
        session's KV pages to the page store for a decode replica to
        claim, instead of parking locally.  Returns True when shipped
        (False falls back to a normal local park)."""
        store = self._store() if self._migration_active() else None
        if store is None:
            return False
        sess = self._sessions.get(req.session)
        gen = (sess.gen if sess is not None else 0) + 1
        try:
            blob = self._export_state(req.session, slot.pos, slot.pending,
                                      list(slot.history), slot.owner, gen)
        except Exception as e:
            _log.warning("prefill handoff export failed: %r", e)
            return False
        if not store.put(self._store_key(req.session),
                         {"kind": "pages", "blob": blob}, gen=gen):
            self._count_store_refusal(store)
            return False
        with self._cond:
            self._sessions.pop(req.session, None)
        self._free_owner(slot.owner)
        self._spec_release(slot.owner, req.session)
        self.metrics.count(self.name, "migrations_out_total")
        return True

    def _expire_queued(self, now):
        with self._cond:
            expired = [r for r in self._queue if r.expired(now)]
            for r in expired:
                self._queue.remove(r)
        for r in expired:
            self.metrics.count(self.name, "deadline_expired_total")
            r.future.set_exception(DeadlineExceededError(
                "generate request expired after %.1f ms in queue"
                % ((now - r.t_enqueue) * 1e3)))

    def _expire_sessions_locked(self):
        if not self.session_ttl_s:
            return
        cutoff = time.monotonic() - self.session_ttl_s
        for sid in [sid for sid, s in self._sessions.items()
                    if not s.busy and s.last_used < cutoff]:
            sess = self._sessions.pop(sid)
            self._free_owner(sess.owner)
            self._spec_release(sess.owner, sid)

    # -- scheduling -------------------------------------------------------
    def _free_slot(self):
        for s in self._slots:
            if not s.active:
                return s
        return None

    def _admit(self):
        """Fill free slots from the queue's head; returns how many
        requests took a slot."""
        admitted = 0
        while True:
            with self._cond:
                if not self._queue:
                    return admitted
                slot = self._free_slot()
                if slot is None:
                    return admitted
                req = self._queue[0]
                sess = (self._sessions.get(req.session)
                        if req.session is not None else None)
                if sess is not None and sess.busy:
                    # head-of-line: continuation waits for its turn
                    return admitted
                self._queue.popleft()
            self.slo.on_dispatch(req.vstart)
            with span("request.admit", rid=req.rid, slot=slot.idx,
                      waited_ms=(time.perf_counter() - req.t_mark) * 1e3):
                go_on = self._activate(slot, req, sess)
            admitted += slot.req is req
            if not go_on:
                return admitted

    def _activate(self, slot, req, sess):
        """Place ``req`` into ``slot``; returns False when admission must
        pause (page watermark) — the request goes back to the head."""
        if req.session is not None and sess is None \
                and self._resume_missing(req):
            return True  # rejected typed; keep admitting
        replaying = False
        pfx_pages, pfx_partial = [], False
        if sess is not None and sess.replay is not None:
            # a migrated transcript: rebuild the pages by replaying the
            # whole conversation as a fresh prefill (recompute is
            # bit-identical to the lost cache — the _preempt oracle)
            prefill = list(sess.replay) + req.prompt
            base, history = 0, []
            self._seq += 1
            owner = ("req", self._seq)
            replaying = True
        elif sess is not None:
            # the session's last emitted token was never fed back; it
            # leads the continuation prompt (None: parked mid-prefill)
            prefill = (([sess.pending] if sess.pending is not None else [])
                       + req.prompt)
            base, owner = sess.pos, sess.owner
            history = list(sess.history)
        else:
            prefill = list(req.prompt)
            base, history = 0, []
            self._seq += 1
            owner = ("req", self._seq)
        if ((sess is None or replaying) and self.prefix_cache is not None
                and len(prefill) > 1):
            # fresh prompts AND replayed transcripts prefill from zero —
            # both can skip whatever prefix the cache already holds
            pages, covered, pfx_partial = self.prefix_cache.lookup(prefill)
            if covered:
                pfx_pages = pages
                history = prefill[:covered]
                prefill = prefill[covered:]
                base = covered
        if not prefill:
            req.future.set_exception(BadRequestError(
                "generate: nothing to prefill (empty prompt and no "
                "pending session context)"))
            if sess is not None:
                sess.last_used = time.monotonic()
            return True
        remaining_new = req.max_new - len(req.prefix)
        final_ctx = base + len(prefill) + max(0, remaining_new - 1)
        if final_ctx > self.max_ctx:
            req.future.set_exception(BadRequestError(
                "generate: session context (%d) + prompt + max_tokens "
                "exceeds max_ctx=%d" % (base, self.max_ctx)))
            if sess is not None:
                sess.last_used = time.monotonic()
            return True
        if pfx_pages:
            # take shared references NOW so no allocation can take the
            # pages back out from under the hit
            self.alloc.share(owner, pfx_pages)
        # watermark: enough pages to finish prefill + the first decode
        # token (plus one for the copy-on-write fork of a shared partial
        # page), otherwise leave it queued until pages come back.  Pages
        # only the prefix cache keeps are room (an allocation takes them);
        # past those, idle parked sessions go (resume migrates or resets)
        need_now = (pages_for(base + len(prefill) + 1, self.page_size)
                    - len(self.alloc.pages(owner))
                    + (1 if pfx_partial else 0))
        while (need_now > self.alloc.num_available
               and self._evict_lru_session(keep=req.session)):
            pass
        if need_now > self.alloc.num_available:
            if pfx_pages or replaying:
                self.alloc.free(owner)  # drop shared refs; retry relooks
            with self._cond:
                self._queue.appendleft(req)
            return False
        if not req.started and not req.future.set_running_or_notify_cancel():
            if pfx_pages or replaying:
                self.alloc.free(owner)
            return True  # client cancelled while queued
        req.started = True
        self._seq += 1
        slot.req = req
        slot.state = "prefill"
        slot.owner = owner
        slot.prompt = prefill
        slot.done = 0
        slot.pos = base
        slot.history = history
        slot.generated = []
        slot.pending = None
        slot.flight = 0
        slot.predraft = None
        slot.t_last = time.perf_counter()
        req.mark(_QUEUE, slot.t_last)
        slot.admit_seq = self._seq
        slot.cacheable = (self.prefix_cache is not None
                          and (sess is None or replaying))
        if req.session is not None:
            sess = self._sessions.get(req.session)
            if sess is None:
                sess = self._sessions[req.session] = _Session(
                    req.session, owner)
            if replaying:
                sess.replay = None
                sess.owner = owner
                sess.pos = 0
                sess.pending = None
                sess.history = []
                self.metrics.count(self.name, "migrations_replayed_total")
            sess.busy = True
        if pfx_pages:
            self.metrics.count(self.name, "prefix_hits_total")
            self.metrics.count(self.name, "prefix_tokens_saved_total",
                               base)
            if self.state_entry_bytes:
                # whole pages only: each brings its state entry along
                self.metrics.count(self.name,
                                   "state_prefix_pages_shared_total",
                                   len(pfx_pages))
            if pfx_partial:
                # the trailing shared page is partially filled and this
                # sequence will write into it: fork copy-on-write before
                # the first divergent write lands
                old = pfx_pages[-1]
                new = self.alloc.fork(owner, old)
                self._fork_page(old, new)
                self.metrics.count(self.name, "cow_forks_total")
        self.metrics.count(self.name, "sequences_total")
        self._sync_table(slot)
        return True

    def _evict_lru_session(self, keep=None):
        """Reclaim the least-recently-used idle parked session's pages
        (the allocator has taken the prefix cache's pages by itself and
        is out).  Returns True when one was evicted."""
        with self._cond:
            idle = [s for s in self._sessions.values()
                    if not s.busy and s.sid != keep]
            if not idle:
                return False
            victim = min(idle, key=lambda s: s.last_used)
            del self._sessions[victim.sid]
        self._free_owner(victim.owner)
        self._spec_release(victim.owner, victim.sid)
        return True

    def _resume_missing(self, req):
        """resume=True but the session is gone (TTL/restart/preempt):
        reject typed.  Returns True when the request was rejected."""
        if req.resume:
            self.metrics.count(self.name, "sessions_reset_total")
            req.future.set_exception(SessionResetError(
                "session %r is not held by this replica (restarted or "
                "expired); restart generation" % (req.session,)))
            return True
        return False

    def _sync_table(self, slot):
        row = self.alloc.pages(slot.owner)
        self._tables[slot.idx, :] = 0
        if row:
            self._tables[slot.idx, :len(row)] = row

    def _launch_decode_step(self, chained=None):
        """Dispatch the decode step over the staged lanes (``_stage_*``)
        with every host input, the whole page table among them, in one
        buffer: one transfer (``decoder.decode_buffer``).  With
        ``chained``, the newest in-flight launch's next tokens, a lane
        marked in ``_stage_carry`` takes its token from there.  Returns
        the launch's next tokens, on the device."""
        carry = None if chained is None else self._stage_carry
        buf = _decoder.decode_buffer(
            self._stage_tokens, self._stage_positions, self._tables,
            self._stage_active, carry)
        self._kp, self._vp, out, _ = self._dispatch(
            self._decode_fn, self.params, self._kp, self._vp,
            self._no_chain if chained is None else chained,
            self._upload(buf))
        return out

    # -- in-flight page pinning (async pipeline) --------------------------
    def _pin_owners(self, fl):
        for o in fl.owners:
            self._flight_owners[o] = self._flight_owners.get(o, 0) + 1

    def _unpin_owners(self, fl):
        for o in fl.owners:
            n = self._flight_owners.get(o, 0) - 1
            if n > 0:
                self._flight_owners[o] = n
            else:
                self._flight_owners.pop(o, None)

    def _free_owner(self, owner):
        """Release an owner's pool pages, deferred past any in-flight
        step that still writes them: the free list must never recycle a
        page an unretired launch targets.  The release callback runs in
        the pinning step's retire (or the pipeline flush), so
        ``check_leaks`` is conserved once the pipe is empty."""
        if owner is None:
            return
        with self._cond:
            if self._flight_owners.get(owner):
                for fl in reversed(self._pipe):
                    if owner in fl.owners:
                        fl.on_retire.append(
                            lambda o=owner: self.alloc.free(o))
                        return
        self.alloc.free(owner)

    def _fork_page(self, old, new):
        """The device half of a copy-on-write fork: page ``old`` copied
        over page ``new`` of both pools, in place."""
        self._kp, self._vp = (
            self._place_kv(_decoder.fork_page(pool, old, new))
            for pool in (self._kp, self._vp))

    def _place_kv(self, pages):
        """Pin (or re-pin) a pool to the TP KV sharding.  No-op when
        serving replicated.  The host-side page edits (imports,
        copy-on-write forks) are jitted programs whose results' placement
        XLA chooses; re-pinning keeps every update on the head-sharded
        layout so the next decode step never inserts a resharding
        transfer."""
        if self._tp_plan is None:
            return pages
        return self._tp_plan.place_kv(pages)

    def _ensure_pages(self, slot, tokens_ahead):
        """Grow the slot's page list to cover ``tokens_ahead`` more cache
        positions; preempts the youngest other sequence on exhaustion.
        Returns False when the SLOT ITSELF was failed (nothing fits)."""
        need = (pages_for(slot.pos + tokens_ahead, self.page_size)
                - len(self.alloc.pages(slot.owner)))
        while need > 0:
            try:
                self.alloc.alloc(slot.owner, need)
                self._sync_table(slot)
                return True
            except CacheOOM:
                victim = self._preempt_victim(exclude=slot)
                if victim is None:
                    self._fail_slot(slot, ServingError(
                        "kv cache too small for this sequence (%d pages "
                        "total)" % (self.alloc.total_pages - 1,)))
                    return False
                self._preempt(victim)
            except Exception as e:
                # injected kvcache.alloc fault (or a real allocator bug):
                # fail only this sequence, keep the engine serving
                self._fail_slot(slot, e if isinstance(e, ServingError)
                                else ServingError(
                                    "kv page allocation failed: %r" % (e,)))
                return False
        self._sync_table(slot)
        return True

    def _preempt_victim(self, exclude):
        victim = None
        for s in self._slots:
            # "finishing" slots are done — their result is decided and
            # their pages release in the imminent deferred phase;
            # preempt-recompute would replay a completed stream
            if s.active and s is not exclude and s.state != "finishing":
                if victim is None or s.admit_seq > victim.admit_seq:
                    victim = s
        return victim

    def _preempt(self, slot):
        """vLLM recompute eviction: free the slot's pages, requeue the
        request at the head with its emitted tokens folded into the
        prompt (the continuation decodes on, nothing is lost)."""
        req = slot.req
        recompute = list(slot.history) + slot.prompt[slot.done:]
        if slot.state == "decode" and slot.pending is not None:
            recompute.append(slot.pending)
        new = _Request(recompute, req.max_new, req.deadline, req.session,
                       False, rid=req.rid)
        new.future = req.future
        new.started = req.started
        new.t_enqueue = req.t_enqueue
        # back to waiting from now on; what it spent so far stays counted
        req.mark(_PREFILL if slot.state == "prefill" else _DECODE,
                 new.t_mark)
        new.phases = req.phases
        new.prefix = req.prefix + slot.generated
        new.ttft_recorded = req.ttft_recorded
        new.prompt_tokens = req.prompt_tokens
        self._free_owner(slot.owner)
        self._spec_release(slot.owner)  # draft cache is stale with the pages
        if req.session is not None:
            # the parked context is gone with the pages; the requeued
            # request re-creates the session from the full history
            self._sessions.pop(req.session, None)
        self._clear(slot)
        with self._cond:
            self._queue.appendleft(new)
        self.metrics.count(self.name, "preemptions_total")

    # -- prefill ----------------------------------------------------------
    def _prefill_phase(self):
        """Advance EVERY prefill-state slot one chunk (round-robin
        start).  Per engine step, decode therefore stalls for at most
        one bounded chunk per admitted-but-not-ready slot — a long
        prompt still cannot monopolize the engine."""
        order = [self._slots[(self._prefill_rr + i) % self.slots]
                 for i in range(self.slots)]
        pending = [s for s in order if s.state == "prefill"]
        if pending:
            self._prefill_rr = (pending[0].idx + 1) % self.slots
        for slot in pending:
            if slot.state == "prefill":  # peers may preempt it mid-loop
                self._prefill_chunk_step(slot)

    def _prefill_chunk_step(self, slot):
        now = time.perf_counter()
        if slot.req.expired(now):
            self._finish(slot, "deadline")
            return
        n = min(self.prefill_chunk, len(slot.prompt) - slot.done)
        if not self._ensure_pages(slot, n):
            return
        rid = slot.req.rid
        # pages of the sequence the chunk writes: each gets a state entry
        # from a model with recurrent layers
        touched = (pages_for(slot.pos + n, self.page_size)
                   - slot.pos // self.page_size)
        if self.state_entry_bytes:
            self.metrics.count(self.name, "state_entries_written_total",
                               touched)
            if slot.pos == 0:
                self.metrics.count(self.name, "state_starts_total")
        # the entries it writes, and the one before pos0 that it reads
        state_bytes = self._count_state("prefill",
                                        touched + (slot.pos > 0))
        # the latent rows its attention reads: the sequence's up to the
        # chunk's end
        self._count_latent("prefill", slot.pos + n)
        with span("engine.prefill_launch", rid=rid, slot=slot.idx,
                  tokens=n, pos=slot.pos,
                  state_pages=touched if self.state_entry_bytes else 0,
                  expert_pairs=n * self._pairs_per_token,
                  state_bytes=state_bytes):
            chunk = slot.prompt[slot.done:slot.done + n]
            padded = onp.zeros(self.prefill_chunk, onp.int32)
            padded[:n] = chunk
            # every host input in one buffer, one transfer
            buf = _decoder.prefill_buffer(padded, slot.pos, n,
                                          self._tables[slot.idx])
            self._kp, self._vp, next_tok, _ = self._dispatch(
                self._prefill_fn, self.params, self._kp, self._vp,
                self._upload(buf))
        self._counts["prefill_launches_total"] += 1
        slot.history.extend(chunk)
        slot.pos += n
        slot.done += n
        self.metrics.count(self.name, "prefill_tokens_total", n)
        if slot.done < len(slot.prompt):
            return
        # prompt fully cached: the prefill's last logits ARE the first
        # generated token — time-to-first-token lands here
        if slot.cacheable:
            # publish the prompt's pages for prefix sharing (pure
            # refcount bumps — consumes no free pages).  Decode will
            # keep writing into the trailing partial page, but only at
            # offsets past its published token count, which hitters
            # never read (and a hitter forks it copy-on-write anyway).
            # A model with recurrent layers publishes WHOLE pages
            # only: a page's state entry is the state after its last
            # token, and the owner keeps writing the trailing page's.
            whole = (len(slot.history) // self.page_size * self.page_size
                     if self.state_entry_bytes else len(slot.history))
            self.prefix_cache.insert(list(slot.history[:whole]),
                                     self.alloc.pages(slot.owner))
        tok = self._device_wait("first_token", int, next_tok,
                                "engine.first_token_read", rid=rid)
        now = time.perf_counter()
        slot.req.mark(_PREFILL, now)
        if not slot.req.ttft_recorded:
            self.metrics.observe_ttft(self.name, now - slot.req.t_enqueue)
            slot.req.ttft_recorded = True
        slot.generated.append(tok)
        slot.pending = tok
        slot.state = "decode"
        slot.t_last = now
        self._maybe_finish(slot, now)

    # -- decode -----------------------------------------------------------
    def _decode(self):
        if self.async_decode:
            return self._decode_async()
        batch = [s for s in self._slots if s.state == "decode"]
        if not batch:
            return
        try:
            faults.check("decode.step")
        except Exception as e:
            # a decode-step fault poisons the in-flight decode batch
            # (typed), frees its pages, and the engine keeps serving —
            # prefills and fresh admissions are unaffected
            for s in batch:
                self._fail_slot(s, ServingError(
                    "decode step failed: %r" % (e,)))
            return
        live = []
        for s in batch:
            if s.req.expired(time.perf_counter()):
                self._finish(s, "deadline")
            elif self._ensure_pages(s, 1):
                if s.state == "decode":  # _ensure_pages may preempt peers
                    live.append(s)
        live = [s for s in live if s.state == "decode"]
        if not live:
            return
        if self._spec is not None and self._decode_speculative(live):
            return
        tokens = self._stage_tokens
        positions = self._stage_positions
        active = self._stage_active
        tokens.fill(0)
        positions.fill(0)
        active.fill(False)
        for s in live:
            tokens[s.idx] = s.pending
            positions[s.idx] = s.pos
            active[s.idx] = True
        self._count_decode_pages(positions, active)
        self._count_state("decode", 2 * len(live))
        t0 = time.perf_counter()
        next_tokens = self._device_wait("sync", onp.asarray,
                                        self._launch_decode_step())
        now = time.perf_counter()
        for s in live:
            tok = int(next_tokens[s.idx])
            s.history.append(s.pending)
            s.pos += 1
            s.generated.append(tok)
            s.pending = tok
            self.metrics.observe_inter_token(self.name, now - s.t_last)
            s.t_last = now
            self._maybe_finish(s, now)
        self.metrics.observe_decode_step(self.name, now - t0, self.slots,
                                         len(live))

    def _count_state(self, phase, entries):
        """Bytes of the state ``entries`` (a page's, over the recurrent
        layers) that a staged ``phase`` launch reads and writes, known to
        the host; counted as ``state_bytes_<phase>_total`` where the model
        has recurrent layers, and returned."""
        n = int(entries) * self.state_entry_bytes
        if n:
            self._state_bytes[phase] += n
            self.metrics.count(self.name, "state_bytes_%s_total" % phase, n)
        return n

    def _count_latent(self, phase, tokens):
        """Bytes of the latent rows of ``tokens`` positions that a staged
        ``phase`` launch's attention reads, over the latent layers;
        counted as ``latent_bytes_<phase>_total`` where the model has
        latent attention layers."""
        n = int(tokens) * self.latent_row_bytes
        if n:
            self._latent_bytes[phase] += n
            self.metrics.count(self.name, "latent_bytes_%s_total" % phase, n)

    def _count_decode_pages(self, positions, active):
        """What the staged launch's attention has to read: the pages
        under ``position + 1`` of each active lane, beside the whole
        table the program is handed."""
        live = -(-(positions[active] + 1) // self.page_size)
        self.metrics.count(self.name, "decode_pages_live_total",
                           int(live.sum()))
        self._count_latent("decode", (positions[active] + 1).sum())
        self.metrics.count(self.name, "decode_pages_table_total",
                           self.slots * self.pages_per_seq)

    # -- async decode pipeline --------------------------------------------
    def _decode_async(self):
        """Double-buffered decode: launch step N+1 while step N's result
        is still materializing on device, then retire launches down to
        the configured dispatch depth.  Sampled tokens stay on device as
        jax.Arrays and chain into the next launch, whose step takes
        ``where(carry, chained, staged)`` first — the host reads a step's
        result (one ``jax.device_get``) only once the next launch is
        already in flight, so scheduling overhead hides behind device
        compute instead of serializing with it."""
        if self._spec is not None:
            return self._decode_async_spec()
        launched = self._launch_decode()
        limit = self.dispatch_ahead if launched else 0
        while len(self._pipe) > limit:
            self._retire_one()

    def _launch_decode(self):
        """Dispatch one plain decode step without waiting for in-flight
        results.  Lanes with work in flight take their input token from
        the newest launch's on-device output (``carry``); fresh lanes
        stage theirs from the host.  Launch-time exclusions (budget,
        context, deadline) count in-flight lanes, and they are monotone
        until a retire runs — so every carried lane is guaranteed to be
        riding ``self._pipe[-1]``.  Returns True when a step launched."""
        now = time.perf_counter()
        batch = []
        for s in self._slots:
            if s.state != "decode":
                continue
            req = s.req
            if req.expired(now):
                # deadline is judged against launch time; a slot with
                # lanes still in flight expires at its retire instead
                if s.flight == 0:
                    self._finish(s, "deadline")
                continue
            if len(s.generated) + s.flight + len(req.prefix) >= req.max_new:
                continue  # in-flight lanes already cover the budget
            if s.pos + s.flight >= self.max_ctx:
                continue
            batch.append(s)
        if not batch:
            return False
        try:
            faults.check("decode.step")
        except Exception as e:
            for s in batch:
                self._fail_slot(s, ServingError(
                    "decode step failed: %r" % (e,)))
            return False
        depth0 = len(self._pipe)
        live = []
        for s in batch:
            if s.state != "decode":
                continue  # a peer's page scramble took it down
            ok = self._grow_pages_inflight(s)
            if len(self._pipe) != depth0:
                # growth flushed the pipeline (OOM relief); every flight
                # count is stale now — abandon this launch and let the
                # next step rebuild from quiesced state
                return False
            if ok and s.state == "decode":
                live.append(s)
        live = [s for s in live if s.state == "decode"]
        if not live:
            return False
        # a lane reads the entry before its position and writes its own
        state_bytes = self._count_state("decode", 2 * len(live))
        with span("engine.decode_launch", lanes=len(live), depth=depth0,
                  expert_pairs=len(live) * self._pairs_per_token,
                  state_bytes=state_bytes):
            st = self._stage_tokens
            sp = self._stage_positions
            sa = self._stage_active
            carry = self._stage_carry
            st.fill(0)
            sp.fill(0)
            sa.fill(False)
            carry.fill(False)
            chain = False
            for s in live:
                sp[s.idx] = s.pos + s.flight
                sa[s.idx] = True
                if s.flight > 0:
                    carry[s.idx] = True  # input is the in-flight step's output
                    chain = True
                else:
                    st[s.idx] = s.pending
            self._count_decode_pages(sp, sa)
            t0 = time.perf_counter()
            # a carried lane's token is the in-flight output's, merged
            # with the staged ones inside the step
            out = self._launch_decode_step(
                self._pipe[-1].out if chain else None)
        fl = _Flight("plain", out, t0, [(s, s.admit_seq) for s in live],
                     set(s.owner for s in live))
        for s in live:
            s.flight += 1
        with self._cond:
            self._pipe.append(fl)
            self._pin_owners(fl)
        return True

    def _retire_one(self, part="retire"):
        """Force the oldest in-flight step's tokens to the host and run
        its bookkeeping (history/pos advance, emission, inter-token +
        decode-step metrics, EOS/length/deadline finishes).  Lanes whose
        slot was recycled since launch (admit-seq mismatch) are
        discarded — their tokens were never promised to anyone.  The
        read is ``part`` of the step's blocking reads."""
        with self._cond:
            if not self._pipe:
                return
            fl = self._pipe.popleft()
        parts = self._parts
        t0, waited = time.perf_counter(), parts["device_wait"]
        with span("engine.retire", lanes=len(fl.lanes)):
            self._retire_flight(fl, part)
        # the bookkeeping alone: the blocking read is device_wait's
        parts["retire_host"] += (time.perf_counter() - t0
                                 - (parts["device_wait"] - waited))

    def _retire_flight(self, fl, part):
        try:
            faults.check("engine.retire")
        except Exception as e:
            self._retire_poisoned(fl, e)
            return
        toks = self._device_wait(part, jax.device_get, fl.out)
        now = time.perf_counter()
        self.metrics.count(self.name, "deferred_reads_total")
        with self._cond:
            self._unpin_owners(fl)
        live = 0
        for s, seq in fl.lanes:
            if s.req is None or s.admit_seq != seq or s.state != "decode":
                continue
            s.flight = max(0, s.flight - 1)
            tok = int(toks[s.idx])
            s.history.append(s.pending)
            s.pos += 1
            s.generated.append(tok)
            s.pending = tok
            self.metrics.observe_inter_token(self.name, now - s.t_last)
            s.t_last = now
            live += 1
            self._maybe_finish(s, now)
        for cb in fl.on_retire:
            cb()
        if live:
            # step wall = retire cadence in steady state (launch→retire
            # spans the whole pipeline depth and would read ~depth× the
            # true per-step time); first retire after an idle pipe falls
            # back to its own launch→retire wall
            base = max(fl.t_launch, self._t_last_retire or 0.0)
            self.metrics.observe_decode_step(self.name, now - base,
                                             self.slots, live)
        self._t_last_retire = now

    def _retire_poisoned(self, fl, exc):
        """An ``engine.retire`` fault (or a real device-read failure)
        poisons exactly one flight: its live lanes fail typed, its pins
        release, and the REST of the pipeline is discarded unread —
        chained launches downstream consumed this step's now-unreadable
        tokens, and surviving slots simply relaunch from their last
        confirmed token (greedy decode recomputes the identical
        stream).  The engine keeps serving."""
        with self._cond:
            self._unpin_owners(fl)
        err = ServingError("decode retire failed: %r" % (exc,))
        for s, seq in fl.lanes:
            if s.req is not None and s.admit_seq == seq \
                    and s.state in ("decode", "finishing"):
                self._fail_slot(s, err)
        for cb in fl.on_retire:
            cb()
        self._flush_pipe(discard=True)

    def _flush_pipe(self, discard=False, cause=None):
        """Drain every in-flight launch (counted by ``cause`` when there
        is one).  ``discard=True`` drops results without reading them
        (downstream of a poisoned flight): valid lanes just lose their
        in-flight count and relaunch from their last confirmed token."""
        if self._pipe and not discard:
            self.metrics.count(self.name, "pipe_flushes_total")
            self.metrics.count(self.name, "pipe_flushes_%s_total" % cause)
        while self._pipe:
            if not discard:
                self._retire_oldest("flush")
                continue
            with self._cond:
                if not self._pipe:
                    break
                fl = self._pipe.popleft()
                self._unpin_owners(fl)
            for s, seq in fl.lanes:
                if s.req is not None and s.admit_seq == seq:
                    s.flight = max(0, s.flight - 1)
            for cb in fl.on_retire:
                cb()

    def _retire_oldest(self, part):
        if self._spec is not None:
            rec = self._retire_spec(part)
            if rec is not None:
                self._run_spec_deferred(rec)
            return
        self._retire_one(part)

    def _grow_pages_inflight(self, s):
        """Page growth for an async launch: the slot's cache must cover
        ``pos + flight + 1`` positions (every unretired lane writes one).
        The happy path allocates, free pages or the prefix cache's,
        without touching peers or the pipe; with live sequences and
        sessions holding the pool the pipeline is flushed FIRST so the
        sync preemption machinery (:meth:`_ensure_pages`) runs against a
        quiesced engine whose flight counts are all zero."""
        need = (pages_for(s.pos + s.flight + 1, self.page_size)
                - len(self.alloc.pages(s.owner)))
        if need <= 0:
            return True
        try:
            self.alloc.alloc(s.owner, need)
            self._sync_table(s)
            return True
        except CacheOOM:
            self._flush_pipe(cause="page_pressure")
            if s.req is None or s.state != "decode":
                return False  # the flush finished / failed / preempted it
            return self._ensure_pages(s, 1)
        except Exception as e:
            self._fail_slot(s, e if isinstance(e, ServingError)
                            else ServingError(
                                "kv page allocation failed: %r" % (e,)))
            return False

    # -- async speculative pipeline ---------------------------------------
    def _decode_async_spec(self):
        """Speculative pipelining.  A verify's input depends on host-side
        acceptance, so spec mode cannot stack two launches — instead the
        overlap comes from reordering: retire the in-flight step with
        only the state updates the next launch needs, launch immediately
        (its draft was pre-computed while the step ran on device), and
        do the remaining bookkeeping (metric emission, future
        resolution, transcript pushes) behind the fresh launch."""
        rec = self._retire_spec()
        self._launch_spec()
        if rec is not None:
            self._run_spec_deferred(rec)

    def _retire_spec(self, part="retire"):
        """Retire the in-flight spec step: force the wide output, run
        longest-prefix acceptance, advance slot state, roll back
        rejected cache positions, feed adaptive-k, validate the
        pre-draft, and DECIDE finishes (slots park in ``finishing``
        state so the next launch skips them).  Returns the deferred
        record for :meth:`_run_spec_deferred`, or None.  The read is
        ``part`` of the step's blocking reads."""
        with self._cond:
            if not self._pipe:
                return None
            fl = self._pipe.popleft()
        try:
            faults.check("engine.retire")
        except Exception as e:
            self._retire_poisoned(fl, e)
            return None
        out = self._device_wait(part, jax.device_get, fl.out)
        now = time.perf_counter()
        self.metrics.count(self.name, "deferred_reads_total")
        with self._cond:
            self._unpin_owners(fl)
        spec = self._spec
        lanes = []
        emitted_total = 0
        for s, seq in fl.lanes:
            if s.req is None or s.admit_seq != seq or s.state != "decode":
                continue
            s.flight = 0
            row = fl.fed[s.idx]
            nv = len(row)
            pos0 = s.pos
            if fl.kind == "verify":
                preds = [int(t) for t in out[s.idx, :nv]]
                accepted = 0
                while accepted < nv - 1 \
                        and row[accepted + 1] == preds[accepted]:
                    accepted += 1
                emitted = preds[:accepted + 1]
            else:
                accepted = 0
                emitted = [int(out[s.idx])]
            budget = (s.req.max_new - len(s.req.prefix)
                      - len(s.generated))
            emitted = emitted[:max(1, budget)]
            if self.eos_id is not None and self.eos_id in emitted:
                emitted = emitted[:emitted.index(self.eos_id) + 1]
            gap = (now - s.t_last) / len(emitted)
            for tok in emitted:
                s.history.append(s.pending)
                s.pos += 1
                s.generated.append(tok)
                s.pending = tok
            s.t_last = now
            emitted_total += len(emitted)
            drafted = nv - 1
            if drafted:
                # adaptive-k learns the outcome BEFORE the next launch
                # budgets its draft width — same ordering as sync mode
                spec.observe(self._spec_key(s), drafted, accepted)
            if fl.kind == "verify":
                self._rollback_kv(s, pos0 + nv)
            # pre-draft validation: keep the overlapped draft's tail iff
            # its prediction of this step's emission was exact — any
            # draft is correctness-safe (verify gates it), this only
            # decides whether the next launch re-drafts
            s.predraft = spec.reuse_predraft(s.predraft, emitted,
                                             spec.k_cap)
            reason = None
            if self.eos_id is not None and s.pending == self.eos_id:
                reason = "eos"
            elif len(s.generated) + len(s.req.prefix) >= s.req.max_new:
                reason = "length"
            elif s.req.expired(now):
                reason = "deadline"
            if reason is not None:
                s.state = "finishing"
            lanes.append((s, len(emitted), gap, drafted, accepted,
                          reason))
        for cb in fl.on_retire:
            cb()
        return {"lanes": lanes, "kind": fl.kind, "t_launch": fl.t_launch,
                "now": now, "emitted_total": emitted_total}

    def _launch_spec(self):
        """Launch the next spec step (wide verify, or a plain staged
        step when nothing drafted), then pre-draft the step after it
        while this one runs on device.  Mirrors the sync
        :meth:`_decode_speculative` admission/gate/page-growth order so
        the emitted streams stay bit-identical."""
        spec = self._spec
        now = time.perf_counter()
        batch = []
        for s in self._slots:
            if s.state != "decode":
                continue
            if s.req.expired(now):
                self._finish(s, "deadline")
                continue
            batch.append(s)
        if not batch:
            return False
        try:
            faults.check("decode.step")
        except Exception as e:
            for s in batch:
                self._fail_slot(s, ServingError(
                    "decode step failed: %r" % (e,)))
            return False
        live = []
        for s in batch:
            if s.state != "decode":
                continue
            if self._ensure_pages(s, 1) and s.state == "decode":
                live.append(s)
        live = [s for s in live if s.state == "decode"]
        if not live:
            return False
        plan = {}
        for s in live:
            req = s.req
            budget = req.max_new - len(req.prefix) - len(s.generated)
            max_k = min(spec.k_cap, budget - 1, self.max_ctx - s.pos - 1)
            k = spec.budget(self._spec_key(s), max_k)
            if k <= 0:
                continue
            pre, s.predraft = s.predraft, None
            if pre:
                draft = pre[:k]  # overlapped draft, validated at retire
            else:
                t0 = time.perf_counter()
                draft = spec.propose(self._spec_key(s), s.owner,
                                     list(s.history) + [s.pending], k)
                self.metrics.observe_draft(self.name,
                                           time.perf_counter() - t0)
            if draft:
                plan[s.idx] = [int(t) for t in draft]
        if plan and not spec.verify_gate([self._spec_key(s) for s in live
                                          if s.idx in plan]):
            plan = {}
        survivors = []
        for s in live:
            if s.state != "decode":
                plan.pop(s.idx, None)
                continue
            if self._ensure_pages(s, 1 + len(plan.get(s.idx, ()))):
                if s.state == "decode":
                    survivors.append(s)
                    continue
            plan.pop(s.idx, None)
        live = [s for s in survivors if s.state == "decode"]
        if not live:
            return False
        fed = {}
        if plan:
            width = 1 + max(len(d) for d in plan.values())
            verify_fn = _decoder.make_verify_step(
                self.cfg, self.page_size, width, sharding=self.sharding,
                quant=self.quant, kv_dtype=self.kv_dtype)
            tokens = onp.zeros((self.slots, width), onp.int32)
            positions = onp.zeros(self.slots, onp.int32)
            n_valid = onp.zeros(self.slots, onp.int32)
            active = onp.zeros(self.slots, bool)
            for s in live:
                row = [s.pending] + plan.get(s.idx, [])
                fed[s.idx] = row
                tokens[s.idx, :len(row)] = row
                positions[s.idx] = s.pos
                n_valid[s.idx] = len(row)
                active[s.idx] = True
            t0 = time.perf_counter()
            self._kp, self._vp, out = self._dispatch(
                verify_fn, self.params, self._kp, self._vp,
                self._upload(tokens), self._upload(positions),
                self._upload(n_valid), self._upload(self._tables),
                self._upload(active))
            kind = "verify"
        else:
            st = self._stage_tokens
            sp = self._stage_positions
            sa = self._stage_active
            st.fill(0)
            sp.fill(0)
            sa.fill(False)
            for s in live:
                fed[s.idx] = [s.pending]
                st[s.idx] = s.pending
                sp[s.idx] = s.pos
                sa[s.idx] = True
            self._count_decode_pages(sp, sa)
            t0 = time.perf_counter()
            out = self._launch_decode_step()
            kind = "plain"
        fl = _Flight(kind, out, t0, [(s, s.admit_seq) for s in live],
                     set(s.owner for s in live), fed)
        for s in live:
            s.flight = 1
        with self._cond:
            self._pipe.append(fl)
            self._pin_owners(fl)
        # overlapped drafting: propose the NEXT step's continuation from
        # the current confirmed context while this launch runs on
        # device.  The proposal covers this step's maximum emission plus
        # a k-deep tail; retire keeps the tail iff the emission prefix
        # matched exactly.  (propose swallows drafter faults itself.)
        for s in live:
            k = spec.budget(self._spec_key(s), spec.k_cap)
            if k <= 0:
                continue
            t0 = time.perf_counter()
            s.predraft = spec.propose(self._spec_key(s), s.owner,
                                      list(s.history) + [s.pending],
                                      len(fed[s.idx]) + k)
            self.metrics.observe_draft(self.name,
                                       time.perf_counter() - t0)
        return True

    def _run_spec_deferred(self, rec):
        """The retired spec step's remaining bookkeeping, run AFTER the
        next launch is in flight: metric emission, verify/step
        histograms, and the actual finishes (future resolution,
        transcript pushes — the expensive host work)."""
        now = rec["now"]
        if not rec["lanes"]:
            return
        for s, n_emitted, gap, drafted, accepted, reason in rec["lanes"]:
            for _ in range(n_emitted):
                self.metrics.observe_inter_token(self.name, gap)
            if drafted:
                self.metrics.count(self.name, "spec_draft_tokens_total",
                                   drafted)
                self.metrics.count(self.name,
                                   "spec_accepted_tokens_total", accepted)
            if reason is not None:
                self._finish(s, reason)
        if rec["kind"] == "verify":
            self.metrics.observe_verify(self.name, now - rec["t_launch"])
            self.metrics.count(self.name, "spec_verify_steps_total")
        self.metrics.observe_decode_step(self.name, now - rec["t_launch"],
                                         self.slots, rec["emitted_total"])

    # -- speculative decoding ---------------------------------------------
    def _build_spec(self, drafter, draft_model, spec_k):
        from .speculate import (DraftModelDrafter, Drafter, NGramDrafter,
                                SpeculativeScheduler)
        if isinstance(drafter, Drafter):
            d = drafter
        else:
            kind = str(drafter if drafter is not None
                       else _config.get("MXNET_GEN_SPEC_DRAFTER")
                       or "ngram")
            if kind == "model" or draft_model is not None:
                dm = draft_model
                if dm is None:
                    builder = str(_config.get(
                        "MXNET_GEN_SPEC_DRAFT_BUILDER") or "")
                    if builder:
                        import importlib
                        mod, _, attr = builder.partition(":")
                        dm = getattr(importlib.import_module(mod),
                                     attr)(self.model)
                    else:
                        dm = _decoder.decoder_draft(self.model)
                d = DraftModelDrafter(dm, page_size=self.page_size)
            else:
                d = NGramDrafter()
        return SpeculativeScheduler(d, k_cap=spec_k, name=self.name)

    def _spec_key(self, slot):
        """Controller key: the session id for session requests (learned
        acceptance carries across turns), else the slot's owner."""
        if slot.req is not None and slot.req.session is not None:
            return slot.req.session
        return slot.owner

    def _spec_release(self, owner, key=None):
        """Drop per-sequence drafter state when ``owner``'s pages are
        retired (finish/fail/preempt/evict/migrate); with ``key`` the
        adaptive-k controller goes too."""
        if self._spec is None or owner is None:
            return
        try:
            self._spec.release(owner, key)
        except Exception:  # pragma: no cover - drafter bug must not kill
            _log.exception("drafter release failed")

    def _decode_speculative(self, live):
        """One draft → wide-verify → accept/rollback step over the whole
        decode batch.  Returns False (nothing consumed) when no slot has
        a draft this step or the verify fault gate trips — the caller
        falls through to the plain one-token decode step.

        Every live slot rides the SAME wide launch: speculating slots
        feed ``1 + k`` positions, plain slots feed their single pending
        token with ``n_valid = 1`` — mixed batches cost nothing extra
        and the launch count stays static per (geometry, width),
        independent of acceptance."""
        spec = self._spec
        plan = {}                       # slot.idx -> draft token list
        for s in live:
            req = s.req
            budget = req.max_new - len(req.prefix) - len(s.generated)
            max_k = min(spec.k_cap, budget - 1, self.max_ctx - s.pos - 1)
            k = spec.budget(self._spec_key(s), max_k)
            if k <= 0:
                continue
            t0 = time.perf_counter()
            draft = spec.propose(self._spec_key(s), s.owner,
                                 list(s.history) + [s.pending], k)
            self.metrics.observe_draft(self.name,
                                       time.perf_counter() - t0)
            if draft:
                plan[s.idx] = [int(t) for t in draft]
        if not plan:
            return False
        if not spec.verify_gate([self._spec_key(s) for s in live
                                 if s.idx in plan]):
            return False
        # page growth AFTER the gate: a speculating slot writes 1 + k
        # cache positions this step (peers may be preempted to fit)
        survivors = []
        for s in live:
            if s.state != "decode":
                plan.pop(s.idx, None)
                continue
            if self._ensure_pages(s, 1 + len(plan.get(s.idx, ()))):
                if s.state == "decode":
                    survivors.append(s)
                    continue
            plan.pop(s.idx, None)
        live = [s for s in survivors if s.state == "decode"]
        if not live:
            return True   # the page scramble consumed the whole batch
        if not plan:
            return False  # every draft's slot died: plain decode is fine
        width = 1 + max(len(d) for d in plan.values())
        verify_fn = _decoder.make_verify_step(self.cfg, self.page_size,
                                              width,
                                              sharding=self.sharding,
                                              quant=self.quant,
                                              kv_dtype=self.kv_dtype)
        tokens = onp.zeros((self.slots, width), onp.int32)
        positions = onp.zeros(self.slots, onp.int32)
        n_valid = onp.zeros(self.slots, onp.int32)
        active = onp.zeros(self.slots, bool)
        fed = {}
        for s in live:
            row = [s.pending] + plan.get(s.idx, [])
            fed[s.idx] = row
            tokens[s.idx, :len(row)] = row
            positions[s.idx] = s.pos
            n_valid[s.idx] = len(row)
            active[s.idx] = True
        t0 = time.perf_counter()
        self._kp, self._vp, out = self._dispatch(
            verify_fn, self.params, self._kp, self._vp,
            self._upload(tokens), self._upload(positions),
            self._upload(n_valid), self._upload(self._tables),
            self._upload(active))
        out = self._device_wait("sync", onp.asarray, out)
        now = time.perf_counter()
        self.metrics.observe_verify(self.name, now - t0)
        self.metrics.count(self.name, "spec_verify_steps_total")
        emitted_total = 0
        for s in live:
            row = fed[s.idx]
            nv = len(row)
            pos0 = s.pos
            preds = [int(t) for t in out[s.idx, :nv]]
            # longest-prefix greedy acceptance: draft token i survives
            # iff it equals the target's own argmax after consuming
            # everything before it — the emitted stream is exactly what
            # plain decode would have produced, token for token
            accepted = 0
            while accepted < nv - 1 and row[accepted + 1] == preds[accepted]:
                accepted += 1
            emitted = preds[:accepted + 1]
            budget = (s.req.max_new - len(s.req.prefix)
                      - len(s.generated))
            emitted = emitted[:max(1, budget)]
            if self.eos_id is not None and self.eos_id in emitted:
                emitted = emitted[:emitted.index(self.eos_id) + 1]
            gap = (now - s.t_last) / len(emitted)
            for tok in emitted:
                s.history.append(s.pending)
                s.pos += 1
                s.generated.append(tok)
                s.pending = tok
                self.metrics.observe_inter_token(self.name, gap)
            s.t_last = now
            emitted_total += len(emitted)
            drafted = nv - 1
            if drafted:
                key = self._spec_key(s)
                spec.observe(key, drafted, accepted)
                self.metrics.count(self.name, "spec_draft_tokens_total",
                                   drafted)
                self.metrics.count(self.name,
                                   "spec_accepted_tokens_total", accepted)
            self._rollback_kv(s, pos0 + nv)
            self._maybe_finish(s, now)
        self.metrics.observe_decode_step(self.name, now - t0, self.slots,
                                         emitted_total)
        return True

    def _rollback_kv(self, slot, written_end):
        """Return the slot's page list to exactly what its confirmed
        length needs after a verify wrote ``written_end`` positions.

        Rejected positions leave garbage KV at offsets the causal mask
        never reads (attention only sees key positions ``<= query``),
        so rollback is pure accounting: whole pages past the confirmed
        length are freed through :meth:`PageAllocator.trim`.  If the
        kept boundary page is SHARED (a published prefix page, refcount
        > 1) and this verify dirtied positions past the confirmed
        length, it is forked copy-on-write first so the truncation
        never mutates a page another sequence (or the prefix cache)
        still references."""
        keep = pages_for(slot.pos, self.page_size)
        pages = self.alloc.pages(slot.owner)
        if written_end > slot.pos and keep > 0 and keep <= len(pages) \
                and slot.pos % self.page_size != 0 \
                and self.alloc.refcount(pages[keep - 1]) > 1:
            old, new = pages[keep - 1], None
            for _ in range(2):
                try:
                    new = self.alloc.fork(slot.owner, old)
                    break
                except CacheOOM:
                    if not self._evict_lru_session(
                            keep=slot.req.session if slot.req else None):
                        break
            if new is not None:
                self._fork_page(old, new)
                self.metrics.count(self.name, "cow_forks_total")
            # (an unforkable pool is safe anyway: the dirty offsets sit
            # past every sharer's published token count, which readers
            # never touch — forking just keeps the invariant airtight)
        if self.alloc.trim(slot.owner, keep):
            self.metrics.count(self.name, "spec_rollbacks_total")
        self._sync_table(slot)

    # -- completion -------------------------------------------------------
    def _maybe_finish(self, slot, now):
        req = slot.req
        if self.eos_id is not None and slot.pending == self.eos_id:
            self._finish(slot, "eos")
        elif len(slot.generated) + len(req.prefix) >= req.max_new:
            self._finish(slot, "length")
        elif req.expired(now):
            self._finish(slot, "deadline")

    def _finish(self, slot, reason):
        req = slot.req
        with span("request.finish", rid=req.rid, reason=reason):
            self._finish_request(slot, req, reason)

    def _finish_request(self, slot, req, reason):
        tokens = req.prefix + slot.generated
        now = time.perf_counter()
        req.mark(_PREFILL if slot.state == "prefill" else _DECODE, now)
        if req.session is not None:
            if self.role == "prefill" and self._handoff(slot, req):
                pass  # pages shipped to the store for a decode replica
            else:
                sess = self._sessions.get(req.session)
                if sess is None:
                    sess = self._sessions[req.session] = _Session(
                        req.session, slot.owner)
                sess.owner = slot.owner
                sess.pos = slot.pos
                sess.pending = slot.pending
                sess.history = list(slot.history)
                sess.busy = False
                sess.last_used = time.monotonic()
                # durability point: the transcript reaches the store
                # before the future resolves, so any turn the client has
                # seen acked is recoverable on a survivor — even after
                # SIGKILL of this replica
                self._push_transcript(sess)
        else:
            self._free_owner(slot.owner)
            self._spec_release(slot.owner, slot.owner)
        self.metrics.count(self.name, "sequences_completed_total")
        total = now - req.t_enqueue
        self.metrics.observe_generate_done(self.name, total, *req.phases)
        self.slo.observe_served(1)  # feeds the drain-rate estimator
        self._clear(slot)
        req.future.set_result({
            "tokens": tokens,
            "finish_reason": reason,
            "session": req.session,
            "prompt_tokens": req.prompt_tokens,
            "completion_tokens": len(tokens),
            "timing_ms": {k: round(v * 1e3, 3) for k, v in zip(
                ("queue_wait", "prefill", "decode", "total"),
                req.phases + [total])},
        })
        with self._cond:
            self._cond.notify_all()

    def _fail_slot(self, slot, exc):
        req = slot.req
        self._free_owner(slot.owner)
        self._spec_release(slot.owner, self._spec_key(slot))
        if req.session is not None:
            self._sessions.pop(req.session, None)
        self.metrics.count(self.name, "errors_total")
        self._clear(slot)
        req.future.set_exception(exc)

    def _clear(self, slot):
        slot.req = None
        slot.state = "idle"
        slot.owner = None
        slot.generated = []
        slot.history = []
        slot.pending = None
        slot.flight = 0
        slot.predraft = None
        self._tables[slot.idx, :] = 0

    # -- lifecycle / stats ------------------------------------------------
    def warmup(self):
        """Compile the prefill + decode programs now (dummy inputs
        against the scratch page) so the first client request never pays
        XLA compile; with the persistent compile cache on
        (``runtime.enable_compile_cache``) these become cache reads on
        replica restart, like the registry's bucket warmup."""
        import jax
        zrow = onp.zeros(self.pages_per_seq, onp.int32)
        self._kp, self._vp, tok, _ = self._prefill_fn(
            self.params, self._kp, self._vp,
            jnp.asarray(_decoder.prefill_buffer(
                onp.zeros(self.prefill_chunk, onp.int32), 0, 1, zrow)))
        idle = onp.zeros(self.slots, onp.int32)
        self._kp, self._vp, toks, _ = self._decode_fn(
            self.params, self._kp, self._vp, self._no_chain,
            jnp.asarray(_decoder.decode_buffer(
                idle, idle, onp.zeros_like(self._tables), idle)))
        jax.block_until_ready(toks)
        compiled = 2
        if self._spec is not None:
            # pre-compile every verify width the adaptive-k controller
            # can reach (2 .. k_cap + 1) so acceptance swings never pay
            # a mid-stream XLA compile
            for w in range(2, self._spec.k_cap + 2):
                vf = _decoder.make_verify_step(self.cfg, self.page_size,
                                               w, sharding=self.sharding,
                                               quant=self.quant,
                                               kv_dtype=self.kv_dtype)
                self._kp, self._vp, out = vf(
                    self.params, self._kp, self._vp,
                    jnp.zeros((self.slots, w), jnp.int32),
                    jnp.zeros(self.slots, jnp.int32),
                    jnp.zeros(self.slots, jnp.int32),
                    jnp.zeros((self.slots, self.pages_per_seq),
                              jnp.int32),
                    jnp.zeros(self.slots, bool))
                jax.block_until_ready(out)
                compiled += 1
        return compiled

    def drain(self, timeout=30.0):
        return self.stop(drain=True, timeout=timeout)

    def stop(self, drain=True, timeout=30.0):
        """Stop admissions; ``drain=True`` serves everything queued and
        in flight first.  Parked sessions are released either way (their
        pages return to the pool — occupancy ends at zero)."""
        with self._cond:
            self._stopping = True
            self._drain_mode = bool(drain)
            if not drain:
                for r in self._queue:
                    r.future.set_exception(ServerClosedError(
                        "decode engine stopped before this request ran"))
                self._queue.clear()
                for s in self._slots:
                    if s.active:
                        s.req.future.set_exception(ServerClosedError(
                            "decode engine stopped mid-generation"))
                        self._free_owner(s.owner)
                        self._spec_release(s.owner, self._spec_key(s))
                        self._clear(s)
            self._cond.notify_all()
            worker = self._worker
        ok = True
        if worker is not None:
            worker.join(timeout)
            ok = not worker.is_alive()
        if ok:
            # worker is gone, so migrate_out runs inline: every parked
            # session ships to the fleet page store (no-op when no store
            # is configured) — a clean stop loses nothing
            try:
                self.migrate_out()
            except Exception:  # pragma: no cover - best-effort
                _log.exception("migrate_out on stop failed")
        with self._cond:
            for sess in self._sessions.values():
                self._free_owner(sess.owner)
                self._spec_release(sess.owner, sess.sid)
            self._sessions.clear()
        if self.prefix_cache is not None:
            self.prefix_cache.clear()
        if self._store_client:
            self._store_client.close()
        return ok

    def _expert_stats(self):
        """What the routed layers counted (``models.routed.COUNTS``), by
        the program that counted it, since the engine's start.  The
        programs sum into a uint32 leaf of their pools (the prefill chunk
        into the K pool's, the decode step into the V pool's); this reads
        both, folds what is new into host integers (exact while fewer than
        2**32 pairs pass between two reads) and into the metrics'
        counters, and never runs in a step's path.  A read that meets a
        pool the next launch has just taken keeps the totals of the last
        one."""
        held = self.cfg.experts_held[1]
        out, names = {}, _routed.COUNTS
        for phase, pool in (("prefill", self._kp), ("decode", self._vp)):
            kept = self._expert_counts[phase]
            try:
                now = onp.asarray(pool.counts).astype(onp.uint32)
            except RuntimeError:        # donated to a launch meanwhile
                now = kept["seen"]
            with self._cond:
                new = [int(d) for d in now - kept["seen"]]  # modulo 2**32
                kept["seen"] = now
                kept["total"] = [a + b for a, b in zip(kept["total"], new)]
                total = dict(zip(names, kept["total"]))
            for counter, n in zip(_EXPERT_COUNTERS, new):
                if n:
                    self.metrics.count(self.name, counter, n)
            n = total["layer_launches"]
            out[phase] = dict(
                total, **{k + "_per_launch": total[k] / n if n else None
                          for k in names[:4]},
                load_max_over_mean=(held * total["pairs_fullest"]
                                    / total["pairs"]
                                    if total["pairs"] else None))
        both = {k: out["prefill"][k] + out["decode"][k] for k in names}
        out.update(both, held=held, of=self.cfg.n_experts,
                   per_token=self.cfg.experts_per_token,
                   load_max_over_mean=(held * both["pairs_fullest"]
                                       / both["pairs"]
                                       if both["pairs"] else None))
        return out

    def _tokens_resident(self):
        """Logical tokens currently cached in pool pages: live slots'
        positions plus parked sessions' (replay-pending sessions hold a
        transcript, not pages)."""
        with self._cond:
            toks = sum(s.pos for s in self._slots if s.active)
            toks += sum(s.pos for s in self._sessions.values()
                        if not s.busy and s.replay is None)
        return toks

    def stats(self):
        with self._cond:
            active = sum(1 for s in self._slots if s.active)
            queued = len(self._queue)
            sessions = len(self._sessions)
        out = {"slots": self.slots, "active": active, "queued": queued,
               "sessions": sessions, "steps": self.steps,
               "page_size": self.page_size,
               "pages_per_seq": self.pages_per_seq,
               "prefill_chunk": self.prefill_chunk,
               "max_ctx": self.max_ctx,
               "role": self.role,
               "slo": {"service_rate": self.slo.service_rate(),
                       "default_tier": self.slo.default_tier},
               "async": {"enabled": self.async_decode,
                         "dispatch_ahead": self.dispatch_ahead,
                         "inflight": len(self._pipe)},
               "kv": self.alloc.stats(),
               "quant": {
                   "weights": self.quant[0] if self.quant else None,
                   "group": (self.quant[1] if self.quant
                             and len(self.quant) > 1 else None),
                   "kv_dtype": self.kv_dtype,
                   "tokens_resident": self._tokens_resident(),
               },
               "migration": {"enabled": self._migration_active(),
                             "pagestore": self._pagestore_addr or None},
               "launches": dict(self.launch_stats),
               "fn_cache": _decoder.fn_cache_stats()}
        if self.sharding is not None:
            out["sharding"] = {"mesh": self.sharding.describe(),
                               "tp": self.tp}
            if self.collective_stats is not None:
                out["sharding"]["collectives"] = dict(
                    self.collective_stats.get("collectives", {}))
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        if self._spec is not None:
            out["speculative"] = self._spec.stats()
        if self._pairs_per_token:
            out["experts"] = self._expert_stats()
        if self.latent_row_bytes:
            out["latent"] = {
                "row_bytes": self.latent_row_bytes,
                "bytes_decode_total": self._latent_bytes["decode"],
                "bytes_prefill_total": self._latent_bytes["prefill"]}
        if self.state_entry_bytes:
            # the recurrent state paged beside the KV rows: one entry a
            # page (scratch page included) and recurrent layer
            out["state"] = {
                "layers": _hybrid.recurrent_layers(self.cfg),
                "entry_bytes": self.state_entry_bytes,
                "pool_bytes": (self.state_entry_bytes
                               * self.alloc.total_pages),
                "pages_with_state_peak": out["kv"]["peak_used_pages"],
                "bytes_decode_total": self._state_bytes["decode"],
                "bytes_prefill_total": self._state_bytes["prefill"]}
            if _hybrid.RETENTION in self.cfg.layer_kinds:
                # which form the decode step's state update took
                out["state"]["decode_path"] = _hybrid.retention_decode_path()
        return out
