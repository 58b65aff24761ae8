"""Speculative decoding: draft/verify with exact greedy acceptance.

The inter-token-latency half of ROADMAP item 3: a cheap *drafter*
proposes up to ``k`` continuation tokens per decode slot, the target
model scores all ``k + 1`` positions in ONE wide verify launch
(``models.decoder.make_verify_step`` — a prefill-chunk-shaped program,
cached per (k, geometry) in the shared ``_FnCache``), and
longest-prefix acceptance keeps whatever matches the target's own
greedy choices.  Every accepted draft token plus the verify's final
argmax is emitted in a single engine step, so a step can produce
``accepted + 1`` tokens for the launch cost of one — while the emitted
stream stays BIT-IDENTICAL to non-speculative decode (Leviathan et al.
2023: with greedy sampling, exact acceptance *is* prefix matching; the
parity matrix in tests/test_speculative.py is the acceptance oracle).

Rejected positions leave garbage KV in the slot's pages; the engine
rolls them back through ``PageAllocator.trim`` (CoW-aware — see
``DecodeEngine._rollback_kv``) so cache accounting stays exact and
``check_leaks()`` stays clean under arbitrary rejection streams.

Drafter ladder (cheapest first):

- :class:`NGramDrafter` — prompt-lookup decoding (Saxena 2023): match
  the transcript's trailing n-gram against its own earlier occurrences
  and propose the tokens that followed.  Model-free, zero extra
  weights, zero extra launches; shines on repetitive streams (code,
  templated output, multi-turn chat quoting its own context — the
  parked-session transcript feeds it across turns).
- :class:`DraftModelDrafter` — a reduced-depth/width ``CausalLM``
  sharing the target's tokenizer, decoding ``k`` tokens ahead against
  its OWN small paged KV cache.  Pays draft-model launches per step but
  proposes on any stream; the win shows where target launches dominate
  draft launches (real accelerators; the CPU lane keeps it correct).

:class:`SpeculativeScheduler` closes the loop per sequence with an
:class:`AdaptiveK` controller: an EMA of the accepted-token rate opens
``k`` toward the ``MXNET_GEN_SPEC_K`` cap while drafts land and walks
it down to 0 (speculation off for that sequence) when acceptance
collapses — a hostile stream degrades to plain decode, never below it.

Fault sites (``mxnet_tpu.faults``): ``speculate.draft`` trips inside
the propose path and poisons only that sequence's controller;
``speculate.verify`` trips before the wide launch and degrades the
whole step to plain decode.  Both leave the engine serving — see
``tools/chaos.py --scenario llm`` with ``MXNET_GEN_SPECULATE=1``.
"""
from __future__ import annotations

import collections
import logging

import numpy as onp

import jax.numpy as jnp

from .. import config as _config
from .. import faults
from ..models import decoder as _decoder
from .kvcache import CacheOOM, PageAllocator, pages_for

__all__ = ["Drafter", "NGramDrafter", "DraftModelDrafter", "AdaptiveK",
           "SpeculativeScheduler"]

_log = logging.getLogger(__name__)


class Drafter:
    """Propose up to ``k`` continuation tokens for one sequence.

    ``context`` is the sequence's full transcript — prompt + generated
    history + the pending last token the target has not yet consumed —
    so a drafter sees exactly what the target will extend.  Returning
    fewer than ``k`` tokens (or none) simply shrinks this step's
    speculation; it is never an error."""

    name = "null"

    def propose(self, owner, context, k):
        return []

    def release(self, owner):
        """Drop any per-sequence state (sequence finished, failed, or
        was preempted — its cache-position bookkeeping is stale)."""

    def stats(self):
        return {}


class NGramDrafter(Drafter):
    """Prompt-lookup decoding: the transcript's trailing n-gram is
    matched against its own earlier occurrences (longest n first, most
    recent match wins) and the tokens that followed become the draft.
    Model-free and launch-free — candidate quality comes entirely from
    the repetitiveness of the stream."""

    name = "ngram"

    def __init__(self, max_ngram=None, min_ngram=1):
        self.max_ngram = int(max_ngram if max_ngram is not None
                             else _config.get("MXNET_GEN_SPEC_NGRAM"))
        self.max_ngram = max(1, self.max_ngram)
        self.min_ngram = max(1, int(min_ngram))
        self.proposals = 0
        self.misses = 0

    def propose(self, owner, context, k):
        n_ctx = len(context)
        k = int(k)
        for n in range(min(self.max_ngram, n_ctx - 1),
                       self.min_ngram - 1, -1):
            pat = list(context[-n:])
            best = None
            for j in range(n_ctx - n - 1, -1, -1):
                if list(context[j:j + n]) == pat:
                    out = list(context[j + n:j + n + k])
                    if len(out) >= k:
                        best = out  # most recent FULL-depth continuation
                        break
                    # a match too close to the suffix truncates its
                    # continuation; keep scanning — on cyclic content an
                    # earlier occurrence carries the full k tokens
                    if out and (best is None or len(out) > len(best)):
                        best = out
            if best:
                self.proposals += 1
                return best
        self.misses += 1
        return []

    def stats(self):
        return {"proposals": self.proposals, "misses": self.misses}


class DraftModelDrafter(Drafter):
    """A small ``CausalLM`` drafter with its own paged KV cache.

    The draft cache tracks each sequence's CONFIRMED transcript only:
    each ``propose`` first catches the cache up to ``context[:-1]``
    (chunked prefill of whatever the target accepted since last step),
    then runs ``k`` greedy single-token decode steps, then trims its
    own speculative writes back (``PageAllocator.trim`` again — the
    rollback primitive is shared).  Draft pool pressure evicts peer
    sequences' draft caches (they re-prefill cheaply — the model is
    small); an unplaceable draft just proposes nothing."""

    name = "model"

    def __init__(self, model, page_size=8, total_pages=None,
                 prefill_chunk=16, max_seqs=8):
        self.model = model
        self.cfg = model.config
        self.params = model.jax_params()
        self.page_size = int(page_size)
        self.prefill_chunk = int(prefill_chunk)
        self.max_ctx = self.cfg.max_length
        self.pages_per_seq = pages_for(self.max_ctx, self.page_size)
        total = int(total_pages or 0)
        if not total:
            total = int(max_seqs) * self.pages_per_seq + 1
        self.alloc = PageAllocator(total, self.page_size)
        self._kp, self._vp = (
            _decoder.fresh_pool(self.cfg, total, self.page_size)
            for _ in range(2))
        self._pos = {}   # owner -> confirmed tokens in the draft cache
        self._decode_fn = _decoder.make_decode_step(self.cfg,
                                                    self.page_size)
        self._prefill_fn = _decoder.make_prefill_chunk(
            self.cfg, self.page_size, self.prefill_chunk)
        self._no_chain = jnp.zeros(1, jnp.int32)

    def _row(self, owner):
        row = onp.zeros(self.pages_per_seq, onp.int32)
        pages = self.alloc.pages(owner)
        row[:len(pages)] = pages
        return row

    def _ensure(self, owner, tokens_total):
        """Grow the owner's draft pages to hold ``tokens_total``
        positions, evicting peer draft caches under pressure.  Returns
        False when even a drained pool cannot fit it."""
        while True:
            need = (pages_for(tokens_total, self.page_size)
                    - len(self.alloc.pages(owner)))
            if need <= 0:
                return True
            try:
                self.alloc.alloc(owner, need)
                return True
            except CacheOOM:
                victims = [o for o in self.alloc.owners() if o != owner]
                if not victims:
                    return False
                self.release(victims[0])

    def propose(self, owner, context, k):
        want = len(context) - 1     # cache everything but the pending token
        if want < 0:
            return []
        st = self._pos.get(owner, 0)
        if st > want:
            # the target rolled this sequence back (preempt/replay):
            # the draft cache is ahead of reality — rebuild from scratch
            self.release(owner)
            st = 0
        # draft lookahead writes land at want .. want+k-1
        k = min(int(k), self.max_ctx - want)
        if k <= 0 or not self._ensure(owner, want + k):
            return []
        while st < want:            # catch up the confirmed transcript
            n = min(self.prefill_chunk, want - st)
            padded = onp.zeros(self.prefill_chunk, onp.int32)
            padded[:n] = context[st:st + n]
            self._kp, self._vp, _, _ = self._prefill_fn(
                self.params, self._kp, self._vp,
                jnp.asarray(_decoder.prefill_buffer(padded, st, n,
                                                    self._row(owner))))
            st += n
        self._pos[owner] = want
        toks = []
        last = int(context[-1])
        pos = want
        row = self._row(owner)[None]
        for _ in range(k):          # greedy k-step lookahead, B=1
            self._kp, self._vp, nxt, _ = self._decode_fn(
                self.params, self._kp, self._vp, self._no_chain,
                jnp.asarray(_decoder.decode_buffer([last], [pos], row,
                                                   [True])))
            last = int(nxt[0])
            toks.append(last)
            pos += 1
        # the lookahead writes are speculative: trim back so only
        # confirmed tokens stay accounted (the next catch-up prefill
        # overwrites any rolled-back offsets before they are read)
        self.alloc.trim(owner, pages_for(want, self.page_size))
        return toks

    def release(self, owner):
        self.alloc.free(owner)
        self._pos.pop(owner, None)

    def stats(self):
        return {"sequences": len(self._pos), "kv": self.alloc.stats()}


class AdaptiveK:
    """Per-sequence speculation-depth controller.

    An EMA of the accepted-token rate (accepted / drafted per verify)
    steers ``k``: above ``hi`` it opens one step toward the cap, below
    ``lo`` it closes one step — and a sequence whose acceptance drives
    ``k`` to zero latches *disabled* (plain decode from then on; the
    fault sites poison the same latch).  Starting at ``k = 1`` makes
    a hostile stream pay at most one wasted draft before collapsing,
    while a cooperative one opens to the cap within a few steps."""

    __slots__ = ("cap", "k", "ema", "alpha", "lo", "hi", "disabled")

    def __init__(self, cap, alpha=0.4, lo=0.25, hi=0.6):
        self.cap = max(0, int(cap))
        self.k = min(1, self.cap)
        self.ema = None
        self.alpha = float(alpha)
        self.lo = float(lo)
        self.hi = float(hi)
        self.disabled = self.cap == 0

    def current(self):
        return 0 if self.disabled else self.k

    def update(self, drafted, accepted):
        if drafted <= 0:
            return
        rate = accepted / float(drafted)
        self.ema = rate if self.ema is None else (
            self.alpha * rate + (1.0 - self.alpha) * self.ema)
        if self.ema < self.lo:
            self.k -= 1
            if self.k <= 0:
                self.k = 0
                self.disabled = True
        elif self.ema > self.hi and not self.disabled:
            self.k = min(self.k + 1, self.cap)

    def poison(self):
        self.k = 0
        self.disabled = True


class SpeculativeScheduler:
    """The DecodeEngine's per-step speculation policy.

    Owns the drafter and one :class:`AdaptiveK` controller per sequence
    key (the session id for session requests — so acceptance learned in
    turn N carries to turn N+1 — else the slot's owner).  The engine
    asks :meth:`budget` for each decode slot's depth, drafts through
    :meth:`propose`, gates the wide launch on :meth:`verify_gate`, and
    reports acceptance back through :meth:`observe`.  Fault trips
    degrade to plain decode by poisoning controllers; the engine never
    stops serving on a speculation failure."""

    #: bound on retained per-sequence controllers (LRU evicted)
    MAX_CONTROLLERS = 4096

    def __init__(self, drafter, k_cap=None, name="llm"):
        self.drafter = drafter
        cap = int(k_cap if k_cap is not None
                  else _config.get("MXNET_GEN_SPEC_K"))
        self.k_cap = max(0, cap)
        self.name = name
        self._ctl = collections.OrderedDict()
        self.counters = {"proposals": 0, "empty_drafts": 0,
                         "draft_faults": 0, "verify_faults": 0,
                         "predraft_hits": 0, "predraft_misses": 0}

    def _controller(self, key):
        c = self._ctl.get(key)
        if c is None:
            c = self._ctl[key] = AdaptiveK(self.k_cap)
            while len(self._ctl) > self.MAX_CONTROLLERS:
                self._ctl.popitem(last=False)
        else:
            self._ctl.move_to_end(key)
        return c

    def budget(self, key, max_k):
        """Speculation depth for this sequence this step (0 = plain)."""
        return max(0, min(self._controller(key).current(), int(max_k)))

    def propose(self, key, owner, context, k):
        """Draft up to ``k`` tokens.  A ``speculate.draft`` fault (or a
        drafter bug) poisons only this sequence's controller and
        proposes nothing — the slot decodes plainly from then on."""
        try:
            faults.check("speculate.draft")
            out = list(self.drafter.propose(owner, context, k))[:int(k)]
        except Exception as e:
            self.counters["draft_faults"] += 1
            self._controller(key).poison()
            _log.warning("drafter fault for %r: %r (sequence degraded "
                         "to plain decode)", key, e)
            return []
        if out:
            self.counters["proposals"] += 1
        else:
            self.counters["empty_drafts"] += 1
        return out

    def verify_gate(self, keys):
        """``speculate.verify`` fault site, checked before the wide
        launch: a trip poisons every planned sequence's controller and
        returns False — the engine runs this step as plain decode."""
        try:
            faults.check("speculate.verify")
            return True
        except Exception as e:
            self.counters["verify_faults"] += 1
            for key in keys:
                self._controller(key).poison()
            _log.warning("verify fault: %r (step degraded to plain "
                         "decode)", e)
            return False

    def reuse_predraft(self, pre, emitted, k):
        """Overlapped drafting (async engine): ``pre`` was proposed from
        the LAUNCH-time context — before the verify it overlapped with
        had emitted anything — with extra lookahead.  If its head
        predicted this step's emissions exactly, the tail is a valid
        draft for the post-emission context and the next verify launches
        without a fresh host drafting pass.  Any draft is correctness-
        safe under longest-prefix greedy acceptance, so a miss only
        costs the overlap (the engine re-drafts synchronously).

        Returns the reusable tail (possibly empty) on a hit, or None."""
        if pre is None or k <= 0:
            return None
        m = len(emitted)
        tail = [int(t) for t in pre[m:m + int(k)]]
        if len(pre) > m and tail \
                and list(pre[:m]) == [int(t) for t in emitted]:
            self.counters["predraft_hits"] += 1
            return tail
        self.counters["predraft_misses"] += 1
        return None

    def observe(self, key, drafted, accepted):
        self._controller(key).update(drafted, accepted)

    def release(self, owner, key=None):
        """Drop per-sequence drafter state (and, for sessionless
        sequences, the controller — a session keeps its learned k
        across turns until the session itself dies)."""
        self.drafter.release(owner)
        if key is not None:
            self._ctl.pop(key, None)

    def stats(self):
        out = {"drafter": self.drafter.name, "k_cap": self.k_cap,
               "controllers": len(self._ctl),
               "counters": dict(self.counters)}
        d = self.drafter.stats()
        if d:
            out["drafter_stats"] = d
        return out
