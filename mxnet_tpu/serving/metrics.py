"""Serving observability: per-model counters + latency histograms.

One always-on registry, ``ServingMetrics.snapshot()`` — a JSON-able dict
(the scrapeable stats endpoint, ``/v1/stats``; ``/metrics`` renders the same
numbers as Prometheus text): counters, p50/p95/p99 for queue-wait / device /
end-to-end latency, the batch-occupancy ratio (items served / bucket slots
dispatched — how full the padded XLA programs actually run), and for a
decode engine the phases of a request and of an engine step.  What happens
*when* is the trace's business, not this module's: the engine and the HTTP
handler write ``profiler.span`` events onto the device trace's clock.
"""
from __future__ import annotations

import threading
import time

from .. import config as _config

#: ring-buffer size per histogram — recent-window percentiles, O(1) memory
_RESERVOIR = 2048

PERCENTILES = (50, 95, 99)


class LatencyHistogram:
    """Bounded reservoir of the most recent ``_RESERVOIR`` samples.

    Serving percentiles are a moving window by design: a p99 over the
    process lifetime would bury a fresh latency regression under hours of
    old samples.  Not thread-safe on its own — the owning
    ``ServingMetrics`` lock serializes access."""

    __slots__ = ("count", "total", "_ring", "_idx")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self._ring = []
        self._idx = 0

    def observe(self, value_s):
        self.count += 1
        self.total += value_s
        if len(self._ring) < _RESERVOIR:
            self._ring.append(value_s)
        else:
            self._ring[self._idx] = value_s
            self._idx = (self._idx + 1) % _RESERVOIR

    def snapshot(self, scale=1e3, suffix="_ms"):
        """{count, mean_ms, p50_ms, p95_ms, p99_ms, max_ms} (ms floats).
        Dimensionless reservoirs (e.g. tokens-per-step) pass
        ``scale=1, suffix=""`` to report raw values."""
        if not self._ring:
            return {"count": 0}
        srt = sorted(self._ring)
        out = {"count": self.count,
               "mean%s" % suffix: round(self.total / self.count * scale,
                                        3),
               "max%s" % suffix: round(srt[-1] * scale, 3)}
        n = len(srt)
        for p in PERCENTILES:
            # nearest-rank percentile over the recent window
            k = min(n - 1, max(0, int(round(p / 100.0 * (n - 1)))))
            out["p%d%s" % (p, suffix)] = round(srt[k] * scale, 3)
        return out


class ModelMetrics:
    """One model's counters + histograms (guarded by the parent lock)."""

    COUNTERS = ("requests_total", "responses_total", "shed_total",
                "deadline_expired_total", "errors_total", "batches_total",
                "items_total", "bucket_slots_total",
                # SLO-aware admission (PR 18): bulk-tier requests evicted
                # to admit latency-tier ones, and requests shed because
                # they provably could not meet their deadline
                "bulk_evicted_total", "infeasible_shed_total",
                # generation (continuous-batching decode engine)
                "tokens_generated_total", "prefill_tokens_total",
                "sequences_total", "sequences_completed_total",
                "decode_steps_total", "decode_slot_steps_total",
                "preemptions_total", "sessions_reset_total",
                # prefix caching + session migration (PR 11)
                "prefix_hits_total", "prefix_tokens_saved_total",
                "cow_forks_total", "migrations_out_total",
                "migrations_in_total", "migrations_replayed_total",
                # speculative decoding (PR 12)
                "spec_draft_tokens_total", "spec_accepted_tokens_total",
                "spec_verify_steps_total", "spec_rollbacks_total",
                # async decode engine (PR 17): device-array reads that
                # happened at retire time, after the next launch was
                # already in flight
                "deferred_reads_total",
                # page-store refusals (PR 20): the engine kept the
                # session local instead of shipping it — degrade paths
                # are counted, never silent
                "store_rejected_total", "store_over_budget_total",
                # the scheduler's work (PR 25): engine steps taken and
                # prefill-chunk programs launched in them
                "engine_steps_total", "prefill_launches_total",
                # a model with state-space layers (PR 29): state entries
                # written by prefill launches (one a page touched),
                # sequences begun from the zero state, whole pages (with
                # their state entries) attached on a prefix hit
                "state_entries_written_total", "state_starts_total",
                "state_prefix_pages_shared_total",
                # bytes of state entries the staged decode and prefill
                # launches read and write (PR 37), known to the host
                "state_bytes_decode_total", "state_bytes_prefill_total",
                # bytes of latent rows the staged decode and prefill
                # launches' attention reads (latent attention layers)
                "latent_bytes_decode_total", "latent_bytes_prefill_total",
                # a model that routes (PR 35), from the programs' own
                # counts, folded in when the engine's stats are asked:
                # token-expert pairs on experts held here and on other
                # chips', and per layer and launch the held experts that
                # a token chose and the fullest one's pairs
                "expert_pairs_total", "expert_pairs_elsewhere_total",
                "experts_hit_total", "expert_pairs_fullest_total",
                # a full pool (PR 32): pages an allocation took back from
                # the prefix cache, and blocking retires of the whole
                # decode pipeline that found launches in flight, by
                # cause: a page a lane grew into with the pool held by
                # live sequences and sessions, a worker op
                "kv_reclaimed_pages_total", "pipe_flushes_total",
                "pipe_flushes_page_pressure_total", "pipe_flushes_ops_total",
                # what a decode step has to read (PR 36), counted where a
                # launch is staged: the pages under ``position + 1`` of
                # its active lanes, and the page table whole (slots x
                # pages a sequence may hold)
                "decode_pages_live_total", "decode_pages_table_total",
                # the step's programs (DecodeEngine._dispatch): launches,
                # and those that found the previous launch's output ready,
                # so the device had run dry; transfers of their inputs
                # (DecodeEngine._upload) and the bytes moved
                "launches_total", "launches_drained_total",
                "host_uploads_total", "host_upload_bytes_total")

    #: counters an engine step hands over at its end, not one by one
    STEP_COUNTERS = ("prefill_launches_total", "launches_total",
                     "launches_drained_total", "host_uploads_total",
                     "host_upload_bytes_total")

    #: parts of an engine step, host wall seconds summed over the window
    #: (DecodeEngine._step): ``ops`` worker ops + expiry, ``admit``,
    #: ``prefill_host`` the prefill phase less its blocking read,
    #: ``launch`` forming and dispatching the decode batch,
    #: ``device_wait`` every blocking read of a device result,
    #: ``retire_host`` a retired step's bookkeeping, ``account`` the
    #: gauges at the step's end.  They add up to ``step``.
    STEP_PARTS = ("ops", "admit", "prefill_host", "launch", "device_wait",
                  "retire_host", "account")
    #: host seconds inside the parts other than ``device_wait``: the jitted
    #: calls of the step's launches until they return, and the transfers
    #: of their inputs
    HOST_PARTS = ("dispatch", "upload")
    #: ``device_wait`` by what was read: a prompt's first token, the oldest
    #: launch of the decode pipeline, a launch retired by a flush of the
    #: whole pipeline, the synchronous engine's step result.  They add up
    #: to ``device_wait``.
    WAIT_PARTS = ("first_token", "retire", "flush", "sync")

    def __init__(self):
        self.counters = dict.fromkeys(self.COUNTERS, 0)
        self.queue_wait = LatencyHistogram()   # submit -> dispatch/admit
        self.device = LatencyHistogram()       # model execution per batch
        self.total = LatencyHistogram()        # submit -> response
        self.batch_size = LatencyHistogram()   # items per dispatched batch
        # the HTTP handler's own wall of a generate request: body read to
        # response written, less the engine's submit -> finish
        self.http_self = LatencyHistogram()
        # generation-path histograms (empty unless a DecodeEngine serves
        # this model): TTFT = submit -> first generated token; inter-token
        # = gap between consecutive tokens of one sequence; decode_step =
        # the host's clock between whole-batch decode steps retired (a
        # launch to its read where nothing else is in flight), not
        # device time
        self.ttft = LatencyHistogram()
        self.inter_token = LatencyHistogram()
        self.decode_step = LatencyHistogram()
        # a finished request on the engine's clock, beside queue_wait:
        # admission -> first token, first token -> finish (a preempted
        # request's repeated phases are summed, so the three add up to
        # its ``total``)
        self.request_prefill = LatencyHistogram()
        self.request_decode = LatencyHistogram()
        # the scheduler: host wall of one engine step, and the window's
        # seconds in each of its parts
        self.engine_step = LatencyHistogram()
        self.phase_s = dict.fromkeys(("step",) + self.STEP_PARTS, 0.0)
        self.host_s = dict.fromkeys(self.HOST_PARTS, 0.0)
        self.wait_s = dict.fromkeys(self.WAIT_PARTS, 0.0)
        # speculative decoding: tokens EMITTED per decode step (a wide
        # verify can land several — this is where >1 token/step shows),
        # plus the draft/verify latency split
        self.tokens_per_step = LatencyHistogram()
        self.draft_step = LatencyHistogram()
        self.verify_step = LatencyHistogram()
        self.kv_cache = {"used_pages": 0, "total_pages": 0,
                         "peak_used_pages": 0, "shared_pages": 0,
                         "leaked_pages": 0, "tokens_resident": 0,
                         "bytes_per_token": 0.0}
        self.tokens_per_s = 0.0  # EMA over decode steps
        # static gauges (set once per engine): the dispatch-count audit
        # of one decode step (fused_cell.count_launches — deterministic,
        # load-independent) and the bounded decode/prefill program cache
        self.decode_launches = None
        self.fn_cache = None
        # static cross-chip census (set once at engine attach when the
        # engine is tensor-parallel): mesh shape + per-step collective
        # counts — how the fleet router tells a TP replica from a dp one
        self.decode_collectives = None

    def snapshot(self):
        items = self.counters["items_total"]
        slots = self.counters["bucket_slots_total"]
        out = {
            "counters": dict(self.counters),
            "batch_occupancy": round(items / slots, 4) if slots else None,
            "queue_wait": self.queue_wait.snapshot(),
            "device": self.device.snapshot(),
            "total": self.total.snapshot(),
            "batch_size": self.batch_size.snapshot(),
            "http_self": self.http_self.snapshot(),
        }
        steps = self.counters["decode_steps_total"]
        if steps or self.counters["sequences_total"]:
            total = self.kv_cache["total_pages"]
            slot_steps = self.counters["decode_slot_steps_total"]
            out["generate"] = {
                "ttft": self.ttft.snapshot(),
                "inter_token": self.inter_token.snapshot(),
                "decode_step": self.decode_step.snapshot(),
                "request_prefill": self.request_prefill.snapshot(),
                "request_decode": self.request_decode.snapshot(),
                "engine_step": self.engine_step.snapshot(),
                # host_self: what the step would still take with an
                # infinitely fast device
                "phase_s": dict(
                    {k: round(v, 6) for k, v in self.phase_s.items()},
                    host_self=round(self.phase_s["step"]
                                    - self.phase_s["device_wait"], 6)),
                "host_s": {k: round(v, 6) for k, v in self.host_s.items()},
                "wait_s": {k: round(v, 6) for k, v in self.wait_s.items()},
                "tokens_per_s": round(self.tokens_per_s, 2),
                # fraction of dispatched decode-slot work that produced a
                # real token — the continuous-batching win over static
                "decode_occupancy": (round(
                    self.counters["tokens_generated_total"]
                    / slot_steps, 4) if slot_steps else None),
                "kv_occupancy": (round(
                    self.kv_cache["used_pages"] / total, 4)
                    if total else None),
                # logical tokens resident in cache pages, and the
                # physical cost per token (scales amortized) — the
                # int8-KV capacity story in two numbers
                "kv_tokens_resident": self.kv_cache["tokens_resident"],
                "kv_bytes_per_token": self.kv_cache["bytes_per_token"],
                "kv_cache": dict(self.kv_cache),
            }
            out["generate"]["tokens_per_step"] = (
                self.tokens_per_step.snapshot(scale=1, suffix=""))
            drafted = self.counters["spec_draft_tokens_total"]
            if drafted or self.counters["spec_verify_steps_total"]:
                out["generate"]["speculative"] = {
                    "draft_step": self.draft_step.snapshot(),
                    "verify_step": self.verify_step.snapshot(),
                    # the one-number health read: of every drafted
                    # token, how many did the target keep
                    "accepted_token_rate": (round(
                        self.counters["spec_accepted_tokens_total"]
                        / drafted, 4) if drafted else None),
                }
            if self.decode_launches is not None:
                out["generate"]["decode_launches"] = dict(
                    self.decode_launches)
            if self.fn_cache is not None:
                out["generate"]["fn_cache"] = dict(self.fn_cache)
        if self.decode_collectives is not None:
            # static census — surfaced from attach time on, before any
            # traffic lands (it never changes while the engine lives)
            out.setdefault("generate", {})["sharding"] = dict(
                self.decode_collectives)
        return out


class ServingMetrics:
    """Thread-safe per-model metrics registry.

    ``replica`` labels every snapshot (and the Prometheus export) with
    the serving replica that produced it — the fleet supervisor stamps
    ``MXNET_SERVING_REPLICA_ID`` into each replica process so the router
    can aggregate per-replica stats without guessing by port."""

    def __init__(self, replica=None):
        self.replica = (str(replica) if replica is not None
                        else (_config.get("MXNET_SERVING_REPLICA_ID")
                              or None))
        self._lock = threading.Lock()
        self._models = {}

    def _model(self, name):
        m = self._models.get(name)
        if m is None:
            m = self._models.setdefault(name, ModelMetrics())
        return m

    def count(self, name, counter, n=1):
        with self._lock:
            self._model(name).counters[counter] += n

    def observe_batch(self, name, batch, bucket, device_s):
        """One dispatched batch: ``batch`` real items padded up to
        ``bucket`` slots, executed in ``device_s`` seconds."""
        with self._lock:
            m = self._model(name)
            m.counters["batches_total"] += 1
            m.counters["items_total"] += batch
            m.counters["bucket_slots_total"] += bucket
            m.device.observe(device_s)
            m.batch_size.observe(float(batch))

    def observe_request(self, name, queue_wait_s, total_s):
        with self._lock:
            m = self._model(name)
            m.counters["responses_total"] += 1
            m.queue_wait.observe(queue_wait_s)
            m.total.observe(total_s)

    # -- generation (continuous-batching decode engine) -------------------
    def observe_generate_done(self, name, total_s, queue_wait_s,
                              prefill_s, decode_s):
        """One completed generation on the engine's clock: submit to
        finish, and its three phases (waiting for a slot, admission to
        first token, first token to finish), which add up to it."""
        with self._lock:
            m = self._model(name)
            m.counters["responses_total"] += 1
            m.total.observe(total_s)
            m.queue_wait.observe(queue_wait_s)
            m.request_prefill.observe(prefill_s)
            m.request_decode.observe(decode_s)

    def observe_http_self(self, name, self_s):
        """The HTTP handler's own share of one generate request."""
        with self._lock:
            self._model(name).http_self.observe(self_s)

    def observe_engine_step(self, name, wall_s, parts, host_s, wait_s,
                            counts):
        """One engine step: its host wall, the seconds of each of
        ``ModelMetrics.STEP_PARTS``, ``HOST_PARTS`` and ``WAIT_PARTS`` in
        it, and its ``STEP_COUNTERS``."""
        with self._lock:
            m = self._model(name)
            m.counters["engine_steps_total"] += 1
            for counter in m.STEP_COUNTERS:
                m.counters[counter] += counts[counter]
            m.engine_step.observe(wall_s)
            m.phase_s["step"] += wall_s
            for acc, new in ((m.phase_s, parts), (m.host_s, host_s),
                             (m.wait_s, wait_s)):
                for part in new:
                    acc[part] += new[part]

    def observe_ttft(self, name, ttft_s):
        with self._lock:
            self._model(name).ttft.observe(ttft_s)

    def observe_inter_token(self, name, gap_s):
        with self._lock:
            self._model(name).inter_token.observe(gap_s)

    def observe_decode_step(self, name, step_s, slots, new_tokens):
        """One whole-batch decode step of ``slots`` decode slots retired,
        ``step_s`` host seconds after the one before it (or after its
        launch), with ``new_tokens`` tokens."""
        with self._lock:
            m = self._model(name)
            m.counters["decode_steps_total"] += 1
            m.counters["decode_slot_steps_total"] += slots
            m.counters["tokens_generated_total"] += new_tokens
            m.decode_step.observe(step_s)
            m.tokens_per_step.observe(float(new_tokens))
            rate = new_tokens / max(step_s, 1e-9)
            m.tokens_per_s = (rate if m.tokens_per_s == 0.0
                              else 0.9 * m.tokens_per_s + 0.1 * rate)

    def observe_draft(self, name, draft_s):
        """Wall time of one slot's draft proposal (speculative path)."""
        with self._lock:
            self._model(name).draft_step.observe(draft_s)

    def observe_verify(self, name, verify_s):
        """Wall time of one whole-batch wide verify launch."""
        with self._lock:
            self._model(name).verify_step.observe(verify_s)

    def observe_decode_launches(self, name, stats):
        """Static launch census of the engine's decode step (see
        models.decoder.decode_launch_stats): launches and Pallas calls
        per step — the _bulk-flush-counter analog for the decode path;
        tests assert on it."""
        with self._lock:
            self._model(name).decode_launches = dict(stats)

    def observe_decode_collectives(self, name, stats):
        """Static per-step collective census of a tensor-parallel
        engine's decode program (models.decoder.decode_collective_stats):
        mesh shape, tp degree, {collective: count}.  Recorded once at
        engine attach — the census is a property of the compiled program,
        not of traffic."""
        with self._lock:
            self._model(name).decode_collectives = dict(stats)

    def observe_fn_cache(self, name, stats):
        """Decode/prefill program-cache gauges ({size, cap, compiles,
        evictions} from models.decoder.fn_cache_stats)."""
        with self._lock:
            self._model(name).fn_cache = dict(stats)

    def observe_kv_cache(self, name, used_pages, total_pages,
                         shared_pages=0, leaked_pages=0,
                         tokens_resident=None, bytes_per_token=None):
        with self._lock:
            kv = self._model(name).kv_cache
            kv["used_pages"] = int(used_pages)
            kv["total_pages"] = int(total_pages)
            kv["shared_pages"] = int(shared_pages)
            kv["leaked_pages"] = int(leaked_pages)
            kv["peak_used_pages"] = max(kv["peak_used_pages"],
                                        int(used_pages))
            if tokens_resident is not None:
                kv["tokens_resident"] = int(tokens_resident)
            if bytes_per_token is not None:
                kv["bytes_per_token"] = float(bytes_per_token)

    def snapshot(self):
        """Scrapeable stats: {model: {counters, batch_occupancy,
        queue_wait/device/total/batch_size histograms}}, labelled with
        the replica id when one is set."""
        with self._lock:
            snap = {"time": time.time(),
                    "models": {n: m.snapshot()
                               for n, m in self._models.items()}}
        if self.replica is not None:
            snap["replica"] = self.replica
        return snap

    def reset(self):
        with self._lock:
            self._models.clear()
