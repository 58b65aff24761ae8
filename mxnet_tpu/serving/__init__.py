"""mxnet_tpu.serving — dynamic-batching inference service.

The inference half of the north star: turns hybridized ``HybridBlock``s
and exported symbol checkpoints into a served endpoint with request
batching, admission control, and latency telemetry.

Layers (each usable on its own):

- ``ModelRegistry`` (``registry.py``) — load/version/hot-swap models;
  per-batch-bucket XLA precompile at load time.
- ``DynamicBatcher`` (``batcher.py``) — per-model queues, size-or-timeout
  flush, shape-bucketed coalescing, futures fan-out, load shedding,
  deadlines, graceful drain, poisoned-request isolation.
- ``ServingMetrics`` (``metrics.py``) — per-model counters + p50/p95/p99
  histograms (queue wait vs device time, batch occupancy), exported
  through ``mxnet_tpu.profiler`` and as a scrapeable snapshot.
- ``ModelServer`` / ``ServingClient`` (``server.py`` / ``client.py``) —
  thin HTTP frontend + stdlib client.

Fleet tier (replicated, self-healing serving — see README "Serving
fleet"):

- ``Router`` / ``RouterServer`` (``router.py``) — least-loaded or
  consistent-hash dispatch over N replicas, /healthz-/readyz-driven
  health, strike/eject/re-admit failure detection, failover retries,
  backpressure propagation (router-level shed with Retry-After).
- ``ReplicaSupervisor`` (``supervisor.py``) — launch/monitor/restart
  replica processes with restart budgets and crash-loop backoff.
- ``ServingFleet`` / ``rollout`` (``fleet.py``) — the two composed,
  plus zero-downtime rolling model rollout with canary abort/rollback.
- the replica entry point turns on JAX's persistent compile cache
  (``runtime.enable_compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` or
  the in-checkout ``.jax_cache``) so replica restarts and rollouts
  re-serve in seconds instead of compile-minutes.

LLM tier (continuous-batching decode serving — see README "LLM
serving"):

- ``DecodeEngine`` (``generate.py``) — iteration-level (continuous)
  batching: the decode batch re-forms every step, with chunked prefill,
  decode sessions, and preemption-by-recompute under cache pressure.
- ``PageAllocator`` (``kvcache.py``) — the paged KV cache's free-list
  allocator and occupancy accounting; the device-side paged attention
  lives in ``ops/pallas/paged_attention.py`` (Pallas kernel on TPU, XLA
  gather reference on CPU).
- ``/v1/models/<name>:generate`` + ``ServingClient.generate`` — the
  HTTP surface; with the fleet router, a generation ``session`` rides
  the consistent-hash ``affinity_key`` back to the replica holding its
  KV pages (``SessionResetError`` when that replica is gone).

Session-migration tier (sessions outlive their replica — see README
"Session migration & prefix caching"):

- ``PrefixCache`` / ``PageAllocator`` refcounts (``kvcache.py``) —
  content-addressed shared prompt-prefix pages, forked copy-on-write at
  the first divergent write; ``pack_session``/``unpack_session`` are
  the CRC-guarded bit-exact session wire format.
- ``PageStoreServer``/``PageStoreClient`` (``kvstore/pagestore.py``) —
  the generation-fenced rendezvous a dying replica pushes sessions to
  and a survivor pulls them from; ``ServingFleet`` boots one and
  ``rollout`` migrates parked sessions instead of resetting them.
- Role specialization — ``roles=["prefill", "decode", ...]`` splits the
  fleet into a prefill pool (chunked long-prompt prefill, KV handoff
  through the store) and a decode pool; the router runs the two-phase
  disaggregated dispatch.
- ``ServingClient.generate(resume_on_reset=True)`` — transparent
  client-side transcript replay when every server-side copy is gone.

Quick start::

    import mxnet_tpu as mx
    reg = mx.serving.ModelRegistry()
    reg.load("resnet", net, item_shape=(3, 224, 224), max_batch_size=32)
    with mx.serving.ModelServer(reg, flush_ms=5) as srv:
        cli = mx.serving.ServingClient(*srv.address)
        preds = cli.predict("resnet", batch_np)
        print(cli.stats())
"""
from __future__ import annotations

from .errors import (BadRequestError, DeadlineExceededError,
                     DeadlineInfeasibleError, FleetUnavailableError,
                     KVLeakError, ModelNotFoundError, QueueFullError,
                     RolloutAbortedError, ServerClosedError,
                     ServingError, SessionResetError)
from .metrics import LatencyHistogram, ModelMetrics, ServingMetrics
from .autoscale import Autoscaler, SLOPolicy
from .registry import (ModelRegistry, ServedModel, default_buckets,
                       load_model_spec, resolve_builder)
from .batcher import DynamicBatcher
from .kvcache import (PageAllocator, PrefixCache, pack_session,
                      unpack_session)
from .generate import DecodeEngine
from .server import ModelServer
from .client import ServingClient
from .router import FleetMetrics, Replica, Router, RouterServer
from .supervisor import ReplicaProcess, ReplicaSupervisor
from .fleet import ServingFleet, rollout

__all__ = [
    "ServingError", "BadRequestError", "ModelNotFoundError",
    "QueueFullError", "ServerClosedError", "DeadlineExceededError",
    "SessionResetError", "FleetUnavailableError", "RolloutAbortedError",
    "KVLeakError", "DeadlineInfeasibleError",
    "Autoscaler", "SLOPolicy",
    "ServingMetrics", "ModelMetrics", "LatencyHistogram",
    "ModelRegistry", "ServedModel", "default_buckets",
    "load_model_spec", "resolve_builder",
    "DynamicBatcher", "PageAllocator", "PrefixCache", "pack_session",
    "unpack_session", "DecodeEngine",
    "ModelServer", "ServingClient",
    "FleetMetrics", "Replica", "Router", "RouterServer",
    "ReplicaProcess", "ReplicaSupervisor", "ServingFleet", "rollout",
]
