"""Replica process entrypoint: one supervised ModelServer.

``python -m mxnet_tpu.serving.replica --spec spec.json --port P --id r0``

boots one fleet replica: enable the persistent XLA compile cache
(``runtime.enable_compile_cache`` — a restarted replica's per-bucket
warmup becomes cache reads, so it re-serves in seconds instead of
compile-minutes), load every model in the spec (warm-before-publish),
start an admin-enabled ModelServer on the given port, and then sit in a
watchdog loop until SIGTERM (graceful: drain the batcher, then exit 0).

The spec file is JSON::

    {"models": [{"name": "m", "builder": "pkg.mod:make_model",
                 "kwargs": {...}, "item_shape": [16], "dtype": "float32",
                 "max_batch_size": 8, "buckets": [1, 4, 8]}, ...],
     "flush_ms": 5.0, "max_queue_depth": 256}

A model spec may instead carry ``"generate": {...}`` (DecodeEngine
kwargs: ``slots``, ``page_size``, ``prefill_chunk``, ``eos_id``, ...):
the builder's model is then served as an LLM decode engine on
``/v1/models/<name>:generate`` (e.g. builder
``mxnet_tpu.models.decoder:decoder_tiny_lm``).  The engine's
session-migration posture comes from the environment the supervisor
stamps per replica: ``MXNET_GEN_PAGESTORE`` (fleet page-store address;
set by ``ServingFleet.start``) and ``MXNET_GEN_ROLE``
(``prefill`` | ``decode`` | ``mixed`` — ``ServingFleet(roles=[...])``),
or explicitly via ``"generate": {"role": ..., "pagestore": ...}``.

A generate spec may also carry a ``"sharding"`` block, making the
replica a tensor-parallel engine: ``{"from_env": true}`` builds the
mesh from the supervisor-stamped ``MXNET_MESH_SHAPE``/``MXNET_MESH_AXES``
(``ServingFleet`` replica specs stamp these per replica), or the block
names it explicitly — ``{"mesh_shape": [1, 2],
"axis_names": ["dp", "tp"]}``.  Either way the Megatron
``for_transformer()`` rules apply (qkv/ffn1 column-parallel, proj/ffn2
row-parallel) and the KV pages shard along KV heads.

A generate spec may also carry a ``"quant"`` block (see
:func:`resolve_quant`) booting the replica quantized: ``{"weights":
"int8" | "int4", "group": 128, "kv": "int8"}`` — weight-only decode
GEMMs and/or int8 KV-cache pages.

Models are named by importable *builder path*, never shipped as code —
only callables already on this process's PYTHONPATH can load (the
restricted-unpickler stance, applied to serving).

Fault site ``replica.crash`` is checked from the watchdog loop
(``MXNET_FAULT_SPEC=replica.crash:kill@n=40`` etc.): the ``kill`` kind
hard-exits the process SIGKILL-style — no drain, no cleanup — which is
exactly the failure the supervisor + router are chaos-tested against.

The ``demo_*`` builders below are the deterministic toy models the
example, the chaos runner, and the test suite serve; ``demo_faulty``
exists so canary-abort drills have a model that fails on purpose.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time

import numpy as onp

__all__ = ["main", "demo_affine", "demo_dense", "demo_faulty",
           "resolve_sharding", "resolve_quant"]


# ---------------------------------------------------------------------------
# demo builders (chaos drills, examples, tests)
# ---------------------------------------------------------------------------
def demo_affine(scale=2.0, shift=0.0, slow_ms=0.0):
    """Pure-host affine model ``x*scale + shift``: deterministic, zero
    compile time (fast replica boot in chaos runs).  ``slow_ms`` sleeps
    per batch — a knob for queue-buildup/backpressure scenarios."""
    scale, shift, slow_s = float(scale), float(shift), float(slow_ms) / 1e3

    def fn(batch):
        if slow_s:
            time.sleep(slow_s)
        return onp.asarray(batch) * scale + shift
    return fn


def demo_dense(units=4, in_units=16, seed=0):
    """Small hybridized Dense net — the real XLA serving path (per-bucket
    precompile, compile-cache reads) at toy size."""
    import mxnet_tpu as mx
    from mxnet_tpu import np as mxnp
    from mxnet_tpu.gluon import nn
    mx.random.seed(int(seed))
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=int(in_units)), nn.Activation("relu"),
            nn.Dense(int(units)))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    net(mxnp.zeros((1, int(in_units))))  # finalize deferred shapes
    return net


def demo_faulty(p=1.0, scale=2.0, seed=0):
    """A model that fails on purpose with probability ``p`` per batch
    (deterministic in sequence): the canary-abort rollout drill needs a
    new version whose error rate regresses."""
    import random as _random
    rng = _random.Random(int(seed))
    good = demo_affine(scale=scale)

    def fn(batch):
        if rng.random() < float(p):
            raise RuntimeError("demo_faulty: injected model failure")
        return good(batch)
    return fn


# ---------------------------------------------------------------------------
# sharding spec resolution
# ---------------------------------------------------------------------------
def resolve_sharding(block):
    """Resolve a generate-spec ``"sharding"`` block into a
    :class:`~mxnet_tpu.parallel.shardcfg.ShardingConfig` carrying the
    Megatron transformer rules.  ``{"from_env": true}`` reads the
    supervisor-stamped ``MXNET_MESH_SHAPE``/``MXNET_MESH_AXES``;
    otherwise the block names the mesh explicitly
    (``{"mesh_shape": [1, 2], "axis_names": ["dp", "tp"]}``).
    ``None``/empty resolves to ``None`` (replicated serving)."""
    if not block:
        return None
    from ..parallel.shardcfg import ShardingConfig
    rules = ShardingConfig.for_transformer(mesh_shape=(1,)).rules
    if block.get("from_env"):
        return ShardingConfig.from_env(rules=rules)
    shape = block.get("mesh_shape")
    axes = block.get("axis_names")
    return ShardingConfig.for_transformer(
        mesh_shape=tuple(int(s) for s in shape) if shape else None,
        axis_names=tuple(axes) if axes else None)


def resolve_quant(block):
    """Resolve a generate-spec ``"quant"`` block into ``DecodeEngine``
    kwargs.  ``{"weights": "int8" | "int4", "group": 128, "kv":
    "int8"}`` — every key optional: ``weights`` picks the weight-only
    mode (``group`` sizes the int4 scale groups), ``kv`` switches the
    KV-cache pages to int8 codes + per-page scales.  ``None``/empty
    resolves to ``{}`` (the engine then follows the
    ``MXNET_QUANT_WEIGHTS``/``MXNET_QUANT_KV`` environment, which the
    fleet supervisor can stamp per replica)."""
    if not block:
        return {}
    out = {}
    if block.get("weights"):
        out["quantize"] = str(block["weights"])
    if block.get("group") is not None:
        out["quant_group"] = int(block["group"])
    if block.get("kv"):
        out["kv_dtype"] = str(block["kv"])
    return out


# ---------------------------------------------------------------------------
# process entry
# ---------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--spec", required=True, help="model spec JSON file")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--id", default="", help="replica id (metrics label)")
    args = ap.parse_args(argv)

    if args.id:
        # stamp BEFORE the serving metrics object exists so every
        # snapshot/export this process produces carries the label
        os.environ["MXNET_SERVING_REPLICA_ID"] = args.id

    from . import ModelServer
    from .registry import ModelRegistry, load_model_spec
    from .. import faults, runtime

    with open(args.spec) as f:
        spec = json.load(f)

    cache = runtime.enable_compile_cache()
    registry = ModelRegistry()
    t0 = time.monotonic()
    generators = []  # (name, model, DecodeEngine kwargs)
    for mspec in spec.get("models", ()):
        if mspec.get("generate") is not None:
            from .registry import resolve_builder
            builder = resolve_builder(mspec["builder"])
            model = builder(**(mspec.get("kwargs") or {}))
            generators.append((mspec["name"], model,
                               dict(mspec["generate"])))
        else:
            load_model_spec(registry, mspec)

    server = ModelServer(
        registry, host=args.host, port=args.port, admin=True,
        flush_ms=float(spec.get("flush_ms", 5.0)),
        max_queue_depth=int(spec.get("max_queue_depth", 256)))
    for name, model, genkw in generators:
        from .generate import DecodeEngine
        genkw["sharding"] = resolve_sharding(genkw.get("sharding"))
        genkw.update(resolve_quant(genkw.pop("quant", None)))
        server.attach_engine(name, DecodeEngine(model, name=name, **genkw))
    warm_s = time.monotonic() - t0      # models built, every program warm
    server.start()
    import jax
    devs = jax.devices()
    print("REPLICA_READY id=%s port=%d warm_s=%.2f cache=%s devices=%s:%s"
          % (args.id, server.port, warm_s, cache, devs[0].platform,
             ",".join(str(d.id) for d in devs)), flush=True)

    stop = threading.Event()

    def _sigterm(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _sigterm)
    signal.signal(signal.SIGINT, _sigterm)

    # watchdog loop: the replica.crash fault site lives here so chaos
    # specs can kill a serving replica deterministically mid-traffic
    while not stop.wait(0.05):
        try:
            kind = faults.check("replica.crash")
        except Exception:
            # exception kinds = unhandled crash: die loudly, non-zero —
            # the supervisor's restart path, not the graceful one
            raise SystemExit(1)
        if kind == "kill":
            os._exit(137)  # SIGKILL-style: no drain, no atexit, nothing

    # graceful: drain queued work, refuse new admissions, exit 0
    server.stop(drain=True, timeout=30.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
