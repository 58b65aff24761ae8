#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

One process, one chip (a TPU is required: without one the script exits
non-zero and prints no result).  It drives the two main paths once, through
the entry points a user calls, at the full width of models the repo
supports, with random weights from a seed:

- serving: the repo's decoder block at GPT-2-small's published sizes
  (12 x 768, 12 heads of 64, vocab 50257, context 1024; fp32 weights and
  fp32 KV, which is what DecodeEngine admits) behind a ModelServer with the
  engine's defaults, asked over loopback HTTP by ServingClient.generate for
  six concurrent requests; then the logits of prefill-then-decode through
  the engine's own compiled programs against decoder.full_forward;
- training: LeNet-5 through the imperative Gluon loop (autograd.record +
  gluon.Trainer, then hybridize()), and BERT-base (12 x 768, vocab 30522,
  bf16 parameters) at batch 32 x 128 through DataParallelTrainer with Adam
  on a one-device mesh.

On a host with four chips it also runs the four-chip legs (BERT-base at
dp=4 and dp=2 x tp=2, the GPT-2-small engine at tp=2); ``--fleet`` instead
brings up a two-replica ServingFleet from a parent that never initialises
JAX (one process per chip: the parent must stay off the device).

It prints which program each leg actually ran, and on success ends its
standard output with one JSON line naming the device as JAX reports it.
Any failure in any leg exits non-zero with the cause as the last lines.

``--rehearse-cpu`` runs the same code at toy sizes on the CPU.  It exists
to debug this script in a sandbox and prints no result line.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import traceback

#: max |paged - reference| / std(reference logits) over every logit of
#: every checked position.  The reference runs under
#: jax.default_matmul_precision("highest"); the engine's programs run at
#: the chip's default precision, where an fp32 matmul is ONE bf16 pass on
#: the MXU (operands rounded to 8 bits of mantissa, fp32 accumulation),
#: through 12 layers.  Measured on the v5e in PR 21: 0.040 as the worst of
#: 25 x 50257 logits, 0.008 rms (my chip run; CHANGES.md).  The bound is
#: twice the measured worst case.  It cannot tell fp32 weights from bf16
#: ones, because at default precision the chip rounds them alike; what it
#: does catch is a dropped or misplaced term (a bias, a residual, a
#: position row, a page read from the wrong slot), which moves logits by
#: order 1 in these units.
LOGIT_TOL = 0.08


class Failure(Exception):
    """A leg's check did not hold."""


def log(msg=""):
    print(msg, flush=True)


def check(cond, what):
    if not cond:
        raise Failure(what)


class Sizes:
    def __init__(self, rehearse):
        self.rehearse = rehearse
        if rehearse:
            self.lm = dict(vocab_size=128, num_layers=2, units=64,
                           hidden_size=128, num_heads=4, max_length=128)
            self.prompts, self.new_tokens = (9, 16, 23, 30, 37, 48), 8
            self.check_prompt, self.check_decode = 21, 6
            self.pool = dict(page_size=4, slots=4, pages_per_seq=8,
                             chunk=8, width=3)
            self.lenet_iters = 12
            self.bert, self.bert_batch, self.bert_len = "bert_tiny", 4, 16
            self.bert_steps = 4
        else:
            self.lm = dict(vocab_size=50257, num_layers=12, units=768,
                           hidden_size=3072, num_heads=12, max_length=1024)
            self.prompts, self.new_tokens = (128, 200, 256, 320, 384,
                                             512), 64
            self.check_prompt, self.check_decode = 200, 24
            # the benchmark's serving cells (chipbench/configs/
            # gpt2-small-serve.json): 32 slots x 1024 positions
            self.pool = dict(page_size=16, slots=32, pages_per_seq=64,
                             chunk=256, width=4)
            self.lenet_iters = 30
            self.bert, self.bert_batch, self.bert_len = "bert_base", 32, 128
            self.bert_steps = 6


# ---------------------------------------------------------------------------
# device gate
# ---------------------------------------------------------------------------
def device_gate(rehearse):
    import jax
    from mxnet_tpu import profiler, runtime
    devs = jax.devices()
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    log("device: platform=%s kind=%r count=%d jax=%s"
        % (d.platform, d.device_kind, len(devs), jax.__version__))
    if not rehearse:
        check(d.platform == "tpu",
              "no TPU: jax.devices()[0].platform is %r" % d.platform)
        spec = profiler.chip_spec(d)
        check(spec["in_table"],
              "device_kind %r is not in profiler's chip table"
              % d.device_kind)
        log("chip table: hbm_bytes=%d peak_flops_bf16=%.3g"
            % (spec["hbm_bytes"], spec["peak_flops_bf16"]))
    feats = runtime.Features()
    from mxnet_tpu import _native
    log("NATIVE_RUNTIME=%s%s" % (
        feats.is_enabled("NATIVE_RUNTIME"),
        "" if _native.build_error is None
        else " (make failed: %s)" % _native.build_error.strip()[-300:]))
    return device


def cache_line(tag):
    from mxnet_tpu import runtime
    st = runtime.compile_cache_stats()
    log("%s: compile cache dir=%s hits=%d misses=%d"
        % (tag, st["dir"], st["hits"], st["misses"]))


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def build_lm(sz):
    from mxnet_tpu.models import decoder
    return decoder.causal_lm(seed=0, **sz.lm)


def paged_logits(engine, prompt, n_decode):
    """Prefill ``prompt`` chunk by chunk, then decode ``n_decode`` greedy
    tokens, through the programs the engine built (the builders' cache
    hands back the same jitted functions) on a page pool of the engine's
    shape.  Returns (tokens fed, one logits row per fed position from the
    last prompt token on)."""
    import jax.numpy as jnp
    import numpy as onp
    from mxnet_tpu.models import decoder
    cfg, S, chunk = engine.cfg, engine.page_size, engine.prefill_chunk
    compiles = decoder.fn_cache_stats()["compiles"]
    prefill = decoder.make_prefill_chunk(cfg, S, chunk,
                                         sharding=engine.sharding)
    decode = decoder.make_decode_step(cfg, S, sharding=engine.sharding)
    check(decoder.fn_cache_stats()["compiles"] == compiles,
          "the logits check built a program the engine did not")
    kp, vp = (decoder.fresh_pool(cfg, engine.alloc.total_pages, S)
              for _ in range(2))
    plan = decoder.tp_plan(cfg, engine.sharding)
    if plan is not None:
        kp, vp = plan.place_kv(kp), plan.place_kv(vp)
    pps, B = engine.pages_per_seq, engine.slots
    row = onp.arange(1, pps + 1, dtype=onp.int32)
    rows = []
    for lo in range(0, len(prompt), chunk):
        part = prompt[lo:lo + chunk]
        padded = onp.zeros(chunk, onp.int32)
        padded[:len(part)] = part
        kp, vp, tok, last = prefill(engine.params, kp, vp,
                                    jnp.asarray(padded), jnp.int32(lo),
                                    jnp.int32(len(part)), jnp.asarray(row))
    rows.append(onp.asarray(last))
    tables = onp.zeros((B, pps), onp.int32)
    tables[0] = row
    active = onp.zeros(B, bool)
    active[0] = True
    fed = list(prompt)
    tok = int(tok)
    for i in range(n_decode):
        tokens = onp.zeros(B, onp.int32)
        positions = onp.zeros(B, onp.int32)
        tokens[0], positions[0] = tok, len(prompt) + i
        fed.append(tok)
        kp, vp, nxt, logits = decode(
            engine.params, kp, vp, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(tables),
            jnp.asarray(active))
        rows.append(onp.asarray(logits)[0])
        tok = int(onp.asarray(nxt)[0])
    return fed, onp.stack(rows)


def reference_logits(lm, fed, n_rows):
    """decoder.full_forward on the fed tokens as the plain float32
    reference: highest matmul precision and no kernels (the flash and
    epilogue gates are switched off while it traces, so attention is the
    O(L^2) jnp reference); the last ``n_rows`` rows."""
    import os
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from mxnet_tpu.models import decoder
    gates = ("MXNET_FLASH_ATTENTION", "MXNET_EPILOGUE_KERNEL")
    saved = {g: os.environ.get(g) for g in gates}
    os.environ.update({g: "0" for g in gates})
    try:
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda p, t: decoder.full_forward(
                p, lm.config, t))(lm.jax_params(),
                                  jnp.asarray([fed], jnp.int32))
    finally:
        for g, v in saved.items():
            if v is None:
                del os.environ[g]
            else:
                os.environ[g] = v
    return onp.asarray(ref)[0, len(fed) - n_rows:]


def logits_agree(tag, got, ref):
    import numpy as onp
    check(got.shape == ref.shape and onp.isfinite(got).all(),
          "%s: logits shape %s vs %s, or not finite"
          % (tag, got.shape, ref.shape))
    err = float(onp.abs(got - ref).max() / ref.std())
    rms = float(onp.sqrt(onp.mean(onp.square(got - ref))) / ref.std())
    log("%s: |paged - reference| / std(reference) = %.4f max, %.4f rms "
        "over %d positions x %d logits (tolerance on the max %.2f)"
        % (tag, err, rms, got.shape[0], got.shape[1], LOGIT_TOL))
    check(err < LOGIT_TOL, "%s: logits disagree with full_forward "
          "(%.4f >= %.2f)" % (tag, err, LOGIT_TOL))
    return err


def describe_engine(engine, st):
    from mxnet_tpu.ops.pallas import epilogue, paged_attention
    log("engine: launches=%s" % json.dumps(st["launches"], sort_keys=True))
    log("engine: last_path: paged_attention=%s bias_gelu=%s"
        % (paged_attention.last_path, epilogue.last_path))


def pool_leg(sz, lm):
    """The decode-step, prefill-chunk and verify programs compiled at the
    benchmark's serving geometry: each takes the K and V pools as they
    lie and hands them back in the same buffers.  Fails if a compiled
    program holds an operation whose result is a whole pool other than
    the in-place update (a relayout or a copy of the pool: two thirds of
    the device's time before PR 26), or if a pool is not aliased input
    to output.  Prints the bytes one pool takes on the device."""
    import re
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import profiler
    from mxnet_tpu.models import decoder
    g = sz.pool
    cfg, S, B, pps = lm.config, g["page_size"], g["slots"], g["pages_per_seq"]
    total = B * pps + 1
    log("== pool: %d pages of %d, %d slots, chunk %d, verify width %d"
        % (total, S, B, g["chunk"], g["width"]))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), lm.jax_params())
    pool = jax.eval_shape(lambda: decoder.fresh_pool(cfg, total, S))
    active = jax.ShapeDtypeStruct((B,), jnp.bool_)
    programs = {
        "decode": (decoder.make_decode_step(cfg, S),
                   (i32(B), i32(B), i32(B, pps), active)),
        "prefill": (decoder.make_prefill_chunk(cfg, S, g["chunk"]),
                    (i32(g["chunk"]), i32(), i32(), i32(pps))),
        "verify": (decoder.make_verify_step(cfg, S, g["width"]),
                   (i32(B, g["width"]), i32(B), i32(B), i32(B, pps),
                    active)),
    }
    dims = ",".join(str(d) for d in pool.shape)
    whole = re.compile(r"^\s*(?:ROOT )?%?(\S+) = f32\[" + dims
                       + r"\]\S* ([\w\-]+)\((.*)$")
    in_place = ("parameter", "get-tuple-element", "bitcast",
                "dynamic-update-slice")
    for name, (fn, rest) in programs.items():
        text = fn.lower(params, pool, pool, *rest).compile().as_text()
        # a computation's name -> the operation at its root
        roots, current = {}, None
        for line in text.splitlines():
            m = re.match(r"^%?(\S+) \(.*\) -> .* \{$", line)
            if m:
                current = m.group(1)
            m = re.match(r"^\s*ROOT %?\S+ = \S+ ([\w\-]+)\(", line)
            if m and current:
                roots[current] = m.group(1)
        bad = []
        for line in text.splitlines():
            m = whole.match(line)
            if not m or m.group(2) in in_place:
                continue
            called = re.search(r"calls=%?([\w.\-]+)", m.group(3))
            if (m.group(2) == "fusion" and called
                    and roots.get(called.group(1)) == "dynamic-update-slice"):
                continue
            bad.append("%s = %s" % (m.group(1), m.group(2)))
        check(not bad, "pool: %s holds whole-pool operations that are not "
              "the in-place update: %s" % (name, ", ".join(bad[:6])))
        alias = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
        check(alias and "{0}:" in alias.group(1) and "{1}:" in alias.group(1),
              "pool: %s does not alias both pools input to output (%s)"
              % (name, alias.group(1) if alias else None))
        log("pool: %s: no whole-pool operation but the in-place update; "
            "K and V aliased input to output" % name)
    dev = jax.devices()[0]
    before = profiler.device_memory_stats(dev)["bytes_in_use"]
    held = jax.block_until_ready(decoder.fresh_pool(cfg, total, S))
    log("pool: one f32%s pool takes %d bytes on the device (%d unpadded)"
        % (list(held.shape), profiler.device_memory_stats(dev)[
            "bytes_in_use"] - before, held.size * 4))


def check_decode_program(sz, st):
    """The decode step the engine traced holds the kernels that ran."""
    from mxnet_tpu.ops.pallas import epilogue, paged_attention
    if sz.rehearse:
        return
    # per layer the tower holds one bias_gelu and one paged attention;
    # each is a Pallas call exactly where its module's last_path says
    # the kernel ran
    want = sz.lm["num_layers"] * (
        (paged_attention.last_path == "pallas")
        + (epilogue.last_path == "pallas"))
    check(st["launches"]["pallas_per_step"] == want,
          "decode tower traced %d Pallas calls; paged attention ran "
          "%r and bias_gelu %r"
          % (st["launches"]["pallas_per_step"],
             paged_attention.last_path, epilogue.last_path))


def serving_leg(sz, lm):
    import numpy as onp
    from mxnet_tpu.serving import DecodeEngine, ModelServer, ServingClient
    log("== serving: %s" % json.dumps(sz.lm, sort_keys=True))
    engine = DecodeEngine(lm)
    server = ModelServer(request_timeout_s=900.0)
    t0 = time.perf_counter()
    server.attach_engine("lm", engine)      # warmup(): compiles
    log("serving: engine warm-up (compile) %.1f s; slots=%d page_size=%d "
        "prefill_chunk=%d async=%s"
        % (time.perf_counter() - t0, engine.slots, engine.page_size,
           engine.prefill_chunk, engine.async_decode))
    host, port = server.start()
    results, errors = {}, []
    rs = onp.random.RandomState(0)
    prompts = [rs.randint(0, sz.lm["vocab_size"], size=n).tolist()
               for n in sz.prompts]

    def ask(i):
        try:
            with ServingClient(host, port, timeout=900.0) as cli:
                results[i] = cli.generate("lm", prompts[i],
                                          max_tokens=sz.new_tokens)
        except Exception as e:  # every request must answer: collect all
            errors.append("request %d (%d prompt tokens): %r"
                          % (i, len(prompts[i]), e))

    try:
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        check(not errors, "serving: " + "; ".join(errors))
        for i, p in enumerate(prompts):
            r = results[i]
            check(len(r["tokens"]) == sz.new_tokens
                  and r["prompt_tokens"] == len(p)
                  and all(0 <= t < sz.lm["vocab_size"]
                          for t in r["tokens"]),
                  "serving: request %d answered %r" % (i, r))
            log("serving: request %d: %d prompt tokens -> %d tokens, "
                "finish_reason=%s" % (i, len(p), len(r["tokens"]),
                                      r["finish_reason"]))
        with ServingClient(host, port, timeout=60.0) as cli:
            st = cli.stats()["generators"]["lm"]
        describe_engine(engine, st)
        check_decode_program(sz, st)
        prompt = rs.randint(0, sz.lm["vocab_size"],
                            size=sz.check_prompt).tolist()
        fed, got = paged_logits(engine, prompt, sz.check_decode)
        ref = reference_logits(lm, fed, got.shape[0])
        logits_agree("serving", got, ref)
    finally:
        server.stop(drain=False, timeout=30.0)
        engine.stop(drain=False)
    return prompt, got


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def bytes_in_use():
    import jax
    from mxnet_tpu import profiler
    return [profiler.device_memory_stats(d)["bytes_in_use"]
            for d in jax.devices()]


def falling(tag, losses):
    import numpy as onp
    log("%s: losses %s" % (tag, " ".join("%.4f" % l for l in losses)))
    check(onp.isfinite(losses).all(), "%s: loss not finite" % tag)
    k = max(1, len(losses) // 3)
    check(onp.mean(losses[-k:]) < onp.mean(losses[:k]),
          "%s: loss did not fall" % tag)


def lenet_leg(sz):
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    log("== training: LeNet-5, imperative Gluon loop, batch 64")
    mx.random.seed(0)
    onp.random.seed(0)      # the loader's shuffle draws from numpy's
    net = nn.HybridSequential()
    net.add(nn.Conv2D(6, 5, activation="tanh"), nn.MaxPool2D(2),
            nn.Conv2D(16, 5, activation="tanh"), nn.MaxPool2D(2),
            nn.Flatten(), nn.Dense(120, activation="tanh"),
            nn.Dense(84, activation="tanh"), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    ds = gluon.data.vision.MNIST(train=True)    # synthetic when offline
    tf = gluon.data.vision.transforms.ToTensor()
    loader = gluon.data.DataLoader(ds.transform_first(tf), batch_size=64,
                                   shuffle=True)
    losses = []
    for i, (x, y) in enumerate(loader):
        if i >= sz.lenet_iters:
            break
        if i == sz.lenet_iters // 3:
            net.hybridize()         # the rest of the loop runs compiled
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(x.shape[0])
        losses.append(float(loss.mean()))
    falling("lenet", losses)


def bert_leg(sz, mesh_shape=(1,), axis_names=("dp",)):
    """BERT through DataParallelTrainer under one ShardingConfig; returns
    the collective census of the compiled step and, sampled while the
    training state is alive, the bytes each device holds."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import np as mxnp
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.models import bert
    from mxnet_tpu.ops import attention
    from mxnet_tpu.ops.pallas import epilogue
    from mxnet_tpu.parallel import (DataParallelTrainer, ShardingConfig,
                                    collective_census)
    cfg = ShardingConfig.for_transformer(mesh_shape=mesh_shape,
                                         axis_names=axis_names)
    B = sz.bert_batch * cfg.axis_size("dp")
    tag = "bert[%s]" % cfg.describe()
    log("== training: %s bf16, batch %d x %d, Adam, mesh %s"
        % (sz.bert, B, sz.bert_len, cfg.describe()))
    mx.random.seed(0)
    net = getattr(bert, sz.bert)()
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    vocab = net.word_embed.weight.shape[0]
    tokens = mxnp.random.randint(0, vocab, size=(B, sz.bert_len))
    net(tokens[:1])
    sce = SoftmaxCrossEntropyLoss()
    trainer = DataParallelTrainer(
        net, lambda out, lab: sce(out[0].astype("float32"), lab), "adam",
        {"learning_rate": 1e-3}, sharding=cfg)
    state = trainer.init_state()
    step = trainer.build_step()
    tok = tokens._data
    lab = jax.random.randint(jax.random.key(1), tok.shape, 0, vocab)
    lr = jnp.float32(1e-3)
    counts0 = dict(epilogue.trace_counts)
    attention.last_path = epilogue.last_path = None
    t0 = time.perf_counter()
    census = collective_census(
        step.lower(state, tok, lab, jax.random.key(0), lr))
    losses = []
    for i in range(sz.bert_steps):
        state, loss = step(state, tok, lab, jax.random.key(i), lr)
        losses.append(float(loss))
        if i == 0:
            log("%s: compile + first step %.1f s"
                % (tag, time.perf_counter() - t0))
    falling(tag, losses)
    used = bytes_in_use()
    traced = {k: epilogue.trace_counts[k] - counts0[k] for k in counts0}
    log("%s: attention.last_path=%s last_sharded=%s epilogue.last_path=%s "
        "epilogue ops traced %s collectives %s"
        % (tag, attention.last_path, attention.last_sharded,
           epilogue.last_path, json.dumps(traced, sort_keys=True),
           json.dumps(census, sort_keys=True)))
    check(min(traced.values()) > 0,
          "%s: a fused epilogue is not in the step: %r" % (tag, traced))
    if not sz.rehearse:
        # over several chips GSPMD partitions the step and cannot
        # partition a Mosaic kernel: flash rides its own shard_map, the
        # epilogues take the XLA chain there (epilogue._mode)
        want = "xla" if cfg.active else "pallas"
        check(attention.last_path == "pallas"
              and epilogue.last_path == want,
              "%s: flash ran %r, epilogues ran %r (expected %r)"
              % (tag, attention.last_path, epilogue.last_path, want))
    return census, used


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def four_chip_legs(sz, lm, prompt, one_chip_logits):
    import jax
    import numpy as onp
    from mxnet_tpu.parallel import ShardingConfig
    from mxnet_tpu.serving import DecodeEngine
    log("== four chips")
    dp, used = bert_leg(sz, (4,), ("dp",))
    log("dp=4: bytes_in_use per device %s" % used)
    check(all(used), "dp=4 left a device empty: %s" % used)
    check(dp["all-reduce"] >= 1, "dp=4 step has no all-reduce: %r" % dp)
    dptp, used = bert_leg(sz, (2, 2), ("dp", "tp"))
    log("dp=2 x tp=2: bytes_in_use per device %s" % used)
    check(all(used), "dp=2 x tp=2 left a device empty: %s" % used)
    check(dptp["all-reduce"] >= 1 and dptp["total"] > dp["total"],
          "dp=2 x tp=2 census %r adds nothing to dp=4's %r" % (dptp, dp))

    log("== serving at tp=2")
    cfg = ShardingConfig.for_transformer(
        mesh_shape=(1, 2), axis_names=("dp", "tp"),
        devices=jax.devices()[:2])
    t0 = time.perf_counter()
    engine = DecodeEngine(lm, sharding=cfg)
    try:
        engine.warmup()
        log("tp=2: engine warm-up (compile) %.1f s"
            % (time.perf_counter() - t0))
        out = engine.submit(prompt, sz.new_tokens).result(timeout=900)
        check(len(out["tokens"]) == sz.new_tokens,
              "tp=2 engine answered %r" % (out,))
        st = engine.stats()
        describe_engine(engine, st)
        log("tp=2: sharding %s" % json.dumps(st["sharding"],
                                             sort_keys=True))
        check(st["sharding"]["tp"] == 2, "engine is not tp=2: %r"
              % (st["sharding"],))
        used = bytes_in_use()
        log("tp=2: bytes_in_use per device %s" % used)
        check(used[0] and used[1], "tp=2 engine left a chip of its mesh "
              "empty: %s" % used)
        check(st["sharding"]["collectives"]["all-reduce"] >= 1,
              "tp=2 decode step has no all-reduce: %r"
              % (st["sharding"],))
        fed, got = paged_logits(engine, prompt, sz.check_decode)
        ref = reference_logits(lm, fed, got.shape[0])
        logits_agree("tp=2 vs full_forward", got, ref)
        # same prompt, same greedy rule: the rows line up unless an
        # argmax flipped on rounding, which the row check above bounds
        err = float(onp.abs(got[0] - one_chip_logits[0]).max()
                    / one_chip_logits[0].std())
        log("tp=2 vs one chip: prefill logits differ by %.4f std "
            "(tolerance %.2f)" % (err, LOGIT_TOL))
        check(err < LOGIT_TOL, "tp=2 logits disagree with one chip")
    finally:
        engine.stop(drain=False)


# ---------------------------------------------------------------------------
# two replicas, one process per chip (the parent stays off JAX)
# ---------------------------------------------------------------------------
def fleet_main(sz):
    """Two replicas of the serving leg's model (the same builder, seed
    and sizes; the engine's defaults), one chip each."""
    import numpy as onp
    from mxnet_tpu.context import host_chip_count
    from mxnet_tpu.serving import ServingClient, ServingFleet
    spec = {"models": [{
        "name": "lm", "builder": "mxnet_tpu.models.decoder:causal_lm",
        "kwargs": dict(sz.lm, seed=0), "generate": {}}]}
    fleet = ServingFleet(spec, replicas=2, supervisor_kwargs={
        "startup_timeout_s": 900.0})
    log("== fleet: 2 replicas of %s, one chip each; this host has %d "
        "chip(s)" % (json.dumps(sz.lm, sort_keys=True), host_chip_count()))
    prompt = onp.random.RandomState(0).randint(
        0, sz.lm["vocab_size"], size=sz.prompts[0]).tolist()
    answers = []

    def ask(who, host, port):
        with ServingClient(host, port, timeout=900.0) as cli:
            out = cli.generate("lm", prompt, max_tokens=sz.new_tokens)
        check(len(out["tokens"]) == sz.new_tokens
              and out["prompt_tokens"] == len(prompt),
              "%s answered %r" % (who, out))
        log("fleet: %s: %d prompt tokens -> %d tokens"
            % (who, len(prompt), len(out["tokens"])))
        answers.append(out["tokens"])

    try:
        fleet.start()
        for r in fleet.supervisor.replicas:
            ready = [ln for ln in fleet.supervisor._log_tail(r).splitlines()
                     if "REPLICA_READY" in ln]
            log("fleet: %s %s" % (r.rid, ready[-1] if ready else "?"))
            check(sz.rehearse or (ready and "devices=tpu:" in ready[-1]),
                  "replica %s is not on a TPU" % r.rid)
            ask("replica " + r.rid, r.host, r.port)
        ask("the router", *fleet.address)
        # one seed, one prompt, greedy: every copy says the same tokens
        check(answers[0] == answers[1] == answers[2],
              "the replicas disagree: %r" % (answers,))
        log("fleet: both replicas and the router answered, token for "
            "token alike")
    finally:
        fleet.stop()
    log("FLEET OK")
    return 0


# ---------------------------------------------------------------------------
def run(args):
    if args.rehearse_cpu:
        log("REHEARSAL on the CPU at toy sizes: this proves nothing about "
            "the chip and prints no result.")
    sz = Sizes(args.rehearse_cpu)
    if args.fleet:
        return fleet_main(sz)       # the parent stays off JAX
    from mxnet_tpu import runtime
    runtime.enable_compile_cache()      # before the first compile
    device = device_gate(args.rehearse_cpu)
    cache_line("start")
    lm = build_lm(sz)
    # every leg runs even after another failed (one run, all the facts);
    # any failure fails the run
    failed = []

    def leg(name, fn, *a):
        try:
            return fn(*a)
        except Exception as e:
            traceback.print_exc()
            sys.stderr.flush()
            failed.append("%s: %s: %s" % (name, type(e).__name__, e))
            log("LEG FAILED %s" % failed[-1])
        finally:
            cache_line("after " + name)

    leg("pool", pool_leg, sz, lm)
    served = leg("serving", serving_leg, sz, lm)
    leg("lenet", lenet_leg, sz)
    leg("bert", bert_leg, sz)
    if device["count"] >= 4 and not args.rehearse_cpu and served:
        leg("four chips", four_chip_legs, sz, lm, *served)
    if failed:
        raise Failure("%d leg(s) failed:\n  %s"
                      % (len(failed), "\n  ".join(failed)))
    if args.rehearse_cpu:
        log("REHEARSAL passed (no result: it proves nothing about the "
            "chip)")
        return 0
    log(json.dumps({"ok": True, "device": device}))
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on the CPU; proves nothing about the "
                         "chip and prints no result")
    ap.add_argument("--fleet", action="store_true",
                    help="two-replica ServingFleet, one chip each, from a "
                         "parent that never initialises JAX")
    args = ap.parse_args()
    t0 = time.perf_counter()
    try:
        rc = run(args)
    except BaseException as e:
        traceback.print_exc()
        sys.stderr.flush()
        log("CHIP_SMOKE FAILED after %.0f s: %s: %s"
            % (time.perf_counter() - t0, type(e).__name__, e))
        return 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
