"""Configurations of kind ``train``: a Gluon model through
DataParallelTrainer (one compiled step over a mesh of the cell's chips),
steps back to back on one batch made on the device from the seed; and the
plain float32 loss the first step is checked against."""
import math
import time

import numpy as np


def bert_mlm_loss(params, config, tokens, labels):
    """BERT's encoder and MLM head as published (post-LN, exact GELU, output
    projection tied to the word embedding) in plain float32 jax.numpy: no
    kernel, O(L^2) attention, no dropout.  Sum over tokens of the
    cross-entropy of ``labels``."""
    import jax
    import jax.numpy as jnp
    H = config["num_attention_heads"]

    def ln(x, name):
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        return ((x - mu) / jnp.sqrt(var + 1e-5) * params[name + ".gamma"]
                + params[name + ".beta"])

    def dense(x, name):
        return x @ params[name + ".weight"].T + params[name + ".bias"]

    B, L = tokens.shape
    x = params["word_embed.weight"][tokens] + params["position_embed"][:L]
    x = ln(x, "embed_ln")
    C = x.shape[-1]
    for i in range(config["num_hidden_layers"]):
        pre = "encoder.layers.%d." % i
        qkv = dense(x, pre + "attention.qkv").reshape(B, L, 3, H, C // H)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(C // H)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = ln(x + dense(att.reshape(B, L, C), pre + "attention.proj"),
               pre + "ln1")
        h = jax.nn.gelu(dense(x, pre + "ffn.ffn1"), approximate=False)
        x = ln(x + dense(h, pre + "ffn.ffn2"), pre + "ln2")
    h = ln(jax.nn.gelu(dense(x, "mlm_dense"), approximate=False), "mlm_ln")
    logits = h @ params["word_embed.weight"].T + params["mlm_bias"]
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)


def reference_loss(fn, params, config, tokens, labels, block):
    """Mean loss of the batch under the reference ``fn``, ``block`` sequences
    at a time (the float32 logits of a whole four-chip batch fit no chip)."""
    import jax
    import jax.numpy as jnp
    p32 = jax.device_get(params)    # off the mesh: the blocks run on one chip
    p32 = {k: jnp.asarray(v, jnp.float32) for k, v in p32.items()}
    tokens, labels = np.asarray(tokens), np.asarray(labels)
    with jax.default_matmul_precision("highest"):
        one = jax.jit(lambda p, t, l: fn(p, config, t, l))
        total = sum(float(one(p32, tokens[i:i + block], labels[i:i + block]))
                    for i in range(0, len(tokens), block))
    return total / tokens.size


def run(ctx):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.ops import attention
    from mxnet_tpu.ops.pallas import epilogue
    from mxnet_tpu.parallel import DataParallelTrainer, ShardingConfig
    from chipbench import trace as reduction
    log, config, traffic = ctx["log"], ctx["config"], ctx["traffic"]
    seed, seconds, chips = ctx["seed"], ctx["seconds"], ctx["chips"]
    lap = ctx["clock"].lap
    lap("imports + device start")
    sharding = ShardingConfig.for_transformer(
        mesh_shape=(chips,), axis_names=("dp",), devices=ctx["devices"])
    B, L = config["sequences_per_chip"] * chips, config["sequence_length"]
    mx.random.seed(seed)
    net = ctx["resolve"](config["builder"])(
        **{k: config[v] for k, v in config["builder_kwargs"].items()})
    net.initialize(mx.init.Xavier())
    net.cast(config["param_dtype"])
    vocab = config["vocab_size"]
    k_tok, k_lab, k_drop = jax.random.split(jax.random.key(seed), 3)
    data_sh = NamedSharding(sharding.mesh, PartitionSpec("dp"))
    tokens = jax.device_put(jax.random.randint(k_tok, (B, L), 0, vocab),
                            data_sh)
    labels = jax.device_put(jax.random.randint(k_lab, (B, L), 0, vocab),
                            data_sh)
    net(mx.np.array(np.zeros((1, L), np.int32)))    # deferred shapes
    sce = SoftmaxCrossEntropyLoss()
    trainer = DataParallelTrainer(
        net, lambda out, lab: sce(out[0].astype("float32"), lab),
        config["optimizer"], {"learning_rate": config["learning_rate"]},
        sharding=sharding)
    state = trainer.init_state()
    step = trainer.build_step()
    lr = jnp.float32(config["learning_rate"])
    log("train: %s, %s parameters, %s lr %g, %d x %d tokens a step over "
        "mesh %s" % (config["builder"], config["param_dtype"],
                     config["optimizer"], config["learning_rate"], B, L,
                     sharding.describe()))
    lap("model + state")

    ref = reference_loss(ctx["resolve"](config["reference"], "train"),
                         state["params"], config, tokens, labels,
                         config["reference_block"])
    lap("reference loss")
    # the step is compiled ahead of time (the same program jit would build),
    # because only the compiled object says how much memory the program
    # takes beside its arguments: the allocator's peak does not count it
    compiled = step.lower(state, tokens, labels, jax.random.fold_in(k_drop, 0),
                          lr).compile()
    losses, syncs = [], []

    def run_steps(n, until=None):
        nonlocal state
        done = 0
        while done < n and (until is None or time.perf_counter() < until):
            state, loss = compiled(state, tokens, labels,
                                   jax.random.fold_in(k_drop, len(losses)), lr)
            losses.append(loss)
            done += 1
            if done % 10 == 0:
                jax.block_until_ready(loss)
                syncs.append(time.perf_counter())
        jax.block_until_ready(losses[-1])
        return done

    run_steps(config["warmup_steps"])   # step 0 is checked below
    lap("compile + %d warm-up steps" % config["warmup_steps"])
    log("step program: attention %s (sharded %s), epilogues %s"
        % (attention.last_path, attention.last_sharded, epilogue.last_path))
    live = max((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in ctx["devices"])
    temp = compiled.memory_analysis().temp_size_in_bytes
    log("memory while a step runs: %.2f GB held + %.2f GB the step program "
        "takes beside its arguments" % (live / 1e9, temp / 1e9))
    ctx["clock"].open_window()

    t_open = time.perf_counter()
    del syncs[:]
    n, profiler_s = 0, 0.0
    if ctx["trace"]:
        # the traced part is a few seconds in the window's middle; starting
        # and stopping the profiler holds the loop for seconds, which a
        # traced run's rate (read by train_mfu) must not count
        n += run_steps(10 ** 9,
                       t_open + traffic["trace_after_share"] * seconds)
        t0 = time.perf_counter()
        reduction.start(ctx["trace_dir"])
        profiler_s += time.perf_counter() - t0
        try:
            n += run_steps(10 ** 9, time.perf_counter()
                           + traffic["trace_seconds"])
        finally:
            t0 = time.perf_counter()
            reduction.stop()
            profiler_s += time.perf_counter() - t0
    n += run_steps(10 ** 9, t_open + seconds)
    elapsed = time.perf_counter() - t_open - profiler_s
    ctx["clock"].close_window()

    host = np.asarray(jax.device_get(losses), np.float64)
    err = abs(host[0] - ref) / ref
    falling = host[-10:].mean() < host[0]
    # the tolerance and its reason are the configuration's (loss_tolerance)
    tol = config["loss_tolerance"]
    ok = bool(np.isfinite(host).all() and err < tol and falling)
    log("reference check: step-0 loss %.5f vs the plain float32 forward "
        "%.5f: off by %.5f of it, tolerance %.3f; last ten steps' mean "
        "%.5f; all finite %s -> %s"
        % (host[0], ref, err, tol, host[-10:].mean(),
           bool(np.isfinite(host).all()), "ok" if ok else "WRONG"))
    gaps = np.diff(syncs) if len(syncs) > 2 else np.zeros(1)
    log("window %.3f s: %d steps of %d tokens (%.2f ms a step); ten steps "
        "took %.3f s at the median, %.3f s at the most (a stall of the "
        "machine shows here)"
        % (elapsed, n, B * L, 1e3 * elapsed / n, np.median(gaps),
           gaps.max()))
    return {
        "correct": ok, "attempted": n, "failed": 0,
        "window_bytes": live + temp,
        "end_to_end": {"tokens_per_s": n * B * L / elapsed,
                       "step_ms": 1e3 * elapsed / n},
        "stats": {"train": {"steps": n, "tokens_per_step": B * L,
                            "loss_first": float(host[0]),
                            "loss_last": float(host[-1])}},
    }
