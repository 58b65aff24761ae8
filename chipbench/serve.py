"""Configurations of kind ``serve``: a decoder LM behind ModelServer ->
DynamicBatcher -> DecodeEngine, asked over HTTP on localhost by the load
generator below; the length tables; and the check of the engine's compiled
programs against the plain float32 forward kept here."""
import math
import statistics
import threading
import time

import numpy as np

#: max |paged - reference| / std(reference logits) over every logit of every
#: checked position.  The reference runs at jax.default_matmul_precision
#: "highest"; the engine's programs run at the chip's default, where a float32
#: matmul is one bf16 pass on the MXU, through 12 layers.  Measured on the
#: v5e: 0.040 worst of 25 x 50257 logits (PR 21), 0.038 (PR 22), 0.008 rms.
#: The bound is twice the worst.  A dropped or misplaced term (a bias, a
#: residual, a position row, a page read from the wrong slot) moves logits by
#: order 1 in these units.
LOGIT_TOL = 0.08


# ---------------------------------------------------------------------------
# length tables: from the traffic file alone, never from the seed
# ---------------------------------------------------------------------------
def lengths(spec, rows):
    """One column of a table.  ``lognormal_quantiles``: the quantiles
    (k+0.5)/rows of a log-normal, clipped, then permuted by
    k -> (multiplier*k + offset) mod rows.  ``cycle``: the values in turn."""
    if spec["dist"] == "cycle":
        return [int(spec["values"][i % len(spec["values"])])
                for i in range(rows)]
    if spec["dist"] != "lognormal_quantiles":
        raise ValueError("unknown length distribution %r" % spec["dist"])
    nd = statistics.NormalDist()
    sorted_ = [min(spec["max"], max(spec["min"], round(
        spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf((k + 0.5) / rows)))))
        for k in range(rows)]
    if math.gcd(spec["multiplier"], rows) != 1:
        raise ValueError("multiplier %d is no permutation of %d rows"
                         % (spec["multiplier"], rows))
    return [sorted_[(spec["multiplier"] * i + spec["offset"]) % rows]
            for i in range(rows)]


def make_table(traffic):
    """[(prompt tokens, output tokens)] in the order of issue."""
    t = traffic["table"]
    return list(zip(lengths(t["prompt"], t["rows"]),
                    lengths(t["output"], t["rows"])))


def due_times(traffic, seconds):
    """Seconds after window open at which request i is due, or None for a
    closed loop (a request is due when a client is free).  ``open``: a fixed
    rate with stratified exponential gaps (the quantiles of the exponential,
    permuted like a table column), the same in every run."""
    if traffic["kind"] == "closed":
        return None
    if traffic["kind"] != "open":
        raise ValueError("unknown traffic kind %r" % traffic["kind"])
    n = int(math.ceil(traffic["rate_per_s"] * seconds))
    g = traffic["gaps"]
    gaps = [-math.log(1 - (((g["multiplier"] * i + g["offset"]) % n) + 0.5) / n)
            / traffic["rate_per_s"] for i in range(n)]
    return [t for t in np.cumsum(gaps) - gaps[0] if t < seconds]


def describe(column):
    q = statistics.quantiles(column, n=20, method="inclusive")
    return {"min": min(column), "p50": statistics.median(column),
            "p95": q[18], "max": max(column), "sum": sum(column)}


# ---------------------------------------------------------------------------
# the load generator
# ---------------------------------------------------------------------------
def run_load(ask, table, traffic, seconds, now=time.perf_counter,
             sleep=time.sleep):
    """Issue requests in table order, cycling, until ``seconds`` after the
    window opened, then wait at most ``drain_s`` for the answers.

    ``ask(i, prompt_len, output_len)`` sends request i and returns the number
    of tokens answered; it raises if the request failed.  In a closed loop
    ``clients`` threads each issue when their last request was answered; in
    an open loop ``workers`` threads issue each request when it is due and
    time it from then.  Returns (one record per request issued, window open
    time).  A request unanswered when the drain limit passes is failed."""
    due = due_times(traffic, seconds)
    n_threads = int(traffic["clients" if due is None else "workers"])
    stagger = traffic.get("stagger_ms", 0) / 1e3
    lock = threading.Lock()
    records, state = [], {"next": 0}
    t_open = now() + 0.05           # every thread is up by then

    def wait_until(t):
        while now() < t:
            sleep(min(0.05, max(0.0, t - now())))

    def client(k):
        if due is None:
            wait_until(t_open + k * stagger)    # the first issues, in order
        while True:
            with lock:
                i = state["next"]
                if due is None:
                    t_due = now()
                else:
                    t_due = t_open + due[i] if i < len(due) else None
                if t_due is None or max(now(), t_due) - t_open >= seconds:
                    return
                state["next"] += 1
                rec = {"i": i, "prompt": table[i % len(table)][0],
                       "output": table[i % len(table)][1], "t_due": t_due,
                       "t_sent": None, "t_done": None, "answered": None,
                       "error": None}
                records.append(rec)
            wait_until(t_due)
            rec["t_sent"] = now()
            try:
                rec["answered"] = ask(i, rec["prompt"], rec["output"])
            except Exception as e:  # a failed request is counted, not raised
                rec["error"] = "%s: %s" % (type(e).__name__, e)
            rec["t_done"] = now()

    threads = [threading.Thread(target=client, args=(k,), daemon=True)
               for k in range(n_threads)]
    for t in threads:
        t.start()
    limit = t_open + seconds + traffic["drain_s"]
    for t in threads:
        t.join(max(0.0, limit - now()))
    t_limit = now()
    with lock:
        out = [dict(r) for r in records]
    for r in out:
        if r["t_done"] is None:
            r["t_done"], r["error"] = t_limit, "not answered in the drain limit"
        # a closed loop times a request from its issue, an open loop from
        # when it was due (a late generator must not flatter the system)
        r["t_from"] = r["t_sent"] if due is None else r["t_due"]
    return out, t_open


def summarize(records, t_open):
    """The serving end-to-end metrics from the load generator's records."""
    ok = [r for r in records if r["error"] is None
          and r["answered"] == r["output"]]
    lat = sorted(1e3 * (r["t_done"] - (r["t_from"] or r["t_due"]))
                 for r in records)
    t_last = max((r["t_done"] for r in ok), default=t_open)
    late = [1e3 * (r["t_sent"] - r["t_due"]) for r in records
            if r["t_sent"] is not None]
    return {
        "attempted": len(records), "failed": len(records) - len(ok),
        "latency_p50_ms": float(np.percentile(lat, 50)) if lat else None,
        "latency_p95_ms": float(np.percentile(lat, 95)) if lat else None,
        "served_tokens_per_s": (sum(r["prompt"] + r["answered"] for r in ok)
                                / (t_last - t_open) if ok else None),
        "loadgen_late_p95_ms": (float(np.percentile(late, 95))
                                if late else None),
        # tokens held in the KV cache, mean over the run: each answered
        # request holds its prompt and, on average, half its answer for as
        # long as it took (what flops_bytes.decode_step_bytes reads)
        "live_tokens_mean": (sum((r["prompt"] + r["answered"] / 2)
                                 * (r["t_done"] - r["t_sent"]) for r in ok)
                             / (t_last - t_open) if ok else None),
        "drain_s": t_last - t_open,
    }


# ---------------------------------------------------------------------------
# the plain reference, and the engine's programs driven against it
# ---------------------------------------------------------------------------
def reference_logits(params, cfg, fed, n_rows):
    """The decoder block as the repo defines it (post-LN, exact GELU, tied
    output embedding, learned positions) in plain float32 jax.numpy at the
    highest matmul precision: no kernel, no cache, O(L^2) attention.  Logits
    of the last ``n_rows`` positions of ``fed``."""
    import jax
    import jax.numpy as jnp

    def ln(x, g, b):
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g + b

    def forward(p, tokens):
        L = tokens.shape[0]
        H, KVH, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        x = p["embed"][tokens] + p["pos"][:L]
        causal = jnp.tril(jnp.ones((L, L), bool))
        for lp in p["layers"]:
            q = (x @ lp["wq"].T + lp["bq"]).reshape(L, H, D)
            k = (x @ lp["wk"].T + lp["bk"]).reshape(L, KVH, D)
            v = (x @ lp["wv"].T + lp["bv"]).reshape(L, KVH, D)
            k, v = (jnp.repeat(a, H // KVH, axis=1) for a in (k, v))
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
            a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            att = jnp.einsum("hqk,khd->qhd", a, v).reshape(L, H * D)
            x = ln(x + att @ lp["wo"].T + lp["bo"], lp["ln1g"], lp["ln1b"])
            h = jax.nn.gelu(x @ lp["w1"].T + lp["b1"], approximate=False)
            x = ln(x + h @ lp["w2"].T + lp["b2"], lp["ln2g"], lp["ln2b"])
        return x[L - n_rows:] @ p["embed"].T

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(forward)(
            jax.tree.map(lambda a: a.astype(jnp.float32), params),
            jnp.asarray(fed, jnp.int32)))


def paged_logits(engine, prompt, n_decode):
    """Prefill ``prompt`` chunk by chunk, then decode ``n_decode`` greedy
    tokens, through the programs the engine built (the builders' cache hands
    back the same jitted functions) on a page pool of the engine's shape.
    Returns (tokens fed, one logits row per fed position from the last
    prompt token on).  After chip_smoke.py:paged_logits."""
    import jax.numpy as jnp
    from mxnet_tpu.models import decoder
    cfg, S, chunk = engine.cfg, engine.page_size, engine.prefill_chunk
    built = decoder.fn_cache_stats()["compiles"]
    prefill = decoder.make_prefill_chunk(cfg, S, chunk,
                                         sharding=engine.sharding)
    decode = decoder.make_decode_step(cfg, S, sharding=engine.sharding)
    if (decoder.fn_cache_stats()["compiles"] != built
            or engine.decode_fused_mode is not None):
        raise RuntimeError("the logits check would drive a program the "
                           "engine does not run")
    shape = (cfg.num_layers, cfg.num_kv_heads, engine.alloc.total_pages, S,
             cfg.head_dim)
    kp, vp = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    pps, B = engine.pages_per_seq, engine.slots
    row = np.arange(1, pps + 1, dtype=np.int32)
    for lo in range(0, len(prompt), chunk):
        part = prompt[lo:lo + chunk]
        padded = np.zeros(chunk, np.int32)
        padded[:len(part)] = part
        kp, vp, tok, last = prefill(engine.params, kp, vp,
                                    jnp.asarray(padded), jnp.int32(lo),
                                    jnp.int32(len(part)), jnp.asarray(row))
    rows = [np.asarray(last)]
    tables = np.zeros((B, pps), np.int32)
    tables[0] = row
    active = np.zeros(B, bool)
    active[0] = True
    fed, tok = list(prompt), int(tok)
    for i in range(n_decode):
        tokens, positions = np.zeros(B, np.int32), np.zeros(B, np.int32)
        tokens[0], positions[0] = tok, len(prompt) + i
        fed.append(tok)
        kp, vp, nxt, logits = decode(
            engine.params, kp, vp, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(tables), jnp.asarray(active))
        rows.append(np.asarray(logits)[0])
        tok = int(np.asarray(nxt)[0])
    return fed, np.stack(rows)


def check_reference(engine, lm, reference, check, seed, log):
    vocab = lm.config.vocab_size
    prompt = np.random.default_rng([seed, 1]).integers(
        0, vocab, size=check["prompt_tokens"]).tolist()
    fed, got = paged_logits(engine, prompt, check["decode_steps"])
    ref = reference(lm.jax_params(), lm.config, fed, got.shape[0])
    err = float(np.abs(got - ref).max() / ref.std())
    rms = float(np.sqrt(np.mean(np.square(got - ref))) / ref.std())
    ok = bool(got.shape == ref.shape and np.isfinite(got).all()
              and err < LOGIT_TOL)
    log("reference check: %d prompt tokens + %d decode steps through the "
        "paged cache vs the plain float32 forward: max %.4f rms %.4f of "
        "std(reference), tolerance %.2f -> %s"
        % (len(prompt), check["decode_steps"], err, rms, LOGIT_TOL,
           "ok" if ok else "DISAGREE"))
    return ok


# ---------------------------------------------------------------------------
def run(ctx):
    import jax
    from mxnet_tpu.serving import DecodeEngine, ModelServer, ServingClient
    from chipbench import trace as reduction
    log, config, traffic = ctx["log"], ctx["config"], ctx["traffic"]
    seed, seconds, lap = ctx["seed"], ctx["seconds"], ctx["clock"].lap
    lap("imports + device start")
    kwargs = {k: config[v] for k, v in config["builder_kwargs"].items()}
    lm = ctx["resolve"](config["builder"])(seed=seed, **kwargs)
    jax.block_until_ready(lm.jax_params())
    lap("weights")
    table = make_table(traffic)
    log("table of %d rows: prompt %s output %s"
        % (len(table), describe([p for p, _ in table]),
           describe([o for _, o in table])))

    limit = seconds + traffic["drain_s"] + 30.0   # above the drain limit
    engine = DecodeEngine(lm, **config["engine"])
    server = ModelServer(request_timeout_s=limit)
    tracing = threading.Event()
    try:
        server.attach_engine("lm", engine)      # warmup(): compiles
        host, port = server.start()
        log("engine: slots=%d page_size=%d pages=%d max_ctx=%d "
            "prefill_chunk=%d async=%s decode_fused=%s kv_dtype=%s "
            "prefix_cache=%s"
            % (engine.slots, engine.page_size, engine.alloc.total_pages,
               engine.max_ctx, engine.prefill_chunk, engine.async_decode,
               engine.decode_fused_mode, engine.kv_dtype,
               engine.prefix_cache is not None))
        lap("engine warm-up")
        ok_ref = check_reference(
            engine, lm, ctx["resolve"](config["reference"], "serve"),
            config["check"], seed, log)
        lap("reference check")

        local = threading.local()

        def ask(i, prompt_len, output_len, stream=2):
            # fresh token ids for every issue: no two requests share a
            # prefix, so the prefix cache never hits unless a mix says so
            ids = np.random.default_rng([seed, stream, i]).integers(
                0, lm.config.vocab_size, size=prompt_len).tolist()
            if getattr(local, "cli", None) is None:
                local.cli = ServingClient(host, port, timeout=limit,
                                          retries=0)
            out = local.cli.generate("lm", ids, max_tokens=output_len)
            return len(out["tokens"])

        # the served path once at each end of the table's prompt lengths,
        # side by side, so every host-side program of a step exists
        longest = max(p for p, _ in table)
        warm, _ = run_load(
            lambda i, p, o: ask(i, p, o, stream=3),
            [(longest, 4), (min(p for p, _ in table), 4)],
            {"kind": "closed", "clients": 2, "drain_s": limit}, 0.5)
        if any(r["error"] for r in warm):
            raise RuntimeError("warm-up request failed: %r" % warm)
        lap("warm-up requests")
        server.metrics.reset()      # the window's counters start at 0
        ctx["clock"].open_window()

        if ctx["trace"]:
            def trace_part():
                time.sleep(traffic["trace_after_share"] * seconds)
                reduction.start(ctx["trace_dir"])
                tracing.set()
                time.sleep(traffic["trace_seconds"])
                reduction.stop()
                tracing.clear()
            tracer = threading.Thread(target=trace_part, daemon=True)
            tracer.start()
        records, t_open = run_load(ask, table, traffic, seconds)
        if ctx["trace"]:
            tracer.join()
        ctx["clock"].close_window()
        snap = server.metrics.snapshot()["models"].get("lm", {})
        stats = engine.stats()
    finally:
        if tracing.is_set():
            reduction.stop()
        server.stop(drain=False, timeout=10.0)
        engine.stop(drain=False)

    out = summarize(records, t_open)
    log("window %.3f s + %.3f s to the last answer: %d requests issued, %d "
        "answered in full, %d failed"
        % (seconds, out["drain_s"] - seconds, out["attempted"],
           out["attempted"] - out["failed"], out["failed"]))
    for r in records:
        if r["error"] or r["answered"] != r["output"]:
            log("  request %d (%d -> %d): answered %s %s"
                % (r["i"], r["prompt"], r["output"], r["answered"],
                   r["error"] or ""))
    done = np.sort([r["t_done"] for r in records] + [t_open])
    log("longest time without an answer: %.3f s (a stall of the machine "
        "shows here)" % np.diff(done).max())
    log("latencies in order of issue, ms: %s" % " ".join(
        "%.0f" % (1e3 * (r["t_done"] - r["t_from"])) for r in records))
    return {
        "correct": ok_ref and out["failed"] == 0 and out["attempted"] > 0,
        "attempted": out["attempted"], "failed": out["failed"],
        "end_to_end": out, "stats": {"serving": snap, "engine": stats},
    }

