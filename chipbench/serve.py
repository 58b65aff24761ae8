"""Configurations of kind ``serve``: a decoder LM behind ModelServer ->
DynamicBatcher -> DecodeEngine, asked over HTTP on localhost by the load
generator below; the length tables; and the check of the engine's compiled
programs against the plain float32 forward kept here."""
import functools
import math
import statistics
import sys
import threading
import time

import numpy as np

#: The statistics of the two sets of numbers a serving run always prints:
#: one error per compared row of logits, max_v |got - ref| / std(all compared
#: reference logits), and one gap per served token, by which its logit lies
#: below the plain reference's best at its position in units of that
#: position's std.  Which of them decide ``correct``, and by what limit, the
#: configuration says (``check.limits``, with the readings each limit was set
#: from under ``assumed``; README.md has the rule): an error of the
#: arithmetic moves every row, so every statistic; a hard choice that falls
#: differently (a routed expert) moves the rows it falls in, so the maxima.
ROW_ERRORS = ("max", "q90", "median")
SERVED_GAPS = ("max", "q90")
#: The statistics a limit may be named for.  Every row and every served token
#: is held by the maxima.  The rows' 90th percentile lets a tenth of the rows
#: go, so only a configuration that declares a hard choice in its forward
#: (``check.hard_choice``, explained under ``assumed``) may name it.  The
#: median (half of the rows wrong would pass) and the gaps' 90th percentile
#: (0.0 in every sound run read so far: a limit on it only asks whether a
#: tenth of the served tokens are arbitrary) are printed and never compared.
ROW_LIMITS = ("max", "q90")
GAP_LIMITS = ("max",)


def row_name(stat, who="program"):
    return "%s_logits_%s_err" % (who, stat)


def gap_name(stat, control=None):
    if control is None:
        return "served_gap_%s" % stat
    return "control_%s_served_gap%s" % (control,
                                        "" if stat == "max" else "_" + stat)


def check_limits(config):
    """``check.limits`` of a ``serve`` configuration: reading -> limit.  The
    harness has no limit of its own, so a configuration that names none, none
    of the rows' errors, none of the served gaps, a reading that no limit is
    taken for, or the rows' 90th percentile without declaring its hard choice
    is refused, before anything is built."""
    rows = [row_name(s) for s in ROW_LIMITS]
    gaps = [gap_name(s) for s in GAP_LIMITS]
    check = config.get("check", {})
    limits = check.get("limits")
    if not isinstance(limits, dict):
        raise ValueError("the configuration has no check.limits: a map from "
                         "a reading (%s) to its limit" % ", ".join(rows + gaps))
    unknown = sorted(set(limits) - set(rows + gaps))
    if unknown:
        raise ValueError("check.limits names %s: the harness takes a limit "
                         "for %s and for nothing else"
                         % (", ".join(unknown), ", ".join(rows + gaps)))
    for what, names in (("the rows' errors", rows), ("the served gaps", gaps)):
        if not set(names) & set(limits):
            raise ValueError("check.limits names no reading of %s (%s)"
                             % (what, ", ".join(names)))
    for name, limit in limits.items():
        if isinstance(limit, bool) or not isinstance(limit, (int, float)) \
                or not 0 <= limit < math.inf:
            raise ValueError("check.limits.%s is %r, no limit" % (name, limit))
    if row_name("q90") in limits:
        for where, said in (
                ("check.hard_choice", check.get("hard_choice")),
                ('assumed["check.hard_choice"]',
                 config.get("assumed", {}).get("check.hard_choice"))):
            if not isinstance(said, str) or not said.strip():
                raise ValueError(
                    "check.limits names %s, which lets a tenth of the rows "
                    "go: only a configuration whose forward makes a hard "
                    "choice may, and it says which under %s (a string)"
                    % (row_name("q90"), where))
    return dict(limits)


def statistics_of(values):
    """max, 90th percentile and median of ``values``; all infinite where one
    of them is not finite or there is none."""
    v = np.asarray(values, np.float64)
    if v.size == 0 or not np.isfinite(v).all():
        return dict.fromkeys(ROW_ERRORS, math.inf)
    return {"max": float(v.max()), "q90": float(np.percentile(v, 90)),
            "median": float(np.median(v))}


def row_errors(got, ref):
    """One error per compared row: its largest |got - ref| over the standard
    deviation of all compared reference logits."""
    if got.shape != ref.shape or not np.isfinite(got).all():
        return np.full(ref.shape[0], np.inf)
    return np.abs(got - ref).max(-1) / ref.std()


def row_readings(got, ref, limits, who="program"):
    """The readings of ``got``'s rows against ``ref``'s, each beside the
    limit the configuration names for that statistic (None: printed, not
    compared)."""
    stats = statistics_of(row_errors(got, ref))
    return [{"name": row_name(s, who), "value": stats[s],
             "limit": limits.get(row_name(s))} for s in ROW_ERRORS]


def gap_readings(gaps, limits, control=None):
    """The same for served tokens' gaps.  Of ``"altered"`` the reading in the
    maximum's place is the least gap: an altered token has to fail wherever
    it stands, a precision at its worst position."""
    stats = statistics_of(gaps)
    if control == "altered" and math.isfinite(stats["max"]):
        stats["max"] = float(np.min(gaps))
    return [{"name": gap_name(s, control), "value": stats[s],
             "limit": limits.get(gap_name(s))} for s in SERVED_GAPS]


def held(entries):
    return all(e["value"] <= e["limit"] for e in entries
               if e["limit"] is not None)


def describe_limits(entries):
    named = ["%s <= %g" % (e["name"], e["limit"]) for e in entries
             if e["limit"] is not None]
    return ", ".join(named) or "none named"


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

    ``ask(i, prompt_len, output_len)`` sends request i and returns the tokens
    answered; it raises if the request failed.  In a closed loop
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
                       "t_sent": None, "t_done": None, "tokens": None,
                       "answered": None, "error": None}
                records.append(rec)
            wait_until(t_due)
            rec["t_sent"] = now()
            try:
                rec["tokens"] = list(ask(i, rec["prompt"], rec["output"]))
                rec["answered"] = len(rec["tokens"])
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


def held_token_seconds(r, t_end):
    """Token-seconds of KV cache that an answered request held up to
    ``t_end``: its prompt all the while, and its answer growing evenly from
    nothing to whole, so over the first share f of its time it holds
    prompt + f/2 of its answer on average."""
    held = min(r["t_done"], t_end) - r["t_sent"]
    if held <= 0:
        return 0.0
    f = held / (r["t_done"] - r["t_sent"])
    return (r["prompt"] + r["answered"] * f / 2) * held


def summarize(records, t_open, seconds):
    """The serving end-to-end metrics from the load generator's records.

    The latencies, ``attempted`` and ``failed`` are over every request issued,
    those answered in the drain included.  The served rate is over the window
    alone: prompt + answered tokens of the requests answered in full at or
    before the window's close (``t_open + seconds``), over the time from the
    window's open to the last of those answers.  Numerator and denominator
    end on the same event, so neither the drain's tail (occupancy falling
    from every caller to none) nor which long row is in flight at the close
    is in the rate."""
    ok = answered_in_full(records)
    lat = sorted(1e3 * (r["t_done"] - (r["t_from"] or r["t_due"]))
                 for r in records)
    inside = [r for r in ok if r["t_done"] <= t_open + seconds]
    # 0 where nothing was answered inside the window: the rates are then None
    span = max((r["t_done"] for r in inside), default=t_open) - t_open
    late = [1e3 * (r["t_sent"] - r["t_due"]) for r in records
            if r["t_sent"] is not None]
    return {
        "attempted": len(records), "failed": len(records) - len(ok),
        "latency_p50_ms": float(np.percentile(lat, 50)) if lat else None,
        "latency_p95_ms": float(np.percentile(lat, 95)) if lat else None,
        "served_tokens_per_s": (sum(r["prompt"] + r["answered"]
                                    for r in inside) / span
                                if span > 0 else None),
        "loadgen_late_p95_ms": (float(np.percentile(late, 95))
                                if late else None),
        # tokens held in the KV cache, mean over the same span (what
        # flops_bytes.decode_step_bytes reads)
        "live_tokens_mean": (sum(held_token_seconds(r, t_open + span)
                                 for r in ok) / span if span > 0 else None),
        "answered_in_window": len(inside),
        "rate_span_s": span,
        "drain_s": max((r["t_done"] for r in ok), default=t_open) - t_open,
    }


# ---------------------------------------------------------------------------
# the plain reference, and the engine's programs driven against it
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _plain_decoder(num_heads, num_kv_heads, head_dim, n_rows, dtype):
    """The jitted plain forward: (params, tokens, start) -> logits of the
    ``n_rows`` positions from ``start`` on.  One program for a given length of
    ``tokens``, whatever ``start`` is."""
    import jax
    import jax.numpy as jnp
    H, KVH, D = num_heads, num_kv_heads, head_dim

    def ln(x, g, b):
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g + b

    # the grids of the coarser controls, for a row scaled to [-1, 1]
    grids = {
        "int8": lambda a: jnp.round(a * 127.0) / 127.0,
        "float8_e4m3fn": lambda a: (a * 448.0).astype(
            jnp.float8_e4m3fn).astype(jnp.float32) / 448.0,
    }

    def on_grid(a):
        """Each row of ``a`` scaled to its range and rounded to the grid."""
        top = jnp.abs(a).max(-1, keepdims=True)
        top = jnp.where(top > 0, top, 1.0)
        return grids[dtype](a / top) * top

    def mm(x, w):
        """x @ w.T; under a coarser control both sides on its grid first
        (each token's activations, each output channel's weights), as a
        serving path that holds them in 8 bits would."""
        return on_grid(x) @ on_grid(w).T if dtype in grids else x @ w.T

    def forward(p, tokens, start):
        p = jax.tree.map(lambda a: a.astype(
            "float32" if dtype in grids else dtype), p)
        L = tokens.shape[0]
        x = p["embed"][tokens] + p["pos"][:L]
        causal = jnp.tril(jnp.ones((L, L), bool))
        for lp in p["layers"]:
            q = (mm(x, lp["wq"]) + lp["bq"]).reshape(L, H, D)
            k = (mm(x, lp["wk"]) + lp["bk"]).reshape(L, KVH, D)
            v = (mm(x, lp["wv"]) + lp["bv"]).reshape(L, KVH, D)
            k, v = (jnp.repeat(a, H // KVH, axis=1) for a in (k, v))
            s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
            a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            att = jnp.einsum("hqk,khd->qhd", a, v).reshape(L, H * D)
            x = ln(x + mm(att, lp["wo"]) + lp["bo"], lp["ln1g"], lp["ln1b"])
            h = jax.nn.gelu(mm(x, lp["w1"]) + lp["b1"], approximate=False)
            x = ln(x + mm(h, lp["w2"]) + lp["b2"], lp["ln2g"], lp["ln2b"])
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_rows, axis=0)
        return mm(rows, p["embed"]).astype(jnp.float32)

    return jax.jit(forward)


def reference_logits(params, cfg, fed, n_rows, pad_to=None, dtype="float32"):
    """The decoder block as the repo defines it (post-LN, exact GELU, tied
    output embedding, learned positions) in plain float32 jax.numpy at the
    highest matmul precision: no kernel, no cache, O(L^2) attention.  Logits
    (on the device) of the last ``n_rows`` positions of ``fed``; where
    ``fed`` has fewer, of its first ``n_rows`` positions.  ``pad_to`` pads
    ``fed`` behind its end (the causal mask keeps the padding out of every
    row before it), so requests of any length share one program.
    ``dtype`` other than float32 is a control: ``"int8"`` and
    ``"float8_e4m3fn"`` the same forward with both sides of every projection
    on that grid, ``"bfloat16"`` with weights and activations in bfloat16 at
    the chip's default precision (which is the precision of the engine's own
    matrix products, so that one does not fail: PERF.md, PR 28)."""
    import jax
    import jax.numpy as jnp
    tokens = np.zeros(max(pad_to or 0, len(fed), n_rows), np.int32)
    tokens[:len(fed)] = fed
    fn = _plain_decoder(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                        int(n_rows), dtype)
    with jax.default_matmul_precision(
            "default" if dtype == "bfloat16" else "highest"):
        return fn(params, jnp.asarray(tokens),
                  jnp.int32(max(0, len(fed) - n_rows)))


@functools.lru_cache(maxsize=None)
def _gap_program():
    import jax
    import jax.numpy as jnp

    def gaps(logits, want, valid):
        best = logits.max(-1)
        picked = jnp.take_along_axis(logits, want[:, None], axis=-1)[:, 0]
        gap = (best - picked) / logits.std(-1)
        return jnp.where(valid, gap, 0.0), logits.argmax(-1)
    return jax.jit(gaps)


def reference_over(reference, params, cfg, prompt, served, n_rows, pad_to,
                   dtype="float32"):
    """One pass of the reference over ``prompt`` + ``served`` (the tokens a
    request was answered with, greedy).  Returns ``judge(tokens)``: per served
    position, the gap by which that token's logit lies below the reference's
    best there, in units of the standard deviation of the position's logits
    (0 where the reference puts that token first), and the token the
    reference puts first."""
    fed = list(prompt) + list(served[:-1])
    lo = len(prompt) - 1 - max(0, len(fed) - n_rows)
    valid = np.zeros(n_rows, bool)
    valid[lo:lo + len(served)] = True
    logits = reference(params, cfg, fed, n_rows, pad_to=pad_to, dtype=dtype)

    def judge(tokens):
        judged = np.zeros(n_rows, np.int32)
        judged[valid] = tokens
        gap, top = _gap_program()(logits, judged, valid)
        return np.asarray(gap)[valid], np.asarray(top)[valid]
    return judge


def answered_in_full(records):
    return [r for r in records if r["error"] is None
            and r["answered"] == r["output"]]


def sample_served(records, seed, n_tokens):
    """The requests the served-token check looks at: the longest answered
    request (prompt + answer; the first such), then answered requests in an
    order drawn from the seed until ``n_tokens`` served tokens are in."""
    ok = answered_in_full(records)
    if not ok:
        return []
    longest = max(ok, key=lambda r: (r["prompt"] + r["output"], -r["i"]))
    picked, tokens = [longest], longest["output"]
    for k in np.random.default_rng([seed, 4]).permutation(len(ok)):
        if tokens >= n_tokens:
            break
        if ok[k] is not longest:
            picked.append(ok[k])
            tokens += ok[k]["output"]
    return picked


def paged_logits(engine, prompt, n_decode):
    """Prefill ``prompt`` chunk by chunk, then decode ``n_decode`` greedy
    tokens, through the programs the engine built (the builders' cache hands
    back the same jitted functions) on a pool the program hands out
    (``decoder.fresh_pool``: its shape and layout are the program's business).
    Returns (tokens fed, one logits row per fed position from the last
    prompt token on).  After chip_smoke.py:paged_logits."""
    import jax.numpy as jnp
    from mxnet_tpu.models import decoder
    cfg, S, chunk = engine.cfg, engine.page_size, engine.prefill_chunk
    built = decoder.fn_cache_stats()["compiles"]
    prefill = decoder.make_prefill_chunk(cfg, S, chunk,
                                         sharding=engine.sharding)
    decode = decoder.make_decode_step(cfg, S, sharding=engine.sharding)
    if decoder.fn_cache_stats()["compiles"] != built:
        raise RuntimeError("the logits check would drive a program the "
                           "engine does not run")
    kp, vp = (decoder.fresh_pool(cfg, engine.alloc.total_pages, S,
                                 engine.kv_dtype) for _ in range(2))
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


def prompt_ids(seed, stream, i, n, vocab):
    """Request i's prompt: fresh token ids for every issue, so no two
    requests share a prefix and the prefix cache never hits unless a mix
    says so."""
    return np.random.default_rng([seed, stream, i]).integers(
        0, vocab, size=n).tolist()


def check_reference(engine, lm, reference, check, limits, seed, log,
                    controls=()):
    """Before the window: the engine's own compiled programs, driven by hand
    over one prompt and a few greedy steps, against the plain forward, every
    logit.  Returns the readings of the rows' errors, each beside the limit
    the configuration names for it, and the same readings of each of
    ``controls`` (the reference at a lower precision)."""
    prompt = prompt_ids(seed, 1, 0, check["prompt_tokens"],
                        lm.config.vocab_size)
    fed, got = paged_logits(engine, prompt, check["decode_steps"])
    ref = np.asarray(reference(lm.jax_params(), lm.config, fed, got.shape[0]))
    out = row_readings(got, ref, limits)
    rms = float(np.sqrt(np.mean(np.square(got - ref))) / ref.std())
    log("reference check: %d prompt tokens + %d decode steps through the "
        "paged cache vs the plain float32 forward, %d rows: max %.4f q90 "
        "%.4f median %.4f rms %.4f of std(reference); limits: %s -> %s"
        % (len(prompt), check["decode_steps"], len(ref),
           *(e["value"] for e in out), rms, describe_limits(out),
           "ok" if held(out) else "DISAGREE"))
    for dtype in controls:
        if dtype == "altered":      # a fault of the answers, not of these
            continue
        low = row_readings(np.asarray(reference(
            lm.jax_params(), lm.config, fed, got.shape[0], dtype=dtype)),
            ref, limits, who="control_" + dtype)
        log("control (the %s reference in the program's place, the same "
            "logits): max %.4f q90 %.4f median %.4f of std(reference) -> %s"
            % (dtype, *(e["value"] for e in low),
               "holds" if held(low) else "fails"))
        out += low
    return out


def check_served(lm, reference, records, seed, check, limits, n_rows, pad_to,
                 log, controls=()):
    """After the window: what the timed path answered, at the timed sizes.
    A sample of the requests it finished (``sample_served``), each run once
    through the plain forward with the tokens it was answered with; the
    readings are of the gaps by which the served tokens' logits lie below the
    reference's best.  ``controls`` reads beside them, at every position of
    the same prompts and tokens, the token that the reference at each lower
    precision puts first, and (``"altered"``) the token next to the served
    one in the vocabulary, judged the same way."""
    params, cfg = lm.jax_params(), lm.config
    gaps, differ = [], 0
    beside = {name: [] for name in controls}
    sample = sample_served(records, seed, check["served_tokens"])
    for r in sample:
        served = np.asarray(r["tokens"])
        args = (reference, params, cfg,
                prompt_ids(seed, 2, r["i"], r["prompt"], cfg.vocab_size),
                served, n_rows, pad_to)
        judge = reference_over(*args)
        gap, top = judge(served)
        gaps.extend(gap.tolist())
        differ += int((top != served).sum())
        for name in controls:
            other = ((served + 1) % cfg.vocab_size if name == "altered"
                     else reference_over(*args, dtype=name)(served)[1])
            beside[name].extend(judge(other)[0].tolist())
    out = gap_readings(gaps, limits)
    log("served-token check: %d requests (the longest among them), %d served "
        "tokens through the plain float32 forward: gap below the reference's "
        "best widest %.4f q90 %.4f of std(logits), mean %.5f, %d tokens are "
        "not the reference's first; limits: %s -> %s"
        % (len(sample), len(gaps), *(e["value"] for e in out),
           np.mean(gaps) if gaps else 0.0, differ, describe_limits(out),
           "ok" if held(out) else "DISAGREE"))
    for name, g in beside.items():
        low, of = gap_readings(g, limits, control=name), statistics_of(g)
        g = np.asarray(g if g else [np.inf])
        log("control (%s, in the program's place at the same positions): "
            "gap widest %.4f q90 %.4f mean %.5f least %.4f, %d of %d tokens "
            "are not the float32 reference's first -> %s"
            % (name, of["max"], of["q90"], g.mean(), g.min(),
               np.count_nonzero(g), g.size,
               "holds" if held(low) else "fails"))
        out += low
    return out


# ---------------------------------------------------------------------------
def serve_window(ctx, lm, table, reference, limits):
    """Engine and server up, the check of the engine's programs, the warm-up
    requests, the measured window with its drain, engine and server down.
    Nothing of the engine outlives the call, so its pools are free when the
    reference runs over the answers."""
    from mxnet_tpu.serving import DecodeEngine, ModelServer, ServingClient
    from chipbench import trace as reduction
    log, config, traffic = ctx["log"], ctx["config"], ctx["traffic"]
    seed, seconds, lap = ctx["seed"], ctx["seconds"], ctx["clock"].lap
    limit = seconds + traffic["drain_s"] + 30.0   # above the drain limit
    engine = DecodeEngine(lm, **config["engine"])
    server = ModelServer(request_timeout_s=limit)
    tracing = threading.Event()
    try:
        server.attach_engine("lm", engine)      # warmup(): compiles
        host, port = server.start()
        log("engine: slots=%d page_size=%d pages=%d max_ctx=%d "
            "prefill_chunk=%d async=%s kv_dtype=%s prefix_cache=%s"
            % (engine.slots, engine.page_size, engine.alloc.total_pages,
               engine.max_ctx, engine.prefill_chunk, engine.async_decode,
               engine.kv_dtype, engine.prefix_cache is not None))
        lap("engine warm-up")
        check = check_reference(engine, lm, reference, config["check"],
                                limits, seed, log, ctx["controls"])
        lap("reference check")

        local = threading.local()

        def ask(i, prompt_len, output_len, stream=2):
            ids = prompt_ids(seed, stream, i, prompt_len,
                             lm.config.vocab_size)
            if getattr(local, "cli", None) is None:
                local.cli = ServingClient(host, port, timeout=limit,
                                          retries=0)
            return local.cli.generate("lm", ids,
                                      max_tokens=output_len)["tokens"]

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
        stats = {"serving": server.metrics.snapshot()["models"].get("lm", {}),
                 "engine": engine.stats()}
        return records, t_open, stats, check
    finally:
        if tracing.is_set():
            reduction.stop()
        server.stop(drain=False, timeout=10.0)
        engine.stop(drain=False)


def sift(entries, readings):
    """The entries a limit of the configuration holds, for ``checks``; the
    others go into ``readings`` (a number that is not finite as 1e30, which
    is JSON)."""
    for e in entries:
        if e["limit"] is None:
            readings[e["name"]] = (e["value"] if math.isfinite(e["value"])
                                   else 1e30)
    return [e for e in entries if e["limit"] is not None]


def run(ctx):
    import jax
    log, config, traffic = ctx["log"], ctx["config"], ctx["traffic"]
    seed, seconds, lap = ctx["seed"], ctx["seconds"], ctx["clock"].lap
    limits = check_limits(config)       # before anything is built
    lap("imports + device start")
    kwargs = {k: config[v] for k, v in config["builder_kwargs"].items()}
    lm = ctx["resolve"](config["builder"])(seed=seed, **kwargs)
    jax.block_until_ready(lm.jax_params())
    lap("weights")
    table = make_table(traffic)
    log("table of %d rows: prompt %s output %s"
        % (len(table), describe([p for p, _ in table]),
           describe([o for _, o in table])))
    reference = ctx["resolve"](config["reference"], "serve")
    records, t_open, stats, check = serve_window(ctx, lm, table, reference,
                                                 limits)
    readings = {}
    check = sift(check, readings)

    out = summarize(records, t_open, seconds)
    log("window %.3f s + %.3f s to the last answer: %d requests issued, %d "
        "answered in full, %d failed; %d answered inside the window, the "
        "last %.3f s after its open: the served rate is over those"
        % (seconds, out["drain_s"] - seconds, out["attempted"],
           out["attempted"] - out["failed"], out["failed"],
           out["answered_in_window"], out["rate_span_s"]))
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
    log("sent, ms after the window's open, in order of issue: %s" % " ".join(
        "%.0f" % (1e3 * (r["t_sent"] - t_open)) for r in records
        if r["t_sent"] is not None))

    def after_window():
        """Run by run.py once the device's memory peak has been read."""
        served = sift(check_served(
            lm, reference, records, seed, config["check"], limits,
            max(o for _, o in table), lm.config.max_length, log,
            ctx["controls"]), readings)
        for name, value in readings.items():
            print("reading %s: %.6g (the configuration names no limit for it)"
                  % (name, value), file=sys.stderr, flush=True)
        return served

    return {
        "attempted": out["attempted"], "failed": out["failed"],
        "checks": check + [{"name": "requests_failed",
                            "value": out["failed"], "limit": 0}],
        "readings": readings, "after_window": after_window,
        "end_to_end": out, "stats": stats,
    }
