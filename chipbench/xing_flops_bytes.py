"""Parameters, operations and bytes of the ``xing4_0`` block (latent attention
over a paged latent cache, mHC's residual streams, a leading dense layer and
routed layers with all their experts held) from the keys its configuration
publishes, and the shares of the chip's peaks that the measured program
times make of them.  The counts are of the model, whatever implements it.
Every reader takes the run's facts (see readers.py) and returns a
percentage, or None where there is nothing to read: the engine's latent
byte counters (``latent_bytes_*_total``) and the routed layers' own counts
are part of what is read, so a program that has none reads nothing."""
from chipbench.readers import trace_module, walk


def sizes(c):
    """The widths the counts below need, from the published keys."""
    layers = c["num_hidden_layers"]
    dense = c["first_k_dense_replace"]
    rows = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    return {"C": c["hidden_size"], "H": c["num_attention_heads"],
            "layers": layers, "dense": dense, "routed": layers - dense,
            "q_rank": c["q_lora_rank"], "kv_rank": c["kv_lora_rank"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "v": c["v_head_dim"], "F": c["intermediate_size"],
            "E": c["moe_intermediate_size"],
            "experts": c["n_routed_experts"],
            "shared": c["n_shared_experts"] * c["moe_intermediate_size"],
            "n": c["hc_mult"], "V": c["vocab_size"],
            "row": -(-rows // 128) * 128}


def param_counts(c):
    """Parameters by part.  ``*_matmul`` are the weights a token is
    multiplied by outside the routed experts (what a step streams once and
    what costs 2 operations a token); ``expert`` is one routed expert."""
    s = sizes(c)
    C, H = s["C"], s["H"]
    attention = (C * s["q_rank"] + s["q_rank"] * H * (s["nope"] + s["rope"])
                 + C * (s["kv_rank"] + s["rope"])
                 + s["kv_rank"] * H * (s["nope"] + s["v"]) + H * s["v"] * C)
    mix = s["n"] * (s["n"] + 2) * s["n"] * C        # one sub-layer's phi
    dense_ffn = 3 * C * s["F"]
    expert = 3 * C * s["E"]
    beside = 3 * C * s["shared"] + s["experts"] * C     # shared, router
    layers_matmul = (s["layers"] * (attention + 2 * mix)
                     + s["dense"] * dense_ffn + s["routed"] * beside)
    head_matmul = s["V"] * C + s["n"] * s["n"] * C
    return {"attention": attention, "mix": mix, "dense_ffn": dense_ffn,
            "expert": expert, "beside_experts": beside,
            "experts_held": s["routed"] * s["experts"] * expert,
            "layers_matmul": layers_matmul, "head_matmul": head_matmul,
            "total": (layers_matmul + s["routed"] * s["experts"] * expert
                      + head_matmul + s["V"] * C)}


def latent_row_bytes(c):
    """One token's cached latent rows over the layers, padded to whole lane
    tiles as the pool holds them, in the dtype the configuration caches
    them in (``kv_cache_dtype``)."""
    itemsize = 4 if c.get("kv_cache_dtype") == "float32" else 2
    return sizes(c)["layers"] * sizes(c)["row"] * itemsize


def weight_bytes(c, experts_hit, itemsize=2):
    """Weights a launch reads at the least: every matmul weight outside the
    routed experts once (the head among them; the embedding's rows are
    gathered, not streamed) and ``experts_hit`` routed experts (summed over
    the routed layers)."""
    p = param_counts(c)
    return itemsize * (p["layers_matmul"] + p["head_matmul"]
                       + experts_hit * p["expert"])


def prefill_launch_flops(c, tokens, end, pairs):
    """Operations of one prefill chunk of ``tokens`` queries whose last
    position is ``end - 1``, at one pass: 2 a weight and token outside the
    routed experts, 2 an expert's weight for each of the ``pairs``
    token-expert pairs (summed over the routed layers), the head for the
    chunk's last token alone, and attention's two products over each
    query's context (``nope + rope`` for the scores, ``v`` for the values,
    every head, every layer; the up-projection of the context's rows is the
    program's choice and not counted: a lower bound)."""
    p, s = param_counts(c), sizes(c)
    seen = tokens * (end - tokens) + tokens * (tokens + 1) / 2
    attention = (s["layers"] * 2 * s["H"]
                 * (s["nope"] + s["rope"] + s["v"]) * seen)
    return (2 * p["layers_matmul"] * tokens + 2 * p["expert"] * pairs
            + 2 * p["head_matmul"] + attention)


def _counter(facts, name):
    counters = walk(facts, ["stats", "serving", "counters"]) or {}
    return counters.get(name)


def _routed(facts, phase, name):
    """A routed layer's count per launch of the whole program: the mean per
    layer and launch times the routed layers."""
    per_layer = walk(facts, ["stats", "engine", "experts", phase,
                             name + "_per_launch"])
    if per_layer is None:
        return None
    return per_layer * sizes(facts["config"])["routed"]


def decode_step_roofline(facts):
    """A decode step is bound by memory bandwidth: the weights outside the
    embedding once, the routed experts its live tokens chose (the routed
    layers' own count) and the live lanes' latent rows (the engine's count,
    ``latent_bytes_decode_total``, over the window's decode launches), over
    the chip's HBM bytes/s, as a share of the step program's median device
    time."""
    latent = _counter(facts, "latent_bytes_decode_total")
    steps = _counter(facts, "decode_steps_total")
    hit = _routed(facts, "decode", "experts_hit")
    if not facts.get("peaks") or not latent or not steps or hit is None:
        return None
    c = facts["config"]
    step_s = trace_module(facts, c["programs"]["decode"], scale=1.0)
    if not step_s:
        return None
    need = weight_bytes(c, hit) + latent / steps
    return 100.0 * need / facts["peaks"]["hbm_bytes_per_s"] / step_s


def prefill_launch_roofline(facts):
    """A prefill chunk: its operations at one bfloat16 pass over the bf16
    peak, or its bytes (the weights, the experts its tokens chose, the
    latent rows its attention reads) over the HBM bytes/s where that is
    more, as a share of the chunk program's median device time.  A
    launch's tokens are the window's mean (prompt tokens over launches) and
    its context's end the mean of the rows its attention read (the
    engine's count) over a token's row bytes."""
    latent = _counter(facts, "latent_bytes_prefill_total")
    launches = _counter(facts, "prefill_launches_total")
    pairs = _routed(facts, "prefill", "pairs")
    hit = _routed(facts, "prefill", "experts_hit")
    if not facts.get("peaks") or not latent or not launches \
            or None in (pairs, hit):
        return None
    c = facts["config"]
    launch_s = trace_module(facts, c["programs"]["prefill"], scale=1.0)
    if not launch_s:
        return None
    tokens = _counter(facts, "prefill_tokens_total") / launches
    end = latent / launches / latent_row_bytes(c)
    least = max(prefill_launch_flops(c, tokens, end, pairs)
                / facts["peaks"]["flops_bf16"],
                (weight_bytes(c, hit) + latent / launches)
                / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / launch_s
