"""Parameters, operations and bytes of the ``solar_open2`` block (gated
delta-rule layers, gated position-free attention, routed experts beside a
shared one) from the keys its configuration publishes, for the share of it
that one chip of an expert-parallel group holds, and the shares of the
chip's peaks that the measured program times make of them.  The counts are
of the model, whatever implements it.  Every reader takes the run's facts
(see readers.py) and returns a percentage, or None where there is nothing
to read: the routed layers' own counts (``engine.stats()["experts"]``) are
part of what is read, so a program that has none reads nothing."""
from chipbench.readers import trace_module, walk


def sizes(c):
    """The widths the counts below need, from the published keys; ``c`` is
    a configuration file (its ``n_routed_experts`` the experts held, its
    ``expert_parallel`` the chips that share a layer) or its ``published``
    values merged over it."""
    la = c["linear_attn_config"]
    layers = c["num_hidden_layers"]
    attention = sum(i < layers for i in c["gqa_layers"])
    return {"C": c["hidden_size"], "layers": layers, "attention": attention,
            "delta": layers - attention,
            "HD": c["num_attention_heads"] * c["head_dim"],
            "KVD": c["num_key_value_heads"] * c["head_dim"],
            "dH": la["num_heads"], "dd": la["head_dim"],
            "K": la["short_conv_kernel_size"], "E": c["moe_intermediate_size"],
            "held": c["n_routed_experts"],
            "experts": c["n_routed_experts"] * c.get("expert_parallel", 1),
            "k": c["num_experts_per_tok"],
            "shared": c["n_shared_experts"] * c["moe_intermediate_size"],
            "V": c["vocab_size"]}


def param_counts(c):
    """Parameters by part.  ``*_matmul`` are the weights a token is
    multiplied by outside the routed experts (what a step streams once and
    what costs 2 operations a token); ``expert`` is one routed expert."""
    s = sizes(c)
    C, r = s["C"], s["dd"]
    Hd = s["dH"] * s["dd"]
    attention_matmul = 3 * C * s["HD"] + 2 * C * s["KVD"]   # q, gate, o; k, v
    delta_matmul = (4 * C * Hd + 2 * (r * C + Hd * r)       # q, k, v, o; f, g
                    + s["dH"] * C)                          # beta
    delta_mixer = delta_matmul + 3 * Hd * s["K"] + s["dH"] + Hd + s["dd"]
    expert = 3 * C * s["E"]
    shared = 3 * C * s["shared"]
    router = s["experts"] * C
    beside = shared + router + s["experts"] + 2 * C     # + bias, two norms
    layers_matmul = (s["attention"] * attention_matmul
                     + s["delta"] * delta_matmul
                     + s["layers"] * (shared + router))
    return {
        "attention_mixer": attention_matmul, "delta_mixer": delta_mixer,
        "expert": expert, "shared": shared, "router": router,
        "attention_layer": attention_matmul + beside,
        "delta_layer": delta_mixer + beside,
        "experts_held": s["layers"] * s["held"] * expert,
        "embedding": 2 * s["V"] * C + C,    # untied head; the final norm
        "total": (s["attention"] * (attention_matmul + beside)
                  + s["delta"] * (delta_mixer + beside)
                  + s["layers"] * s["held"] * expert + 2 * s["V"] * C + C),
        "layers_matmul": layers_matmul, "head_matmul": s["V"] * C,
    }


def published_param_count(c):
    """The whole model's parameters: the published depth, experts and
    vocabulary (``c["published"]``) in place of the chip's share."""
    whole = dict(c, expert_parallel=1, **c["published"])
    return param_counts(whole)["total"]


def kv_bytes_per_token(c):
    """Keys and values of one token over the attention layers, in the dtype
    the configuration caches them in (``kv_cache_dtype``)."""
    s = sizes(c)
    itemsize = 4 if c.get("kv_cache_dtype") == "float32" else 2
    return 2 * s["attention"] * s["KVD"] * itemsize


def state_entry_bytes(c):
    """The delta rule's state of one sequence over its layers, float32:
    ``S`` (d x d) of every head and the last K - 1 inputs of the three
    convolutions."""
    s = sizes(c)
    Hd = s["dH"] * s["dd"]
    return s["delta"] * (Hd * s["dd"] + 3 * (s["K"] - 1) * Hd) * 4


def launch_bytes(c, experts_hit, tokens_kv, entries, itemsize=2):
    """Bytes a launch has to move at the least: every matmul weight outside
    the routed experts once (the head among them), the ``experts_hit``
    held experts (summed over the layers) that a token of the launch chose,
    ``tokens_kv`` tokens' keys and values, ``entries`` state entries."""
    p = param_counts(c)
    return (itemsize * (p["layers_matmul"] + p["head_matmul"]
                        + experts_hit * p["expert"])
            + tokens_kv * kv_bytes_per_token(c)
            + entries * state_entry_bytes(c))


def prefill_launch_flops(c, tokens, pairs):
    """Operations of one prefill chunk at one bfloat16 pass: 2 a weight and
    token outside the routed experts, 2 an expert's weight for each of the
    ``pairs`` token-expert pairs on held experts (summed over the layers),
    the head for the chunk's last token alone, and attention's two products
    under the causal mask inside the chunk (what lies before the chunk is
    not counted: a lower bound)."""
    p, s = param_counts(c), sizes(c)
    attention = s["attention"] * 2 * 2 * s["HD"] * tokens * (tokens + 1) // 2
    return (2 * p["layers_matmul"] * tokens + 2 * p["expert"] * pairs
            + 2 * p["head_matmul"] + attention)


def _counted(facts, phase, name):
    """A routed layer's count per launch of the whole program: the mean per
    layer and launch times the layers."""
    per_layer = walk(facts, ["stats", "engine", "experts", phase,
                             name + "_per_launch"])
    if per_layer is None:
        return None
    return per_layer * facts["config"]["num_hidden_layers"]


def decode_step_roofline(facts):
    """A decode step is bound by memory bandwidth: its bytes over the chip's
    HBM bytes/s, as a share of the step program's median device time.  The
    held experts a live token chose come from the routed layers' own count
    (so a program that skips the others cannot read above 100 %), the live
    lanes are the window's decode occupancy times the slots, the live
    tokens the load generator's mean; each live lane's state entry is read
    and written."""
    occupancy = walk(facts, ["stats", "serving", "generate",
                             "decode_occupancy"])
    live = walk(facts, ["end_to_end", "live_tokens_mean"])
    hit = _counted(facts, "decode", "experts_hit")
    if not facts.get("peaks") or None in (occupancy, live, hit):
        return None
    c = facts["config"]
    step_s = trace_module(facts, c["programs"]["decode"], scale=1.0)
    if not step_s:
        return None
    lanes = occupancy * c["engine"]["slots"]
    need = launch_bytes(c, hit, live, 2 * lanes)
    return 100.0 * need / facts["peaks"]["hbm_bytes_per_s"] / step_s


def prefill_launch_roofline(facts):
    """A prefill chunk: the larger of its operations (one bfloat16 pass)
    over the bf16 peak and its bytes over the HBM bytes/s, as a share of
    the chunk program's median device time.  The tokens of a launch are the
    window's mean (prompt tokens over launches), the pairs on held experts
    and the held experts hit the routed layers' own counts."""
    counters = walk(facts, ["stats", "serving", "counters"]) or {}
    launches = counters.get("prefill_launches_total")
    pairs = _counted(facts, "prefill", "pairs")
    hit = _counted(facts, "prefill", "experts_hit")
    if not facts.get("peaks") or not launches or None in (pairs, hit):
        return None
    c = facts["config"]
    launch_s = trace_module(facts, c["programs"]["prefill"], scale=1.0)
    if not launch_s:
        return None
    tokens = counters["prefill_tokens_total"] / launches
    least = max(prefill_launch_flops(c, tokens, pairs)
                / facts["peaks"]["flops_bf16"],
                launch_bytes(c, hit, tokens, 2)
                / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / launch_s
