"""Operations and bytes of a hybrid state-space / attention decoder (the
``jamba`` model type) from the sizes its configuration publishes, and the
shares of the chip's peaks that the measured program times make of them.
The counts are of the model, whatever implements it.  Every reader takes
the run's facts (see readers.py) and returns a percentage, or None where
there is nothing to read."""
from chipbench.readers import trace_module, walk


def sizes(c):
    """The widths the counts below need, from the published keys."""
    C = c["hidden_size"]
    layers = c["num_hidden_layers"]
    attention = sum(i % c["attn_layer_period"] == c["attn_layer_offset"]
                    for i in range(layers))
    return {"C": C, "F": c["intermediate_size"],
            "di": c["mamba_expand"] * C, "N": c["mamba_d_state"],
            "K": c["mamba_d_conv"], "R": c["mamba_dt_rank"],
            "HD": C, "KVD": (C // c["num_attention_heads"]
                             * c["num_key_value_heads"]),
            "V": c["vocab_size"], "attention": attention,
            "state_space": layers - attention}


def param_counts(c):
    """Parameters by part.  ``*_matmul`` are the weights a token is
    multiplied by (what a step streams and what costs 2 FLOPs a token);
    the rest are norms, biases, the convolution, ``A_log`` and ``D``."""
    s = sizes(c)
    C, F, di, N, K, R = s["C"], s["F"], s["di"], s["N"], s["K"], s["R"]
    ssm_matmul = C * 2 * di + di * (R + 2 * N) + R * di + di * C
    ssm_mixer = ssm_matmul + (di * K + di) + di + di * N + di + (R + 2 * N)
    attention_mixer = 2 * C * s["HD"] + 2 * C * s["KVD"]
    mlp = 3 * C * F
    ssm_layer = ssm_mixer + mlp + 2 * C
    attention_layer = attention_mixer + mlp + 2 * C
    embedding = s["V"] * C + C          # tied to the head; the final norm
    return {
        "ssm_mixer": ssm_mixer, "attention_mixer": attention_mixer,
        "mlp": mlp, "ssm_layer": ssm_layer,
        "attention_layer": attention_layer, "embedding": embedding,
        "total": (s["state_space"] * ssm_layer
                  + s["attention"] * attention_layer + embedding),
        "layers_matmul": (s["state_space"] * (ssm_matmul + mlp)
                          + s["attention"] * (attention_mixer + mlp)),
        "head_matmul": s["V"] * C,
    }


def kv_bytes_per_token(c, itemsize=2):
    """Keys and values of one token over the attention layers."""
    s = sizes(c)
    return 2 * s["attention"] * s["KVD"] * itemsize


def state_entry_bytes(c):
    """The recurrent state of one sequence over the state-space layers,
    float32: ``h`` (d_inner x d_state) and the last K - 1 convolution
    inputs."""
    s = sizes(c)
    return s["state_space"] * s["di"] * (s["N"] + s["K"] - 1) * 4


def decode_step_bytes(c, live_lanes, live_tokens, itemsize=2):
    """Bytes one decode step has to move at the least: every matmul weight
    once (the embedding once, as the head), the keys and values of every
    live token, and one state entry read and written for each live lane."""
    p = param_counts(c)
    return (itemsize * (p["layers_matmul"] + p["head_matmul"])
            + live_tokens * kv_bytes_per_token(c, itemsize)
            + live_lanes * 2 * state_entry_bytes(c))


def prefill_launch_flops(c, tokens):
    """Operations of one prefill chunk of ``tokens`` tokens: 2 a matmul
    weight and token in the layers, the head for the chunk's last token
    alone, and attention's two products under the causal mask inside the
    chunk (what lies before the chunk is not counted: a lower bound)."""
    p, s = param_counts(c), sizes(c)
    attention = s["attention"] * 2 * 2 * s["HD"] * tokens * (tokens + 1) // 2
    return 2 * p["layers_matmul"] * tokens + 2 * p["head_matmul"] + attention


def prefill_launch_bytes(c, tokens, itemsize=2):
    """Bytes one prefill chunk has to move at the least: every matmul
    weight once, the state entry read and the one written, the chunk's
    keys and values written."""
    p = param_counts(c)
    return (itemsize * (p["layers_matmul"] + p["head_matmul"])
            + 2 * state_entry_bytes(c)
            + tokens * kv_bytes_per_token(c, itemsize))


def decode_step_roofline(facts):
    """A decode step is bound by memory bandwidth: its bytes over the
    chip's HBM bytes/s, as a share of the step program's median device
    time.  The live lanes are the window's decode occupancy times the
    slots, the live tokens the load generator's mean."""
    occupancy = walk(facts, ["stats", "serving", "generate",
                             "decode_occupancy"])
    live = walk(facts, ["end_to_end", "live_tokens_mean"])
    if not facts.get("peaks") or occupancy is None or live is None:
        return None
    c = facts["config"]
    step_s = trace_module(facts, c["programs"]["decode"], scale=1.0)
    if not step_s:
        return None
    need = decode_step_bytes(c, occupancy * c["engine"]["slots"], live)
    return 100.0 * need / facts["peaks"]["hbm_bytes_per_s"] / step_s


def prefill_launch_roofline(facts):
    """A prefill chunk of a model this size sits on the chip's ridge: the
    larger of its operations over the bf16 peak and its bytes over the HBM
    bytes/s, as a share of the chunk program's median device time."""
    if not facts.get("peaks"):
        return None
    c = facts["config"]
    launch_s = trace_module(facts, c["programs"]["prefill"], scale=1.0)
    if not launch_s:
        return None
    tokens = c["engine"]["prefill_chunk"]
    least = max(prefill_launch_flops(c, tokens) / facts["peaks"]["flops_bf16"],
                prefill_launch_bytes(c, tokens)
                / facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / launch_s
