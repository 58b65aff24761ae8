"""Parameters, state, operations and bytes of the ``brumby`` block (degree-2
power retention in every layer, the dense SiLU-gated MLP) from the keys its
configuration publishes, and the shares of the chip's peaks that the
measured program times make of them.  The counts are of the model, whatever
implements it.  The state bytes a launch moves are the engine's own count
(``state_bytes_decode_total``, ``state_bytes_prefill_total``: entries read
and written, known to the host), so a program that keeps no state entry
reads nothing here, and neither does a program that does not count them."""
from chipbench.readers import trace_module, walk


def sizes(c):
    """The widths the counts below need, from the published keys of a
    configuration file (or its ``published`` values merged over it)."""
    D = c["head_dim"]
    return {"C": c["hidden_size"], "layers": c["num_hidden_layers"],
            "H": c["num_attention_heads"], "KVH": c["num_key_value_heads"],
            "D": D, "F": D * (D + 1) // 2, "I": c["intermediate_size"],
            "V": c["vocab_size"]}


def param_counts(c):
    """Parameters by part; ``layers_matmul`` are the weights a token is
    multiplied by in the layers (what a step streams once and what costs 2
    operations a token), ``head_matmul`` the untied head's."""
    s = sizes(c)
    C, H, KVH, D = s["C"], s["H"], s["KVH"], s["D"]
    parts = {"q": H * D * C, "k": KVH * D * C, "v": KVH * D * C,
             "o": C * H * D, "mlp": 3 * C * s["I"], "norms": 2 * C,
             "qk_norm": 2 * D,
             "gate": KVH * C + KVH}
    layer = sum(parts.values())
    matmul = (parts["q"] + parts["k"] + parts["v"] + parts["o"]
              + parts["mlp"] + KVH * C)
    embedding = s["V"] * C * (1 if c["tie_word_embeddings"] else 2)
    return dict(parts, layer=layer, embedding=embedding,
                layers_matmul=s["layers"] * matmul,
                head_matmul=s["V"] * C,
                total=s["layers"] * layer + embedding + C)  # + final norm


def state_entry_bytes(c, layers=None):
    """One sequence's state over its layers (or ``layers``), float32: S
    (F x D) and z (F) of every KV head."""
    s = sizes(c)
    n = s["layers"] if layers is None else layers
    return n * s["KVH"] * (s["F"] * s["D"] + s["F"]) * 4


def weight_bytes(c, itemsize=2):
    """Every weight a step reads whole, at ``itemsize``: all but the
    embedding table, of which a step reads its tokens' rows only."""
    p = param_counts(c)
    return itemsize * (p["total"] - sizes(c)["V"] * sizes(c)["C"])


def prefill_launch_flops(c, tokens):
    """Operations of one prefill chunk of ``tokens`` at one pass: 2 a
    weight and token in the layers, the head for the chunk's last token,
    and per layer the retention's own products: inside the chunk the
    (q.k)^2 weights and their sum over the values under the causal mask,
    from the state before it ``phi(q) S`` and ``phi(q) . z`` for every query
    head, and into the state after it ``phi(k)^T V``."""
    p, s = param_counts(c), sizes(c)
    H, KVH, D, F = s["H"], s["KVH"], s["D"], s["F"]
    causal = tokens * (tokens + 1) // 2
    retention = (2 * 2 * H * D * causal             # (q.k), weights x v
                 + 2 * H * tokens * F * (D + 1)     # phi(q) S, phi(q) z
                 + 2 * KVH * tokens * F * D)        # phi(k)^T v
    return (2 * p["layers_matmul"] * tokens + 2 * p["head_matmul"]
            + s["layers"] * retention)


def _per_launch(facts, counter, launches):
    counters = walk(facts, ["stats", "serving", "counters"]) or {}
    if not counters.get(launches) or counter not in counters:
        return None
    return counters[counter] / counters[launches]


def decode_step_roofline(facts):
    """A decode step is bound by memory bandwidth: every weight outside the
    embedding once at 2 bytes, and the state entries its live lanes read
    and write (the engine's count over the window's decode launches), over
    the chip's HBM bytes/s, as a share of the step program's median device
    time."""
    state = _per_launch(facts, "state_bytes_decode_total",
                        "decode_steps_total")
    if not facts.get("peaks") or not state:
        return None
    c = facts["config"]
    step_s = trace_module(facts, c["programs"]["decode"], scale=1.0)
    if not step_s:
        return None
    need = weight_bytes(c) + state
    return 100.0 * need / facts["peaks"]["hbm_bytes_per_s"] / step_s


def prefill_launch_roofline(facts):
    """A prefill chunk: the larger of its bytes (the weights, and the state
    entries it reads and writes by the engine's count) over the HBM bytes/s
    and its operations at one pass over the bf16 peak, as a share of the
    chunk program's median device time; the tokens of a launch are the
    window's mean (prompt tokens over launches)."""
    state = _per_launch(facts, "state_bytes_prefill_total",
                        "prefill_launches_total")
    tokens = _per_launch(facts, "prefill_tokens_total",
                         "prefill_launches_total")
    if not facts.get("peaks") or not state or not tokens:
        return None
    c = facts["config"]
    launch_s = trace_module(facts, c["programs"]["prefill"], scale=1.0)
    if not launch_s:
        return None
    least = max((weight_bytes(c) + state) / facts["peaks"]["hbm_bytes_per_s"],
                prefill_launch_flops(c, tokens) / facts["peaks"]["flops_bf16"])
    return 100.0 * least / launch_s
