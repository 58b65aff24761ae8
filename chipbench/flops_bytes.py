"""Operations and bytes from shapes, and the shares of the chip's peaks
that the measured times make of them.  Every function takes the run's facts
(see readers.py) and returns a percentage, or None where a time is missing."""
from chipbench.readers import trace_module, walk


def decoder_matmul_params(c):
    """Weights a decoder LM's step multiplies by: per layer the four
    attention projections (4 C^2) and the two of the FFN (2 C F), and the
    tied output projection (V C)."""
    C, F = c["n_embd"], c["n_inner"]
    return c["n_layer"] * (4 * C * C + 2 * C * F) + c["vocab_size"] * C


def decode_step_bytes(facts):
    """Bytes one decode step has to read at the least: every matmul weight
    once (float32) and the keys and values of every live token (float32, both,
    all layers and KV heads).  The live tokens are the window's mean from the
    load generator's records (serve.summarize)."""
    c = facts["config"]
    live = walk(facts, ["end_to_end", "live_tokens_mean"])
    if live is None:
        return None
    kv_token = 2 * c["n_layer"] * c["n_embd"] * 4   # K and V: heads x head_dim
    return 4 * decoder_matmul_params(c) + live * kv_token


def decode_step_roofline(facts):
    """A decode step is bound by memory bandwidth (one token a sequence):
    the least time is its bytes over the chip's HBM bytes/s, as a share of
    the step program's median device time."""
    if not facts.get("peaks"):
        return None
    step_s = trace_module(facts, facts["config"]["programs"]["decode"],
                          scale=1.0)
    need = decode_step_bytes(facts)
    if not step_s or need is None:
        return None
    return 100.0 * need / facts["peaks"]["hbm_bytes_per_s"] / step_s


def encoder_train_flops_per_token(c, length):
    """Forward and backward of a BERT-style encoder with an MLM head over
    every position: 6 per matmul weight (per layer 4 C^2 + 2 C F, the MLM
    transform C^2, the tied output projection V C), and attention's two
    batched products, 2 x 2 L C forward and twice that backward, per layer.
    Recomputed operations (flash attention's backward) do not count."""
    C, F = c["hidden_size"], c["intermediate_size"]
    weights = (c["num_hidden_layers"] * (4 * C * C + 2 * C * F) + C * C
               + c["vocab_size"] * C)
    return 6 * weights + 12 * c["num_hidden_layers"] * length * C


def train_mfu(facts):
    """Model FLOP/s utilisation: the operations the model needs per token
    times the tokens per second of this run, over the chips' bf16 peak."""
    rate = walk(facts, ["end_to_end", "tokens_per_s"])
    if rate is None or not facts.get("peaks"):
        return None
    c = facts["config"]
    flops = encoder_train_flops_per_token(c, c["sequence_length"])
    return 100.0 * flops * rate / (facts["chips"]
                                   * facts["peaks"]["flops_bf16"])
