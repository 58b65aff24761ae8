"""``correct`` has to come out false when the timed path is broken, and the
control has to fail (PR 28).

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_faults.py -q -p no:cacheprovider

The first test skips run.py's look for a chip and drives the rest of a run of a
serving cell, in this process, at a tiny size: sound, then with a token altered
where the answer is produced, then with an answer cut short.  The second puts
the 8-bit references in the program's place at GPT-2-small's own widths.  No
number here is a measurement."""
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0:0] = [ROOT, HERE]

from chipbench import run, serve  # noqa: E402
from test_chipbench import TINY  # noqa: E402

CELL = "gpt2s_chat_closed"


def drive_tiny(monkeypatch, capsys, tmp_path, fault=None):
    """run.main() on the CPU with the cell's files cut to the tiny size as
    they are loaded (no --override, which would make the run incorrect by
    itself), and ``fault(tokens) -> tokens`` laid over every answer."""
    import jax
    from mxnet_tpu.serving import ServingClient
    real_load = run.load

    def tiny_load(*parts):
        doc = real_load(*parts)
        if parts[0] == "peaks.json":
            doc["chips"]["cpu"] = doc["chips"]["TPU v5 lite"]
        if parts[0] in ("configs", "traffic"):
            run.merge(doc, TINY["gpt2-small-serve"][parts[0].rstrip("s")])
        return doc

    monkeypatch.setattr(run, "load", tiny_load)
    monkeypatch.setattr(run, "device_gate", lambda chips, allow: jax.devices())
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    if fault is not None:
        real = ServingClient.generate

        def generate(self, model, prompt, max_tokens=16, **kw):
            out = real(self, model, prompt, max_tokens=max_tokens, **kw)
            return dict(out, tokens=fault(list(out["tokens"])))
        monkeypatch.setattr(ServingClient, "generate", generate)
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", CELL, "--seed", "3000000019", "--seconds",
        "2", "--trace", "0"])
    assert run.main() == 0
    captured = capsys.readouterr()
    result = json.loads(captured.out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    # the numbers compared are the last lines on standard error too
    last = captured.err.strip().splitlines()[-len(result["checks"]):]
    assert [line.split()[1].rstrip(":") for line in last] \
        == list(result["checks"])
    return result


def test_a_broken_timed_path_is_not_correct(monkeypatch, capsys, tmp_path):
    sound = drive_tiny(monkeypatch, capsys, tmp_path)
    assert sound["correct"] is True and sound["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in sound["checks"].values())
    assert sound["checks"]["served_gap_max"]["value"] < 1e-3

    # a token altered where the answer is produced: the last of every answer
    def alter(tokens):
        return tokens[:-1] + [(tokens[-1] + 1) % 128]
    broken = drive_tiny(monkeypatch, capsys, tmp_path, fault=alter)
    assert broken["correct"] is False and broken["failed"] == 0
    gap = broken["checks"]["served_gap_max"]
    assert gap["value"] > gap["limit"]
    assert broken["checks"]["program_logits_max_err"]["value"] < 1e-3

    # an answer cut short is a failed request
    short = drive_tiny(monkeypatch, capsys, tmp_path,
                       fault=lambda tokens: tokens[:-1])
    assert short["correct"] is False
    assert short["failed"] == short["attempted"] > 0
    assert short["checks"]["requests_failed"]["value"] == short["failed"]


def test_the_eight_bit_controls_fail_at_the_cells_widths():
    """The reference at GPT-2-small's widths (12 x 768, 12 heads, vocabulary
    50257) over one sequence, weights drawn here.  In the program's place the
    fp8 forward fails both numbers: its logits lie further from the float32
    reference's than ``LOGIT_TOL``, and the tokens it puts first further
    below the reference's best than ``SERVED_GAP_TOL``.  The int8 forward
    fails the first.  The float32 forward's own first tokens lie at 0, and a
    token altered lies standard deviations below."""
    rng = np.random.default_rng(2718281829)
    units, hidden, vocab, layers, n_ctx, n_rows = 768, 3072, 50257, 12, 256, 192

    def xavier(*shape):
        bound = np.sqrt(6.0 / (shape[0] + shape[-1]))
        return rng.uniform(-bound, bound, shape).astype(np.float32)

    def layer():
        out = {k: xavier(units, units) for k in ("wq", "wk", "wv", "wo")}
        out.update(w1=xavier(hidden, units), w2=xavier(units, hidden))
        out.update({b: np.zeros(units, np.float32)
                    for b in ("bq", "bk", "bv", "bo", "b2", "ln1b", "ln2b")})
        out.update(b1=np.zeros(hidden, np.float32),
                   ln1g=np.ones(units, np.float32),
                   ln2g=np.ones(units, np.float32))
        return out

    params = {"embed": xavier(vocab, units), "pos": xavier(n_ctx, units),
              "layers": [layer() for _ in range(layers)]}
    cfg = types.SimpleNamespace(num_heads=12, num_kv_heads=12, head_dim=64)
    prompt = rng.integers(0, vocab, size=n_ctx - n_rows + 1).tolist()
    filler = rng.integers(0, vocab, size=n_rows).tolist()

    def over(dtype="float32"):
        return serve.reference_over(serve.reference_logits, params, cfg,
                                    prompt, filler, n_rows, n_ctx,
                                    dtype=dtype)

    judge = over()
    first = judge(filler)[1]
    assert judge(first)[0].max() == 0.0
    low = over("float8_e4m3fn")(filler)[1]
    assert judge(low)[0].max() > 2 * serve.SERVED_GAP_TOL
    altered = judge((np.asarray(first) + 1) % vocab)[0]
    assert altered.min() > 2 * serve.SERVED_GAP_TOL

    fed = prompt + filler[:-1]
    ref = np.asarray(serve.reference_logits(params, cfg, fed, 25))
    for dtype, times in (("float8_e4m3fn", 3.0), ("int8", 1.2)):
        got = np.asarray(serve.reference_logits(params, cfg, fed, 25,
                                                dtype=dtype))
        assert np.abs(got - ref).max() / ref.std() > times * serve.LOGIT_TOL
