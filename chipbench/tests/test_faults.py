"""``correct`` has to come out false when the timed path is broken, and the
control has to fail (PR 28).

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests/test_faults.py -q -p no:cacheprovider

The first test skips run.py's look for a chip and drives the rest of a run of a
serving cell, in this process, at a tiny size: sound, then with a token altered
where the answer is produced, then with an answer cut short.  The second does
the same under other limits: what the configuration names is compared, what it
does not name is printed and not compared (PR 34).  The third puts the 8-bit
references in the program's place at GPT-2-small's own widths and holds them
to the limits that configuration states.  The last: a configuration that does
not state its limits stops the run before anything is built.  No number here
is a measurement."""
import itertools
import json
import os
import re
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


def drive_tiny(monkeypatch, capsys, tmp_path, fault=None, limits=None,
               hard_choice=None):
    """run.main() on the CPU with the cell's files cut to the tiny size as
    they are loaded (no --override, which would make the run incorrect by
    itself), ``fault(tokens) -> tokens`` laid over every answer, ``limits``
    in the place of the configuration's ``check.limits`` and ``hard_choice``
    declared and explained as a configuration that routes would."""
    import jax
    from mxnet_tpu.serving import ServingClient
    real_load = run.load

    def tiny_load(*parts):
        doc = real_load(*parts)
        if parts[0] == "peaks.json":
            doc["chips"]["cpu"] = doc["chips"]["TPU v5 lite"]
        if parts[0] in ("configs", "traffic"):
            run.merge(doc, TINY["gpt2-small-serve"][parts[0].rstrip("s")])
        if parts[0] == "configs" and limits is not None:
            doc["check"]["limits"] = limits
        if parts[0] == "configs" and hard_choice is not None:
            doc["check"]["hard_choice"] = hard_choice
            doc["assumed"]["check.hard_choice"] = "a test's: none is made"
        return doc

    with monkeypatch.context() as patch:    # undone before the next drive
        patch.setattr(run, "load", tiny_load)
        patch.setattr(run, "device_gate", lambda chips, allow: jax.devices())
        patch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
        if fault is not None:
            real = ServingClient.generate

            def generate(self, model, prompt, max_tokens=16, **kw):
                out = real(self, model, prompt, max_tokens=max_tokens, **kw)
                return dict(out, tokens=fault(list(out["tokens"])))
            patch.setattr(ServingClient, "generate", generate)
        patch.setattr(sys, "argv", [
            "run.py", "--workload", CELL, "--seed", "3000000019", "--seconds",
            "2", "--trace", "0"])
        assert run.main() == 0
    captured = capsys.readouterr()
    result = json.loads(captured.out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    # the numbers compared are the last lines on standard error too
    err = captured.err.strip().splitlines()
    last = err[-len(result["checks"]):]
    assert [line.split()[1].rstrip(":") for line in last] \
        == list(result["checks"])
    # and before them every reading that no limit was named for, the same
    # numbers as under the result's ``readings``, which comes before ``checks``
    assert list(result)[-2] == "readings"
    assert [line.split()[1].rstrip(":") for line in err
            if line.startswith("reading ")] == list(result["readings"])
    assert not set(result["readings"]) & set(result["checks"])
    return result


def test_a_broken_timed_path_is_not_correct(monkeypatch, capsys, tmp_path):
    sound = drive_tiny(monkeypatch, capsys, tmp_path)
    assert sound["correct"] is True and sound["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in sound["checks"].values())
    assert sound["checks"]["served_gap_max"]["value"] < 1e-3
    # the limits are the configuration's, and all five readings are there
    limits = run.load("configs", "gpt2-small-serve.json")["check"]["limits"]
    assert {k: c["limit"] for k, c in sound["checks"].items()
            if k in limits} == limits
    assert set(sound["readings"]) == {
        "program_logits_q90_err", "program_logits_median_err",
        "served_gap_q90"}

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


def test_a_limit_named_is_compared_and_one_not_named_is_printed(
        monkeypatch, capsys, tmp_path):
    """The same tiny run under the limits a configuration that routes would
    name: the rows' 90th percentile, its hard choice declared, and the widest
    served gap.  The q90 is compared and the rows' maximum printed beside
    it; with the q90's limit under the reading the run is not ``correct``.
    Every served token is held whatever the rows' statistic: a token altered
    in one answer of the window fails."""
    routed = {"program_logits_q90_err": 0.08, "served_gap_max": 0.12}
    sound = drive_tiny(monkeypatch, capsys, tmp_path, limits=routed,
                       hard_choice="top-k of experts")
    assert sound["correct"] is True
    assert set(sound["checks"]) == set(routed) | {"requests_failed",
                                                  "compilations_in_window"}
    assert set(sound["readings"]) == {
        "program_logits_max_err", "program_logits_median_err",
        "served_gap_q90"}
    q90 = sound["checks"]["program_logits_q90_err"]["value"]
    assert 0 < q90 <= sound["readings"]["program_logits_max_err"]

    tight = drive_tiny(monkeypatch, capsys, tmp_path, hard_choice="top-k",
                       limits=dict(routed, program_logits_q90_err=q90 / 2))
    assert tight["correct"] is False
    assert tight["checks"]["program_logits_q90_err"]["value"] > q90 / 2
    assert tight["checks"]["served_gap_max"]["value"] < 1e-3

    def alter_once():
        """The last token of the twentieth answer of the window (the table's
        answers have 3 or 5 tokens, the warm-up's 4)."""
        calls = itertools.count()
        return lambda tokens: tokens[:-1] + [(tokens[-1] + 1) % 128] if (
            len(tokens) != 4 and next(calls) == 20) else tokens
    one = drive_tiny(monkeypatch, capsys, tmp_path, fault=alter_once(),
                     limits=routed, hard_choice="top-k of experts")
    assert one["correct"] is False
    gap = one["checks"]["served_gap_max"]
    assert gap["value"] > gap["limit"]
    assert one["readings"]["served_gap_q90"] < 1e-3


BAD_CHECKS = {
    "no_limits": ({"prompt_tokens": 21}, "check.limits"),
    "limits_that_are_no_map": ({"limits": 0.08}, "check.limits"),
    "none_of_the_rows_errors": ({"limits": {"served_gap_max": 0.12}},
                                "the rows' errors"),
    "none_of_the_served_gaps": ({"limits": {"program_logits_max_err": 0.08}},
                                "the served gaps"),
    "a_name_that_is_not_printed": (
        {"limits": {"program_logits_max_err": 0.08, "served_gap_max": 0.12,
                    "program_logits_p99_err": 0.08}},
        "program_logits_p99_err"),
    "a_limit_on_the_median": (
        {"limits": {"program_logits_median_err": 0.08,
                    "served_gap_max": 0.12}}, "program_logits_median_err"),
    "a_limit_on_the_gaps_q90": (
        {"limits": {"program_logits_max_err": 0.08, "served_gap_q90": 0.12}},
        "served_gap_q90"),
    "the_rows_q90_without_a_declared_hard_choice": (
        {"limits": {"program_logits_q90_err": 0.08, "served_gap_max": 0.12}},
        "under check.hard_choice"),
    "a_hard_choice_that_assumed_does_not_explain": (
        {"hard_choice": "top-k of experts",
         "limits": {"program_logits_q90_err": 0.08, "served_gap_max": 0.12}},
        'under assumed["check.hard_choice"]'),
    "a_limit_that_is_no_number": (
        {"limits": {"program_logits_max_err": "0.08", "served_gap_max": 0.12}},
        "check.limits.program_logits_max_err"),
}


@pytest.mark.parametrize("case", sorted(BAD_CHECKS))
def test_a_configuration_that_does_not_state_its_limits_stops_the_run(case):
    """... before the weights, the engine or the reference are built, with a
    message that names the key."""
    check, named = BAD_CHECKS[case]
    config = dict(run.load("configs", "gpt2-small-serve.json"), check=check)

    def built(*_):
        raise AssertionError("something was built first")
    ctx = {"log": built, "resolve": built, "config": config, "traffic": {},
           "seed": 1, "seconds": 1.0,
           "clock": types.SimpleNamespace(lap=lambda name: None)}
    with pytest.raises(ValueError, match=re.escape(named)):
        serve.run(ctx)


def test_the_eight_bit_controls_fail_at_the_cells_widths():
    """The reference at GPT-2-small's widths (12 x 768, 12 heads, vocabulary
    50257) over one sequence, weights drawn here, held to the limits that
    ``gpt2-small-serve.json`` states.  In the program's place the fp8
    forward fails both: its logits lie further from the float32 reference's
    than ``program_logits_max_err`` allows, and the tokens it puts first
    further below the reference's best than ``served_gap_max``.  The int8
    forward fails the first.  The float32 forward's own first tokens lie at
    0, and a token altered lies standard deviations below."""
    limits = serve.check_limits(run.load("configs", "gpt2-small-serve.json"))
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
    gap = serve.gap_readings(judge(low)[0], limits, control="float8_e4m3fn")
    assert gap[0]["name"] == "control_float8_e4m3fn_served_gap"
    assert gap[0]["value"] > 2 * gap[0]["limit"] and not serve.held(gap)
    altered = serve.gap_readings(judge((np.asarray(first) + 1) % vocab)[0],
                                 limits, control="altered")
    assert altered[0]["value"] > 2 * altered[0]["limit"]

    fed = prompt + filler[:-1]
    ref = np.asarray(serve.reference_logits(params, cfg, fed, 25))
    for dtype, times in (("float8_e4m3fn", 3.0), ("int8", 1.2)):
        got = np.asarray(serve.reference_logits(params, cfg, fed, 25,
                                                dtype=dtype))
        worst = serve.row_readings(got, ref, limits, who="control_" + dtype)[0]
        assert worst["name"] == "control_%s_logits_max_err" % dtype
        assert worst["value"] > times * worst["limit"]
