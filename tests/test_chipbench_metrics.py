"""Every per-layer metric file of the benchmark against what it reads.

One case per ``chipbench/metrics/<name>.json``: the file is named in
``BENCHMARK.json``'s ``per_layer`` with the same unit, layer and ``moves``;
its reader resolves; and a ``stats_path`` metric's path (and ``over``)
resolve to a number in the facts of a tiny CPU engine that served three
requests through ModelServer.  A misspelt path is otherwise found only on
the chip, as a metric silently left out of the line.

No number here is a measurement of the chip."""
from __future__ import annotations

import glob
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as harness  # noqa: E402

from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.models import decoder  # noqa: E402

METRICS = sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
    os.path.join(ROOT, "chipbench", "metrics", "*.json")))

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
PER_LAYER = {m["name"]: m for m in BENCH["per_layer"]}
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}


CONFIG_OF = {w["name"]: w["config"] for w in BENCH["workloads"]}


def engine_kind(config):
    """Which of the tiny engines below stands for a configuration's: the
    classic block's, one with state-space layers, or one that routes over
    delta-rule layers.  A metric reads the facts of every kind of engine
    among the cells that report it."""
    builder = harness.load("configs", config + ".json").get("builder", "")
    return ("routed" if "routed_delta_lm" in builder
            else "hybrid" if "hybrid_lm" in builder else "classic")


KIND_OF = {c["name"]: engine_kind(c["name"]) for c in BENCH["configs"]}


def served_facts(lm):
    """What chipbench/serve.py hands the readers under ``stats``, from a
    tiny engine: three requests of two prefill chunks each."""
    engine = serving.DecodeEngine(lm, slots=4, page_size=8, max_ctx=64,
                                  prefill_chunk=8)
    server = serving.ModelServer()
    try:
        server.attach_engine("lm", engine)
        client = serving.ServingClient(*server.start())
        server.metrics.reset()
        for i in range(3):
            assert len(client.generate(
                "lm", list(range(i + 1, i + 13)), max_tokens=4)["tokens"]) == 4
        snap = server.metrics.snapshot()["models"]["lm"]
        stats = engine.stats()
    finally:
        server.stop()
    return {"stats": {"serving": snap, "engine": stats}}


@pytest.fixture(scope="module")
def facts():
    return {"classic": served_facts(decoder.decoder_tiny_lm(
                seed=0, vocab_size=128)),
            "hybrid": served_facts(decoder.hybrid_lm(seed=0)),
            "routed": served_facts(decoder.routed_delta_lm(
                seed=0, vocab_size=128, num_layers=4, units=32, num_heads=4,
                num_kv_heads=2, head_dim=16, attention_layers=[0],
                linear_attn={"num_heads": 4, "head_dim": 8,
                             "short_conv_kernel_size": 4},
                experts_held=4, expert_shares=2, experts_per_token=2,
                expert_hidden=16, max_length=128))}


def test_every_per_layer_metric_has_its_file():
    assert METRICS == sorted(PER_LAYER)


@pytest.mark.parametrize("name", METRICS)
def test_metric_file_matches_benchmark_and_reads_a_number(name, facts):
    spec = harness.load("metrics", name + ".json")
    entry = PER_LAYER[name]
    assert {k: spec[k] for k in ("unit", "layer", "moves")} == {
        k: entry[k] for k in ("unit", "layer", "moves")}
    # the metric it should move is reported in every cell that reports it
    moved = END_TO_END[spec["moves"]]
    assert set(entry["workloads"]) <= set(
        moved.get("workloads", entry["workloads"]))
    reader = harness.resolve(spec["reader"], "readers")
    assert callable(reader)
    if spec["reader"] != "stats_path":
        return
    args = spec["args"]
    for kind in sorted({KIND_OF[CONFIG_OF[w]] for w in entry["workloads"]
                        if harness.load("configs", CONFIG_OF[w] + ".json")
                        ["kind"] == "serve"}):
        served = facts[kind]
        for path in (args["path"], args.get("over")):
            if path is not None and path[0] == "stats":
                value = served
                for key in path:
                    assert isinstance(value, dict) and key in value, (
                        "%s (%s engine): no %r on the way down %r"
                        % (name, kind, key, path))
                    value = value[key]
                assert isinstance(value, (int, float)), (name, path, value)
        if all(p is None or p[0] == "stats"
               for p in (args["path"], args.get("over"))):
            value = reader(served, **args)
            assert value is not None and value >= 0.0, (name, kind, value)
