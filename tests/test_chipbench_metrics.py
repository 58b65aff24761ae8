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
#: configurations whose model has state-space layers: a metric that only
#: their cells report reads the facts of such an engine
HYBRID = {c["name"] for c in BENCH["configs"] if "hybrid_lm" in
          harness.load("configs", c["name"] + ".json").get("builder", "")}


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
    return {False: served_facts(decoder.decoder_tiny_lm(seed=0,
                                                        vocab_size=128)),
            True: served_facts(decoder.hybrid_lm(seed=0))}


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
    facts = facts[all(CONFIG_OF[w] in HYBRID for w in entry["workloads"])]
    args = spec["args"]
    for path in (args["path"], args.get("over")):
        if path is not None and path[0] == "stats":
            value = facts
            for key in path:
                assert isinstance(value, dict) and key in value, (
                    "%s: no %r on the way down %r" % (name, key, path))
                value = value[key]
            assert isinstance(value, (int, float)), (name, path, value)
    if all(p is None or p[0] == "stats"
           for p in (args["path"], args.get("over"))):
        value = reader(facts, **args)
        assert value is not None and value >= 0.0, (name, value)
