"""CPU tests of the benchmark's own code: the length tables, the load
generator, the trace reduction on a small recorded trace, the readers, and
run.py end to end at tiny sizes under --override.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q -p no:cacheprovider

They prove nothing about the chip: no number they see is a measurement."""
import json
import os
import subprocess
import sys
import threading

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import flops_bytes, readers, run, serve, trace  # noqa: E402


def load(*parts):
    with open(os.path.join(ROOT, "chipbench", *parts)) as f:
        return json.load(f)


BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SERVE_MIXES = sorted({w["traffic"] for w in BENCH["workloads"]
                      if load("configs", w["config"] + ".json")["kind"]
                      == "serve"})


# ---------------------------------------------------------------------------
# tables
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_table_is_fixed_by_the_traffic_file(mix):
    traffic = load("traffic", mix + ".json")
    table = serve.make_table(traffic)
    assert table == serve.make_table(load("traffic", mix + ".json"))
    assert len(table) == traffic["table"]["rows"]
    # make_table takes no seed: lengths, order and count cannot depend on it
    assert "seed" not in serve.make_table.__code__.co_varnames


def test_chat_table_is_the_issues():
    table = serve.make_table(load("traffic", "chat_closed.json"))
    prompts, outputs = sorted(p for p, _ in table), sorted(o for _, o in table)
    assert (prompts[0], prompts[-1]) == (32, 768)
    assert (outputs[0], outputs[-1]) == (8, 192)
    assert serve.describe(prompts)["p50"] == pytest.approx(256, abs=3)
    assert serve.describe(outputs)["p50"] == pytest.approx(48, abs=1)
    assert max(p + o for p, o in table) <= 1024     # fits max_ctx
    # the pairing is a permutation: every quantile of each column once
    assert sorted(serve.lengths(
        load("traffic", "chat_closed.json")["table"]["prompt"], 128)) == prompts


def test_prefill_table_is_the_issues():
    table = serve.make_table(load("traffic", "prefill_closed.json"))
    assert [p for p, _ in table[:8]] == list(range(512, 961, 64))
    assert all(o == 8 for _, o in table) and len(table) == 32
    assert all([p for p, _ in table].count(v) == 4
               for v in range(512, 961, 64))


def test_a_multiplier_that_is_no_permutation_is_refused():
    spec = dict(load("traffic", "chat_closed.json")["table"]["prompt"],
                multiplier=4)
    with pytest.raises(ValueError):
        serve.lengths(spec, 128)


def test_open_loop_gaps_are_fixed_and_at_the_rate():
    traffic = {"kind": "open", "rate_per_s": 5.0,
               "gaps": {"multiplier": 7, "offset": 3}}
    due = serve.due_times(traffic, 20.0)
    assert due == serve.due_times(traffic, 20.0) and due[0] == 0.0
    assert len(due) == pytest.approx(100, abs=8)
    assert all(b > a for a, b in zip(due, due[1:]))


# ---------------------------------------------------------------------------
# the load generator, against a fake system on a fake clock-free ask()
# ---------------------------------------------------------------------------
def test_closed_loop_issues_in_table_order_and_counts_failures():
    table = [(10, 2), (20, 3), (30, 4)]
    traffic = {"kind": "closed", "clients": 2, "drain_s": 0.3,
               "stagger_ms": 20}
    seen, lock, hang = [], threading.Lock(), threading.Event()

    def ask(i, prompt, output):
        with lock:
            seen.append((i, prompt, output))
        if i == 5:
            hang.wait(5.0)          # never answered inside the drain limit
        if i == 3:
            raise RuntimeError("refused")
        return [1] * (output - 1 if i == 4 else output)     # 4 answers short

    records, t_open = serve.run_load(ask, table, traffic, 0.15)
    hang.set()
    # indices are handed out in table order, cycling, each sent once
    assert [r["i"] for r in records] == list(range(len(records)))
    assert sorted(s[0] for s in seen) == list(range(len(seen)))
    assert all((p, o) == table[i % 3] for i, p, o in seen)
    assert len(seen) > 6
    out = serve.summarize(records, t_open, 0.15)
    assert out["attempted"] == len(records) == len(seen)
    bad = {r["i"] for r in records
           if r["error"] or r["answered"] != r["output"]}
    assert bad == {3, 4, 5} and out["failed"] == 3
    assert "drain limit" in records[5]["error"]
    # the unanswered request is timed to the moment it was given up
    assert records[5]["t_done"] - records[5]["t_from"] >= 0.25
    assert out["latency_p95_ms"] >= out["latency_p50_ms"] > 0
    assert out["served_tokens_per_s"] > 0


def test_open_loop_times_from_the_due_time():
    traffic = {"kind": "open", "workers": 4, "drain_s": 1.0,
               "rate_per_s": 50.0, "gaps": {"multiplier": 3, "offset": 1}}
    records, t_open = serve.run_load(lambda i, p, o: [0] * o, [(5, 1)],
                                     traffic, 0.2)
    assert len(records) == len(serve.due_times(traffic, 0.2))
    assert all(r["t_from"] == r["t_due"] <= r["t_sent"] for r in records)
    assert serve.summarize(records, t_open, 0.2)["failed"] == 0


# ---------------------------------------------------------------------------
# the trace reduction, on a trace recorded on the v5e (record_trace.py)
# ---------------------------------------------------------------------------
RECORDED = os.path.join(HERE, "data", "small.xplane.pb")
EXPECTED = json.load(open(os.path.join(HERE, "data", "small.expected.json")))


def test_trace_reduction_on_the_recorded_trace():
    facts = trace.reduce_file(RECORDED, 1)
    assert sorted(facts["modules"]) == sorted(EXPECTED["modules"])
    for name, want in EXPECTED["modules"].items():
        got = facts["modules"][name]
        assert len(got) == want["launches"]
        assert sum(got) == pytest.approx(want["seconds"], rel=1e-6)
    # nothing but these programs ran, so busy time is at most their sum and
    # at least the operations inside them
    total = sum(w["seconds"] for w in EXPECTED["modules"].values())
    assert 0.5 * total < facts["busy_s"] <= total * (1 + 1e-9)
    # four host sleeps of 3 ms lie inside the window, so it is mostly idle
    assert facts["window_s"] > 0.012 + facts["busy_s"]
    assert len(facts["device_ops"]) <= 10 and facts["device_ops"][0][1] > 0
    assert facts["idle_gaps"] and all(t > 0 for _, t in facts["idle_gaps"])
    idle = sum(t for _, t in facts["idle_gaps"])
    assert idle == pytest.approx(facts["window_s"] - facts["busy_s"],
                                 rel=0.05)


def test_union_merges_overlaps_and_keeps_gaps():
    s, e = trace.union([0.0, 1.0, 5.0, 5.5], [2.0, 1.5, 6.0, 7.0])
    assert list(s) == [0.0, 5.0] and list(e) == [2.0, 7.0]


def test_readers_on_the_recorded_trace():
    facts = {"trace": trace.reduce_file(RECORDED, 1),
             "memory": {"peak_bytes": 4, "bytes_limit": 16}}
    assert readers.memory(facts) == 25.0
    name = next(iter(EXPECTED["modules"]))
    want = EXPECTED["modules"][name]
    assert readers.trace_module(facts, name) == pytest.approx(
        1e3 * want["seconds"] / want["launches"], rel=0.5)
    assert readers.trace_module(facts, "jit_absent") is None
    assert 0 < readers.idle_share(facts) < 100
    assert readers.trace_op_share(facts, "all-reduce") == 0.0
    assert readers.stats_path(facts, ["stats", "nothing"]) is None


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_its_file_and_reader(metric):
    spec = load("metrics", metric + ".json")
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert (spec["layer"], spec["unit"], spec["moves"]) == (
        entry["layer"], entry["unit"], entry["moves"])
    reader = run.resolve(spec["reader"], "readers")
    # a reader that finds nothing to read returns nothing
    assert reader({}, **spec.get("args", {})) is None


def test_flops_and_bytes_from_the_published_sizes():
    gpt2 = load("configs", "gpt2-small-serve.json")
    # 12 x (4 x 768^2 + 2 x 768 x 3072) + 50257 x 768
    assert flops_bytes.decoder_matmul_params(gpt2) == 84934656 + 38597376
    bert = load("configs", "bert-base-train.json")
    per_token = flops_bytes.encoder_train_flops_per_token(bert, 512)
    assert per_token == 6 * (84934656 + 768 * 768 + 30522 * 768) \
        + 12 * 12 * 512 * 768
    facts = {"config": dict(bert), "chips": 1,
             "peaks": load("peaks.json")["chips"]["TPU v5 lite"],
             "end_to_end": {"tokens_per_s": 197e12 / per_token}}
    assert flops_bytes.train_mfu(facts) == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# run.py end to end, tiny, on the CPU
# ---------------------------------------------------------------------------
TINY = {
    "gpt2-small-serve": {
        "config": {"n_layer": 2, "n_embd": 64, "n_head": 4, "n_inner": 128,
                   "n_positions": 128, "vocab_size": 128,
                   "engine": {"slots": 4, "max_ctx": 128,
                              "prefill_chunk": 16},
                   "check": {"prompt_tokens": 21, "decode_steps": 6}},
        "traffic": {"clients": 3, "drain_s": 20, "trace_seconds": 0.5,
                    "table": {"rows": 8,
                              "prompt": {"dist": "cycle",
                                         "values": [9, 24, 40]},
                              "output": {"dist": "cycle",
                                         "values": [3, 5]}}}},
    "jamba2-3b-serve": {
        "config": {"num_hidden_layers": 6, "hidden_size": 64,
                   "intermediate_size": 128, "num_attention_heads": 4,
                   "attn_layer_period": 3, "attn_layer_offset": 1,
                   "mamba_dt_rank": 4, "vocab_size": 128, "max_length": 128,
                   "engine": {"slots": 4, "page_size": 16, "max_ctx": 128,
                              "prefill_chunk": 16},
                   # a page's edge crossed in prefill and in decode, as the
                   # cell's own check crosses one
                   "check": {"prompt_tokens": 18, "decode_steps": 16}},
        "traffic": {"clients": 3, "drain_s": 20, "trace_seconds": 0.5,
                    "table": {"rows": 8,
                              "prompt": {"dist": "cycle",
                                         "values": [9, 24, 40]},
                              "output": {"dist": "cycle",
                                         "values": [3, 5]}}}},
    "bert-base-train": {
        "config": {"num_hidden_layers": 2, "hidden_size": 64,
                   "num_attention_heads": 2, "intermediate_size": 128,
                   "vocab_size": 1000, "max_position_embeddings": 128,
                   "sequence_length": 16, "sequences_per_chip": 4,
                   "reference_block": 2,
                   # 64 tokens do not average the dropout noise away
                   "loss_tolerance": 0.05},
        "traffic": {"trace_seconds": 0.5}},
}
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    """The rehearsals' compile cache: not the checkout's, which is the
    chip's."""
    return str(tmp_path_factory.mktemp("jax_cache"))


def run_cell(cell, trace_flag, override, cache_dir, seed=3000000019):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", "2",
         "--trace", str(trace_flag)]
        + (["--override", json.dumps(override)] if override else []),
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    return proc


@pytest.mark.parametrize("trace_flag", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]
                                  if w["chips"] == 1])
def test_run_py_rehearsal_prints_the_contracts_line(cell, trace_flag,
                                                    cache_dir):
    config = next(w["config"] for w in BENCH["workloads"]
                  if w["name"] == cell)
    proc = run_cell(cell, trace_flag, dict(TINY[config], allow_cpu=True),
                    cache_dir)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("OVERRIDE ") and \
        "this is not a run of the cell" in lines[0]
    assert "compilations inside the window: 0 (must be 0)" in proc.stdout
    result = json.loads(lines[-1])
    assert KEYS <= set(result) and result["correct"] is False
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert "-> ok" in proc.stdout          # the reference check itself held
    group = "per_layer" if trace_flag else "end_to_end"
    allowed = {m["name"] for m in BENCH[group]
               if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) <= allowed
    if trace_flag:
        assert {"busy_s", "window_s"} <= set(result["device"])
        assert "breakdown" in result
    else:
        assert set(result["metrics"]) == allowed
        assert all(m["value"] > 0 for m in result["metrics"].values())


def test_run_py_refuses_the_cpu_and_prints_no_result(cache_dir):
    cell = BENCH["workloads"][0]["name"]
    proc = run_cell(cell, 0, None, cache_dir)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
