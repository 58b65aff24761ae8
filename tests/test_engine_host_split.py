"""The engine step's host time split where it is spent (`llm` marker, CPU
tier-1, a tiny `decoder_tiny_lm` engine).

- every program the step launches goes through ``DecodeEngine._dispatch``
  and is counted in ``launches_total``;
- every transfer of a launch's inputs goes through ``DecodeEngine._upload``
  and is counted, with its bytes, in ``host_uploads_total``;
- a launch that finds the previous launch's output ready counts as drained,
  except the first after the worker waited for work;
- the helpers cost a few microseconds a launch.

(``wait_s`` against ``phase_s.device_wait`` is checked with the other
phases in ``test_serving_spans.py``.)  No number here is a measurement of
the chip."""
from __future__ import annotations

import time
import types

import numpy as onp
import pytest

import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.models import decoder
from mxnet_tpu.serving import generate

pytestmark = pytest.mark.llm

PROMPTS = [[1, 2, 3], [7, 5], [2, 9, 4, 1], [3], [11, 3, 7]]


@pytest.fixture(scope="module")
def lm():
    return decoder.decoder_tiny_lm(seed=0, vocab_size=128)


def make_engine(lm, **kw):
    return serving.DecodeEngine(lm, name="llm", slots=4, page_size=8,
                                prefill_chunk=8, max_ctx=64, **kw)


def counted(fn, calls, key):
    def wrapper(*args):
        calls[key] += 1
        return fn(*args)
    return wrapper


@pytest.mark.parametrize("async_decode", [True, False])
def test_launches_total_counts_every_program(lm, async_decode):
    """Prefill chunks and decode steps, each counted where it is called,
    add up to ``launches_total``: no other program is launched.  The long
    prompt joins the batch two steps late, so the async engine launches
    steps whose lanes mix carried and staged tokens, merged inside the
    decode step."""
    prompts = [list(range(1, 20))] + PROMPTS
    calls = dict.fromkeys(("prefill", "decode"), 0)
    eng = make_engine(lm, async_decode=async_decode, prefix_cache=False)
    eng._prefill_fn = counted(eng._prefill_fn, calls, "prefill")
    eng._decode_fn = counted(eng._decode_fn, calls, "decode")
    try:
        futs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for f in futs:
            assert len(f.result(timeout=120)["tokens"]) == 10
    finally:
        assert eng.stop()
    counters = eng.metrics.snapshot()["models"]["llm"]["counters"]
    assert calls["prefill"] == counters["prefill_launches_total"] == sum(
        -(-len(p) // 8) for p in prompts)
    # the async engine reads every decode launch once, at its retire
    assert calls["decode"] == counters[
        "deferred_reads_total" if async_decode else "decode_steps_total"] > 0
    assert counters["launches_total"] == calls["prefill"] + calls["decode"]


def test_host_uploads_are_counted_exactly(lm):
    """The synchronous engine, one prompt of three tokens in one page, four
    tokens: one upload a launch.  The prefill chunk's holds the padded
    chunk, pos0, n_valid and the page-table row; each of the three decode
    steps' its tokens, carry mask, positions and active mask, a column
    each, and the whole page table."""
    eng = make_engine(lm, async_decode=False, prefix_cache=False)
    try:
        assert len(eng.submit([1, 2, 3], max_new_tokens=4).result(
            timeout=120)["tokens"]) == 4
    finally:
        assert eng.stop()
    counters = eng.metrics.snapshot()["models"]["llm"]["counters"]
    slots, pages = eng._tables.shape
    i32 = onp.dtype(onp.int32).itemsize
    prefill = [(eng.prefill_chunk + 2 + pages) * i32]
    decode = [slots * (decoder.DECODE_COLUMNS + pages) * i32]
    uploads = prefill + 3 * decode
    assert decoder.DECODE_COLUMNS == 4
    assert counters["launches_total"] == 4
    assert counters["host_uploads_total"] == len(uploads)
    assert counters["host_upload_bytes_total"] == sum(uploads)


@pytest.mark.parametrize("async_decode", [True, False])
def test_first_launch_after_waiting_for_work_is_not_drained(lm,
                                                            async_decode):
    """The device idled for want of requests, not for the host: the
    prefill launch of a request that arrives at an idle engine finds the
    previous request's output ready, and is not counted as drained."""
    eng = make_engine(lm, async_decode=async_decode)
    try:
        eng.submit([1, 2, 3], max_new_tokens=6).result(timeout=120)
        time.sleep(0.3)                  # the worker waits for work
        eng.metrics.reset()
        assert len(eng.submit([4, 5], max_new_tokens=1).result(
            timeout=120)["tokens"]) == 1
    finally:
        assert eng.stop()
    counters = eng.metrics.snapshot()["models"]["llm"]["counters"]
    assert counters["launches_total"] == 1
    assert counters["launches_drained_total"] == 0


def test_the_helpers_cost_microseconds(lm, monkeypatch):
    """A loose CPU gate on what the counters add to a launch: the
    ``is_ready`` poll, ``_dispatch`` beyond the call it wraps and
    ``_upload`` beyond its copy (the transfer left out: its own time
    varies by more than the bookkeeping costs), each under 10 us."""
    eng = make_engine(lm)
    ready = jnp.zeros(4, jnp.int32)
    ready.block_until_ready()
    host = onp.zeros(4, onp.int32)
    out = (None, None, ready, None)

    def per_call(fn, *args, n=2000):
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                fn(*args)
            best = min(best, (time.perf_counter() - t0) / n)
        return best

    def launch():
        return out

    try:
        eng._last_out = ready
        assert per_call(ready.is_ready) < 10e-6
        assert per_call(eng._dispatch, launch) - per_call(launch) < 10e-6
        assert eng._counts["launches_drained_total"] > 0  # ready was polled
        monkeypatch.setattr(generate, "jnp", types.SimpleNamespace(
            asarray=lambda x: x))
        assert per_call(eng._upload, host) - per_call(onp.array, host) < 10e-6
    finally:
        assert eng.stop()
