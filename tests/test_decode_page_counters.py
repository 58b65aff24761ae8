"""What a decode step has to read, counted where the engine stages it
(PR 36): ``decode_pages_live_total`` is the pages under ``position + 1`` of
every active lane of every launch, ``decode_pages_table_total`` the whole
page table (slots x pages a sequence may hold) a launch.  Their ratio is
the benchmark's ``decode_live_page_share``: the share of the table the
step's attention walks since it reads each lane up to its length.

Counts on the CPU; no number here is a measurement of the chip."""
from __future__ import annotations

import numpy as onp
import pytest

from mxnet_tpu import serving
from mxnet_tpu.models import decoder

pytestmark = pytest.mark.llm

SLOTS, PAGE, MAX_CTX = 4, 4, 32
PPS = MAX_CTX // PAGE


def counters(engine):
    return engine.metrics.snapshot()["models"][engine.name]["counters"]


@pytest.mark.parametrize("async_decode", [True, False],
                         ids=["async", "sync"])
def test_engine_counts_the_pages_under_the_positions_it_staged(async_decode):
    lm = decoder.decoder_tiny_lm(seed=0, vocab_size=64)
    engine = serving.DecodeEngine(lm, name="llm", slots=SLOTS, page_size=PAGE,
                                  max_ctx=MAX_CTX, async_decode=async_decode,
                                  prefix_cache=False)
    staged, step = [], engine._decode_fn

    def recording(params, kp, vp, chained, buf):
        buf = onp.array(buf)            # decoder.decode_buffer's columns
        staged.append((buf[:, 2], buf[:, 3] != 0))
        return step(params, kp, vp, chained, buf)
    engine._decode_fn = recording
    try:
        prompts = [[1, 2, 3], list(range(1, 10)), [5] * 6]
        futures = [engine.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, (7, 4, 9))]
        for f in futures:
            f.result(300)
        c = counters(engine)
        assert staged
        live = sum(int(-(-(int(p) + 1) // PAGE))
                   for pos, act in staged for p in pos[act])
        assert c["decode_pages_live_total"] == live
        assert c["decode_pages_table_total"] == len(staged) * SLOTS * PPS
        # a lane at position p holds p + 1 tokens: at least a page, at
        # most the table's row
        lanes = sum(int(act.sum()) for _, act in staged)
        assert lanes <= live <= lanes * PPS
        # the longest lane: 6 prompt tokens and 9 answered, the first of
        # them by the prefill and the last one read by nobody
        assert max(int(pos[act].max()) for pos, act in staged) == 6 + 9 - 2
        # reset with the window, as every counter of ServingMetrics
        engine.metrics.reset()
        engine.submit([7, 8], max_new_tokens=3).result(300)
        again = counters(engine)
        assert 0 < again["decode_pages_live_total"] <= 3
        assert again["decode_pages_table_total"] % (SLOTS * PPS) == 0
    finally:
        assert engine.stop()
