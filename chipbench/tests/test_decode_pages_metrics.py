"""The two per-layer metrics of PR 36, read by the generic readers from
facts recorded on the v5e (``data/decode_pages.facts.json``: the serving
counters and the named operations of one traced ``gpt2s_chat_closed`` run
of the parent and one of the change, the same seed, cut to what the two
metrics read and a few operations they must not).

``decode_live_page_share`` (``stats_path``): the pages under the active
lanes' positions over the table's, from counters the parent does not
have, so its line leaves the metric out.  ``decode_ctx_gather_share``
(``trace_op_share``): the device time of the decode step's 24 gathers of
a layer's whole context over busy time, which the change's trace no
longer holds, so it reads 0.0 there."""
import json
import os
import re

import pytest

from chipbench import run
from chipbench.run import load

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "decode_pages.facts.json")) as f:
    RECORDED = json.load(f)
METRICS = ("decode_live_page_share", "decode_ctx_gather_share")


def read(name, facts):
    spec = load("metrics", name + ".json")
    return run.resolve(spec["reader"], "readers")(facts, **spec["args"])


@pytest.mark.parametrize("name", METRICS)
@pytest.mark.parametrize("side", ["parent", "change"])
def test_metric_reads_the_recorded_facts(side, name):
    got, want = read(name, RECORDED[side]["facts"]), RECORDED[side][
        "expected"][name]
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-9)


def test_the_gathers_are_the_parents_24_and_nothing_else():
    spec = load("metrics", "decode_ctx_gather_share.json")
    ops = RECORDED["parent"]["facts"]["trace"]["ops"]
    hit = [n for n in ops if re.search(spec["args"]["match"], n)]
    assert len(hit) == 24 and all("2048_16_768" in n for n in hit)
    assert len(ops) > len(hit)          # the others are there to be passed
    ops = RECORDED["change"]["facts"]["trace"]["ops"]
    assert ops and not [n for n in ops
                        if re.search(spec["args"]["match"], n)]


def test_the_share_is_live_pages_over_table_entries():
    c = RECORDED["change"]["facts"]["stats"]["serving"]["counters"]
    share = read("decode_live_page_share", RECORDED["change"]["facts"])
    assert share == pytest.approx(
        100.0 * c["decode_pages_live_total"] / c["decode_pages_table_total"])
    assert c["decode_pages_table_total"] % (32 * 64) == 0
    assert 0.0 < share < 100.0
