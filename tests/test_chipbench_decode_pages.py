"""The two per-layer metrics of PR 36 on their recorded facts, in tier-1:
the cases of ``chipbench/tests/test_decode_pages_metrics.py``, which the
benchmark keeps beside its harness and tier-1 does not collect.  The
cases are imported, not copied."""
from __future__ import annotations

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
_spec = importlib.util.spec_from_file_location(
    "chipbench_tests_test_decode_pages_metrics",
    os.path.join(ROOT, "chipbench", "tests", "test_decode_pages_metrics.py"))
cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cases)


@pytest.mark.parametrize("name", cases.METRICS)
@pytest.mark.parametrize("side", ["parent", "change"])
def test_metric_reads_the_recorded_facts(side, name):
    cases.test_metric_reads_the_recorded_facts(side, name)


def test_the_gathers_are_the_parents_24_and_nothing_else():
    cases.test_the_gathers_are_the_parents_24_and_nothing_else()


def test_the_share_is_live_pages_over_table_entries():
    cases.test_the_share_is_live_pages_over_table_entries()
