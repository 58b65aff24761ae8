"""The served rate's definition (PR 28) in tier-1: the cases of
``chipbench/tests/test_summarize.py`` (a fake clock and a fake ask(): no
engine, no jax), which the benchmark keeps beside its harness and tier-1
does not collect.  The cases are imported, not copied."""
from __future__ import annotations

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chipbench_tests_test_summarize",
    os.path.join(ROOT, "chipbench", "tests", "test_summarize.py"))
cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cases)

CASES = sorted(n for n in dir(cases) if n.startswith("case_"))


def test_the_cases_are_there():
    assert len(CASES) >= 5


@pytest.mark.parametrize("name", CASES, ids=[n[5:] for n in CASES])
def test_served_rate_ends_on_the_last_answer_inside_the_window(name):
    getattr(cases, name)()


def test_held_token_seconds_grows_with_the_answer():
    cases.test_held_token_seconds_grows_with_the_answer()
