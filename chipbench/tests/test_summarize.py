"""The served rate's definition (PR 28), on a fake clock and a fake ask(): no
engine, no jax.

    python3 -m pytest chipbench/tests/test_summarize.py -q -p no:cacheprovider

``served_tokens_per_s`` is prompt + answered tokens of the requests answered
in full at or before the window's close, over window open to the last of
those answers.  The latencies, ``attempted`` and ``failed`` stay over every
request issued, the drain's included."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import serve  # noqa: E402

SECONDS = 40.0


class FakeClock:
    """now() and sleep() for run_load with one caller: time moves only when
    the caller sleeps or the fake system takes its time over an answer."""

    def __init__(self):
        self.t = 100.0

    def now(self):
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 1e-3)


def drive(service_s, seconds=SECONDS, drain_s=60.0, answer=None):
    """One caller through run_load on the fake clock: request i takes
    ``service_s(i)`` and is answered with ``answer(i, output)`` tokens (all of
    them by default; an exception instance is raised)."""
    clock = FakeClock()

    def ask(i, prompt, output):
        clock.t += service_s(i)
        got = output if answer is None else answer(i, output)
        if isinstance(got, Exception):
            raise got
        return [7] * got

    records, t_open = serve.run_load(
        ask, [(100, 20)], {"kind": "closed", "clients": 1, "drain_s": drain_s},
        seconds, now=clock.now, sleep=clock.sleep)
    return records, t_open


def rec(i, t_sent, t_done, prompt=100, output=20, answered=None, error=None):
    """A record as run_load leaves it, for runs of several callers."""
    return {"i": i, "prompt": prompt, "output": output, "t_due": t_sent,
            "t_sent": t_sent, "t_from": t_sent, "t_done": t_done,
            "tokens": None, "error": error,
            "answered": output if answered is None else answered}


def bunches(last_bunch_at):
    """Eight lockstep callers, a bunch of answers every half second from 0.5 s
    to 39.5 s, and one more bunch at ``last_bunch_at``."""
    ends = [0.5 * k for k in range(1, 80)] + [last_bunch_at]
    return [rec(8 * k + c, end - 0.5, end, prompt=700, output=8)
            for k, end in enumerate(ends) for c in range(8)]


def case_drain_tail():
    """(i) the same run with a 0.1 s and a 3 s drain tail: the same rate."""
    def run(tail):
        # requests of 1 s back to back; the one in flight at the close ends
        # ``tail`` after it
        return drive(lambda i: 1.0 if i < 39 else 0.999 + tail)
    (short, t0), (long_, t1) = run(0.1), run(3.0)
    a, b = serve.summarize(short, t0, SECONDS), serve.summarize(long_, t1,
                                                                SECONDS)
    assert len(short) == len(long_) == 40
    assert a["drain_s"] - SECONDS == pytest.approx(0.1, abs=0.01)
    assert b["drain_s"] - SECONDS == pytest.approx(3.0, abs=0.01)
    assert a["served_tokens_per_s"] == pytest.approx(b["served_tokens_per_s"],
                                                     rel=1e-12)
    assert a["served_tokens_per_s"] == pytest.approx(120.0, rel=1e-3)
    assert a["live_tokens_mean"] == pytest.approx(b["live_tokens_mean"],
                                                  rel=1e-3)
    # the rate to the last answer of the drain (before PR 28) moved with it
    old = [sum(r["prompt"] + r["answered"] for r in recs) / out["drain_s"]
           for recs, out in ((short, a), (long_, b))]
    assert old[0] / old[1] - 1 > 0.05


def case_lockstep_bunch():
    """(ii) a bunch of eight that ends just before or just after the close
    moves the rate by less than the bunch's share of the window's tokens."""
    before = serve.summarize(bunches(39.99), 0.0, SECONDS)
    after = serve.summarize(bunches(40.01), 0.0, SECONDS)
    assert before["answered_in_window"] == after["answered_in_window"] + 8
    share = 8 / before["answered_in_window"]
    moved = abs(before["served_tokens_per_s"] / after["served_tokens_per_s"]
                - 1)
    assert moved < share / 4
    # dividing by the window's length instead would move it by the whole share
    per_window = [sum(r["prompt"] + r["answered"] for r in recs
                      if r["t_done"] <= SECONDS) / SECONDS
                  for recs in (bunches(39.99), bunches(40.01))]
    assert per_window[0] / per_window[1] - 1 == pytest.approx(
        share, rel=0.05)


def case_answered_in_the_drain():
    """(iii) a request answered in the drain is in the latencies, in
    ``attempted`` and not in ``failed``; it is not in the rate."""
    recs = [rec(i, float(i), i + 1.0) for i in range(40)]
    late = [rec(40 + c, 39.5, 47.5) for c in range(4)]
    base = serve.summarize(recs, 0.0, SECONDS)
    out = serve.summarize(recs + late, 0.0, SECONDS)
    assert (out["attempted"], out["failed"]) == (44, 0)
    assert out["served_tokens_per_s"] == base["served_tokens_per_s"] \
        == pytest.approx(120.0)
    assert out["answered_in_window"] == 40 and out["rate_span_s"] == 40.0
    assert out["latency_p95_ms"] > base["latency_p95_ms"]
    assert out["drain_s"] == 47.5
    # what it held of the cache before the close still counts as held
    assert out["live_tokens_mean"] > base["live_tokens_mean"]


def case_failed_or_short():
    """(iv) a failed or a short answer is in ``failed``; it adds neither its
    tokens nor its end to the rate."""
    recs = [rec(i, float(i), i + 1.0) for i in range(30)]
    base = serve.summarize(recs, 0.0, SECONDS)
    bad = [rec(30, 30.0, 35.0, answered=19),
           rec(31, 30.0, 36.0, answered=None, error="RuntimeError: refused")]
    bad[1]["answered"] = None
    out = serve.summarize(recs + bad, 0.0, SECONDS)
    assert (out["attempted"], out["failed"]) == (32, 2)
    assert out["served_tokens_per_s"] == base["served_tokens_per_s"]
    assert out["rate_span_s"] == base["rate_span_s"] == 30.0
    assert out["live_tokens_mean"] == base["live_tokens_mean"]
    # through run_load too: the load generator counts them, never raises
    records, t_open = drive(
        lambda i: 1.0, seconds=10.0,
        answer=lambda i, o: (o - 1 if i == 3 else
                             RuntimeError("refused") if i == 5 else o))
    got = serve.summarize(records, t_open, 10.0)
    assert got["failed"] == 2 and got["attempted"] == len(records)
    assert got["answered_in_window"] == len(records) - 2


def case_nothing_before_the_close():
    """(v) no answer before the close: the rates are None, not a division by
    zero; the latencies are there."""
    records, t_open = drive(lambda i: 50.0)
    out = serve.summarize(records, t_open, SECONDS)
    assert len(records) == 1 and out["failed"] == 0
    assert out["served_tokens_per_s"] is None
    assert out["live_tokens_mean"] is None
    assert out["answered_in_window"] == 0 and out["rate_span_s"] == 0
    assert out["latency_p50_ms"] == pytest.approx(50e3, rel=1e-3)
    empty = serve.summarize([], 0.0, SECONDS)
    assert empty["served_tokens_per_s"] is None and empty["attempted"] == 0
    assert empty["latency_p50_ms"] is None


@pytest.mark.parametrize("case", [
    case_drain_tail, case_lockstep_bunch, case_answered_in_the_drain,
    case_failed_or_short, case_nothing_before_the_close,
], ids=lambda f: f.__name__[5:])
def test_served_rate_ends_on_the_last_answer_inside_the_window(case):
    case()


def test_held_token_seconds_grows_with_the_answer():
    r = rec(0, 10.0, 20.0, prompt=100, output=40)
    assert serve.held_token_seconds(r, 30.0) == (100 + 20) * 10.0
    assert serve.held_token_seconds(r, 15.0) == (100 + 10) * 5.0
    assert serve.held_token_seconds(r, 10.0) == 0.0
    assert serve.held_token_seconds(r, 5.0) == 0.0
