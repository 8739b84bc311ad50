"""The rate is over whole calls only, and the window ends at the first
call boundary at or after ``seconds``."""
import time

import pytest

from bench import window
from bench.window import Call


def test_rate_counts_every_call_and_all_of_its_time():
    calls = [Call(10.0, 11.0, {"events": 100}),
             Call(11.5, 12.0, {"events": 100}),
             Call(12.0, 14.0, {"events": 200})]
    # 400 events from the first start (10.0) to the last end (14.0),
    # the host's half second between the first two calls included
    assert window.rate(calls) == pytest.approx(100.0)
    assert window.wall_s(calls) == pytest.approx(4.0)
    assert window.total(calls, "events") == 400


def test_measure_ends_at_a_call_boundary():
    n = []

    def call():
        n.append(1)
        time.sleep(0.03)
        return len(n)

    calls, last = window.measure(call, lambda out: {"events": out}, 0.1)
    assert last == len(calls) == len(n)
    assert calls[-1].end - calls[0].start >= 0.1
    assert calls[-2].end - calls[0].start < 0.1
    assert [c.counts["events"] for c in calls] == list(
        range(1, len(calls) + 1))
