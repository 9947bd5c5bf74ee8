"""The trace reduction, on a small trace recorded on a TPU v5e chip.

``fixture/fixture.xplane.pb`` was recorded by ``record_trace_fixture.py``:
five calls of a small jitted step, each after a 20 ms host sleep inside a
``host_sleep`` span; ``fixture/fixture.json`` holds the facts of that run.
"""
import json
from pathlib import Path

import pytest

from bench.trace_reduce import reduce_trace, union_ns

FIX = Path(__file__).parent / "fixture"


def test_union_of_intervals():
    assert union_ns([]) == 0
    assert union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert union_ns([(20, 30), (0, 100)]) == 100


@pytest.fixture(scope="module")
def reduced():
    facts = json.loads((FIX / "fixture.json").read_text())
    return facts, reduce_trace(FIX / "fixture.xplane.pb",
                               kernel_patterns={"any": "."})


def test_chip_trace_busy_and_window(reduced):
    facts, r = reduced
    assert r["devices"] == facts["devices"]
    # the window runs from start_trace to stop_trace on the host clock
    assert facts["loop_wall_s"] * 0.9 <= r["window_s"]
    assert r["window_s"] <= facts["loop_wall_s"] + 1.0
    assert 0 < r["busy_s"] < r["window_s"]
    # the device idles at least through the host's sleeps
    assert r["window_s"] - r["busy_s"] >= 0.8 * facts["host_sleep_s"]


def test_chip_trace_ops_and_gaps(reduced):
    facts, r = reduced
    assert r["device_ops"] and all(t > 0 for _, t in r["device_ops"])
    assert r["kernels"]["any"]["calls"] >= facts["calls"]
    assert r["kernels"]["any"]["seconds"] == pytest.approx(
        sum(t for _, t in r["device_ops"]), rel=0.5)
    # the longest idle stretches are the sleeps, named by the innermost
    # host event that covers them
    names = [n for n, s in r["idle_gaps"] if s >= 0.015]
    assert len(names) >= 4 and all("sleep" in n for n in names), names
