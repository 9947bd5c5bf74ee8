"""The trace reduction, on a small trace recorded on a TPU v5e chip.

``fixture/fixture.xplane.pb`` was recorded by ``record_trace_fixture.py``:
five calls of a small jitted step, each after a 20 ms host sleep inside a
``host_sleep`` span; ``fixture/fixture.json`` holds the facts of that run.
Its one fusion is ``jnp.dot``'s, framework op name
``jit(<lambda>)/dot_general``, which the named-scope tests read as a scope.
"""
import json
import shutil
from pathlib import Path

import pytest

from bench import trace_reduce as tr
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


@pytest.fixture
def trace(tmp_path):
    path = tmp_path / "fixture.xplane.pb"
    shutil.copy(FIX / "fixture.xplane.pb", path)
    return path


def test_scope_seconds_read_framework_op_names(trace):
    fusion = sum(t for nm, t in reduce_trace(trace)["device_ops"]
                 if "fusion" in nm.split(" = ")[0])
    sc = tr.scope_seconds(trace, ("dot_general", "no_such_scope"))
    assert sc["dot_general"] == pytest.approx(fusion, rel=0.1)
    assert sc["no_such_scope"] == 0.0
    assert sc["dot_general"] + sc["unnamed"] == pytest.approx(sc["all"])
    # xprof's cache lands beside the copy it reads, never beside the trace
    assert sorted(p.name for p in trace.parent.iterdir()) == [trace.name]


def test_reduce_trace_carries_scopes_and_idle_by_span(trace):
    # the cell file's scopes, plus all and unnamed; none asked, none read
    r = reduce_trace(trace, scopes=["dot_general"])
    assert r["scopes"] == tr.scope_seconds(trace, ["dot_general"])
    assert set(r["scopes"]) == {"dot_general", "all", "unnamed"}
    assert reduce_trace(trace)["scopes"] is None
    assert r["idle_by_span"] == tr.idle_by_span(trace)


def test_scopes_read_none_without_xprof(trace, monkeypatch):
    import sys
    monkeypatch.setitem(sys.modules, "xprof.convert.raw_to_tool_data", None)
    assert tr.scope_seconds(trace, ["dot_general"]) is None
    r = reduce_trace(trace, scopes=["dot_general"])
    assert r["scopes"] is None and r["busy_s"] > 0


def test_hlo_graphs_read_the_trace_modules(trace):
    (graph,) = tr._hlo_graphs(trace).values()
    assert graph["convolution_reduce_fusion"] == (
        "jit(<lambda>)/dot_general", ["copy-done"])
    assert graph["copy-done"] == ("", ["copy-start"])


def test_unnamed_ops_take_the_scope_on_both_sides():
    # a layout copy the compiler put between two ops of the scope, through
    # a bitcast, is the scope's; one at the scope's edge is no scope's
    graph = {"gather": ("jit(f)/paged_attention/gather", ["pool"]),
             "pool": ("pool", []),
             "bitcast.1": ("", ["gather"]),
             "copy.1": ("", ["bitcast.1"]),
             "attend": ("jit(f)/paged_attention/dot_general", ["copy.1"]),
             "copy.2": ("", ["attend"]),
             "proj": ("jit(f)/dot_general", ["copy.2"]),
             "copy.3": ("", ["pool"])}
    scopes = ("paged_attention", "tp_codec")
    assert tr.inherited_scopes(graph, "copy.1", scopes) == {"paged_attention"}
    assert tr.inherited_scopes(graph, "copy.2", scopes) == frozenset()
    assert tr.inherited_scopes(graph, "copy.3", scopes) == frozenset()


def test_idle_by_span_names_the_sleeps(trace):
    facts = json.loads((FIX / "fixture.json").read_text())
    r = reduce_trace(trace)
    idle = tr.idle_by_span(trace, prefix="host_sleep")
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)
    assert idle["host_sleep"] >= 0.9 * facts["host_sleep_s"]
    # the engine's spans are not in this trace: its idle is all outside them
    assert set(tr.idle_by_span(trace)) == {"none"}


def test_innermost_span_pieces():
    spans = [(0, 10, "step"), (1, 3, "schedule"), (3, 9, "wait"),
             (12, 14, "idle")]
    assert tr._innermost(spans) == [
        (0, 1, "step"), (1, 3, "schedule"), (3, 9, "wait"), (9, 10, "step"),
        (12, 14, "idle")]
