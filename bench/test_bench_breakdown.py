"""``bench/breakdown.py``: its reductions on the small trace recorded on a
TPU v5e chip (``fixture/``, see ``test_bench_trace.py``), and a traced tiny
cell on the CPU."""
import json
import shutil
from pathlib import Path

import pytest

from bench import breakdown as bd
from bench import run as bench_run
from bench.tiny_cells import make_root
from bench.trace_reduce import reduce_trace

FIX = Path(__file__).parent / "fixture"


@pytest.fixture
def trace(tmp_path):
    path = tmp_path / "fixture.xplane.pb"
    shutil.copy(FIX / "fixture.xplane.pb", path)
    return path


def test_scope_seconds_read_framework_op_names(trace):
    # the fixture's one fusion is jnp.dot's: framework op name
    # "jit(<lambda>)/dot_general"
    fusion = sum(t for nm, t in reduce_trace(trace)["device_ops"]
                 if "fusion" in nm.split(" = ")[0])
    sc = bd.scope_seconds(trace, ("dot_general", "no_such_scope"))
    assert sc["dot_general"] == pytest.approx(fusion, rel=0.1)
    assert sc["no_such_scope"] == 0.0
    assert sc["dot_general"] + sc["unnamed"] == pytest.approx(sc["all"])
    # xprof's cache lands beside the copy it reads, never beside the trace
    assert sorted(p.name for p in trace.parent.iterdir()) == [trace.name]


def test_hlo_graphs_read_the_trace_modules(trace):
    (graph,) = bd._hlo_graphs(trace).values()
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
    assert bd.inherited_scopes(graph, "copy.1", scopes) == {"paged_attention"}
    assert bd.inherited_scopes(graph, "copy.2", scopes) == frozenset()
    assert bd.inherited_scopes(graph, "copy.3", scopes) == frozenset()


def test_idle_by_span_names_the_sleeps(trace):
    facts = json.loads((FIX / "fixture.json").read_text())
    r = reduce_trace(trace)
    idle = bd.idle_by_span(trace, prefix="host_sleep")
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)
    assert idle["host_sleep"] >= 0.9 * facts["host_sleep_s"]
    # the engine's spans are not in this trace: its idle is all outside them
    assert set(bd.idle_by_span(trace)) == {"none"}


def test_innermost_span_pieces():
    spans = [(0, 10, "step"), (1, 3, "schedule"), (3, 9, "wait"),
             (12, 14, "idle")]
    assert bd._innermost(spans) == [
        (0, 1, "step"), (1, 3, "schedule"), (3, 9, "wait"), (9, 10, "step"),
        (12, 14, "idle")]


def test_readers_without_their_data_read_none():
    assert bd.host_ms_per_step({}, 10) is None
    assert bd.host_ms_per_step({"engine.step": 1.0}, 10) is None
    assert bd.host_ms_per_step({"engine.schedule": 1.0}, 0) is None
    assert bd.phase_ms_per_step(None, 10) is None
    assert bd.host_ms_per_step({"engine.admit": 0.1, "engine.schedule": 0.2,
                                "engine.wait": 5.0, "engine.emit": 0.1},
                               100) == pytest.approx(4)


def test_traced_tiny_cell_breaks_down_host_time(tmp_path):
    # Poisson arrivals: three land inside the traced mid-window second
    cfg = make_root(tmp_path)
    monitor = bench_run.Monitor
    res, out = bd.breakdown("tiny-cell", 2**31 + 7, 3.0, root=tmp_path,
                            allow_cpu=True, model_cfg=cfg, check=False,
                            log=lambda *a, **k: None)
    assert bench_run.Monitor is monitor
    assert res["attempted"] == 12 and "step_ms" in res["metrics"]
    assert out["host_ms_per_step"] > 0
    ph = out["phase_ms_per_step"]
    assert all(ph[p] > 0 for p in bd.HOST_PHASES + ("engine.wait",))
    assert out["host_ms_per_step"] == pytest.approx(
        sum(ph[p] for p in bd.HOST_PHASES))
    assert out["phase_n"]["engine.step"] >= out["phase_n"]["engine.emit"] > 0
    assert out["traced"]["steps"] > 0
    assert out["traced"]["phase_ms_per_step"]["engine.step"] > 0
    # the CPU has no device plane: the trace readers find nothing
    assert out["scopes"] is None and out["idle_by_span"] is None
    assert "shares" not in out
