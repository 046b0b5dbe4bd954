"""``lib/hostgaps.py`` on planes made by hand, and the eight layer
metrics that read the resident plan's spans and counters (PR 25) through
``layers.evaluate`` on a ``ctx`` made by hand.

``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_hostgaps.py -q``
"""

import json
import os

import pytest

from benchmarks.lib import client, hostgaps, layers

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FOLD = "jit(_chunk_fold)/window.chunk_fold/scatter-add"
APPLY = "jit(_moment_apply)/window.moment_apply/reduce_max"


def test_plan_of():
    assert hostgaps.plan_of(FOLD) == "window.chunk_fold"
    assert hostgaps.plan_of("jit(f)/pjit(g)/rollup.fold/mul") == "rollup.fold"
    assert hostgaps.plan_of("jit(zeros)/broadcast_in_dim") is None
    assert hostgaps.plan_of("jit_zeros") is None
    assert hostgaps.plan_of("") is None


def test_innermost_goes_to_the_deepest_open_event():
    got = hostgaps.innermost([
        ("query", 0, 100), ("planner.pick", 10, 80),
        ("resident.stage", 20, 30), ("resident.wait", 50, 40),
        ("http.q.encode", 200, 50)])
    assert got == [
        ("query", 0, 10), ("planner.pick", 10, 20),
        ("resident.stage", 20, 50), ("resident.wait", 50, 90),
        ("query", 90, 100), ("http.q.encode", 200, 250)]


def test_gaps_are_named_by_what_the_host_was_doing():
    ms = 1_000_000
    planes = [
        ("/device:TPU:0", [
            ("XLA Ops", [
                ("%fusion = f32[8]{0} fusion(...)", 0, 10 * ms, FOLD),
                # a 100 ms gap: encode covers 70, results 30
                ("%fusion = f32[8]{0} fusion(...)", 110 * ms, 10 * ms, APPLY),
                # a 50 ms gap nobody covers
                ("%copy.1 = f32[8]{0} copy(...)", 170 * ms, 5 * ms,
                 "jit_zeros"),
                # a 20 ms gap: the other worker waits beside the encode
                ("%fusion.1 = f32[8]{0} fusion(...)", 195 * ms, 5 * ms,
                 FOLD)]),
            ("XLA Modules", [("jit__chunk_fold(1)", 0, 200 * ms, "")])]),
        ("/host:CPU", [
            ("worker-1", [
                ("resident.results", 80 * ms, 30 * ms, ""),
                ("PjitFunction(_chunk_fold)", 130 * ms, 30 * ms, ""),
                ("resident.stage", 174 * ms, 22 * ms, "")]),
            ("event-loop", [
                ("http.q.encode", 10 * ms, 70 * ms, ""),
                ("http.q.encode", 175 * ms, 10 * ms, "")])]),
        ("/host:metadata", []),
    ]
    out = hostgaps.reduce_planes(planes)
    g100, g50, g20 = out["gaps"]
    assert g100["s"] == pytest.approx(0.100) and g100["before"] == "fusion"
    assert g100["host"] == [["http.q.encode", pytest.approx(0.070)],
                            ["resident.results", pytest.approx(0.030)]]
    assert g100["unattributed_s"] == pytest.approx(0.0)
    # The runtime's own host events are not the program's annotations.
    assert g50["s"] == pytest.approx(0.050) and g50["before"] == "copy.1"
    assert g50["host"] == []
    assert g50["unattributed_s"] == pytest.approx(0.050)
    # Two threads side by side: the names add up to more than the gap.
    assert g20["host"] == [["resident.stage", pytest.approx(0.020)],
                           ["http.q.encode", pytest.approx(0.010)]]
    assert g20["unattributed_s"] == pytest.approx(0.0)
    assert out["gap_s"] == pytest.approx(0.170)
    assert out["attributed_s"] == pytest.approx(0.120)
    # Two programs that both hold a `fusion` keep their own keys, and
    # an operation with no plan is keyed by its program.
    assert out["device_by_plan"] == [
        ["window.chunk_fold", pytest.approx(0.015)],
        ["window.moment_apply", pytest.approx(0.010)],
        ["jit_zeros:copy.1", pytest.approx(0.005)]]


def test_no_device_plane_no_gaps():
    out = hostgaps.reduce_planes([
        ("/host:CPU", [("t", [("query", 0, 10, "")])])])
    assert out == {"gaps": [], "gap_s": 0.0, "attributed_s": 0.0,
                   "device_by_plan": []}


def _tree(ms):
    kids = [{"name": "resident." + n, "t0": 1.0, "ms": v}
            for n, v in ms.items()]
    pick = {"name": "planner.pick", "t0": 1.0,
            "ms": sum(ms.values()) + 0.5, "spans": kids}
    return {"name": "query", "t0": 1.0, "ms": pick["ms"] + 0.5,
            "spans": [pick]}


def test_the_eight_layer_files_of_pr_25():
    a = {"columns": 0.1, "groups": 2.0, "stage": 1.5, "apply": 0.2,
         "wait": 190.0, "fetch": 3.0, "results": 4.0}
    b = dict(a, groups=6.0, wait=210.0)
    done = []
    for i, trees in enumerate(([_tree(a)], [_tree(a), _tree(b)],
                               [_tree(b), _tree(b), _tree(b)])):
        req = client.Request("double-groupby-5", "/q", ["m"] * len(trees),
                             0, 10, 1, 100)
        d = client.Done(req, 5.0 + i, 500.0, True, "", None, i)
        d.spans, d.results, d.resident, d.cached = trees, 1, 1, 0
        done.append(d)
    ctx = {"kind": "queries", "done": done, "window_s": 50.0,
           "before": {"tsd.http.q.encode.sum_ms": 100.0,
                      "tsd.devwindow.stage.miss": 40.0,
                      "tsd.devwindow.stage.hit": 7.0,
                      "tsd.checkpoint.snapshot.sum_ms{kind=sketch}": 10.0},
           "after": {"tsd.http.q.encode.sum_ms": 2600.0,
                     "tsd.devwindow.stage.miss": 46.0,
                     "tsd.devwindow.stage.hit": 7.0,
                     "tsd.checkpoint.snapshot.sum_ms{kind=sketch}": 410.0,
                     "tsd.checkpoint.snapshot.sum_ms{kind=tenant}": 100.0,
                     "tsd.checkpoint.phase.sum_ms{phase=spill}": 9000.0}}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}

    def val(name, kind="queries"):
        with open(os.path.join(HERE, "..", "layers", name + ".json")) as f:
            layer = json.load(f)
        # The file and the root entry say the same of the metric.
        entry = listed[name]
        assert {k: layer[k] for k in ("unit", "source", "layer", "moves")} \
            == {k: entry[k] for k in ("unit", "source", "layer", "moves")}
        assert entry["workloads"] == ["cpu4k.dash-1h", "cpu100.dash-12h"]
        assert entry["better"] == "lower"
        return layers.evaluate(layer, dict(ctx, kind=kind))

    # Per request the sub-queries' spans add up (2, 8, 18 ms of groups);
    # the median over the three requests is the second.
    assert val("groups_ms") == pytest.approx(8.0)
    assert val("stage_dispatch_ms") == pytest.approx(3.0)
    assert val("device_wait_ms") == pytest.approx(400.0)
    assert val("fetch_ms") == pytest.approx(6.0)
    assert val("results_ms") == pytest.approx(8.0)
    assert val("encode_busy_share") == pytest.approx(5.0)     # 2.5 of 50 s
    assert val("stage_builds") == 6.0
    assert val("snapshot_busy_share") == pytest.approx(1.0)   # 0.5 of 50 s
    assert val("snapshot_busy_share", "load") == pytest.approx(1.0)
    assert val("stage_builds", "load") is None
    # The spans read here tile the planner's span, as plan_ms reads it.
    with open(os.path.join(HERE, "..", "layers", "plan_ms.json")) as f:
        plan = layers.evaluate(json.load(f), ctx)
    five = sum(val(n) for n in ("groups_ms", "stage_dispatch_ms",
                                "device_wait_ms", "fetch_ms", "results_ms"))
    assert five <= plan <= five + 2.0
    # A program without the spans and counters (the parent commit):
    # nothing to read, nothing raised, the metric is left out.
    for d in done:
        d.spans = [{"name": "query", "ms": 5.0, "spans": [
            {"name": "planner.pick", "ms": 4.0}]}]
    for name in ("groups_ms", "stage_dispatch_ms", "device_wait_ms",
                 "fetch_ms", "results_ms"):
        assert val(name) is None
    ctx["before"] = ctx["after"] = {"tsd.datapoints.added": 1.0}
    assert val("stage_builds") == 0.0
    assert val("encode_busy_share") == 0.0
