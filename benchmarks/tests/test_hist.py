"""The over-budget deployment's cell, ``cpu4k-13h.hist-12h``: its files
against the ones they were copied from, the arithmetic of its two
``/stats`` shares, and its rehearsal on the CPU (40 hosts x 13 h, the
budget set by the daemon's flag at the deployment's 0.358 of what is
stored). Slow like ``test_rehearsal.py``: two cases start a daemon.
"""

import json
import os

import pytest

from benchmarks.lib import layers
from benchmarks.tests import rehearsal_cells
from benchmarks.tests.test_rehearsal import (DEVICE_KEYS, LINE_KEYS, bench,
                                             device_metrics)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "cpu4k-13h.hist-12h"
NEW = {"scan_ms", "aggregate_ms", "aggregate_wait_ms", "raw_pack_ms",
       "horizon_miss_share", "window_evicted_share"}


def load(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module", autouse=True)
def rehearsal_json():
    rehearsal_cells.write()


def test_config_is_tsbs_cpu4k_at_13_hours():
    base, cfg = load("configs", "tsbs-cpu4k.json"), load(
        "configs", "tsbs-cpu4k-13h.json")
    differ = {k for k in set(base) | set(cfg) if base.get(k) != cfg.get(k)}
    assert differ == {"name", "hours", "deployment", "layout", "guarantees",
                      "assumed", "daemon"}
    assert cfg["hours"] == 13 and cfg["reduced"] == ["hours"]
    # Same daemon at the default budget, which this deployment is over
    # and so states: the one flag more on its argv carries the default.
    assert cfg["daemon"] == base["daemon"] + [
        "--device-window-points", str(1 << 26)]
    points = cfg["hosts"] * len(cfg["metrics"]) * cfg["hours"] * 360
    assert points == 187_200_000 > 2 * (1 << 26)
    same = {k for k in base["guarantees"]
            if base["guarantees"][k] == cfg["guarantees"][k]}
    assert same == set(base["guarantees"]) - {"resident"}
    assert set(cfg["assumed"]) - set(base["assumed"]) == {
        "device_window_points"}
    small = load("tests", "rehearsal", "tsbs-cpu40-13h.json")
    assert small["rehearsal_of"] == cfg["name"] and small["hours"] == 13
    stored = small["hosts"] * len(small["metrics"]) * 13 * 360
    flag = small["daemon"].index("--device-window-points")
    assert small["daemon"][:flag + 1] == cfg["daemon"][:-1]
    budget = int(small["daemon"][flag + 1])
    assert budget / stored == pytest.approx((1 << 26) / points, abs=1e-4)
    # The flag derives 64 chunks to the budget: a metric is loaded as
    # at least 8 chunks.
    assert stored / len(small["metrics"]) / (budget // 64) >= 8


def test_mix_is_five_of_dash_12h_with_no_window_cut():
    mix, full = load("traffic", "hist-12h.json"), load(
        "traffic", "dash-12h.json")
    assert mix["types"] == full["types"][:5]
    assert [t["window_s"] for t in mix["types"]] == [
        43200, 43200, 28800, 28800, 43200]
    assert not any("tsbs_window_s" in t for t in mix["types"])
    for key in ("kind", "loop", "workers", "initial_state", "check_max",
                "translation"):
        assert mix[key] == full[key]


def test_the_two_stats_shares_by_hand():
    after = {"tsd.devwindow.hits": 30.0, "tsd.devwindow.misses": 90.0,
             "tsd.devwindow.misses.horizon": 60.0,
             "tsd.devwindow.points.appended": 187_200_000.0,
             "tsd.devwindow.points.evicted": 120_598_200.0}
    ctx = {"kind": "queries", "after": after}
    assert layers.evaluate(load("layers", "horizon_miss_share.json"),
                           ctx) == pytest.approx(50.0)
    # 115 of the 1,048,680-point chunks of the refill: 64.42%.
    assert layers.evaluate(load("layers", "window_evicted_share.json"),
                           ctx) == pytest.approx(64.4221, abs=1e-4)
    # The lump stays a lump: misses.horizon is not added to it.
    only = {"tsd.devwindow.hits": 0.0, "tsd.devwindow.misses": 7.0,
            "tsd.devwindow.misses.horizon": 7.0}
    assert layers.evaluate(load("layers", "horizon_miss_share.json"),
                           {"kind": "queries", "after": only}) == 100.0
    # A program with neither counter, or a run with no traced readings.
    for ctx in ({"kind": "queries", "after": {}}, {"kind": "queries"},
                {"kind": "load", "after": after}):
        for name in ("horizon_miss_share", "window_evicted_share"):
            assert layers.evaluate(load("layers", name + ".json"),
                                   ctx) is None


def test_span_metrics_read_nothing_on_a_resident_tree():
    class Req:
        type = "double-groupby-1"

    class Done:
        ok, req, ms = True, Req, 10.0
        spans = [{"name": "query", "ms": 9.0, "spans": [
            {"name": "planner.pick", "ms": 8.0, "spans": [
                {"name": "resident.wait", "ms": 5.0}]}]}]
    ctx = {"kind": "queries", "done": [Done]}
    for name in ("scan_ms", "aggregate_ms", "aggregate_wait_ms",
                 "raw_pack_ms"):
        assert layers.evaluate(load("layers", name + ".json"), ctx) is None
    Done.spans = [{"name": "query", "ms": 9.0, "spans": [
        {"name": "planner.pick", "ms": 0.2},
        {"name": "scan", "ms": 3.0, "spans": [
            {"name": "chunk.decode", "ms": 2.0},
            {"name": "scan.group", "ms": 0.9}]},
        {"name": "aggregate", "ms": 5.0, "spans": [
            {"name": "aggregate.pack", "ms": 1.0},
            {"name": "aggregate.wait", "ms": 2.5}]}]}] * 2
    read = {name: layers.evaluate(load("layers", name + ".json"), ctx)
            for name in ("scan_ms", "aggregate_ms", "aggregate_wait_ms",
                         "raw_pack_ms")}
    assert read == {"scan_ms": 6.0, "aggregate_ms": 10.0,
                    "aggregate_wait_ms": 5.0, "raw_pack_ms": 2.0}


def test_new_metrics_are_this_cells_alone():
    spec = rehearsal_cells.cells()
    for m in spec["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
    assert NEW <= {m["name"] for m in spec["per_layer"]}
    assert {m["name"] for m in spec["end_to_end"]
            if CELL in m.get("workloads", [CELL])} == {
                "q_mean_ms", "queries_per_s", "setup_s"}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace):
    res, line, lines = bench(CELL, trace, seed=(1 << 31) + 78)
    assert res.returncode == 0, res.stderr[-3000:]
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert set(line["device"]) == DEVICE_KEYS
    spec = rehearsal_cells.cells()
    got = {k: v["value"] for k, v in line["metrics"].items()}
    if not trace:
        assert set(got) == {"q_mean_ms", "queries_per_s", "setup_s"}
        assert all(v > 0 for v in got.values())
        return
    listed = {m["name"] for m in spec["per_layer"]
              if CELL in m["workloads"]}
    assert set(got) == listed - device_metrics()
    assert NEW <= set(got)
    # Every request left the resident plan for the horizon's sake, and
    # the refill dropped what the budget could not hold: 64.2% less the
    # chunks in flight (a chunk is 1/64 of the budget, 0.56% of the store).
    assert got["resident_share"] == 0.0
    assert got["horizon_miss_share"] == 100.0
    assert 63.0 <= got["window_evicted_share"] <= 65.0
    assert got["compiles_in_window"] == 0
    for name in ("scan_ms", "aggregate_ms", "aggregate_wait_ms",
                 "raw_pack_ms"):
        assert got[name] > 0
    assert got["aggregate_ms"] > got["aggregate_wait_ms"]
    checks = {ln.split()[1]: float(ln.split()[3]) for ln in lines
              if ln.startswith("check ")}
    assert checks["devwindow_appended_minus_stored"] == 0.0
    assert checks["exact_answers_unequal"] == 0.0
    assert checks["f32_max_rel_err"] <= 1e-4


def test_control_lower_precision_on_the_raw_plan():
    """wire_bf16 is the resident plan's fetch alone: the raw plan does
    not read it, so the control shows nothing in this cell (PERF.md §2
    says so and claims nothing from it)."""
    res, line, lines = bench(CELL, control="wire_bf16")
    assert res.returncode == 0, res.stderr[-3000:]
    assert line["correct"] is True


def test_control_answer_off_fails_on_the_raw_plan():
    res, line, lines = bench(CELL, control="answer_off_4e-3")
    assert res.returncode == 0, res.stderr[-3000:]
    assert line["correct"] is False
    assert any(ln.startswith("check f32_max_rel_err")
               and ln.endswith("FAIL") for ln in lines)
