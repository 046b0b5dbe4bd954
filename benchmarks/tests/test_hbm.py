"""The whole-history deployment's cell, ``cpu4k-hbm.dash-12h``: its
files against the ones they were copied from, and its rehearsal on the
CPU (40 hosts x 13 h, the budget set by the daemon's flag at the
deployment's 1.434 of what is stored, so nothing is evicted). Slow like
``test_rehearsal.py``: three cases start a daemon. Which metrics list
the cell is read from ``BENCHMARK.json`` and held from below only.
"""

import json
import os

import pytest

from benchmarks.tests import rehearsal_cells
from benchmarks.tests.test_rehearsal import (DEVICE_KEYS, LINE_KEYS, bench,
                                             device_metrics)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "cpu4k-hbm.dash-12h"
READ = {"wide_device_wait_ms", "wide_stage_dispatch_ms", "wide_fetch_ms",
        "wide_results_ms", "fold_dispatches_per_100_stages",
        "resident_share", "fold_slot_share", "fold_narrowed_share"}
# Read on the chip alone: a CPU states no memory limit, so the daemon
# records no tsd.device.* there and the line leaves the share out.
CHIP_ONLY = {"hbm_resident_share"}


def load(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module", autouse=True)
def rehearsal_json():
    rehearsal_cells.write()


def test_config_is_tsbs_cpu4k_13h_with_all_of_it_resident():
    base, cfg = load("configs", "tsbs-cpu4k-13h.json"), load(
        "configs", "tsbs-cpu4k-hbm.json")
    differ = {k for k in set(base) | set(cfg) if base.get(k) != cfg.get(k)}
    assert differ == {"name", "source", "deployment", "layout",
                      "guarantees", "assumed", "daemon"}
    assert 13 <= cfg["hours"] <= 16 and cfg["reduced"] == ["hours"]
    points = cfg["hosts"] * len(cfg["metrics"]) * cfg["hours"] * 360
    # The sibling's argv with the budget at the least power of two that
    # holds every stored point, and no other flag.
    budget = int(cfg["daemon"][-1])
    assert cfg["daemon"][:-1] == base["daemon"][:-1]
    assert budget & (budget - 1) == 0 and budget // 2 < points <= budget
    same = {k for k in base["guarantees"]
            if base["guarantees"][k] == cfg["guarantees"][k]}
    assert same == set(base["guarantees"]) - {"resident"}
    assert set(cfg["assumed"]) - set(base["assumed"]) == {"hours"}
    spec = load("..", "BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        cfg["name"], "dash-12h", 1)
    small = load("tests", "rehearsal", "tsbs-cpu40-hbm.json")
    assert small["rehearsal_of"] == cfg["name"]
    assert small["hours"] == cfg["hours"]
    stored = small["hosts"] * len(small["metrics"]) * small["hours"] * 360
    assert small["daemon"][:-1] == cfg["daemon"][:-1]
    assert int(small["daemon"][-1]) / stored == pytest.approx(
        budget / points, abs=1e-4)
    assert {k for k in set(cfg) | set(small) if cfg.get(k) != small.get(k)} \
        == {"name", "hosts", "daemon", "assumed", "rehearsal",
            "rehearsal_of"}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace):
    res, line, lines = bench(CELL, trace, seed=(1 << 31) + 79)
    assert res.returncode == 0, res.stderr[-3000:]
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert set(line["device"]) == DEVICE_KEYS
    spec = rehearsal_cells.cells()
    got = {k: v["value"] for k, v in line["metrics"].items()}
    checks = {ln.split()[1]: float(ln.split()[3]) for ln in lines
              if ln.startswith("check ")}
    assert checks["devwindow_appended_minus_stored"] == 0.0
    assert checks["exact_answers_unequal"] == 0.0
    assert checks["answers_wrong_shape"] == 0.0
    assert checks["f32_max_rel_err"] <= 1e-4
    # All seven types are among the answers compared.
    assert any("compared" in ln and "double-groupby-all" in ln
               and "single-groupby-1-1-12" in ln
               for ln in res.stderr.splitlines())
    if not trace:
        assert set(got) == {"q_mean_ms", "queries_per_s", "setup_s"}
        assert all(v > 0 for v in got.values())
        return
    listed = {m["name"] for m in spec["per_layer"]
              if CELL in m["workloads"]}
    assert CHIP_ONLY <= listed
    assert set(got) == listed - device_metrics() - CHIP_ONLY
    assert READ <= set(got)
    # Every request is resident, whatever the seed draws: the window
    # holds every stored point.
    assert got["resident_share"] == 100.0
    assert got["compiles_in_window"] == 0
    # A 12 h range spans several of a metric's five chunks here, so a
    # stage dispatches more than one fold.
    assert got["fold_dispatches_per_100_stages"] > 100.0
    for name in ("wide_device_wait_ms", "wide_stage_dispatch_ms",
                 "wide_fetch_ms", "wide_results_ms"):
        assert got[name] > 0


def test_control_lower_precision_comes_out_not_correct():
    res, line, lines = bench(CELL, control="wire_bf16")
    assert res.returncode == 0, res.stderr[-3000:]
    assert line["correct"] is False
    assert any(ln.startswith("check ") and ln.endswith("FAIL")
               for ln in lines)


def test_hbm_share_by_hand():
    from benchmarks.lib import layers
    layer = load("layers", "hbm_resident_share.json")
    entry = next(m for m in load("..", "BENCHMARK.json")["per_layer"]
                 if m["name"] == layer["name"])
    assert {k: layer[k] for k in ("unit", "source", "layer", "moves")} \
        == {k: entry[k] for k in ("unit", "source", "layer", "moves")}
    after = {"tsd.devwindow.bytes": 4_771_020_800.0,
             "tsd.device.bytes_limit": 16_909_336_064.0}
    assert layers.evaluate(layer, {"kind": "queries", "after": after}) \
        == pytest.approx(28.215, abs=1e-3)
    # A CPU (no limit stated) has the window's bytes alone, the parent
    # commit neither: nothing raised, the metric left out.
    for ctx in ({"kind": "queries", "after": {
                    "tsd.devwindow.bytes": 4_771_020_800.0}},
                {"kind": "queries", "after": {}}, {"kind": "queries"}):
        assert layers.evaluate(layer, ctx) is None
