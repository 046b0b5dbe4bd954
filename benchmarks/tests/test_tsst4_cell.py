"""The compressed-history deployment's cell, ``cpu-13h-tsst4.hist-12h``:
its config against the sibling it was copied from (``tsbs-cpu4k-13h``,
the same points stored plain), its entries in ``BENCHMARK.json``, and
its rehearsal on the CPU (40 hosts x 13 h in TSST4 blocks): every
request past the horizon and served by plan ``fused``, ``correct``, and
not ``correct`` under the program's lower-precision control. Slow like
``test_rehearsal.py``: three cases start a daemon.
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
CELL, SIBLING = "cpu-13h-tsst4.hist-12h", "cpu4k-13h.hist-12h"
CONFIG = "tsbs-cpu-13h-tsst4"
SPANS = {"fused_gather_ms": "fused.gather",
         "fused_dispatch_ms": "fused.dispatch",
         "fused_wait_ms": "fused.wait", "fused_fetch_ms": "fused.fetch",
         "fused_results_ms": "fused.results",
         "fused_fill_ms": "fused.fill"}
WIDE = {"wide_fused_gather_ms", "wide_fused_wait_ms"}
SHARES = {"fused_served_share", "devblock_hit_share",
          "devblock_evict_share", "fused_gather_oncpu_share",
          "fused_dispatch_oncpu_share"}
# What only a device that states its memory reports (test_hbm.py).
CHIP_ONLY = {"hbm_resident_share", "devblock_hbm_share"}
NEW = set(SPANS) | WIDE | SHARES | {"devblock_hbm_share"}
# Lists a test of this directory pins to the cells they had: the cell
# reports these quantities under a split name, as PR 38's live cell.
SPLIT = {"horizon_miss_share", "window_evicted_share", "pool_queue_ms",
         "loop_resume_ms", "encode_oncpu_share", "host_cpu_cores",
         "background_cpu_share", "encode_busy_share",
         "snapshot_busy_share"}


def load(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module", autouse=True)
def rehearsal_json():
    rehearsal_cells.write()


def test_config_is_the_sibling_stored_compressed():
    base, cfg = load("configs", "tsbs-cpu4k-13h.json"), load(
        "configs", CONFIG + ".json")
    differ = {k for k in set(base) | set(cfg) if base.get(k) != cfg.get(k)}
    assert differ == {"name", "source", "deployment", "layout", "store",
                      "guarantees", "assumed", "daemon"}
    assert cfg["store"] == {"sstable_codec": "tsst4"}
    # The sibling's argv; what a checkpoint spills later is in the
    # codec the history is in; and the budget of the block cache,
    # stated as the window's is and the same number: well under the
    # history, so that a request decodes most of what it touches.
    budget = base["daemon"][base["daemon"].index(
        "--device-window-points") + 1]
    assert cfg["daemon"] == base["daemon"] + [
        "--sstable-codec", "tsst4", "--device-block-points", budget]
    points = cfg["hosts"] * len(cfg["metrics"]) * cfg["hours"] * 360
    assert int(budget) == 1 << 26 and int(budget) * 2 < points
    assert (cfg["hosts"], cfg["hours"], cfg["reduced"],
            cfg["source_hours"]) == (4000, 13, ["hours"], 72)
    # The five guarantees word for word, and one more.
    assert {k: cfg["guarantees"][k] for k in base["guarantees"]} \
        == base["guarantees"]
    assert set(cfg["guarantees"]) - set(base["guarantees"]) \
        == {"compressed"}
    assert set(cfg["assumed"]) - set(base["assumed"]) == {
        "compressed_opentsdb", "compressed_codec", "device_block_points"}
    assert {k: cfg["assumed"][k] for k in base["assumed"]} \
        == base["assumed"]
    small, plain = load("tests", "rehearsal", "tsbs-cpu40-13h-tsst4.json"), \
        load("tests", "rehearsal", "tsbs-cpu40-13h.json")
    assert small["rehearsal_of"] == CONFIG and small["rehearsal"] is True
    assert small["store"] == cfg["store"]
    stored = small["hosts"] * len(small["metrics"]) * small["hours"] * 360
    assert small["daemon"][:-1] == plain["daemon"] + [
        "--sstable-codec", "tsst4", "--device-block-points"]
    assert int(small["daemon"][-1]) / stored == pytest.approx(
        (1 << 26) / points, abs=1e-4)
    assert {k for k in set(cfg) | set(small) if cfg.get(k) != small.get(k)} \
        == {"name", "hosts", "daemon", "assumed", "rehearsal",
            "rehearsal_of"}


def test_the_root_lists_the_config_and_the_cell():
    root = load("..", "BENCHMARK.json")
    cfg = load("configs", CONFIG + ".json")
    entry, = [c for c in root["configs"] if c["name"] == CONFIG]
    assert (entry["file"], entry["reduced"]) == (
        "benchmarks/configs/" + CONFIG + ".json", ["hours"])
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert len(entry["why"]) <= 200
    cell, = [w for w in root["workloads"] if w["name"] == CELL]
    sibling = next(w for w in root["workloads"] if w["name"] == SIBLING)
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, CONFIG, sibling["traffic"], 1)
    assert len(cell["why"]) <= 200
    assert {m["name"] for m in root["end_to_end"]
            if CELL in m.get("workloads", [CELL])} == {
                "q_mean_ms", "queries_per_s", "setup_s"}
    entries = {m["name"]: m for m in root["per_layer"]}
    for name in NEW | {s + ".tsst4" for s in SPLIT}:
        assert entries[name]["workloads"] == [CELL], name
        lay = load("layers", name + ".json")
        assert lay["name"] == name
        assert {k: lay[k] for k in ("unit", "source", "layer", "moves")} \
            == {k: entries[name][k]
                for k in ("unit", "source", "layer", "moves")}
    for name in SPLIT:
        copy, base = load("layers", name + ".tsst4.json"), load(
            "layers", name + ".json")
        for key in ("unit", "source", "layer", "reader", "args", "kinds"):
            assert copy[key] == base[key], (name, key)
        assert CELL not in entries[name]["workloads"]
        assert entries[name + ".tsst4"]["better"] == entries[name]["better"]
    # Added after the cells that were there, whose order is kept: in
    # `workloads`, and in every list that holds the cell beside others.
    parent = ["cpu4k.dash-1h", "cpu100.dash-12h", SIBLING,
              "cpu4k-hbm.dash-12h", "cpu4k-live.live-1h"]
    names = [w["name"] for w in root["workloads"]]
    assert [n for n in names if n in parent] == parent
    assert names.index(CELL) > names.index(SIBLING)
    for m in root["per_layer"] + root["end_to_end"]:
        held = m.get("workloads", [])
        if CELL in held and len(held) > 1:
            before = held[:held.index(CELL)]
            assert before == [n for n in parent if n in before] != []
    # The new kernels' share of their roofline is the accepted one.
    assert CELL in entries["kernel_hbm_share"]["workloads"]
    assert CELL in entries["kernel_ms_per_q"]["workloads"]


def test_the_new_readings_by_hand():
    after = {"tsd.compress.fused.attempt": 40.0,
             "tsd.compress.fused.served": 30.0,
             "tsd.compress.devcache.hit": 90.0,
             "tsd.compress.devcache.miss": 10.0,
             "tsd.compress.devcache.evict": 4.0,
             "tsd.compress.devcache.bytes": 536_739_840.0,
             "tsd.device.bytes_limit": 16_909_336_064.0,
             "tsd.query.span.cpu_ms{span=fused.gather}": 60.0,
             "tsd.query.span.wall_ms{span=fused.gather}": 80.0,
             "tsd.query.span.cpu_ms{span=fused.dispatch}": 10.0,
             "tsd.query.span.wall_ms{span=fused.dispatch}": 40.0}
    ctx = {"kind": "queries", "after": after}
    assert {n: round(layers.evaluate(load("layers", n + ".json"), ctx), 3)
            for n in SHARES | {"devblock_hbm_share"}} == {
        "fused_served_share": 75.0, "devblock_hit_share": 90.0,
        "devblock_evict_share": 40.0, "devblock_hbm_share": 3.174,
        "fused_gather_oncpu_share": 75.0,
        "fused_dispatch_oncpu_share": 25.0}

    class Req:
        type = "double-groupby-1"

    class Done:
        ok, req, ms = True, Req, 10.0
        spans = [{"name": "query", "ms": 9.0, "spans": [
            {"name": "planner.pick", "ms": 8.0, "spans": [
                {"name": "resident.columns", "ms": 0.1},
                {"name": "fused.gather", "ms": 1.0},
                {"name": "fused.dispatch", "ms": 2.0, "spans": [
                    {"name": "fused.fill", "ms": 0.75}]},
                {"name": "fused.wait", "ms": 3.0},
                {"name": "fused.fetch", "ms": 0.5},
                {"name": "fused.results", "ms": 1.5}]}]}] * 2
    ctx = {"kind": "queries", "done": [Done]}
    for name, span in SPANS.items():
        assert load("layers", name + ".json")["args"] == {"span": span}
    read = {n: layers.evaluate(load("layers", n + ".json"), ctx)
            for n in set(SPANS) | WIDE}
    assert read == {"fused_gather_ms": 2.0, "fused_dispatch_ms": 4.0,
                    "fused_wait_ms": 6.0, "fused_fetch_ms": 1.0,
                    "fused_results_ms": 3.0, "fused_fill_ms": 1.5,
                    "wide_fused_gather_ms": 2.0,
                    "wide_fused_wait_ms": 6.0}
    # A program without the spans and counters (the parent commit), a
    # narrow request under the wide readers, a run with no readings.
    Req.type = "single-groupby-1-1-12"
    assert layers.evaluate(load("layers", "wide_fused_wait_ms.json"),
                           ctx) is None
    Done.spans = [{"name": "query", "ms": 9.0, "spans": [
        {"name": "planner.pick", "ms": 8.0}]}]
    for name in NEW:
        for c in (ctx, {"kind": "queries", "after": {}},
                  {"kind": "queries"}):
            assert layers.evaluate(load("layers", name + ".json"),
                                   c) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace):
    res, line, lines = bench(CELL, trace, seed=(1 << 31) + 42)
    assert res.returncode == 0, res.stderr[-3000:]
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert set(line["device"]) == DEVICE_KEYS
    checks = {ln.split()[1]: float(ln.split()[3]) for ln in lines
              if ln.startswith("check ")}
    assert checks["devwindow_appended_minus_stored"] == 0.0
    assert checks["exact_answers_unequal"] == 0.0
    assert checks["answers_wrong_shape"] == 0.0
    assert checks["f32_max_rel_err"] <= 1e-4
    # All five types are among the answers compared.
    assert any("compared" in ln and "double-groupby-1" in ln
               and "single-groupby-5-1-12" in ln and "cpu-max-all-8" in ln
               for ln in res.stderr.splitlines())
    got = {k: v["value"] for k, v in line["metrics"].items()}
    if not trace:
        assert set(got) == {"q_mean_ms", "queries_per_s", "setup_s"}
        assert all(v > 0 for v in got.values())
        return
    spec = rehearsal_cells.cells()
    listed = {m["name"] for m in spec["per_layer"]
              if CELL in m["workloads"]}
    assert CHIP_ONLY <= listed
    assert set(got) == listed - device_metrics() - CHIP_ONLY
    assert NEW - CHIP_ONLY <= set(got)
    # Every request left the resident plan for the horizon's sake and
    # was served whole by the fused plan: no storage scan, nothing from
    # the /q cache, no program compiled in the window.
    assert got["resident_share"] == 0.0
    assert got["horizon_miss_share.tsst4"] == 100.0
    assert got["fused_served_share"] == 100.0
    assert got["qcache_hit_share"] == 0.0
    assert got["compiles_in_window"] == 0
    assert 63.0 <= got["window_evicted_share.tsst4"] <= 65.0
    # The cache has 15 rows for the store's 45 blocks and is filled by
    # nothing but requests: they find some of their blocks, decode the
    # rest on the device, and nearly every decode pushes a block out.
    assert 5.0 < got["devblock_hit_share"] < 95.0
    assert got["devblock_evict_share"] > 50.0
    for name in set(SPANS) | WIDE:
        assert got[name] > 0, name
    assert 0 < got["fused_gather_oncpu_share"] <= 100.0


def test_control_lower_precision_comes_out_not_correct():
    """The fused plan ends in the resident plan's apply and fetch, so
    the program's bfloat16 wire reaches its answers too."""
    res, line, lines = bench(CELL, control="wire_bf16")
    assert res.returncode == 0, res.stderr[-3000:]
    assert line["correct"] is False
    assert any(ln.startswith("check ") and ln.endswith("FAIL")
               for ln in lines)
