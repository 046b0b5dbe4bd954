"""The four-chip deployment's cell, ``cpu4k-mesh4.hist-12h``: its config
against the one-chip twin it was copied from (``tsbs-cpu4k-hbm``, the
same points resident on one device), its entries in ``BENCHMARK.json``
(the new ones last, the lists it joined), the five metrics it brings
read by hand, and its rehearsal on the CPU (40 hosts x 13 h over four
virtual devices, which this file asks XLA for): every request served by
plan ``resident`` through four shards, ``correct``; not ``correct``
under the program's lower-precision control, nor with one shard's rows
misplaced at the gather. Slow like ``test_rehearsal.py``: four cases
start a daemon.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import layers
from benchmarks.tests import rehearsal_cells
from benchmarks.tests.test_rehearsal import (DEVICE_KEYS, LINE_KEYS, ROOT,
                                             bench, device_metrics)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL, TWIN = "cpu4k-mesh4.hist-12h", "cpu4k-hbm.dash-12h"
CONFIG, TWIN_CONFIG = "tsbs-cpu4k-mesh4", "tsbs-cpu4k-hbm"
SHARD_FLAGS = ["--mesh", "4", "--devwindow-shards", "4"]
SPANS = {"shard_fanout_ms": "resident.shard",
         "shard_gather_ms": "resident.gather",
         "wide_shard_gather_ms": "resident.gather"}
SHARES = {"shards_per_100_stages", "shard_balance_share"}
NEW = set(SPANS) | SHARES
# What only a device that states its memory reports (test_hbm.py).
CHIP_ONLY = {"hbm_resident_share"}
# The share of one chip's roofline divides by the busy seconds averaged
# over the device planes: on four planes it reads four times the share,
# so the cell is kept off that list (PERF.md section 7).
NOT_JOINED = {"kernel_hbm_share"}
FOUR_DEVICES = "--xla_force_host_platform_device_count=4"


def load(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module", autouse=True)
def rehearsal_json():
    rehearsal_cells.write()


@pytest.fixture(autouse=True)
def four_virtual_devices(monkeypatch):
    """The daemon a case starts builds its mesh over four CPU devices."""
    monkeypatch.setenv("XLA_FLAGS", FOUR_DEVICES)


def test_config_is_the_one_chip_twin_sharded_over_four():
    base, cfg = load("configs", TWIN_CONFIG + ".json"), load(
        "configs", CONFIG + ".json")
    differ = {k for k in set(base) | set(cfg) if base.get(k) != cfg.get(k)}
    assert differ == {"name", "source", "deployment", "layout", "chips",
                      "guarantees", "assumed", "daemon", "hosts",
                      "source_hosts", "reduced"}
    # The twin's 13 h, and of its 4,000 hosts what the sizing rule left
    # (a cut by 500 at a time, listed): the shapes are TSBS's own.
    assert (cfg["hours"], cfg["source_hours"], cfg["source_hosts"],
            cfg["chips"]) == (13, 72, base["hosts"], 4)
    assert cfg["reduced"] == ["hours", "hosts"]
    assert 0 < cfg["hosts"] <= 4000 and cfg["hosts"] % 500 == 0
    # The twin's argv with the budget of the whole published span, the
    # least power of two over it, and the layout; no other flag.
    at = base["daemon"].index("--device-window-points") + 1
    assert cfg["daemon"] == (base["daemon"][:at] + [str(1 << 30)]
                             + base["daemon"][at + 1:] + SHARD_FLAGS)
    published = (cfg["source_hosts"] * len(cfg["metrics"])
                 * cfg["source_hours"] * 360)
    assert (1 << 29) < published <= (1 << 30)
    assert (1 << 30) // 4 == int(base["daemon"][at])
    # The twin's five guarantees, `resident` reworded, and one more.
    same = {k for k in base["guarantees"]
            if base["guarantees"][k] == cfg["guarantees"][k]}
    assert same == set(base["guarantees"]) - {"resident"}
    assert set(cfg["guarantees"]) - set(base["guarantees"]) == {"sharded"}
    for word in ("resident_share 100", "evicted is 0", "shards"):
        assert word in cfg["guarantees"]["resident"]
    assert cfg["guarantees"]["f32_rtol"] == 1e-4
    assert set(cfg["assumed"]) - set(base["assumed"]) == {"shards",
                                                          "hosts"}
    assert {k for k in base["assumed"]
            if base["assumed"][k] != cfg["assumed"][k]} == {
                "device_window_points", "hours"}
    assert len(cfg["source"]) <= 200
    small, one = load("tests", "rehearsal", "tsbs-cpu40-mesh4.json"), \
        load("tests", "rehearsal", "tsbs-cpu40-hbm.json")
    assert small["rehearsal_of"] == CONFIG and small["rehearsal"] is True
    loaded = cfg["hosts"] * len(cfg["metrics"]) * cfg["hours"] * 360
    stored = small["hosts"] * len(small["metrics"]) * small["hours"] * 360
    assert small["daemon"][:at] == one["daemon"][:at]
    assert small["daemon"][at + 1:] == SHARD_FLAGS
    assert int(small["daemon"][at]) / stored == pytest.approx(
        (1 << 30) / loaded, abs=1e-4)
    assert {k for k in set(cfg) | set(small) if cfg.get(k) != small.get(k)} \
        == {"name", "hosts", "daemon", "assumed", "rehearsal",
            "rehearsal_of"}


def test_the_root_gained_the_config_the_cell_and_five_metrics_last():
    root = load("..", "BENCHMARK.json")
    cfg = load("configs", CONFIG + ".json")
    entry = root["configs"][-1]
    assert (entry["name"], entry["file"], entry["reduced"]) == (
        CONFIG, "benchmarks/configs/" + CONFIG + ".json", cfg["reduced"])
    assert entry["source"] == cfg["source"] and len(entry["why"]) <= 200
    cell = root["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, CONFIG, "hist-12h", 4)
    assert len(cell["why"]) <= 200 and "13 h" in cell["why"]
    # The first four-chip cell, and the only one.
    assert [w["name"] for w in root["workloads"] if w["chips"] != 1] \
        == [CELL]
    assert (root["run_seconds"], {m["name"]: m.get("bound")
                                  for m in root["end_to_end"]}) == (
        45, {"q_mean_ms": 0.24, "queries_per_s": 0.23, "setup_s": 0.25})
    assert {m["name"] for m in root["end_to_end"]
            if CELL in m.get("workloads", [CELL])} == {
                "q_mean_ms", "queries_per_s", "setup_s"}
    # Last in every list it joined: the twin's lists but the roofline's.
    entries = {m["name"]: m for m in root["per_layer"]}
    for m in root["per_layer"] + root["end_to_end"]:
        held = m.get("workloads", [])
        if CELL in held:
            assert held[-1] == CELL and held.count(CELL) == 1, m["name"]
    joined = {n for n, m in entries.items() if CELL in m["workloads"]}
    assert joined - NEW == {n for n, m in entries.items()
                            if TWIN in m["workloads"]} - NOT_JOINED
    assert CHIP_ONLY <= joined and "kernel_ms_per_q" in joined
    # The five it brings, last, for this cell alone, each on a reader
    # that was there.
    assert [m["name"] for m in root["per_layer"][-5:]] == [
        "shard_fanout_ms", "shard_gather_ms", "wide_shard_gather_ms",
        "shards_per_100_stages", "shard_balance_share"]
    for name in NEW:
        assert entries[name]["workloads"] == [CELL]
        assert (entries[name]["better"], entries[name]["moves"]) == (
            "lower", "q_mean_ms")
        lay = load("layers", name + ".json")
        assert lay["name"] == name and lay["kinds"] == ["queries"]
        assert lay["reader"] in ("span_median", "stats_share_at_end")
        assert {k: lay[k] for k in ("unit", "source", "layer", "moves")} \
            == {k: entries[name][k]
                for k in ("unit", "source", "layer", "moves")}
    assert {entries[n]["layer"] for n in SPANS} == {
        "query/executor planner"}
    assert {entries[n]["layer"] for n in SHARES} == {"storage/devstore"}
    # The traffic is the file the two other hist-12h cells use.
    assert sum(w["traffic"] == "hist-12h" for w in root["workloads"]) == 3


def test_the_new_readings_by_hand():
    after = {"tsd.devwindow.stage.shards": 360.0,
             "tsd.devwindow.stage.miss": 100.0,
             "tsd.devwindow.bytes": 1_190_000_000.0,
             "tsd.mesh.resident.bytes": 4_640_000_000.0}
    ctx = {"kind": "queries", "after": after}
    assert {n: round(layers.evaluate(load("layers", n + ".json"), ctx), 3)
            for n in SHARES} == {"shards_per_100_stages": 360.0,
                                 "shard_balance_share": 25.647}

    class Req:
        type = "double-groupby-1"

    class Done:
        ok, req, ms = True, Req, 10.0
        spans = [{"name": "query", "ms": 9.0, "spans": [
            {"name": "planner.pick", "ms": 8.0, "spans": [
                {"name": "resident.stage", "ms": 5.0, "spans": [
                    {"name": "resident.shard", "ms": 0.5},
                    {"name": "resident.shard", "ms": 0.75},
                    {"name": "resident.shard", "ms": 0.25},
                    {"name": "resident.shard", "ms": 1.0},
                    {"name": "resident.gather", "ms": 2.0}]}]}]}] * 2
    ctx = {"kind": "queries", "done": [Done]}
    for name, span in SPANS.items():
        assert load("layers", name + ".json")["args"]["span"] == span
    assert {n: layers.evaluate(load("layers", n + ".json"), ctx)
            for n in SPANS} == {"shard_fanout_ms": 5.0,
                                "shard_gather_ms": 4.0,
                                "wide_shard_gather_ms": 4.0}
    # A narrow request under the wide reader; a program without the
    # spans and counters (the parent commit, a one-shard window): a
    # stage with no children, /stats without the names, no readings.
    Req.type = "single-groupby-1-1-12"
    assert layers.evaluate(load("layers", "wide_shard_gather_ms.json"),
                           ctx) is None
    Done.spans = [{"name": "query", "ms": 9.0, "spans": [
        {"name": "planner.pick", "ms": 8.0, "spans": [
            {"name": "resident.stage", "ms": 5.0}]}]}]
    for name in set(SPANS) | {"shard_balance_share"}:
        for c in (ctx, {"kind": "queries", "after": {
                "tsd.devwindow.bytes": 4_771_020_800.0,
                "tsd.devwindow.stage.miss": 100.0}},
                {"kind": "queries", "after": {}}, {"kind": "queries"}):
            assert layers.evaluate(load("layers", name + ".json"),
                                   c) is None, name
    # The parent built stages and counted no shard: 0, as
    # stage_programs_per_100_stages reads on a program without its
    # counter; one that built no stage reads nothing.
    lay = load("layers", "shards_per_100_stages.json")
    assert layers.evaluate(lay, {"kind": "queries", "after": {
        "tsd.devwindow.stage.miss": 100.0}}) == 0.0
    for c in ({"kind": "queries", "after": {}}, {"kind": "queries"}):
        assert layers.evaluate(lay, c) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace, tmp_path, monkeypatch):
    # A compile cache of its own, empty: whatever the window compiles
    # is then a file it gains, and not one an earlier run left.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    res, line, lines = bench(CELL, trace, seed=(1 << 31) + 44)
    assert res.returncode == 0, res.stderr[-3000:]
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["count"] == 4
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
    assert CHIP_ONLY <= listed and NEW <= listed
    assert set(got) == listed - device_metrics() - CHIP_ONLY
    # Every request through the sharded window, whatever the seed
    # draws; nothing from the /q cache.
    assert got["resident_share"] == 100.0
    assert got["qcache_hit_share"] == 0.0
    # The warm-up asks for one host a type and, after its first type,
    # for the first metric alone; the window's other hosts, on other
    # shards, and other metrics compile nothing: a shard's first stage
    # of a kind compiles its programs on its own device for every shape
    # class its window holds, and the join takes its rows by an array.
    assert got["compiles_in_window"] == 0
    # Every stage is folded on all four shards (`narrowed` drops none),
    # and 40 hosts by hash are not spread evenly.
    assert got["shards_per_100_stages"] == 400.0
    assert 25.0 <= got["shard_balance_share"] < 60.0
    assert got["stage_programs_per_100_stages"] >= 800.0
    for name in SPANS:
        assert got[name] > 0, name
    assert got["shard_fanout_ms"] + got["shard_gather_ms"] \
        <= got["plan_ms"]


def test_control_lower_precision_comes_out_not_correct():
    res, line, lines = bench(CELL, control="wire_bf16")
    assert res.returncode == 0, res.stderr[-3000:]
    assert line["correct"] is False
    assert any(ln.startswith("check ") and ln.endswith("FAIL")
               for ln in lines)


def test_control_one_shards_rows_misplaced_comes_out_not_correct():
    """A fault in one shard's grids at the gather: the three other
    shards answer whole, and the comparison still fails, by the exact
    answers and by the averages."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=FOUR_DEVICES)
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.tests.run_control_shard",
         "shard_rows_shifted", "--workload", CELL, "--seed",
         str((1 << 31) + 44), "--seconds", "3", "--trace", "0",
         "--benchmark-json", rehearsal_cells.PATH],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    lines = res.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is False and line["failed"] == 0
    checks = {ln.split()[1]: (float(ln.split()[3]), ln.split()[-1])
              for ln in lines if ln.startswith("check ")}
    assert checks["exact_answers_unequal"][1] == "FAIL"
    assert checks["f32_max_rel_err"][1] == "FAIL"
    assert checks["devwindow_appended_minus_stored"] == (0.0, "ok")
    assert checks["answers_wrong_shape"] == (0.0, "ok")
