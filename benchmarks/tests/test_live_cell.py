"""The admitted live cell, ``cpu4k-live.live-1h`` on ``tsbs-cpu4k-live``:
its files held to the ones they copy, the cell rehearsed on its CPU
stand-in (``rehearsal/tsbs-cpu40-live.json``), and the three controls
that must come out not ``correct`` on it.

The pending ``cpu4k.live-1h`` beside it (``test_live.py``) is the same
mix on ``tsbs-cpu4k``; it stays until a ``benchmark`` PR removes it.
Slow like ``test_live.py``: five cases start a daemon.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import layers, tsbs
from benchmarks.tests import rehearsal_cells
from benchmarks.tests.test_rehearsal import (DEVICE_KEYS, LINE_KEYS, REHEARSAL,
                                             bench, device_metrics)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "cpu4k-live.live-1h"
CONFIG = "tsbs-cpu4k-live"
# ``parse_ms_per_kpt.live`` may have no file of its own (test_live.py::
# test_a_live_window_reads_as_both_kinds holds that name to its
# quantity's file) and a root entry needs one (test_units.py): that one
# split is named by the mix.
PARSE = "parse_ms_per_kpt.live-1h"
PUT_SIDE = ["wal_ms_per_kpt", "fsyncs_per_kpt", "checkpoint_busy_share",
            "upload_stalls"]
PINNED = ["groups_ms", "stage_dispatch_ms", "device_wait_ms", "fetch_ms",
          "results_ms", "encode_busy_share", "stage_builds",
          "snapshot_busy_share"]
NEW = ["ingest_ms_per_kpt", "wal_appends_per_kpt", "chunks_per_kpt"]
# The rows that hold each guarantee the config states.
ROWS = {"acknowledged_points": ["count_minus_acknowledged",
                                "sampled_series_unequal",
                                "recount_after_kill_minus_acknowledged",
                                "put_error_lines"],
        "exact": ["exact_answers_unequal", "answers_wrong_shape"],
        "f32": ["f32_max_rel_err"],
        "resident": ["devwindow_appended_minus_stored"],
        "visible": ["exact_answers_unequal", "f32_max_rel_err",
                    "answers_ahead_of_edge"],
        "rate": ["collector_late_steps"]}


def load(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


@pytest.fixture(scope="module", autouse=True)
def rehearsal_json():
    rehearsal_cells.write()


def root_entries():
    root = load("..", "BENCHMARK.json")
    return root, {m["name"]: m for m in root["per_layer"]}


def test_the_config_is_tsbs_cpu4k_written_to_while_it_is_read():
    live, base = load("configs", CONFIG + ".json"), load("configs",
                                                         "tsbs-cpu4k.json")
    for key in ("hosts", "interval_s", "hours", "source_hours", "t0",
                "metrics", "tags", "daemon", "chips", "reduced"):
        assert live[key] == base[key], key
    assert live["name"] == CONFIG and live["source"] != base["source"]
    assert len(live["source"]) <= 200
    # tsbs-cpu4k's five guarantees word for word, and the two of a
    # deployment that is read while it is written.
    assert {k: live["guarantees"][k] for k in base["guarantees"]} \
        == base["guarantees"]
    assert set(live["guarantees"]) - set(base["guarantees"]) \
        == {"visible", "rate"}
    assert set(ROWS) == set(live["guarantees"]) - {"f32_rtol"}
    assert set(base["assumed"]) <= set(live["assumed"])
    assert "separate phases" in live["assumed"]["phases"]
    small = load("tests", "rehearsal", "tsbs-cpu40-live.json")
    cpu40 = load("tests", "rehearsal", "tsbs-cpu40.json")
    assert small["rehearsal_of"] == CONFIG and small["rehearsal"] is True
    for key in ("hosts", "interval_s", "hours", "t0", "metrics", "tags",
                "daemon"):
        assert small[key] == cpu40[key], key
    assert small["guarantees"] == live["guarantees"]


def test_the_store_is_tsbs_cpu4k_s_under_the_config_s_own_name():
    """``recorded/targets-seed7.json`` ``store_keys`` holds the four
    configs PR 36 knew, and ``test_live.py::
    test_a_config_without_store_keeps_the_parents_key`` looks every
    root config up there (a fifth raises KeyError; neither file is this
    PR's to edit). The new config's key is held here: its own name, the
    seed, and the hash of tsbs-cpu4k's shape, so the same points."""
    cfg = tsbs.load_config(os.path.join(BENCH, "configs", CONFIG + ".json"))
    base = tsbs.load_config(os.path.join(BENCH, "configs",
                                         "tsbs-cpu4k.json"))
    assert bench_run.store_key(cfg, 7) == CONFIG + "-7-dab39e11"
    assert bench_run.store_key(base, 7) == "tsbs-cpu4k-7-dab39e11"
    with open(os.path.join(HERE, "recorded", "targets-seed7.json")) as f:
        assert json.load(f)["store_keys"]["tsbs-cpu4k"] \
            == "tsbs-cpu4k-7-dab39e11"


SPLITS = [n + ".live" for n in PUT_SIDE + PINNED] + [PARSE]


@pytest.mark.parametrize("split", SPLITS)
def test_a_live_copy_says_what_its_base_says(split):
    name = split.rsplit(".", 1)[0]
    copy, base = load("layers", split + ".json"), load(
        "layers", name + ".json")
    for key in ("unit", "source", "layer", "reader", "args", "kinds"):
        assert copy[key] == base[key], key
    assert copy["name"] == split and copy["moves"] == "q_mean_ms"
    _root, entries = root_entries()
    entry = entries[split]
    assert entry["workloads"] == [CELL]
    assert entry["better"] == entries.get(name, entry)["better"]


def test_the_root_lists_the_cell_and_only_gained_entries():
    root, entries = root_entries()
    cell, = [w for w in root["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "live-1h", 1)
    assert root["workloads"][-1] is cell and len(cell["why"]) <= 200
    for m in root["end_to_end"]:
        assert (CELL in m.get("workloads", [CELL])) is True
    listed = {n for n, m in entries.items() if CELL in m["workloads"]}
    dash = {n for n, m in entries.items()
            if "cpu4k.dash-1h" in m["workloads"]}
    # Every metric of cpu4k.dash-1h, the pinned ones under their split
    # names; the put side's five; the three this PR's counters feed.
    assert listed == (dash - set(PINNED)) | set(SPLITS) | set(NEW)
    for name in NEW:
        with open(layers.find(BENCH, name)) as f:
            layer = json.load(f)
        assert layer["reader"] == "stats_ratio" and layer["kinds"] == ["load"]
        assert layer["args"]["per"] == "kpoints"
    # The four cells that were there keep their lists' order.
    for m in root["per_layer"] + root["end_to_end"]:
        if CELL in m.get("workloads", []) and len(m["workloads"]) > 1:
            assert m["workloads"][-1] == CELL


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(trace):
    res, line, lines = bench(CELL, trace, seed=(1 << 31) + 38, seconds=12)
    assert res.returncode == 0, res.stderr[-3000:]
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert set(line["device"]) == DEVICE_KEYS
    got = {k: v["value"] for k, v in line["metrics"].items()}
    checks = {ln.split()[1]: ln.split() for ln in lines
              if ln.startswith("check ")}
    # Every guarantee of the config is held by a row, and every row ok.
    assert {r for rows in ROWS.values() for r in rows} <= set(checks)
    assert all(words[-1] == "ok" for words in checks.values())
    assert float(checks["collector_late_steps"][3]) == 0.0
    assert "edge 6 -> 8 (moved at +" in res.stderr     # due at 0 and 10 s
    if not trace:
        assert set(got) == {"q_mean_ms", "queries_per_s", "setup_s"}
        assert all(v > 0 for v in got.values())
        return
    spec = rehearsal_cells.cells()
    listed = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert set(got) == listed - device_metrics()
    assert got["resident_share"] == 100.0
    assert got["compiles_in_window"] == 0 and got["upload_stalls.live"] == 0
    # A chunk is one put. At this size a collector's write is 100
    # points, which the daemon reads off its socket in one to four
    # pieces, a put each: 10-40 puts a thousand points (0.4 at the
    # deployment's 2,500 a write, if read whole), where a put a series
    # would read 1,000. The WAL's total holds a record a put and,
    # beside them, the compaction thread's two (merged cell, delete) a
    # row it merges, at most 100 rows a wake-up here: up to 500 a
    # thousand points at 400 points a step (11 at the deployment's
    # 40,000).
    assert 10.0 <= got["chunks_per_kpt"] < 100
    assert got["chunks_per_kpt"] <= got["wal_appends_per_kpt"] < 600
    assert got["ingest_ms_per_kpt"] > 0
    assert got[PARSE] > 0 and got["wal_ms_per_kpt.live"] > 0


def bench_control(control):
    """``test_rehearsal.bench`` with the daemon started through
    ``tsd_control_chunk.py``: the fault planted where a chunk is one
    put, the path the cell times."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.tests.run_control_chunk", control,
         "--workload", CELL, "--seed", "5", "--seconds", "12", "--trace",
         "0", "--benchmark-json", REHEARSAL],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=600)
    lines = res.stdout.strip().splitlines()
    return res, json.loads(lines[-1]), lines


@pytest.mark.parametrize("control,row", [
    ("drop_staged_steps", "exact_answers_unequal"),
    ("late_staged_steps", "f32_max_rel_err"),
    ("wal_unflushed", "recount_after_kill_minus_acknowledged"),
])
def test_control_comes_out_not_correct(control, row):
    res, line, lines = bench_control(control)
    assert res.returncode == 0, res.stderr[-3000:]
    assert line["correct"] is False
    assert any(ln.startswith(f"check {row} ") and ln.endswith("FAIL")
               for ln in lines)
