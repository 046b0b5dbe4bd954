"""The metrics PR 33 brings to the resident cells: the fold's dispatches
a stage, and the four ``resident.*`` spans over the ``double-groupby``
requests alone. Their arithmetic by hand, and what a program without
the counter (the parent commit) reads. No daemon is started here:
``test_rehearsal.py`` runs every cell that lists them and holds each
line to the lists. Which cells list them is held from below only: a
later cell that reads them is added to their lists with no edit here.
"""

import json
import os

import pytest

from benchmarks.lib import client, layers

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
DASH_CELLS = {"cpu4k.dash-1h", "cpu100.dash-12h"}
WIDE = {"wide_device_wait_ms": "resident.wait",
        "wide_stage_dispatch_ms": "resident.stage",
        "wide_fetch_ms": "resident.fetch",
        "wide_results_ms": "resident.results"}
NEW = set(WIDE) | {"fold_dispatches_per_100_stages"}


def load(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


def test_the_dash_cells_list_them():
    listed = {m["name"]: m for m in load("..", "BENCHMARK.json")["per_layer"]}
    assert NEW <= set(listed)
    for name in NEW:
        assert DASH_CELLS <= set(listed[name]["workloads"])


def test_dispatches_a_stage_by_hand():
    layer = load("layers", "fold_dispatches_per_100_stages.json")
    after = {"tsd.devwindow.fold.dispatches": 7_000.0,
             "tsd.devwindow.stage.miss": 500.0}
    assert layers.evaluate(layer, {"kind": "queries", "after": after}) \
        == pytest.approx(1400.0)
    # A program without the counter (the parent commit): nothing
    # raised, and since it has the denominator the metric reads 0.
    parent = {"kind": "queries", "after": {
        "tsd.devwindow.stage.miss": 500.0}}
    assert layers.evaluate(layer, parent) == 0.0
    # A run with no traced readings, a daemon that built no stage, a
    # load cell: nothing read, the metric left out.
    for ctx in ({"kind": "queries"}, {"kind": "queries", "after": {}},
                {"kind": "load", "after": after}):
        assert layers.evaluate(layer, ctx) is None


def test_wide_metrics_read_the_double_groupby_requests_alone():
    def tree(ms):
        return {"name": "query", "ms": 10 * ms, "spans": [
            {"name": "planner.pick", "ms": 9 * ms, "spans": [
                {"name": span, "ms": ms * (i + 1)}
                for i, span in enumerate(WIDE.values())]}]}

    done = []
    # Three wide requests of 1, 5 and 10 sub-queries among six narrow
    # ones whose spans are a hundredth: a median over all nine reads a
    # narrow request, the wide metrics read the middle wide one.
    for i, (qtype, subs, ms) in enumerate(
            [("double-groupby-1", 1, 300.0), ("double-groupby-5", 5, 300.0),
             ("double-groupby-all", 10, 300.0)]
            + [("single-groupby-1-1-12", 1, 3.0),
               ("cpu-max-all-8", 10, 3.0)] * 3):
        req = client.Request(qtype, "/q", ["m"] * subs, 0, 10, 1, 100)
        d = client.Done(req, 5.0 + i, 500.0, True, "", None, i % 2)
        d.spans = [tree(ms)] * subs
        done.append(d)
    ctx = {"kind": "queries", "done": done}
    for i, name in enumerate(WIDE):
        assert layers.evaluate(load("layers", name + ".json"),
                               ctx) == pytest.approx(5 * 300.0 * (i + 1))
    assert layers.evaluate(load("layers", "device_wait_ms.json"),
                           ctx) == pytest.approx(30.0)
    # The file and the root entry say the same of each metric.
    listed = {m["name"]: m for m in load("..", "BENCHMARK.json")["per_layer"]}
    for name in NEW:
        layer, entry = load("layers", name + ".json"), listed[name]
        assert {k: layer[k] for k in ("unit", "source", "layer", "moves")} \
            == {k: entry[k] for k in ("unit", "source", "layer", "moves")}
    # No double-groupby request, or a program without the spans: nothing.
    ctx = {"kind": "queries", "done": done[3:]}
    assert all(layers.evaluate(load("layers", n + ".json"), ctx) is None
               for n in WIDE)
    for d in done:
        d.spans = [{"name": "query", "ms": 5.0}]
    ctx = {"kind": "queries", "done": done}
    assert all(layers.evaluate(load("layers", n + ".json"), ctx) is None
               for n in WIDE)
