"""The metrics PR 40 brings: the two hops of a sub-query between the
event loop and the pool (``http.q.queue`` / ``http.q.resume``), the
share of a span's or a timed block's wall time its thread was on a CPU
(``tsd.query.span.cpu_ms`` of ``.wall_ms``, ``<timer>.cpu_ms`` of its
``sum_ms``), and the process's CPU time over the window. Each is a data
file on a reader that was there. Their files held to the root's
entries, their arithmetic by hand on ``/stats`` lines spelled as the
daemon spells them, what a program without the spans and counters (the
parent commit) reads, and one traced rehearsal that holds them all.
"""

import json
import os

import pytest

from benchmarks.lib import client, layers, stats, tsbs
from benchmarks.tests import rehearsal_cells
from benchmarks.tests.test_rehearsal import bench

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
LIVE = "cpu4k-live.live-1h"
ALL = ["cpu4k.dash-1h", "cpu100.dash-12h", "cpu4k-13h.hist-12h",
       "cpu4k-hbm.dash-12h", LIVE]
RESIDENT = [c for c in ALL if c != "cpu4k-13h.hist-12h"]
HIST = ["cpu4k-13h.hist-12h"]
FRONT, PLANNER = "server/tsd front end", "query/executor planner"
# name -> (cells, layer, unit, better, source)
NEW = {
    "pool_queue_ms": (ALL, FRONT, "ms", "lower", "program_span"),
    "loop_resume_ms": (ALL, FRONT, "ms", "lower", "program_span"),
    "stage_oncpu_share": (RESIDENT, PLANNER, "%", "higher",
                          "program_counter"),
    "groups_oncpu_share": (RESIDENT, PLANNER, "%", "higher",
                           "program_counter"),
    "results_oncpu_share": (RESIDENT, PLANNER, "%", "higher",
                            "program_counter"),
    "scan_oncpu_share": (HIST, "storage/kv scan", "%", "higher",
                         "program_counter"),
    "raw_pack_oncpu_share": (HIST, PLANNER, "%", "higher",
                             "program_counter"),
    "encode_oncpu_share": (ALL, FRONT, "%", "higher", "program_counter"),
    "host_cpu_cores": (ALL, FRONT, "cores", "higher", "program_counter"),
    "background_cpu_share": (ALL, "checkpoint spill", "%", "lower",
                             "program_counter"),
}
SPAN_OF = {"stage_oncpu_share": "resident.stage",
           "groups_oncpu_share": "resident.groups",
           "results_oncpu_share": "resident.results",
           "scan_oncpu_share": "scan",
           "raw_pack_oncpu_share": "aggregate.pack"}


def load(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


def layer(name):
    return load("layers", name + ".json")


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_file_and_the_root_entry_say_the_same(name):
    cells, where, unit, better, source = NEW[name]
    root = load("..", "BENCHMARK.json")
    entry, = [m for m in root["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": where,
                     "moves": "q_mean_ms", "workloads": cells}
    # Appended: after every entry the parent had, the live cell last.
    assert root["per_layer"].index(entry) >= 55
    assert LIVE not in cells or cells[-1] == LIVE
    lay = layer(name)
    assert {k: lay[k] for k in ("name", "unit", "source", "layer",
                                "moves")} \
        == {k: entry[k] for k in ("name", "unit", "source", "layer",
                                  "moves")}
    assert lay["reader"] in layers.READERS and lay["note"]
    # Every cell that lists it drives traffic of a kind it reads in.
    traffic = {w["name"]: w["traffic"] for w in root["workloads"]}
    for cell in cells:
        with open(tsbs.find_file(BENCH, "traffic", traffic[cell])) as f:
            kind = json.load(f)["kind"]
        assert set(layers.READS_AS.get(kind, (kind,))) & set(lay["kinds"])


def tree(ms, queue, resume, extra=()):
    return {"name": "query", "ms": ms, "cpu_ms": ms / 2, "spans": [
        {"name": "http.q.queue", "ms": queue, "cpu_ms": 0.0},
        {"name": "planner.pick", "ms": ms - 1.0, "cpu_ms": ms / 2}]
        + list(extra)
        + [{"name": "http.q.resume", "ms": resume, "cpu_ms": 0.0}]}


def requests(per_request):
    """One request a list of (ms, queue, resume), one tree each."""
    done = []
    for i, subs in enumerate(per_request):
        req = client.Request("cpu-max-all-1", "/q", ["m"] * len(subs), 0,
                             10, 1, 100)
        d = client.Done(req, 5.0 + i, 500.0, True, "", None, i % 2)
        d.spans = [tree(*sub) for sub in subs]
        done.append(d)
    return done


def test_the_two_hops_by_hand():
    # Per request the hops of its sub-queries add up (queue 0.3, 3, 10;
    # resume 0.6, 6, 30); the median over the three requests is the
    # second. The root's own span is not theirs to move.
    done = requests([[(10.0, 0.1, 0.2)] * 3,
                     [(20.0, 1.0, 2.0)] * 3,
                     [(40.0, 1.0, 3.0)] * 10])
    ctx = {"kind": "queries", "done": done}
    assert layers.evaluate(layer("pool_queue_ms"), ctx) \
        == pytest.approx(3.0)
    assert layers.evaluate(layer("loop_resume_ms"), ctx) \
        == pytest.approx(6.0)
    front = layers.evaluate(layer("frontend_ms"), ctx)
    assert front == pytest.approx(500.0 - 60.0)
    # The live window reads them as a dash window does; a load window
    # holds no request.
    assert layers.evaluate(layer("pool_queue_ms"),
                           dict(ctx, kind="live")) == pytest.approx(3.0)
    assert layers.evaluate(layer("pool_queue_ms"),
                           dict(ctx, kind="load")) is None
    # A program without the spans (the parent commit): left out.
    for d in done:
        d.spans = [{"name": "query", "ms": 5.0, "spans": [
            {"name": "planner.pick", "ms": 4.0}]}]
    for name in ("pool_queue_ms", "loop_resume_ms"):
        assert layers.evaluate(layer(name), ctx) is None


def stats_lines(pairs, ts=1790000000):
    """``/stats?json`` as the daemon gives it: ``name ts value tags``,
    the daemon's host tag last."""
    return [f"tsd.{name} {ts} {value}"
            + "".join(" " + t for t in tags.split()) + " host=vm"
            for name, value, tags in pairs]


def test_the_oncpu_shares_by_hand():
    after = stats.parse_stats(stats_lines([
        ("query.span.wall_ms", 2000.0, "span=resident.stage"),
        ("query.span.cpu_ms", 500.0, "span=resident.stage"),
        ("query.span.wall_ms", 100.0, "span=resident.groups"),
        ("query.span.cpu_ms", 90.0, "span=resident.groups"),
        ("query.span.wall_ms", 40.0, "span=resident.results"),
        ("query.span.cpu_ms", 40.0, "span=resident.results"),
        ("query.span.wall_ms", 800.0, "span=scan"),
        ("query.span.cpu_ms", 600.0, "span=scan"),
        # Another span's name starts as this one's: not summed in.
        ("query.span.wall_ms", 999.0, "span=scan.group"),
        ("query.span.cpu_ms", 1.0, "span=scan.group"),
        ("query.span.wall_ms", 50.0, "span=aggregate.pack"),
        ("query.span.cpu_ms", 10.0, "span=aggregate.pack"),
        ("http.q.encode.sum_ms", 3000.0, ""),
        ("http.q.encode.count", 100, ""),
        ("http.q.encode.cpu_ms", 2400.0, "")]))
    assert "tsd.query.span.cpu_ms{span=resident.stage}" in after
    ctx = {"kind": "queries", "after": after}
    want = {"stage_oncpu_share": 25.0, "groups_oncpu_share": 90.0,
            "results_oncpu_share": 100.0, "scan_oncpu_share": 75.0,
            "raw_pack_oncpu_share": 20.0, "encode_oncpu_share": 80.0}
    for name, share in want.items():
        assert layers.evaluate(layer(name), ctx) == pytest.approx(share)
    for name, span in SPAN_OF.items():
        args = layer(name)["args"]
        assert args == {"names": ["tsd.query.span.cpu_ms{span=%s}" % span],
                        "of": ["tsd.query.span.wall_ms{span=%s}" % span]}
    # A program without the counters (the parent commit): the span
    # shares have nothing to divide by and are left out; the encode's
    # timer is there and its share reads 0. Nothing raised.
    parent = {"kind": "queries", "after": stats.parse_stats(stats_lines([
        ("http.q.encode.sum_ms", 3000.0, "")]))}
    for name in SPAN_OF:
        assert layers.evaluate(layer(name), parent) is None
    assert layers.evaluate(layer("encode_oncpu_share"), parent) == 0.0
    # No request traced in a resident cell: no scan span, left out.
    assert layers.evaluate(layer("scan_oncpu_share"), {
        "kind": "queries", "after": {
            "tsd.query.span.wall_ms{span=query}": 5.0}}) is None
    for name in want:
        assert layers.evaluate(layer(name), {"kind": "queries"}) is None
        assert layers.evaluate(layer(name), dict(ctx, kind="load")) is None


def test_the_two_cpu_ratios_by_hand():
    before = stats.parse_stats(stats_lines([
        ("process.cpu_ms", 100000.0, ""),
        ("checkpoint.phase.cpu_ms", 50.0, "phase=spill"),
        ("sketch.fold.cpu_ms", 1000.0, "")]))
    after = stats.parse_stats(stats_lines([
        ("process.cpu_ms", 175000.0, ""),
        ("checkpoint.phase.cpu_ms", 350.0, "phase=spill"),
        ("checkpoint.phase.cpu_ms", 100.0, "phase=commit"),
        ("checkpoint.snapshot.cpu_ms", 50.0, "kind=sketch"),
        ("ingest.batch.cpu_ms", 2000.0, ""),
        ("ingest.parse.cpu_ms", 500.0, ""),
        ("devwindow.upload.cpu_ms", 30.0, ""),
        ("sketch.fold.cpu_ms", 1020.0, ""),
        # The wall times beside them are not counted in.
        ("ingest.batch.sum_ms", 9999.0, ""),
        ("checkpoint.phase.sum_ms", 9999.0, "phase=spill")]))
    ctx = {"kind": "queries", "window_s": 50.0, "before": before,
           "after": after}
    # 75 s of CPU in a window of 50 s: a core and a half.
    assert layers.evaluate(layer("host_cpu_cores"), ctx) \
        == pytest.approx(1.5)
    # 300 + 100 + 50 + 2000 + 500 + 30 + 20 ms of 50 s: 6% of a core.
    assert layers.evaluate(layer("background_cpu_share"), ctx) \
        == pytest.approx(6.0)
    assert layers.evaluate(layer("background_cpu_share"),
                           dict(ctx, kind="live")) == pytest.approx(6.0)
    # A program without the gauge and the counters: both read 0.
    parent = dict(ctx, before={"tsd.uptime_s": 1.0},
                  after={"tsd.uptime_s": 51.0})
    assert layers.evaluate(layer("host_cpu_cores"), parent) == 0.0
    assert layers.evaluate(layer("background_cpu_share"), parent) == 0.0
    for name in ("host_cpu_cores", "background_cpu_share"):
        assert layers.evaluate(layer(name), {"kind": "queries"}) is None


def test_a_traced_rehearsal_holds_them_all():
    rehearsal_cells.write()
    res, line, _lines = bench("cpu4k.dash-1h", trace=1, seed=(1 << 31) + 40)
    assert res.returncode == 0, res.stderr[-3000:]
    assert line["correct"] is True and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    here = {n for n, spec in NEW.items() if "cpu4k.dash-1h" in spec[0]}
    assert here <= set(got) and not (set(NEW) - here) & set(got)
    assert got["pool_queue_ms"] >= 0 and got["loop_resume_ms"] >= 0
    # Both hops are part of what frontend_ms holds by subtraction.
    assert got["pool_queue_ms"] + got["loop_resume_ms"] \
        <= got["frontend_ms"] * 1.5 + 5.0
    for name in here:
        if name.endswith("_oncpu_share"):
            assert 0 < got[name] <= 102.0, name
    assert got["host_cpu_cores"] > 0.1
    assert got["background_cpu_share"] >= 0
